"""The ``falcon_h1`` family: its table names every tensor the server's
loader asks for and no other, its ``score`` agrees with the repo's plain
``jax.numpy`` reference at a small size, the configuration's widths are
the catalog row's (the depth aside), and the roofline reader's byte count
is the program counter's."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import checkpoint
import run

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
CONFIG = "falcon-h1-34b-instruct-l6"
CELL = "falconh1-34b-l6.short-chat"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    from tests.fixture_models import TINY_FALCON_H1_CONFIG

    hf = dict(TINY_FALCON_H1_CONFIG)
    family = run.load_family("falcon_h1")
    path, _ = checkpoint.ensure_checkpoint(
        tmp_path_factory.mktemp("work"), "tiny-falcon", hf, 7, "float32",
        family.tensors)
    return path, hf, family


def test_the_table_names_what_the_loader_asks_for_and_no_other():
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig
    from vllm_tgis_adapter_tpu.engine.weights import falcon_h1_tensor_names

    hf = run.served_config(run.load("configs", CONFIG))
    top, layers = run.load_family("falcon_h1").tensors(hf)
    mcfg = ModelConfig.from_hf_config(CONFIG, hf, max_model_len=4096,
                                      dtype="bfloat16")
    table = [n for t in (top, *layers) for n in t]
    assert sorted(table) == sorted(falcon_h1_tensor_names(mcfg))
    assert len(table) == len(set(table))


@pytest.mark.parametrize("head_block", [None, 100])
def test_score_agrees_with_the_repos_plain_reference(tiny, head_block,
                                                     monkeypatch):
    """Also with the head cut into several blocks of rows, the last one
    short (the tiny vocabulary is one block of the default size)."""
    import jax

    from vllm_tgis_adapter_tpu.engine.config import ModelConfig
    from vllm_tgis_adapter_tpu.engine.weights import load_model_params
    from vllm_tgis_adapter_tpu.models.reference import falcon_h1 as plain

    path, hf, family = tiny
    if head_block:
        monkeypatch.setattr(family, "HEAD_BLOCK", head_block)
    rng = np.random.default_rng(3)
    sequences = [[int(t) for t in rng.integers(3, 500, n)] for n in (31, 52)]
    prompt_lens = [17, 40]
    got = family.score(path, hf, sequences, prompt_lens)
    mcfg = ModelConfig.from_pretrained(str(path), dtype="float32")
    params = load_model_params(mcfg, str(path))
    for ids, n, scores in zip(sequences, prompt_lens, got):
        logprobs = np.asarray(jax.nn.log_softmax(
            plain.forward(mcfg, params, ids), axis=-1))
        want = [logprobs[n - 1 + i, tok] for i, tok in enumerate(ids[n:])]
        assert np.abs(np.asarray(scores) - np.asarray(want)).max() < 1e-5


def test_a_lower_precision_does_not_score_the_same(tiny):
    """The control's handle: ``cast`` rounds every matmul operand."""
    import ml_dtypes

    path, hf, family = tiny
    ids = [[int(t) for t in np.random.default_rng(4).integers(3, 500, 40)]]
    exact = family.score(path, hf, ids, [20])[0]
    low = family.score(
        path, hf, ids, [20],
        cast=lambda a: a.astype(ml_dtypes.float8_e4m3fn).astype(np.float32))
    assert np.abs(np.asarray(exact) - np.asarray(low)).max() > 0.01


def test_the_configurations_widths_are_the_catalogs():
    if not CATALOG.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Falcon-H1-34B-Instruct")
    full = run.load("configs", CONFIG)
    assert full["source"] == row["source_url"]
    assert full["reduced"] == ["num_hidden_layers"]
    differs = {k for k, v in row["config"].items() if full.get(k) != v}
    assert differs == {"num_hidden_layers"}
    assert 4 <= full["num_hidden_layers"] <= 6
    assert full["family"] == "falcon_h1" and full["chips"] == 1
    assert full["serve_args"] == ["--dtype", "bfloat16", "--max-model-len",
                                  "4096"]
    for key in ("assumed", "deployment"):
        assert full[key]


def test_the_cell_is_listed_with_its_four_metrics():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == run.load("cells", CELL)["traffic"]
    mine = {m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"ssm_live_rows", "ssm_state_used_peak_pct",
                    "ssm_update_kernel_pct", "ssm_update_roofline_pct"}
    listed = run.listed(CELL, "per_layer")
    assert mine <= set(listed) and len(listed) == 11 + 4
    for name in mine:
        assert run.load("layer_metrics", name)["moves"] == "gap_p99_ms"


def test_one_period_of_the_replay_is_one_window():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = run.load("cells", CELL)
    mix = run.traffic_mod.load_traffic(cell["traffic"])
    assert abs(mix["replay"]["requests"] / cell["rate_rps"]
               - spec["run_seconds"]) < 0.5


def test_the_roofline_reader_divides_bytes_a_call_by_seconds_a_call():
    from readers import roofline

    hf = run.served_config(run.load("configs", CONFIG))
    per_row = roofline.ssm_update_bytes(hf)
    assert per_row == 2 * 32 * 256 * 128 * 4  # 8 MB: 4 MB in, 4 MB out
    params = run.load("layer_metrics", "ssm_update_roofline_pct")["params"]

    def scrape(calls, rows):
        return {("tgis_tpu_ssm_update_calls_total", frozenset()): calls,
                ("tgis_tpu_ssm_state_bytes_total", frozenset()):
                    calls * rows * per_row}

    # 30 live rows a call at half the published bandwidth
    seconds_a_call = 30 * per_row / 819e9 * 2
    ctx = {
        # a scrape every quarter of a second around a capture of 2 s
        "fine_samples": [(1000.0 + k / 4, scrape(75 * k, 30))
                         for k in range(28)],
        "device_kind": "TPU v5 lite",
        "trace": {"device_planes": ["/device:TPU:0"], "busy_s": 1.0,
                  "capture_wall": [1002.6, 1004.6],
                  "op_seconds": {"ssm_decode_update custom-call:x":
                                 600 * seconds_a_call, "fusion": 0.5},
                  "op_calls": {"ssm_decode_update custom-call:x": 600,
                               "fusion": 9}},
    }
    assert roofline.read(params, ctx) == pytest.approx(50.0)
    # a parent without the counters, or a trace without the kernel: silent
    empty = [(t, {}) for t, _ in ctx["fine_samples"]]
    assert roofline.read(params, {**ctx, "fine_samples": empty}) is None
    bare = {**ctx["trace"], "op_seconds": {"fusion": 0.5},
            "op_calls": {"fusion": 9}}
    assert roofline.read(params, {**ctx, "trace": bare}) is None
    with pytest.raises(KeyError):
        roofline.read(params, {**ctx, "device_kind": "TPU v9"})


def test_no_branch_is_flattened_at_the_published_widths():
    """The fills are part of the result: one layer at Falcon-H1-34B's
    widths on 64 random tokens, from the family's own table.  Attention,
    mixer and MLP each add at least a tenth of what the others add, and
    inside the mixer the scan's ``C . h`` and the ``D x`` skip are within
    a factor of ten of each other, so a server wrong in one of them is
    wrong in the logits.  (PERF.md section 4 has the readings.)"""
    family = run.load_family("falcon_h1")
    hf = dict(run.served_config(run.load("configs", CONFIG)),
              num_hidden_layers=1, vocab_size=1024)
    top, (table,) = family.tensors(hf)
    rng = np.random.default_rng(0)

    def make(shape, fill):
        if fill[0] == "ones":
            return np.ones(shape, np.float32)
        if fill[0] == "pool":
            return rng.standard_normal(shape, dtype=np.float32) * fill[1]
        return np.asarray(fill[1](shape, rng), np.float32)

    lw = {name.split("layers.0.")[1]: make(*entry)
          for name, entry in table.items()}
    embed = make(*top["model.embed_tokens.weight"])
    x = embed[rng.integers(0, 1024, 64)] * hf["embedding_multiplier"]
    branches, inside = [], []
    u = family.rms_norm(x, lw["input_layernorm.weight"], hf["rms_norm_eps"])
    family.mixer(u, lw, hf, parts=inside)
    family.layer(x, lw, hf, parts=branches)

    def rms(v):
        return float(np.sqrt(np.mean(v * v)))

    sizes = [rms(v) for v in branches[0]]  # attention, mixer, MLP
    assert max(sizes) < 10 * min(sizes), sizes
    assert 0.05 < min(sizes) and max(sizes) < 5 * rms(x), sizes
    scan, skip = (rms(v) for v in inside[0])
    assert 0.1 < scan / skip < 10, (scan, skip)
