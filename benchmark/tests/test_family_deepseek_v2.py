"""The ``deepseek_v2`` family: its table names every tensor the server's
loader asks for and no other, its ``score`` agrees with the repo's plain
``jax.numpy`` reference at a small size, the configuration's widths are
the catalog row's (the depth and the experts held aside), the replay's
period is ``run_seconds``, and the three branches of an expert layer are
of one size at the published widths."""

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
CONFIG = "deepseek-v2-ep8-l8"
CELL = "deepseekv2-ep8-l8.long-answer"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """One chip's share at the small size: the router scores 4 groups of
    4, the first group's experts are held."""
    from tests.fixture_models import TINY_DEEPSEEK_V2_CONFIG

    hf = dict(TINY_DEEPSEEK_V2_CONFIG, n_routed_experts=4)
    family = run.load_family("deepseek_v2")
    path, _ = checkpoint.ensure_checkpoint(
        tmp_path_factory.mktemp("work"), "tiny-deepseek", hf, 7, "float32",
        family.tensors)
    return path, hf, family


def test_the_table_names_what_the_loader_asks_for_and_no_other():
    import dataclasses

    from vllm_tgis_adapter_tpu.engine.config import ModelConfig
    from vllm_tgis_adapter_tpu.engine.weights import deepseek_v2_tensor_names

    hf = run.served_config(run.load("configs", CONFIG))
    family = run.load_family("deepseek_v2")
    top, layers = family.tensors(hf)
    mcfg = ModelConfig.from_hf_config(CONFIG, hf, max_model_len=4096,
                                      dtype="bfloat16")
    assert mcfg.experts.held == (0, 20)
    table = [n for t in (top, *layers) for n in t]
    assert sorted(table) == sorted(deepseek_v2_tensor_names(mcfg))
    assert len(table) == len(set(table))
    # the router keeps every row: the published 160
    assert layers[1]["model.layers.1.mlp.gate.weight"][0] == (160, 5120)
    assert family.routed(hf) == 160
    assert "model.layers.0.mlp.gate.weight" not in layers[0]


def test_the_server_reads_the_share_from_the_checkpoint(tiny):
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

    path, _, _ = tiny
    mcfg = ModelConfig.from_pretrained(str(path), dtype="float32")
    assert mcfg.experts.held == (0, 4) and mcfg.experts.num_routed == 16


@pytest.mark.parametrize("head_block", [None, 100])
def test_score_agrees_with_the_repos_plain_reference(tiny, head_block,
                                                     monkeypatch):
    """Also with the head cut into several blocks of rows, the last one
    short (the tiny vocabulary is one block of the default size)."""
    import jax

    from vllm_tgis_adapter_tpu.engine.weights import load_checkpoint_tensors
    from vllm_tgis_adapter_tpu.models.reference import deepseek_v2 as plain

    path, hf, family = tiny
    if head_block:
        monkeypatch.setattr(family, "HEAD_BLOCK", head_block)
    rng = np.random.default_rng(3)
    sequences = [[int(t) for t in rng.integers(3, 500, n)] for n in (31, 52)]
    prompt_lens = [17, 40]
    got = family.score(path, hf, sequences, prompt_lens)
    tensors = load_checkpoint_tensors(str(path))
    for ids, n, scores in zip(sequences, prompt_lens, got):
        logprobs = np.asarray(jax.nn.log_softmax(
            plain.forward(hf, tensors, ids, held=(0, 4)), axis=-1))
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


def test_routes_and_margins_are_collected(tiny):
    path, hf, family = tiny
    ids = [[int(t) for t in np.random.default_rng(5).integers(3, 500, 24)]]
    routes = []
    family.score(path, hf, ids, [12], routes=routes)
    assert len(routes) == 3  # one entry an expert layer
    experts, margins = routes[0]
    assert experts.shape == (24, 3) and margins.shape == (24,)
    assert (margins >= 0).all() and experts.max() < 16


def test_the_configurations_widths_are_the_catalogs():
    if not CATALOG.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "DeepSeek-V2")
    full = run.load("configs", CONFIG)
    assert full["source"] == row["source_url"]
    assert full["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    differs = {k for k, v in row["config"].items() if full.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts"}
    assert (full["num_hidden_layers"], full["n_routed_experts"]) == (8, 20)
    assert full["family"] == "deepseek_v2" and full["chips"] == 1
    # the mix's answers go to 1,536 tokens and the server's default
    # limit is 1,024: the one argument that is not a default
    assert full["serve_args"] == ["--dtype", "bfloat16", "--max-model-len",
                                  "4096", "--max-new-tokens", "1536"]
    for key in ("assumed", "reduced_why", "deployment"):
        assert full[key]
    assert "160" in full["reduced_why"]


def test_the_cell_is_listed_with_its_five_metrics():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == run.load("cells", CELL)["traffic"]
    assert len(cell["why"]) <= 200
    mine = {m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"mla_attn_kernel_pct", "mla_attn_roofline_pct",
                    "moe_experts_kernel_pct", "moe_experts_roofline_pct",
                    "moe_expert_tokens"}
    listed = run.listed(CELL, "per_layer")
    assert mine <= set(listed) and len(listed) == 11 + 5
    for name in mine:
        assert run.load("layer_metrics", name)["moves"] == "gap_p99_ms"
    peaks = {name: run.load("layer_metrics", name)["params"]["peak"]
             for name in mine if name.endswith("roofline_pct")}
    assert peaks == {"mla_attn_roofline_pct": "bf16_flops_per_s",
                     "moe_experts_roofline_pct": "hbm_bytes_per_s"}


def test_one_period_of_the_replay_is_one_window():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = run.load("cells", CELL)
    mix = run.traffic_mod.load_traffic(cell["traffic"])
    assert abs(mix["replay"]["requests"] / cell["rate_rps"]
               - spec["run_seconds"]) < 0.5
    assert mix["prompt_tokens"]["max"] <= 1024
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 0.4, "min": 256, "max": 1536}


def test_the_roofline_reader_takes_the_flop_counters():
    """The accepted reader, pointed at the latent kernel's FLOP and call
    counters and at the bf16 peak: operations a call over seconds a
    call."""
    from readers import roofline

    params = run.load("layer_metrics", "mla_attn_roofline_pct")["params"]
    flops_a_call = 40 * 1000 * 128 * 2 * (576 + 512)  # 40 rows at 1 k

    def scrape(calls):
        return {("tgis_tpu_mla_attn_calls_total", frozenset()): calls,
                ("tgis_tpu_mla_attn_flops_total", frozenset()):
                    calls * flops_a_call}

    seconds_a_call = flops_a_call / 197e12 * 10  # a tenth of the peak
    name = "mla_ragged_attention custom-call:tpu_custom_call"
    ctx = {
        # a scrape every quarter of a second around a capture of 2 s
        "fine_samples": [(1000.0 + k / 4, scrape(75 * k))
                         for k in range(28)],
        "device_kind": "TPU v5 lite",
        "trace": {"device_planes": ["/device:TPU:0"], "busy_s": 1.0,
                  "capture_wall": [1002.6, 1004.6],
                  "op_seconds": {name: 600 * seconds_a_call, "fusion": 0.5},
                  "op_calls": {name: 600, "fusion": 9}},
    }
    assert roofline.read(params, ctx) == pytest.approx(10.0)
    # a parent without the counters: silent
    empty = [(t, {}) for t, _ in ctx["fine_samples"]]
    assert roofline.read(params, {**ctx, "fine_samples": empty}) is None


def test_no_branch_is_flattened_at_the_published_widths():
    """The fills are part of the result: one expert layer at DeepSeek-V2's
    widths on 64 random tokens, from the family's own table, with four
    experts held so that the test stays small.  Attention, the shared
    experts and the held routed experts each add at least a tenth of
    what the others add.  (PERF.md section 4 has the readings at 20
    held experts and two expert layers.)"""
    family = run.load_family("deepseek_v2")
    hf = dict(run.served_config(run.load("configs", CONFIG)),
              num_hidden_layers=1, first_k_dense_replace=0, vocab_size=1024,
              n_routed_experts=4, n_group=8)
    top, (table,) = family.tensors(hf)
    rng = np.random.default_rng(0)

    def make(shape, fill):
        if fill[0] == "ones":
            return np.ones(shape, np.float32)
        return rng.standard_normal(shape, dtype=np.float32) * fill[1]

    lw = {name: make(*entry) for name, entry in table.items()}

    class W:
        def get(self, name):
            return lw[name]

    embed = make(*top["model.embed_tokens.weight"])
    x = embed[rng.integers(0, 1024, 64)]
    parts = []
    family.layer(x, W(), 0, hf, parts=parts)

    def rms(v):
        return float(np.sqrt(np.mean(v * v)))

    # with 4 of 32 experts held a token's pairs fall here an eighth as
    # often as in the cell; scale the routed branch's mean square to it
    a, routed, shared = parts[0]
    sizes = [rms(a), rms(routed), rms(shared)]
    assert max(sizes) < 10 * min(sizes), sizes
    assert 0.02 < min(sizes) and max(sizes) < 5 * rms(x), sizes
