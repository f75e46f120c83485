"""The ``cohere2_moe`` family: its table names every tensor the server's
loader asks for and no other, its ``score`` agrees with the repo's plain
``jax.numpy`` reference at a small size across the band (attention in
query blocks that read only the keys their rows may see), a lower
precision does not score the same, the configuration's widths are the
catalog row's (the depth and the experts held aside), the cell is listed
with its metrics, the replay's period is ``run_seconds``, and the
mixture draws both of its parts."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import checkpoint
import run
import traffic

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
CONFIG = "command-a-plus-ep8-l4"
CELL = "cmdaplus-ep8-l4.rag-and-chat"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """One chip's share at the small size: the router scores the
    family's 128 experts, the first two are held."""
    from tests.fixture_models import TINY_COHERE2_MOE_CONFIG

    hf = dict(TINY_COHERE2_MOE_CONFIG, num_experts=2)
    family = run.load_family("cohere2_moe")
    path, _ = checkpoint.ensure_checkpoint(
        tmp_path_factory.mktemp("work"), "tiny-cohere2", hf, 7, "float32",
        family.tensors)
    return path, hf, family


def test_the_table_names_what_the_loader_asks_for_and_no_other():
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig
    from vllm_tgis_adapter_tpu.engine.weights import cohere2_moe_tensor_names

    hf = run.served_config(run.load("configs", CONFIG))
    family = run.load_family("cohere2_moe")
    top, layers = family.tensors(hf)
    mcfg = ModelConfig.from_hf_config(CONFIG, hf, max_model_len=16384,
                                      dtype="bfloat16")
    assert mcfg.experts.held == (0, 16) and mcfg.num_layers == 4
    assert mcfg.layer_windows == (4096, 4096, 4096, 0)
    table = [n for t in (top, *layers) for n in t]
    assert sorted(table) == sorted(cohere2_moe_tensor_names(mcfg))
    assert len(table) == len(set(table))
    # the router keeps every row (the published 128); no lm_head: tied
    assert layers[1]["model.layers.1.mlp.gate.weight"][0] == (128, 4096)
    assert "lm_head.weight" not in top
    # 11.35 GB at 2 bytes a parameter (the configuration's reduced_why)
    params = sum(int(np.prod(shape)) for t in (top, *layers)
                 for shape, _ in t.values())
    assert 11.3e9 < 2 * params < 11.4e9


def test_the_server_reads_the_share_from_the_checkpoint(tiny):
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

    path, _, _ = tiny
    mcfg = ModelConfig.from_pretrained(str(path), dtype="float32")
    assert mcfg.experts.held == (0, 2) and mcfg.experts.num_routed == 128


@pytest.mark.parametrize("blocks", [(None, None), (100, 7)])
def test_score_agrees_with_the_repos_plain_reference(tiny, blocks,
                                                     monkeypatch):
    """Sequences of 4-6 windows; also with the head cut into several
    blocks of rows and attention into query blocks shorter than the
    window, so a block's key range starts inside the sequence."""
    import jax

    from vllm_tgis_adapter_tpu.engine.weights import load_checkpoint_tensors
    from vllm_tgis_adapter_tpu.models.reference import cohere2_moe as plain

    path, hf, family = tiny
    head_block, query_block = blocks
    if head_block:
        monkeypatch.setattr(family, "HEAD_BLOCK", head_block)
        monkeypatch.setattr(family, "QUERY_BLOCK", query_block)
    rng = np.random.default_rng(3)
    sequences = [[int(t) for t in rng.integers(3, 500, n)] for n in (31, 52)]
    prompt_lens = [17, 40]
    got = family.score(path, hf, sequences, prompt_lens)
    tensors = load_checkpoint_tensors(str(path))
    for ids, n, scores in zip(sequences, prompt_lens, got):
        logprobs = np.asarray(jax.nn.log_softmax(
            plain.forward(hf, tensors, ids, held=(0, 2)), axis=-1))
        want = [logprobs[n - 1 + i, tok] for i, tok in enumerate(ids[n:])]
        assert np.abs(np.asarray(scores) - np.asarray(want)).max() < 2e-5


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


def test_the_control_tool_judges_both_precisions(tiny, monkeypatch, capsys):
    """``tools/lower_precision_control.py`` at the small size: the
    family's reference with rounded operands through ``run.judge``; it
    exits 0 only where the stated precision passes and the one below
    fails."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "control", ROOT / "tools" / "lower_precision_control.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    path, hf, family = tiny
    monkeypatch.setattr(run, "load", lambda kind, name: {
        "cells": {"config": "tiny-cohere2"},
        "configs": {"family": "cohere2_moe", "dtype": "float32"}}[kind])
    monkeypatch.setattr(run, "served_config", lambda config: hf)
    monkeypatch.setattr(checkpoint, "ensure_checkpoint",
                        lambda *a, **k: (path, True))
    monkeypatch.setattr(sys, "argv", ["control", "--workload", "tiny",
                                      "--seed", "5"])
    for tolerance, code in ((1e-3, 0), (10.0, 1)):
        monkeypatch.setattr(family, "LOGPROB_TOLERANCE", tolerance)
        with pytest.raises(SystemExit) as exit_:
            control.main()
        assert exit_.value.code == code
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["float32"]["max_abs_logprob_diff"] == 0.0
        assert out["bfloat16"]["max_abs_logprob_diff"] > 1e-3
        assert out["ok"] == (code == 0)


def test_routes_margins_and_branches_are_collected(tiny):
    path, hf, family = tiny
    ids = [[int(t) for t in np.random.default_rng(5).integers(3, 500, 24)]]
    routes, parts = [], []
    family.score(path, hf, ids, [12], routes=routes, parts=parts)
    assert len(routes) == len(parts) == 4  # one entry a layer
    experts, margins = routes[0]
    assert experts.shape == (24, 2) and margins.shape == (24,)
    assert (margins >= 0).all() and experts.max() < 128
    assert [p.shape for p in parts[0]] == [(24, 64)] * 4


def test_the_configurations_widths_are_the_catalogs():
    if not CATALOG.exists():
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "command-a-plus-05-2026")
    full = run.load("configs", CONFIG)
    assert full["source"] == row["source_url"]
    assert full["reduced"] == ["num_hidden_layers", "num_experts"]
    differs = {k for k, v in row["config"].items() if full.get(k) != v}
    assert differs == {"num_hidden_layers", "num_experts"}
    assert (full["num_hidden_layers"], full["num_experts"]) == (4, 16)
    assert full["family"] == "cohere2_moe" and full["chips"] == 1
    # long prompts and answers to 768; every other flag is the server's
    # default (pages of 128 tokens are what it derives for a model with
    # a window group, engine/config.py ``default_block_size``)
    assert full["serve_args"] == [
        "--dtype", "bfloat16", "--max-model-len", "16384",
        "--max-new-tokens", "768"]
    for key in ("assumed", "reduced_why", "deployment"):
        assert full[key]


def test_the_cell_is_listed_with_its_metrics():
    listed = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    cell = run.load("cells", CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "rag-and-chat", 1)
    own = {m["name"] for m in SPEC["per_layer"]
           if m.get("workloads") == [CELL]}
    assert own == {
        "window_attn_kernel_pct", "window_attn_roofline_pct",
        "full_attn_kernel_pct", "full_attn_roofline_pct",
        "kv_window_pages_freed", "kv_window_used_peak_pct",
        "window_items_skipped_pct"}
    assert own <= set(run.listed(CELL, "per_layer"))
    assert set(run.listed(CELL, "end_to_end")) == {"gap_p99_ms", "setup_s"}
    # the warm-up's rows reach the longest prompt the mix can draw
    assert max(s["prompt_tokens"] for s in cell["warmup"]["script"]) >= (
        traffic.load_traffic("rag-and-chat")["prompt_tokens"]["max"] - 64)


def test_one_period_of_the_replay_is_one_window():
    mix = traffic.load_traffic("rag-and-chat")
    cell = run.load("cells", CELL)
    assert mix["replay"]["requests"] == round(
        SPEC["run_seconds"] * cell["rate_rps"])
    plan = traffic.open_schedule(mix, cell["rate_rps"],
                                 SPEC["run_seconds"], seed=2**31 + 5)
    assert abs(len(plan) - mix["replay"]["requests"]) <= 1


def test_the_mixture_draws_both_parts_inside_their_clips():
    mix = traffic.load_traffic("rag-and-chat")
    drawn = {k: v for k, v in mix.items() if k != "replay"}
    sizes = traffic.sizes(drawn, 2000, seed=2**31 + 3)
    prompts = sorted(p for p, _ in sizes)
    short = [p for p in prompts if p <= 2048]
    long = [p for p in prompts if p >= 4608]
    assert len(short) + len(long) == 2000  # nothing between the parts
    assert 900 <= len(short) <= 1100
    assert 230 <= short[len(short) // 2] <= 285
    assert 7800 <= long[len(long) // 2] <= 8600
    assert prompts[0] == 32 and prompts[-1] == 14336
    assert all(64 <= o <= 768 for _, o in sizes)
    # every long prompt is longer than one window
    assert min(long) > 4096


def test_the_replayed_seed_is_the_one_the_rule_gives():
    """PR 33's rule on the draw alone: the smallest seed whose N prompts
    and N answers each sum to within 2 % of N times the clipped
    distributions' means (a mixture's mean is its parts' by weight)."""
    import itertools
    import math
    import statistics

    def clipped_mean(spec: dict, steps: int = 20000) -> float:
        if spec["dist"] == "mixture":
            total = sum(p["weight"] for p in spec["parts"])
            return sum(p["weight"] / total * clipped_mean(p)
                       for p in spec["parts"])
        normal = statistics.NormalDist(math.log(spec["median"]),
                                       spec["sigma"])
        return statistics.fmean(
            min(spec["max"], max(spec["min"], round(math.exp(
                normal.inv_cdf((i + 0.5) / steps))))) for i in range(steps))

    mix = traffic.load_traffic("rag-and-chat")
    n, rate = mix["replay"]["requests"], run.load("cells", CELL)["rate_rps"]
    want = {k: n * clipped_mean(mix[k])
            for k in ("prompt_tokens", "output_tokens")}

    def fits(seed: int) -> bool:
        one = list(itertools.islice(traffic._stream(mix, rate, seed), n))
        return all(abs(sum(getattr(p, k) for p in one) / want[k] - 1) <= 0.02
                   for k in want)

    assert next(s for s in itertools.count() if fits(s)) == (
        mix["replay"]["seed"])
