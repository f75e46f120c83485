"""Quantized KV pages (--kv-quantization, ops/kv_quant.py,
docs/QUANTIZATION.md).

Layers: scale-discipline units (per-page-per-head scale set at the
page's first-slot write, dequant roundtrip bounds, page-reuse reset,
byte-identity of the ``none`` scheme), ragged-kernel parity against the
XLA reference dequant in pallas-interpret mode, the quantized
demote→promote roundtrip through the host KV tier (scale sidecar
travels with the page, token-identical, digest/validation over the
quantized bytes), compile discipline (the quantized path adds ZERO
entry-point shapes over the unquantized lattice), token-quality bounds
vs an unquantized baseline, and the truthful-flags surface
(--kv-quantization validation subsuming --kv-cache-dtype).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vllm_tgis_adapter_tpu.ops import kv_quant


# --------------------------------------------------------- scale units


def _fresh_cache(scheme, *, layers=1, heads=2, pages=8, bs=16, dh=32):
    return kv_quant.make_kv_cache(
        (layers, heads, pages * bs, dh), jnp.float32, scheme, bs
    )


@pytest.mark.parametrize("scheme", ["int8", "fp8"])
def test_full_page_scatter_roundtrip(scheme):
    """A page written in one scatter dequantizes back within the
    scheme's quantization step (scale = slot-0 row amax x margin)."""
    rng = np.random.default_rng(0)
    cache = _fresh_cache(scheme)
    vals = rng.standard_normal((16, 2, 32)).astype(np.float32)
    slots = jnp.arange(16, dtype=jnp.int32)  # page 0, slot 0 included
    cache = kv_quant.scatter_layer(cache, 0, slots, jnp.asarray(vals))
    scale = np.asarray(cache.scale[0][:, 0])  # [H]
    assert (scale > 0).all()
    dec = np.asarray(kv_quant.dequantize(
        cache.data[0, :, :16, :], cache.scale[0][:, 0][:, None, None]
    ))
    orig = np.swapaxes(vals, 0, 1)
    # error bound per scheme: int8 is uniform (half a scale bin);
    # fp8 e4m3 carries 3 mantissa bits, so its error is RELATIVE
    # (~value/16 at half-spacing).  Clipping slack for rows larger
    # than margin x slot-0 amax.
    amax0 = np.abs(orig[:, 0, :]).max(axis=-1)
    limit = scale * kv_quant.qmax_for(cache.data.dtype)
    clipped = np.abs(orig) > limit[:, None, None]
    target = np.clip(orig, -limit[:, None, None], limit[:, None, None])
    err = np.abs(dec - target)
    bound = np.maximum(
        scale[:, None, None] * 0.75, np.abs(target) / 16.0
    )
    assert (err <= bound).all(), err.max()
    # the margin keeps clipping rare on near-stationary magnitudes
    assert clipped.mean() < 0.02
    assert (amax0 > 0).all()


def test_scale_set_only_at_slot0_and_append_clips():
    """Appends to a page KEEP the stored scale (append-consistency —
    the token-identity anchor): values past margin x the slot-0 amax
    clip instead of silently rescaling previously stored integers."""
    cache = _fresh_cache("int8")
    first = jnp.ones((1, 2, 32), jnp.float32)
    cache = kv_quant.scatter_layer(
        cache, 0, jnp.asarray([0], jnp.int32), first
    )
    s0 = np.asarray(cache.scale[0][:, 0]).copy()
    np.testing.assert_allclose(
        s0, kv_quant.SCALE_MARGIN / 127.0, rtol=1e-6
    )
    # append a much larger row: scale must NOT move, value must clip
    big = jnp.full((1, 2, 32), 100.0, jnp.float32)
    cache = kv_quant.scatter_layer(
        cache, 0, jnp.asarray([1], jnp.int32), big
    )
    np.testing.assert_array_equal(
        np.asarray(cache.scale[0][:, 0]), s0
    )
    assert int(np.asarray(cache.data[0, 0, 1, 0])) == 127  # clipped
    # rewriting slot 0 (page reuse / spec rewrite) re-sets the scale
    cache = kv_quant.scatter_layer(
        cache, 0, jnp.asarray([0], jnp.int32), big
    )
    np.testing.assert_allclose(
        np.asarray(cache.scale[0][:, 0]),
        100.0 * kv_quant.SCALE_MARGIN / 127.0, rtol=1e-6,
    )


def test_pad_rows_never_touch_scale_or_data():
    """Padding rows carry slot == num_slots (positive OOB): their page
    index lands out of bounds and BOTH scatters drop them."""
    cache = _fresh_cache("int8")
    vals = jnp.ones((2, 2, 32), jnp.float32)
    slots = jnp.asarray([cache.shape[2], cache.shape[2]], jnp.int32)
    out = kv_quant.scatter_layer(cache, 0, slots, vals)
    np.testing.assert_array_equal(
        np.asarray(out.data), np.asarray(cache.data)
    )
    np.testing.assert_array_equal(
        np.asarray(out.scale), np.asarray(cache.scale)
    )


def test_none_scheme_is_byte_identical():
    """``none`` keeps plain arrays and the helper paths ARE the
    historical expressions — bit-for-bit, not just numerically."""
    rng = np.random.default_rng(1)
    shape = (1, 2, 64, 8)
    cache = kv_quant.make_kv_cache(shape, jnp.bfloat16, "none", 16)
    assert isinstance(cache, jax.Array)
    assert not kv_quant.is_quantized(cache)
    vals = jnp.asarray(
        rng.standard_normal((4, 2, 8)).astype(np.float32)
    )
    slots = jnp.asarray([0, 1, 64, 7], jnp.int32)  # incl. a pad drop
    got = kv_quant.scatter_layer(cache, 0, slots, vals)
    want = cache.at[0, :, slots].set(
        vals.astype(cache.dtype), mode="drop"
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert kv_quant.layer_scales(cache, cache, 0) is None
    # page movement keeps the historical (k, v) tuple
    moved = kv_quant.gather_kv_page(
        got, got, jnp.arange(16, dtype=jnp.int32)
    )
    assert len(moved) == 2


# ------------------------------------------------- kernel parity (pallas)


@pytest.mark.parametrize("scheme", ["int8", "fp8"])
def test_ragged_kernel_parity_pallas_interpret(scheme, monkeypatch):
    """The Pallas in-register dequant must match the XLA post-gather
    reference on a mixed prompt+decode stream (sparse host schedule),
    scale sidecars included."""
    from vllm_tgis_adapter_tpu.ops.ragged_attention import (
        build_work_schedule,
        ragged_paged_attention,
    )

    rng = np.random.default_rng(0)
    Hkv, H, Dh, bs = 2, 4, 32, 16
    num_pages = 8
    kc = _fresh_cache(scheme, heads=Hkv, pages=num_pages, dh=Dh)
    vc = _fresh_cache(scheme, heads=Hkv, pages=num_pages, dh=Dh)

    # seq 1's 30-token context lives in pages 4-5 (written first)
    ctx_slots = jnp.asarray(
        np.arange(4 * bs, 4 * bs + 30, dtype=np.int32)
    )
    kc = kv_quant.scatter_layer(
        kc, 0, ctx_slots,
        jnp.asarray(rng.standard_normal((30, Hkv, Dh)), jnp.float32),
    )
    vc = kv_quant.scatter_layer(
        vc, 0, ctx_slots,
        jnp.asarray(rng.standard_normal((30, Hkv, Dh)), jnp.float32),
    )
    # flat stream: 20-row prompt span (seq 0) + 1 decode row (seq 1)
    t = 21
    slots = jnp.asarray(
        np.concatenate([np.arange(0, 20), [4 * bs + 30]]), jnp.int32
    )
    kc = kv_quant.scatter_layer(
        kc, 0, slots,
        jnp.asarray(rng.standard_normal((t, Hkv, Dh)), jnp.float32),
    )
    vc = kv_quant.scatter_layer(
        vc, 0, slots,
        jnp.asarray(rng.standard_normal((t, Hkv, Dh)), jnp.float32),
    )

    q = jnp.asarray(rng.standard_normal((t, H, Dh)), jnp.float32)
    positions = jnp.asarray(
        np.concatenate([np.arange(20), [30]]), jnp.int32
    )
    seq_starts = jnp.asarray([0, 20, 21], jnp.int32)
    pos_base = jnp.asarray([0, 30], jnp.int32)
    tables = np.full((2, num_pages), -1, np.int32)
    tables[0, :2] = [0, 1]
    tables[1, :3] = [4, 5, 6]

    args = (
        q, kv_quant.layer_data(kc, 0), kv_quant.layer_data(vc, 0),
        positions, seq_starts, pos_base, jnp.asarray(t, jnp.int32),
        jnp.asarray(tables), bs, Dh ** -0.5,
    )
    scales = kv_quant.layer_scales(kc, vc, 0)
    ref = ragged_paged_attention(*args, kv_scales=scales)  # XLA on CPU
    work, _ = build_work_schedule(
        [(0, 20, 0), (20, 1, 30)], tables,
        block_size=bs, block_q=8, t_pad=24,
    )
    monkeypatch.setenv("ATTENTION_BACKEND", "pallas")
    got = ragged_paged_attention(
        *args, kv_scales=scales, work=jnp.asarray(work), block_q=8
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-5
    )


# --------------------------------------------- store units (scale sidecar)


def test_tier_entry_carries_and_validates_scale_sidecar():
    """Quantized tier entries are 4-array tuples (k, v, k_scale,
    v_scale); validation pins EVERY member — a corrupt scale column is
    dropped, never served."""
    from vllm_tgis_adapter_tpu.engine.kv_tier import HostKVTier

    tier = HostKVTier(1 << 20, 16)
    rng = np.random.default_rng(0)

    def page(seed):
        r = np.random.default_rng(seed)
        return (
            r.integers(-127, 127, size=(2, 2, 16, 8), dtype=np.int64)
            .astype(np.int8),
            r.integers(-127, 127, size=(2, 2, 16, 8), dtype=np.int64)
            .astype(np.int8),
            r.random((2, 2)).astype(np.float32),
            r.random((2, 2)).astype(np.float32),
        )

    d_ok, d_bad = b"ok" * 16, b"bad" * 11
    tier.submit([(d_ok, *page(0)), (d_bad, *page(1))])
    assert tier.peek_pages([d_ok]) == 1
    entry = tier._entries[d_ok]
    assert len(entry.arrays) == 4
    # corrupt the SCALE member only
    bad = tier._entries[d_bad]
    bad.arrays = bad.arrays[:2] + (
        bad.arrays[2][:1], bad.arrays[3]
    )
    assert tier._get_valid(d_bad) is None
    assert d_bad not in tier._entries  # dropped, not served
    assert tier.dropped_corrupt == 1
    assert tier._get_valid(d_ok) is not None
    _ = rng


# ----------------------------------- engine: quality, tier, compile shapes


def _build_engine(model_dir, kvq, *, num_blocks=64, tier_gb=0.0,
                  prefix=False, lora=False):
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    mcfg = ModelConfig.from_pretrained(model_dir, dtype="float32")
    return LLMEngine.from_config(EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16, num_blocks=num_blocks, cache_dtype=mcfg.dtype,
            kv_quantization=kvq, enable_prefix_caching=prefix,
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(32, 64)
        ),
        parallel_config=ParallelConfig(),
        lora_config=(
            LoRAConfig(enabled=True, max_loras=2, max_lora_rank=8)
            if lora else LoRAConfig()
        ),
        kv_host_cache_gb=tier_gb,
    ))


def _run(eng, rid, ids, n=10, logprobs=None, lora_name=None):
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        SamplingParams,
    )

    eng.add_request(
        rid, None,
        SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True,
                       logprobs=logprobs),
        prompt_token_ids=ids,
        lora_name=lora_name,
    )
    for _ in range(600):
        if not eng.has_unfinished_requests():
            break
        for out in eng.step():
            if out.finished and out.request_id == rid:
                return out.outputs[0]
    raise AssertionError(f"request {rid} did not finish")


@pytest.mark.parametrize(
    ("scheme", "lora"), [("int8", None), ("fp8", None), ("int8", "tiny-lora")]
)
def test_quantized_engine_token_quality(
    tiny_model_dir, adapter_cache_dir, scheme, lora
):
    """Greedy decode under quantized KV must track the unquantized
    baseline: bounded chosen-token logprob deltas over the matched
    prefix — for base-model rows and for rows that ride an adapter
    (whose K/V the quantized pages hold all the same)."""
    import asyncio

    prompt = list(range(3, 40))

    def generate(kvq):
        eng = _build_engine(tiny_model_dir, kvq, lora=lora is not None)
        if lora is not None:
            asyncio.run(eng.lora_manager.load_lora_adapter(
                lora, f"{adapter_cache_dir}/{lora}"
            ))
        return _run(eng, "r", prompt, 12, 1, lora_name=lora)

    base = generate("none")
    got = generate(scheme)
    matched = 0
    deltas = []
    for tb, tq, db, dq in zip(
        base.token_ids, got.token_ids, base.logprobs, got.logprobs
    ):
        if tb != tq:
            break
        matched += 1
        deltas.append(abs(db[tb].logprob - dq[tq].logprob))
    assert matched >= int(0.8 * len(base.token_ids))
    assert float(np.mean(deltas)) < 0.05


def test_quantized_demote_promote_token_identical(tiny_model_dir):
    """The acceptance shape: a device pool too small to keep the warm
    prefix resident demotes QUANTIZED pages (+ scale sidecars) into the
    host tier; the warm re-send promotes them back and decodes
    token-identically — and the per-page movement programs hold ONE
    compiled shape each (zero new shapes on the quantized path)."""
    from vllm_tgis_adapter_tpu import compile_tracker

    eng = _build_engine(
        tiny_model_dir, "int8", num_blocks=8, tier_gb=1.0, prefix=True
    )
    prompt = list(range(3, 40))
    cold = _run(eng, "cold", prompt).token_ids
    _run(eng, "churn1", list(range(100, 160)))
    _run(eng, "churn2", list(range(200, 260)))
    warm = _run(eng, "warm", prompt).token_ids
    assert warm == cold
    st = eng.kv_tier.debug_state()
    assert st["demoted_pages"] > 0
    assert st["promoted_pages"] > 0
    for fn in ("gather_kv", "scatter_kv"):
        shapes = {s for s in compile_tracker.shapes() if s[0] == fn}
        assert len(shapes) == 1, (fn, shapes)


def test_quantized_spec_verify_matches_plain_decode(tiny_model_dir):
    """Speculative verify spans under quantized KV: greedy outputs must
    equal the same quantized engine WITHOUT a draft.  This pins the
    scale discipline across the verify-rewrite path — a rejected
    draft's slot-0 rewrite re-sets the page scale from the corrected
    token, so the quantized ints a later read sees are identical to
    the plain decode's."""
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
        SpeculativeConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    def build(spec: bool):
        mcfg = ModelConfig.from_pretrained(tiny_model_dir,
                                           dtype="float32")
        return LLMEngine.from_config(EngineConfig(
            model_config=mcfg,
            cache_config=CacheConfig(
                block_size=16, num_blocks=64, cache_dtype=mcfg.dtype,
                kv_quantization="int8",
            ),
            scheduler_config=SchedulerConfig(
                max_num_seqs=4, prefill_buckets=(32, 64)
            ),
            parallel_config=ParallelConfig(),
            lora_config=LoRAConfig(),
            speculative=(
                SpeculativeConfig(
                    draft_model=tiny_model_dir,
                    num_speculative_tokens=3,
                    draft_model_config=mcfg,
                )
                if spec
                else None
            ),
        ))

    prompt = list(range(3, 30))
    plain = _run(build(False), "r", prompt, 14).token_ids
    eng = build(True)
    spec = _run(eng, "r", prompt, 14).token_ids
    assert eng.runner.spec is not None
    assert eng.runner.spec.stats.proposed > 0  # verify actually ran
    assert spec == plain


def test_quantized_path_adds_no_entry_point_shapes(tiny_model_dir):
    """Same workload, quantized vs not: the set of compiled
    (fn, shape) labels is IDENTICAL — quantization lives inside the
    existing programs, never as a new compile surface."""
    from vllm_tgis_adapter_tpu import compile_tracker

    def shapes_for(kvq):
        compile_tracker.reset()
        eng = _build_engine(tiny_model_dir, kvq)
        _run(eng, "a", list(range(3, 30)))
        _run(eng, "b", list(range(50, 95)), 6)
        return set(compile_tracker.shapes())

    assert shapes_for("none") == shapes_for("int8")


# ------------------------------------------------------- truthful flags


def test_kv_quantization_flag_validation(tiny_model_dir):
    from vllm_tgis_adapter_tpu.engine.config import EngineConfig
    from vllm_tgis_adapter_tpu.tgis_utils.args import make_parser

    def cfg(*extra):
        return EngineConfig.from_args(make_parser().parse_args(
            ["--model", tiny_model_dir, *extra]
        ))

    assert cfg().cache_config.kv_quantization == "none"
    assert cfg(
        "--kv-quantization", "int8"
    ).cache_config.kv_quantization == "int8"
    # --kv-cache-dtype quantized spellings FOLD into --kv-quantization
    # (the raw-cast path is retired: docs/QUANTIZATION.md)
    assert cfg(
        "--kv-cache-dtype", "float8_e4m3"
    ).cache_config.kv_quantization == "fp8"
    assert cfg(
        "--kv-cache-dtype", "int8"
    ).cache_config.kv_quantization == "int8"
    with pytest.raises(ValueError, match="conflicts"):
        cfg("--kv-cache-dtype", "fp8", "--kv-quantization", "int8")
    # agreeing spellings are fine
    assert cfg(
        "--kv-cache-dtype", "fp8", "--kv-quantization", "fp8"
    ).cache_config.kv_quantization == "fp8"
    # kernel-unsupported combos refuse at BOOT with actionable text
    with pytest.raises(ValueError, match="swap-space"):
        cfg("--kv-quantization", "int8", "--swap-space", "1")
    with pytest.raises(ValueError, match="sequence-parallel"):
        cfg("--kv-quantization", "int8",
            "--sequence-parallel-size", "2")
    with pytest.raises(ValueError, match="pipeline"):
        cfg("--kv-quantization", "int8",
            "--pipeline-parallel-size", "2")


def test_kv_cache_dtype_rejects_unserved_layouts(tiny_model_dir):
    """The old path resolved any dtype string and failed as a trace
    error inside make_kv_caches; now an unserved layout is an
    actionable BOOT error."""
    import argparse

    from vllm_tgis_adapter_tpu.engine.config import EngineConfig
    from vllm_tgis_adapter_tpu.tgis_utils.args import make_parser

    args = make_parser().parse_args(["--model", tiny_model_dir])
    # bypass argparse choices: the library path accepts any namespace
    args = argparse.Namespace(**{**vars(args), "kv_cache_dtype": "int4"})
    with pytest.raises(ValueError, match="kv-quantization"):
        EngineConfig.from_args(args)


# ------------------------------------- calibrated scale floors (ISSUE 14)


def test_calibrated_floor_raises_page_scale_at_slot0():
    """A checkpoint-calibrated k/v scale FLOORS the slot-0 amax scale
    (outlier-prone heads keep the calibrated headroom) without ever
    SHRINKING an amax that genuinely exceeds it — and appends still
    never move the stored scale."""
    floor = np.asarray([[0.5, 0.001]], np.float32)  # [L=1, H=2]
    cache = kv_quant.make_kv_cache(
        (1, 2, 8 * 16, 32), jnp.float32, "int8", 16, scale_floor=floor
    )
    vals = jnp.ones((1, 2, 32), jnp.float32)
    cache = kv_quant.scatter_layer(
        cache, 0, jnp.asarray([0], jnp.int32), vals
    )
    got = np.asarray(cache.scale[0][:, 0])
    amax_scale = kv_quant.SCALE_MARGIN / 127.0  # ~0.0157
    # head 0: floored at 0.5 (calibration wins over amax)
    np.testing.assert_allclose(got[0], 0.5, rtol=1e-6)
    # head 1: amax wins over the tiny floor
    np.testing.assert_allclose(got[1], amax_scale, rtol=1e-6)
    # appends keep the floored scale (append-consistency holds)
    cache = kv_quant.scatter_layer(
        cache, 0, jnp.asarray([1], jnp.int32),
        jnp.full((1, 2, 32), 3.0, jnp.float32),
    )
    np.testing.assert_allclose(
        np.asarray(cache.scale[0][:, 0]), got, rtol=1e-6
    )
    # page movement carries the floor through (pytree child survives)
    moved = kv_quant.restore_kv_page(
        cache, cache, jnp.arange(16, dtype=jnp.int32),
        *kv_quant.gather_kv_page(cache, cache, jnp.arange(16, dtype=jnp.int32)),
    )
    assert moved[0].floor is not None
    np.testing.assert_allclose(np.asarray(moved[0].floor), floor)


def test_calibrated_checkpoint_floors_load_and_apply(tmp_path):
    """A synthetic calibrated checkpoint (k_scale/v_scale tensors per
    layer) surfaces [L, Hkv] floors through the loader, the runner
    pops them off the params pytree, and the quantized caches carry
    them (ISSUE 14 satellite)."""
    import os

    from safetensors.numpy import load_file, save_file

    from tests.fixture_models import TINY_LLAMA_CONFIG, build_tiny_llama

    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.weights import load_model_params

    model_dir = str(tmp_path / "calib")
    build_tiny_llama(model_dir)
    st = os.path.join(model_dir, "model.safetensors")
    tensors = dict(load_file(st))
    hkv = TINY_LLAMA_CONFIG["num_key_value_heads"]
    # layer 0: scalar k_scale (broadcasts over heads) + per-head v
    tensors["model.layers.0.self_attn.k_scale"] = np.asarray(
        [0.25], np.float32
    )
    tensors["model.layers.0.self_attn.v_scale"] = np.linspace(
        0.1, 0.2, hkv
    ).astype(np.float32)
    save_file(tensors, st)

    mcfg = ModelConfig.from_pretrained(model_dir, dtype="float32")
    params = load_model_params(mcfg, model_dir)
    k_floors, v_floors = params["kv_scale_floors"]
    assert k_floors.shape == (mcfg.num_layers, hkv)
    np.testing.assert_allclose(k_floors[0], 0.25)
    np.testing.assert_allclose(k_floors[1], 0.0)  # layer 1 uncalibrated
    np.testing.assert_allclose(v_floors[0, -1], 0.2)

    engine = LLMEngine.from_config(EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16, num_blocks=32, cache_dtype=mcfg.dtype,
            kv_quantization="int8",
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(32, 64)),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    ))
    k_cache, v_cache = engine.runner.caches
    assert k_cache.floor is not None
    np.testing.assert_allclose(np.asarray(k_cache.floor)[0], 0.25)
    # the sidecar never leaked into the jitted params pytree
    assert "kv_scale_floors" not in engine.runner.params
    # and the engine still serves (floored scales participate in the
    # real scatter path)
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        SamplingParams,
    )

    engine.add_request(
        "c", None,
        SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
        prompt_token_ids=list(range(3, 40)),
    )
    done = False
    for _ in range(200):
        if not engine.has_unfinished_requests():
            done = True
            break
        for out in engine.step():
            pass
    assert done
    # the steps donated the caches read above: take the live ones
    k_cache, _ = engine.runner.caches
    scale0 = np.asarray(k_cache.scale[0])
    assert (scale0[scale0 > 0] >= 0.25 - 1e-6).all(), (
        "written pages ignored the calibrated floor"
    )
