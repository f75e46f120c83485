"""Pallas kernel parity vs the XLA reference attention (interpreter mode).

The XLA implementations in ops/attention.py are the numerical ground
truth; the Pallas kernels must match them bit-for-shape on every backend.
On CPU CI the kernels run through the Pallas interpreter; on TPU the same
code compiles through Mosaic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_tgis_adapter_tpu.ops import attention as ref_ops
from vllm_tgis_adapter_tpu.ops import pallas_attention as pk


def make_paged_case(seed, b, num_kv, g, head_dim, block_size, max_blocks,
                    num_slots, dtype=np.float32):
    """Shared paged-decode test case builder (also used by the on-hardware
    gate in test_tpu_kernels.py — one construction, two suites)."""
    rng = np.random.default_rng(seed)
    h = num_kv * g
    q = rng.standard_normal((b, h, head_dim)).astype(dtype)
    # head-leading cache layout (ops/pallas_attention.py docstring)
    k_cache = rng.standard_normal((num_kv, num_slots, head_dim)).astype(dtype)
    v_cache = rng.standard_normal((num_kv, num_slots, head_dim)).astype(dtype)
    # distinct random pages per sequence, random context lengths
    pages = rng.permutation(num_slots // block_size)[: b * max_blocks]
    block_tables = pages.reshape(b, max_blocks).astype(np.int32)
    context_lens = rng.integers(
        1, max_blocks * block_size + 1, size=b
    ).astype(np.int32)
    return q, k_cache, v_cache, block_tables, context_lens


def ragged_decode_pallas(q, k_cache, v_cache, block_tables, context_lens,
                         block_size, scale, *, window=0, alibi_slopes=None):
    """Serving decode through the RAGGED Pallas kernel (interpret mode):
    each batch row is a one-token span — the formulation that replaced
    the retired folded/perhead decode kernels (docs/ATTENTION.md)."""
    from vllm_tgis_adapter_tpu.ops import ragged_attention as R

    b = int(np.asarray(q).shape[0])
    pos = jnp.maximum(jnp.asarray(context_lens, jnp.int32), 1) - 1
    starts = jnp.arange(b + 1, dtype=jnp.int32)
    # the wave's compact schedule; its traced extent is the kernel's grid
    work, n_items = R.decode_work_schedule(
        pos, jnp.ones(b, bool), jnp.asarray(block_tables, jnp.int32),
        block_size=block_size,
    )
    return R._ragged_attention_pallas(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        starts, pos, work, block_size, scale,
        block_q=R.decode_block_q(b), window=window,
        alibi_slopes=alibi_slopes, interpret=True, n_items=n_items,
    )


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("g", [1, 4])
def test_ragged_decode_matches_reference(seed, g):
    b, num_kv, head_dim, block_size, max_blocks = 5, 2, 64, 16, 4
    q, k_cache, v_cache, bt, cl = make_paged_case(
        seed, b, num_kv, g, head_dim, block_size, max_blocks, num_slots=512
    )
    scale = head_dim**-0.5
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(bt), jnp.asarray(cl), block_size, scale,
    )
    got = ragged_decode_pallas(q, k_cache, v_cache, bt, cl, block_size,
                               scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ragged_decode_short_context_ignores_garbage_pages():
    """Pages beyond context_len must not leak into the output even when
    the block table rows carry arbitrary ids there."""
    b, num_kv, g, head_dim, block_size, max_blocks = 2, 2, 2, 64, 16, 4
    q, k_cache, v_cache, bt, _ = make_paged_case(
        7, b, num_kv, g, head_dim, block_size, max_blocks, num_slots=256
    )
    cl = np.asarray([3, 17], np.int32)  # partial first / second page
    bt_garbage = bt.copy()
    bt_garbage[0, 1:] = 999999  # ids far out of range
    bt_garbage[1, 2:] = -1
    scale = head_dim**-0.5
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(bt), jnp.asarray(cl), block_size, scale,
    )
    got = ragged_decode_pallas(q, k_cache, v_cache, bt_garbage, cl,
                               block_size, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,valid", [(128, 128), (128, 100), (256, 33)])
@pytest.mark.parametrize("g", [1, 4])
def test_flash_prefill_matches_reference(t, valid, g):
    num_kv, head_dim = 2, 64
    h = num_kv * g
    rng = np.random.default_rng(t + valid + g)
    q = rng.standard_normal((t, h, head_dim), dtype=np.float32)
    k = rng.standard_normal((t, num_kv, head_dim), dtype=np.float32)
    v = rng.standard_normal((t, num_kv, head_dim), dtype=np.float32)
    scale = head_dim**-0.5
    ref = ref_ops.prefill_attention_xla(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), scale,
        jnp.asarray(valid),
    )
    got = pk.prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), scale,
        jnp.asarray(valid, jnp.int32),
        block_q=64, block_k=64, interpret=True,
    )
    # only rows the engine consumes (real tokens) must match
    np.testing.assert_allclose(
        np.asarray(got)[:valid], np.asarray(ref)[:valid],
        rtol=2e-5, atol=2e-5,
    )


def test_flash_prefill_bf16():
    t, num_kv, g, head_dim = 128, 2, 2, 64
    h = num_kv * g
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((t, h, head_dim)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((t, num_kv, head_dim)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((t, num_kv, head_dim)), jnp.bfloat16)
    scale = head_dim**-0.5
    ref = ref_ops.prefill_attention_xla(q, k, v, scale, jnp.asarray(t))
    got = pk.prefill_attention(q, k, v, scale, jnp.asarray(t, jnp.int32),
                               interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_engine_end_to_end_with_pallas_backend(tiny_model_dir, monkeypatch):
    """Full engine slice with the Pallas kernels forced (interpreter on
    CPU): prefill writes pages, fused multi-step decode reads them."""
    monkeypatch.setenv("ATTENTION_BACKEND", "pallas")
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=32,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(32,), num_decode_steps=2),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )
    engine = LLMEngine.from_config(config)
    engine.add_request("p", "hello world", SamplingParams(
        temperature=0.0, max_tokens=4, ignore_eos=True))
    outs = []
    for _ in range(50):
        if not engine.has_unfinished_requests():
            break
        outs.extend(engine.step())
    assert outs and len(outs[-1].outputs[0].token_ids) == 4


def test_pallas_kernels_under_tp_mesh(monkeypatch):
    """shard_map-wrapped kernels over the head-sharded TP mesh must match
    the unsharded XLA reference (each shard reads only local heads)."""
    from vllm_tgis_adapter_tpu.ops import attention as attn
    from vllm_tgis_adapter_tpu.parallel import build_mesh

    monkeypatch.setenv("ATTENTION_BACKEND", "pallas")
    b, num_kv, g, head_dim, block_size, max_blocks = 3, 4, 2, 64, 16, 4
    q, k_cache, v_cache, bt, cl = make_paged_case(
        3, b, num_kv, g, head_dim, block_size, max_blocks, num_slots=512
    )
    scale = head_dim**-0.5
    ref = attn.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(bt), jnp.asarray(cl), block_size, scale,
    )

    mesh = build_mesh(tensor_parallel_size=4)
    # decode through the serving ragged kernel (one-token spans), mesh
    # shard_map over the head axis
    from vllm_tgis_adapter_tpu.ops import ragged_attention as R

    pos = jnp.maximum(jnp.asarray(cl, jnp.int32), 1) - 1
    work, n_items = R.decode_work_schedule(
        pos, jnp.ones(b, bool), jnp.asarray(bt), block_size=block_size
    )
    got = R.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        pos, jnp.arange(b + 1, dtype=jnp.int32), pos,
        jnp.asarray(b, jnp.int32), jnp.asarray(bt), block_size, scale,
        work=work, n_items=n_items, block_q=R.decode_block_q(b),
        mesh=mesh,
    )
    # prefill too
    t, valid = 128, 100
    rng = np.random.default_rng(5)
    qp = rng.standard_normal((t, num_kv * g, head_dim), dtype=np.float32)
    kp = rng.standard_normal((t, num_kv, head_dim), dtype=np.float32)
    vp = rng.standard_normal((t, num_kv, head_dim), dtype=np.float32)
    ref_p = attn.prefill_attention_xla(
        jnp.asarray(qp), jnp.asarray(kp), jnp.asarray(vp), scale,
        jnp.asarray(valid),
    )
    got_p = attn.prefill_attention(
        jnp.asarray(qp), jnp.asarray(kp), jnp.asarray(vp), scale,
        jnp.asarray(valid, jnp.int32), mesh=mesh,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_p)[:valid],
                               np.asarray(ref_p)[:valid],
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ chunked prefill


def make_chunk_case(seed, t, valid, start, num_kv, g, head_dim, block_size,
                    dtype=np.float32):
    """A chunk of queries + a cache holding start+valid tokens of context
    at the block table's pages (rest of the cache is noise)."""
    rng = np.random.default_rng(seed)
    total = start + valid
    max_blocks = -(-max(total, 1) // block_size) + 2
    num_slots = max(512, (max_blocks + 4) * block_size)
    q = rng.standard_normal((t, num_kv * g, head_dim)).astype(dtype)
    k_cache = rng.standard_normal((num_kv, num_slots, head_dim)).astype(dtype)
    v_cache = rng.standard_normal((num_kv, num_slots, head_dim)).astype(dtype)
    table = rng.permutation(num_slots // block_size)[:max_blocks].astype(
        np.int32
    )
    return q, k_cache, v_cache, table


@pytest.mark.parametrize("t,valid,start", [
    (64, 64, 128),   # full chunk, deep context
    (64, 33, 48),    # ragged chunk, unaligned start
    (128, 100, 0),   # first chunk (no prior context)
    (32, 32, 7),     # start not page-aligned
])
@pytest.mark.parametrize("g", [1, 4])
def test_chunked_prefill_kernel_matches_decode_formulation(t, valid, start, g):
    num_kv, head_dim, block_size = 2, 64, 16
    q, kc, vc, table = make_chunk_case(
        t + valid + start, t, valid, start, num_kv, g, head_dim, block_size
    )
    scale = head_dim**-0.5

    # ground truth: each query as a decode row with context pos+1
    local = np.arange(t)
    positions = start + local
    ctx = np.where(local < valid, positions + 1, 1).astype(np.int32)
    tables = np.broadcast_to(table[None, :], (t, table.shape[0]))
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(ctx), block_size, scale,
    )

    got = pk.chunked_prefill_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(table), jnp.asarray(start, jnp.int32),
        jnp.asarray(valid, jnp.int32), block_size, scale,
        block_q=32, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got)[:valid], np.asarray(ref)[:valid],
        rtol=2e-5, atol=2e-5,
    )


def test_chunked_prefill_dispatch_under_tp_mesh(monkeypatch):
    """shard_map-wrapped chunk kernel over the head-sharded mesh matches
    the unsharded fallback."""
    from vllm_tgis_adapter_tpu.ops import attention as attn
    from vllm_tgis_adapter_tpu.parallel import build_mesh

    num_kv, g, head_dim, block_size = 4, 2, 64, 16
    t, valid, start = 64, 50, 32
    q, kc, vc, table = make_chunk_case(
        9, t, valid, start, num_kv, g, head_dim, block_size
    )
    scale = head_dim**-0.5
    monkeypatch.setenv("ATTENTION_BACKEND", "xla")
    ref = attn.chunked_prefill_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(table), jnp.asarray(start), jnp.asarray(valid),
        block_size, scale,
    )
    monkeypatch.setenv("ATTENTION_BACKEND", "pallas")
    mesh = build_mesh(tensor_parallel_size=4)
    got = attn.chunked_prefill_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(table), jnp.asarray(start), jnp.asarray(valid),
        block_size, scale, mesh=mesh,
    )
    np.testing.assert_allclose(
        np.asarray(got)[:valid], np.asarray(ref)[:valid],
        rtol=2e-5, atol=2e-5,
    )


# ------------------------------------------------------- sliding window

@pytest.mark.parametrize("window", [8, 24])
@pytest.mark.parametrize("g", [1, 4])
def test_windowed_ragged_decode_matches_reference(window, g):
    """Band-masked ragged decode vs the XLA windowed reference."""
    b, num_kv, head_dim, block_size, max_blocks = 5, 2, 64, 16, 4
    q, k_cache, v_cache, bt, cl = make_paged_case(
        3, b, num_kv, g, head_dim, block_size, max_blocks, num_slots=512
    )
    scale = head_dim**-0.5
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(bt), jnp.asarray(cl), block_size, scale, window=window,
    )
    got = ragged_decode_pallas(q, k_cache, v_cache, bt, cl, block_size,
                               scale, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,valid,window", [
    (128, 128, 16),   # band cuts deep into the prompt
    (128, 100, 16),   # + padding region
    (256, 256, 200),  # window wider than most rows' context
    (64, 64, 1),      # degenerate: attend to self only
])
def test_windowed_flash_prefill_matches_reference(t, valid, window):
    rng = np.random.default_rng(7)
    num_kv, g, head_dim = 2, 2, 32
    h = num_kv * g
    q = rng.standard_normal((t, h, head_dim)).astype(np.float32)
    k = rng.standard_normal((t, num_kv, head_dim)).astype(np.float32)
    v = rng.standard_normal((t, num_kv, head_dim)).astype(np.float32)
    scale = head_dim**-0.5
    ref = ref_ops.prefill_attention_xla(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), scale,
        jnp.asarray(valid), window=window,
    )
    got = pk.prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), scale,
        jnp.asarray(valid, dtype=jnp.int32), window=window, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got)[:valid], np.asarray(ref)[:valid],
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("t,valid,start,window", [
    (64, 64, 64, 16),   # later chunk, band inside prior context
    (64, 40, 100, 8),   # ragged chunk deep in context
    (32, 32, 0, 48),    # first chunk, window wider than the chunk
])
def test_windowed_chunked_prefill_matches_reference(t, valid, start, window):
    """Band-masked chunked kernel vs the windowed decode formulation."""
    rng = np.random.default_rng(11)
    num_kv, g, head_dim, block_size = 2, 2, 32, 16
    h = num_kv * g
    total = start + t
    max_blocks = -(-total // block_size) + 2
    num_slots = 1024
    q = rng.standard_normal((t, h, head_dim)).astype(np.float32)
    k_cache = rng.standard_normal(
        (num_kv, num_slots, head_dim)).astype(np.float32)
    v_cache = rng.standard_normal(
        (num_kv, num_slots, head_dim)).astype(np.float32)
    table = rng.permutation(num_slots // block_size)[:max_blocks].astype(
        np.int32
    )

    # reference: each chunk query as a decode row with a banded context
    local = np.arange(t)
    positions = start + local
    ctx = np.where(local < valid, positions + 1, 1).astype(np.int32)
    tables = np.broadcast_to(table, (t, max_blocks))
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(tables), jnp.asarray(ctx),
        block_size, head_dim**-0.5, window=window,
    )
    got = pk.chunked_prefill_attention(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(table), jnp.asarray(start, jnp.int32),
        jnp.asarray(valid, jnp.int32), block_size, head_dim**-0.5,
        window=window, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got)[:valid], np.asarray(ref)[:valid],
        rtol=2e-5, atol=2e-5,
    )


# ---------------------------------------------------------------- alibi

def _slopes(h):
    from vllm_tgis_adapter_tpu.models.llama import alibi_slopes
    return jnp.asarray(alibi_slopes(h), jnp.float32)


@pytest.mark.parametrize("g", [1, 4])
def test_alibi_ragged_decode_matches_reference(g):
    b, num_kv, head_dim, block_size, max_blocks = 5, 2, 64, 16, 4
    q, k_cache, v_cache, bt, cl = make_paged_case(
        13, b, num_kv, g, head_dim, block_size, max_blocks, num_slots=512
    )
    scale = head_dim**-0.5
    slopes = _slopes(num_kv * g)
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(bt), jnp.asarray(cl), block_size, scale,
        alibi_slopes=slopes,
    )
    got = ragged_decode_pallas(q, k_cache, v_cache, bt, cl, block_size,
                               scale, alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,valid", [(128, 128), (256, 200)])
def test_alibi_flash_prefill_matches_reference(t, valid):
    rng = np.random.default_rng(17)
    num_kv, g, head_dim = 2, 2, 32
    h = num_kv * g
    q = rng.standard_normal((t, h, head_dim)).astype(np.float32)
    k = rng.standard_normal((t, num_kv, head_dim)).astype(np.float32)
    v = rng.standard_normal((t, num_kv, head_dim)).astype(np.float32)
    scale = head_dim**-0.5
    slopes = _slopes(h)
    ref = ref_ops.prefill_attention_xla(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), scale,
        jnp.asarray(valid), alibi_slopes=slopes,
    )
    got = pk.prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), scale,
        jnp.asarray(valid, dtype=jnp.int32), alibi_slopes=slopes,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got)[:valid], np.asarray(ref)[:valid],
        rtol=2e-5, atol=2e-5,
    )


def test_alibi_chunked_prefill_matches_reference():
    rng = np.random.default_rng(19)
    num_kv, g, head_dim, block_size = 2, 2, 32, 16
    h = num_kv * g
    t, start = 64, 64
    max_blocks = -(-(start + t) // block_size) + 2
    num_slots = 1024
    q = rng.standard_normal((t, h, head_dim)).astype(np.float32)
    k_cache = rng.standard_normal(
        (num_kv, num_slots, head_dim)).astype(np.float32)
    v_cache = rng.standard_normal(
        (num_kv, num_slots, head_dim)).astype(np.float32)
    table = rng.permutation(num_slots // block_size)[:max_blocks].astype(
        np.int32
    )
    slopes = _slopes(h)

    local = np.arange(t)
    ctx = (start + local + 1).astype(np.int32)
    tables = np.broadcast_to(table, (t, max_blocks))
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(tables), jnp.asarray(ctx),
        block_size, head_dim**-0.5, alibi_slopes=slopes,
    )
    got = pk.chunked_prefill_attention(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(table), jnp.asarray(start, jnp.int32),
        jnp.asarray(t, jnp.int32), block_size, head_dim**-0.5,
        alibi_slopes=slopes, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ragged_decode_fp8_cache_matches_reference():
    """--kv-cache-dtype float8_e4m3 through the ragged Pallas kernel:
    the cache stores f8, the kernel casts to f32 on read — parity with
    the XLA formulation on the same quantized cache (the on-chip Mosaic
    gate for this dtype rides tests/test_tpu_kernels.py)."""
    b, num_kv, g, head_dim, block_size, max_blocks = 4, 2, 2, 64, 16, 4
    q, k_cache, v_cache, bt, cl = make_paged_case(
        0, b, num_kv, g, head_dim, block_size, max_blocks, num_slots=512
    )
    kc8 = jnp.asarray(k_cache).astype(jnp.float8_e4m3fn)
    vc8 = jnp.asarray(v_cache).astype(jnp.float8_e4m3fn)
    scale = head_dim**-0.5
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), kc8, vc8,
        jnp.asarray(bt), jnp.asarray(cl), block_size, scale,
    )
    got = ragged_decode_pallas(q, kc8, vc8, bt, cl, block_size, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "window,alibi,g",
    [(0, False, 4), (0, False, 1), (24, False, 2), (0, True, 2)],
)
def test_ragged_decode_mask_combinations_match(window, alibi, g):
    """Window/ALiBi/GQA combinations through the ONE serving decode
    kernel (ragged) — the grid the retired folded/perhead variants used
    to cover."""
    b, num_kv, head_dim, block_size, max_blocks = 4, 2, 64, 16, 4
    q, k_cache, v_cache, bt, cl = make_paged_case(
        3, b, num_kv, g, head_dim, block_size, max_blocks, num_slots=512
    )
    h = num_kv * g
    slopes = (
        jnp.asarray(np.geomspace(0.5, 0.004, h), jnp.float32)
        if alibi else None
    )
    scale = head_dim**-0.5
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(bt), jnp.asarray(cl), block_size, scale,
        window=window, alibi_slopes=slopes,
    )
    got = ragged_decode_pallas(q, k_cache, v_cache, bt, cl, block_size,
                               scale, window=window, alibi_slopes=slopes)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5,
    )


def test_decode_kernel_ladder_is_retired():
    """The folded/perhead/xla decode variant ladder is GONE: neither the
    ops dispatcher nor the Pallas module exposes it, and the runner has
    no retry chain — a lowering failure is a real error, not a silent
    slow-path fallback (docs/ATTENTION.md)."""
    from vllm_tgis_adapter_tpu.engine.runner import ModelRunner

    for name in ("paged_decode_attention", "decode_kernel_variant",
                 "degrade_decode_kernel", "reset_decode_kernel",
                 "is_kernel_lowering_error"):
        assert not hasattr(ref_ops, name), name
    assert not hasattr(pk, "paged_decode_attention")
    assert not hasattr(ModelRunner, "_decode_kernel_retry")


def test_ragged_decode_single_row():
    """b=1 decode (the narrowest serving shape) through the ragged
    kernel."""
    b, num_kv, g, head_dim, block_size, max_blocks = 1, 2, 2, 64, 16, 4
    q, k_cache, v_cache, bt, cl = make_paged_case(
        21, b, num_kv, g, head_dim, block_size, max_blocks, num_slots=256
    )
    scale = head_dim**-0.5
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(bt), jnp.asarray(cl), block_size, scale,
    )
    got = ragged_decode_pallas(q, k_cache, v_cache, bt, cl, block_size,
                               scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ragged_decode_nonpow2_batch():
    """Non-power-of-two batch widths must not mis-pad the in-kernel
    query-block grid (the dense-schedule t_pad regression class)."""
    b, num_kv, g, head_dim, block_size, max_blocks = 11, 2, 2, 64, 16, 4
    q, k_cache, v_cache, bt, cl = make_paged_case(
        23, b, num_kv, g, head_dim, block_size, max_blocks, num_slots=1024
    )
    scale = head_dim**-0.5
    ref = ref_ops.paged_decode_attention_xla(
        jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
        jnp.asarray(bt), jnp.asarray(cl), block_size, scale,
    )
    got = ragged_decode_pallas(q, k_cache, v_cache, bt, cl, block_size,
                               scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
