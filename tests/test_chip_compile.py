"""Compile gates that need no chip: the served path's kernels and one
whole step, compiled for a DESCRIBED TPU v5e by the TPU compiler that
ships with the installation.

Interpret-mode parity (test_ragged_attention.py, test_kv_quant.py) checks
numerics; it cannot see what Mosaic or XLA:TPU refuse — a block shape off
the tiling, a program that does not fit the chip, a pool the compiler
re-lays on every step.  These tests see exactly that, in seconds and with
no chip time.  Nothing runs: a compile that passes is not a chip run
(``chip_smoke.py`` is).

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture, never at import and
never ``autouse``; everything compiles in the test's own process; the
persistent compile cache is off around the compiles (such an entry cannot
be read back without a chip); all of it lives in this one file, so one
xdist worker loads the TPU library and keeps it.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from vllm_tgis_adapter_tpu.engine.runner import TPU_STEP_COMPILER_OPTIONS
from vllm_tgis_adapter_tpu.ops import attention as attn_ops
from vllm_tgis_adapter_tpu.ops import kv_quant
from vllm_tgis_adapter_tpu.ops import pallas_attention as pk
from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

#: ibm-granite/granite-3.0-2b-instruct, the widths chip_smoke.py serves
GRANITE_2B = dict(
    vocab_size=49155, hidden_size=2048, intermediate_size=8192,
    num_layers=40, num_heads=32, num_kv_heads=8, head_dim=64,
)
HBM_BYTES = 16 * 10**9  # one v5e chip
BLOCK = 16  # CacheConfig.block_size default
RUN = ra.pages_per_item(BLOCK)  # pages of one work item: 8
PAGES = 1024
LAYERS = 4  # of the pools the kernel-only gates address


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Steer the code's own device decisions to their chip branch: it
    asks ``jax.default_backend()``, which here still says cpu."""
    monkeypatch.setattr(attn_ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(attn_ops, "_pallas_interpret", lambda: False)
    monkeypatch.setattr(ra, "_use_pallas", lambda: True)
    monkeypatch.setattr(ra, "_pallas_interpret", lambda: False)


def _compile(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _ragged_args(spec, *, h, hkv, dh, t, work_w, cache_dtype, seqs=64):
    args = [
        spec((t, h, dh), jnp.bfloat16),
        spec((LAYERS, hkv, PAGES * BLOCK, dh), cache_dtype),
        spec((LAYERS, hkv, PAGES * BLOCK, dh), cache_dtype),
        spec((seqs + 1,), jnp.int32),
        spec((seqs,), jnp.int32),
        spec((ra.work_rows(BLOCK), work_w), jnp.int32),
        spec((), jnp.int32),  # n_items
    ]
    return args


def _layer_shaped(text: str, pool_shape) -> list[str]:
    """Instructions of a compiled program whose result is ONE layer of a
    ``[L, Hkv, slots, Dh]`` pool: the slice, copy or bitcast fusion that
    would feed a kernel a ``cache[layer]`` operand (~127 MB a call at
    granite-2b's pool)."""
    import re

    _, hkv, slots, dh = pool_shape
    return re.findall(
        rf"^\s*(?:ROOT )?(\S+) = \w+\[{hkv},{slots},{dh}\]", text, re.M
    )


@pytest.mark.parametrize(
    "h,hkv,dh,t,block_q,window,alibi,cache_dtype",
    [
        pytest.param(32, 8, 64, 512, 128, 0, False, jnp.bfloat16,
                     id="granite2b-mixed"),
        # the pool's rows are lane-padded on the chip (cache_head_dim):
        # Dh=128 is what granite-2b's served kernel sees, and the width
        # of Mistral-7B / granite-8b heads
        pytest.param(32, 8, 128, 512, 128, 0, False, jnp.bfloat16,
                     id="dh128-mixed"),
        pytest.param(32, 8, 128, 512, 128, 4096, False, jnp.bfloat16,
                     id="dh128-window4096"),
        pytest.param(32, 32, 128, 512, 128, 0, True, jnp.bfloat16,
                     id="alibi-mha"),
        pytest.param(32, 8, 128, 512, 128, 0, False, jnp.int8,
                     id="int8-pages"),
        pytest.param(32, 8, 128, 512, 128, 0, False, jnp.float8_e4m3fn,
                     id="fp8-pages"),
        pytest.param(32, 8, 64, 64, 8, 0, False, jnp.int8,
                     id="int8-pages-dh64-decode"),
    ],
)
def test_ragged_kernel_compiles(
    topo, one_chip, h, hkv, dh, t, block_q, window, alibi, cache_dtype
):
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    quantized = cache_dtype != jnp.bfloat16
    args = _ragged_args(
        spec, h=h, hkv=hkv, dh=dh, t=t, work_w=256,
        cache_dtype=cache_dtype,
    )
    if quantized:
        args += [spec((LAYERS, hkv, PAGES), jnp.float32)] * 2
    if alibi:
        args.append(spec((h,), jnp.float32))

    def kernel(q, kc, vc, starts, base, work, n_items, *rest):
        rest = list(rest)
        scales = (rest.pop(0), rest.pop(0)) if quantized else None
        return ra._ragged_attention_pallas(
            q, kc, vc, starts, base, work, BLOCK, dh**-0.5,
            block_q=block_q, window=window,
            alibi_slopes=rest[0] if alibi else None,
            interpret=False, kv_scales=scales, n_items=n_items,
            layer=LAYERS - 2,
        )

    # the pool is the kernel's operand as it stands: nothing cuts a
    # layer out of it on the way in
    text = _compile(kernel, *args).as_text()
    assert not _layer_shaped(text, (LAYERS, hkv, PAGES * BLOCK, dh))


@pytest.mark.parametrize("tp", [1, 4])
def test_ragged_decode_schedule_compiles(topo, on_chip, tp):
    """The fused decode wave's formulation: one-row spans over the
    compact schedule built in the trace, ``block_q`` 8, and a grid whose
    extent is the TRACED ``n_items`` — on one chip and inside the tp=4
    ``shard_map``, where the scalar travels replicated like ``work``."""
    mesh = Mesh(np.asarray(topo.devices[:tp]).reshape(1, 1, tp),
                ("dp", "sp", "tp"))

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes))
        )

    b, hkv, dh = 64, 8, 128
    max_blocks = 4096 // BLOCK

    def decode(q, kc, vc, pos, active, tables):
        work, n_items = ra.decode_work_schedule(
            pos, active, tables, block_size=BLOCK
        )
        assert work.shape == (ra.work_rows(BLOCK), b * max_blocks // RUN)
        return ra.ragged_paged_attention(
            q, kc, vc, pos, jnp.arange(b + 1, dtype=jnp.int32), pos,
            jnp.asarray(b, jnp.int32), tables, BLOCK, dh**-0.5,
            work=work, n_items=n_items, block_q=ra.decode_block_q(b),
            mesh=mesh if tp > 1 else None, layer=LAYERS - 1,
        )

    pool = (LAYERS, hkv, PAGES * BLOCK, dh)
    text = _compile(
        decode,
        spec((b, 32, dh), jnp.bfloat16, None, "tp", None),
        spec(pool, jnp.bfloat16, None, "tp", None, None),
        spec(pool, jnp.bfloat16, None, "tp", None, None),
        spec((b,), jnp.int32),
        spec((b,), jnp.bool_),
        spec((b, max_blocks), jnp.int32),
    ).as_text()
    assert not _layer_shaped(text, (LAYERS, hkv // tp, PAGES * BLOCK, dh))


def test_prefill_kernels_compile(topo, one_chip):
    """The prompt-logprob path's kernels (ops/pallas_attention.py) at
    granite-2b widths."""
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    t, h, hkv, dh = 512, 32, 8, 64
    _compile(
        lambda q, k, v, n: pk.prefill_attention(q, k, v, dh**-0.5, n),
        spec((t, h, dh), jnp.bfloat16), spec((t, hkv, dh), jnp.bfloat16),
        spec((t, hkv, dh), jnp.bfloat16), spec((), jnp.int32),
    )
    _compile(
        lambda q, kc, vc, table, start, n: pk.chunked_prefill_attention(
            q, kc, vc, table, start, n, BLOCK, dh**-0.5
        ),
        spec((t, h, dh), jnp.bfloat16),
        spec((hkv, PAGES * BLOCK, dh), jnp.bfloat16),
        spec((hkv, PAGES * BLOCK, dh), jnp.bfloat16),
        spec((4096 // BLOCK,), jnp.int32), spec((), jnp.int32),
        spec((), jnp.int32),
    )


def test_ragged_kernel_under_tp4_shard_map(topo, on_chip):
    """tp=4 over the described host's four chips: the kernel cannot be
    partitioned by GSPMD, so it must sit inside shard_map with heads and
    the pool's kv-head axis split four ways — the WHOLE pool, each
    device addressing the layer in the shard it holds, and the mixed
    step's traced grid extent replicated beside the schedule."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4),
                ("dp", "sp", "tp"))

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes))
        )

    t, h, hkv, dh, seqs = 512, 32, 8, 128, 64

    def attend(q, kc, vc, pos, starts, base, tables, work, n_items):
        return ra.ragged_paged_attention(
            q, kc, vc, pos, starts, base, jnp.asarray(t, jnp.int32),
            tables, BLOCK, dh**-0.5, work=work, n_items=n_items,
            mesh=mesh, layer=1,
        )

    pool = (LAYERS, hkv, PAGES * BLOCK, dh)
    text = _compile(
        attend,
        spec((t, h, dh), jnp.bfloat16, None, "tp", None),
        spec(pool, jnp.bfloat16, None, "tp", None, None),
        spec(pool, jnp.bfloat16, None, "tp", None, None),
        spec((t,), jnp.int32), spec((seqs + 1,), jnp.int32),
        spec((seqs,), jnp.int32), spec((seqs, 4096 // BLOCK), jnp.int32),
        spec((ra.work_rows(BLOCK), 256), jnp.int32), spec((), jnp.int32),
    ).as_text()
    assert not _layer_shaped(text, (LAYERS, hkv // 4, PAGES * BLOCK, dh))


def test_head_dim_64_pool_is_lane_padded_on_the_chip(topo):
    """The v5e lays out a pool whose rows are narrower than a lane tile
    with the slot axis minor; scatter and kernel need the head axis
    minor, so the pool's rows are padded there — and nowhere else."""
    chip = topo.devices[0]
    assert kv_quant.cache_head_dim(64, chip) == 128
    assert kv_quant.cache_head_dim(128, chip) == 128
    assert kv_quant.cache_head_dim(64, jax.local_devices()[0]) == 64


def _model_on_chip(topo, one_chip, monkeypatch, cfg):
    """A model at published widths as a described chip would hold it
    (granite-2b at full depth; Mistral-7B at the 16 layers that fit one
    chip): the model, its parameters' shapes (``eval_shape``), the pool
    ``resolve_num_blocks`` would size (90% of HBM less the weights) with
    rows lane-padded as the chip pads them, and the pool's bytes."""
    from vllm_tgis_adapter_tpu.models.llama import LlamaForCausalLM

    chip = topo.devices[0]
    probe = kv_quant.cache_head_dim
    monkeypatch.setattr(
        kv_quant, "cache_head_dim", lambda dh, device=None: probe(dh, chip)
    )
    model = LlamaForCausalLM(cfg)

    def on_chip_shapes(tree):
        return jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=one_chip
            ),
            tree,
        )

    params = on_chip_shapes(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    )
    weight_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params)
    )
    page_bytes = 2 * cfg.num_layers * cfg.num_kv_heads * BLOCK * 128 * 2
    pages = (int(HBM_BYTES * 0.9) - weight_bytes) // page_bytes
    caches = on_chip_shapes(jax.eval_shape(
        lambda: model.make_kv_caches(pages * BLOCK, cfg.dtype)
    ))
    assert caches[0].shape == (
        cfg.num_layers, cfg.num_kv_heads, pages * BLOCK, 128
    )
    return model, params, caches, 2 * caches[0].size * 2


def _assert_in_place_and_fits(compiled, caches, pool_bytes):
    """The kernel is in the program; the donated pool is its operand
    whole and is updated IN PLACE: nothing produces one layer of it (the
    ``cache[layer]`` slice a kernel operand used to be), temporaries are
    activations only (a layer's K and V copies were 250 MB, a pool
    re-layout would show as gigabytes); and weights + pool + temporaries
    fit one 16 GB chip."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _layer_shaped(text, caches[0].shape)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 128 << 20
    total = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert total < HBM_BYTES


def _assert_no_weight_prefetch(compiled):
    """With the runner's own options (``TPU_STEP_COMPILER_OPTIONS``) the
    compiler copies no weight into VMEM ahead of its matmul: each such
    copy is three events of a device trace, and they were three fifths
    of a step's (a 6 s trace of the PR 31 server could not be handed
    over inside the profiler client's 60 s)."""
    import re

    text = compiled.as_text()
    prefetches = re.findall(r" (slice-start|copy-start)\(", text)
    # a handful of copies into the program remain (cross-program
    # prefetch of the first operands); none inside the layers
    assert prefetches.count("slice-start") == 0
    assert prefetches.count("copy-start") <= 4, len(prefetches)


#: the two served widths: head dim 64 (pool rows lane-padded to 128) at
#: full depth, and head dim 128 unpadded at the depth one chip holds
SERVED = pytest.mark.parametrize(
    "cfg", ["granite2b", "mistral7b-l16"], indirect=True
)


@pytest.fixture
def cfg(request):
    return {"granite2b": _granite_2b, "mistral7b-l16": _mistral_7b_l16}[
        request.param
    ]()


@SERVED
def test_whole_ragged_step_compiles_and_fits(topo, one_chip, on_chip,
                                             monkeypatch, cfg):
    """One whole step program of the runner's serving path (mixed
    ragged step, T=512) at published widths, from ``eval_shape``
    parameters (the faults this file exists for: the pre-PR-23 scatter
    re-laid the whole pool twice a step; until PR 31 every attention
    call was fed a copy of its layer's K and V).  The schedule's real
    item count is an operand beside it: the grid's traced extent."""
    model, params, caches, pool_bytes = _model_on_chip(
        topo, one_chip, monkeypatch, cfg
    )
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    t, seqs = 512, 64
    step = jax.jit(
        functools.partial(model.ragged_forward, block_size=BLOCK),
        donate_argnums=(1,),
    )
    compiled = step.lower(
        params, caches, spec((t,), jnp.int32), spec((t,), jnp.int32),
        spec((t,), jnp.int32), spec((seqs + 1,), jnp.int32),
        spec((seqs,), jnp.int32), spec((), jnp.int32),
        spec((seqs, 4096 // BLOCK), jnp.int32), spec((seqs,), jnp.int32),
        work=spec((ra.work_rows(BLOCK), 2048), jnp.int32),
        n_items=spec((), jnp.int32),
    ).compile(compiler_options=TPU_STEP_COMPILER_OPTIONS)
    _assert_in_place_and_fits(compiled, caches, pool_bytes)
    _assert_no_weight_prefetch(compiled)


@SERVED
def test_whole_decode_wave_compiles_and_fits(topo, one_chip, on_chip,
                                             monkeypatch, cfg):
    """The mixed step's twin for the fused wave: ``model.decode`` with
    its hoisted schedule inside a loop of TRACED length, at published
    widths (granite-2b's 40 layers: 16 s of the tier-1 run).
    One program serves every step count and every number of live rows
    and pages: the kernel's grid extent and the loop's bound are both
    operands.  The pool is carried through the loop in place."""
    model, params, caches, pool_bytes = _model_on_chip(
        topo, one_chip, monkeypatch, cfg
    )
    b, max_blocks = 64, 4096 // BLOCK

    def wave(params, caches, tokens, pos0, limits, tables, num_steps):
        def step(k, carry):
            caches, tokens = carry
            pos = pos0 + k
            blk = jnp.take_along_axis(
                tables, (pos // BLOCK)[:, None], axis=1
            )[:, 0]
            slot = jnp.where(pos <= limits, blk * BLOCK + pos % BLOCK, -1)
            logits, caches = model.decode(
                params, caches, tokens, pos, slot, tables, pos + 1, BLOCK
            )
            return caches, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return jax.lax.fori_loop(0, num_steps, step, (caches, tokens))

    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(wave, donate_argnums=(1,)).lower(
        params, caches, spec((b,), jnp.int32), spec((b,), jnp.int32),
        spec((b,), jnp.int32), spec((b, max_blocks), jnp.int32),
        spec((), jnp.int32),
    ).compile(compiler_options=TPU_STEP_COMPILER_OPTIONS)
    # the schedule keeps its one static shape, whatever is live
    # (a row's pages in runs of RUN: an eighth of the items a page each)
    assert (
        f"s32[{ra.work_rows(BLOCK)},{b * max_blocks // RUN}]"
        in compiled.as_text()
    )
    _assert_in_place_and_fits(compiled, caches, pool_bytes)
    _assert_no_weight_prefetch(compiled)


def _granite_2b(num_layers: int = 40):
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

    return ModelConfig(
        model="granite-3.0-2b-instruct", model_type="granite",
        max_model_len=4096, rms_norm_eps=1e-5, tie_word_embeddings=True,
        dtype=jnp.bfloat16, logits_scaling=8.0, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.015625,
        **{**GRANITE_2B, "num_layers": num_layers},
    )


def _mistral_7b_l16():
    """``benchmark/configs/mistral-7b-v0.3-l16.json``: head dim 128, so
    the pool's rows are not padded."""
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

    return ModelConfig(
        model="mistral-7b-v0.3-l16", model_type="mistral",
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=128,
        max_model_len=4096, rope_theta=1e6, rms_norm_eps=1e-5,
        dtype=jnp.bfloat16,
    )


def test_page_movement_is_in_place(topo, one_chip):
    """The host KV tier's one-page gather and restore (ops/kv_quant.py)
    against a pool of the size one chip holds: the first chip run died
    here, the gather asking for a second copy of the K pool."""
    pages = 3872  # what resolve_num_blocks gave granite-2b on the chip
    pool = jax.ShapeDtypeStruct(
        (40, 8, pages * BLOCK, 128), jnp.bfloat16, sharding=one_chip
    )
    page = jax.ShapeDtypeStruct(
        (40, 8, BLOCK, 128), jnp.bfloat16, sharding=one_chip
    )
    idx = jax.ShapeDtypeStruct((BLOCK,), jnp.int32, sharding=one_chip)
    pool_bytes = 2 * pool.size * 2
    gather = jax.jit(kv_quant.gather_kv_page).lower(pool, pool, idx).compile()
    assert gather.memory_analysis().temp_size_in_bytes < pool_bytes // 100
    restore = jax.jit(kv_quant.restore_kv_page, donate_argnums=(0, 1)).lower(
        pool, pool, idx, page, page
    ).compile()
    mem = restore.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100


def test_tp4_step_keeps_the_pool_on_its_shards(topo, on_chip, monkeypatch):
    """The ragged step, 2 layers deep at granite-2b widths, over tp=4 of
    the described host: all-reduces and the kernel are in it, each
    device updates its quarter of the pool in place, and nothing
    gathers the pool across devices."""
    from vllm_tgis_adapter_tpu.models.llama import LlamaForCausalLM
    from vllm_tgis_adapter_tpu.parallel import (
        cache_sharding,
        llama_param_specs,
    )

    chip = topo.devices[0]
    probe = kv_quant.cache_head_dim
    monkeypatch.setattr(
        kv_quant, "cache_head_dim", lambda dh, device=None: probe(dh, chip)
    )
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4),
                ("dp", "sp", "tp"))
    model = LlamaForCausalLM(_granite_2b(num_layers=2))
    model.mesh = mesh
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda leaf, spec: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        ),
        shapes, llama_param_specs(shapes, tp=4),
    )
    caches = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=cache_sharding(mesh)
        ),
        jax.eval_shape(
            lambda: model.make_kv_caches(8192 * BLOCK, jnp.bfloat16)
        ),
    )
    shard_pool_bytes = 2 * caches[0].size * 2 // 4

    def spec(*shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=NamedSharding(mesh, P())
        )

    t, seqs = 512, 64
    compiled = jax.jit(
        functools.partial(model.ragged_forward, block_size=BLOCK),
        donate_argnums=(1,),
    ).lower(
        params, caches, spec(t), spec(t), spec(t), spec(seqs + 1),
        spec(seqs), spec(), spec(seqs, 4096 // BLOCK), spec(seqs),
        work=spec(ra.work_rows(BLOCK), 256), n_items=spec(),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce(" in text
    # each device's kernel reads its own shard of the whole pool
    layers, hkv, slots, dh = caches[0].shape
    assert not _layer_shaped(text, (layers, hkv // 4, slots, dh))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == shard_pool_bytes
    assert mem.temp_size_in_bytes < shard_pool_bytes // 10


def test_lane_padded_pool_serves_the_same_tokens(tiny_model_dir,
                                                 monkeypatch):
    """The padded pool is chosen on the chip only, so the CPU suite would
    never run it: force it for the tiny model (Dh 16 → 128) and require
    the greedy tokens of the unpadded engine."""
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

    def serve() -> tuple[list[int], int]:
        mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
        engine = LLMEngine.from_config(EngineConfig(
            model_config=mcfg,
            cache_config=CacheConfig(block_size=16, num_blocks=32,
                                     cache_dtype=mcfg.dtype),
            scheduler_config=SchedulerConfig(
                max_num_seqs=4, prefill_buckets=(32, 64)
            ),
            parallel_config=ParallelConfig(),
            lora_config=LoRAConfig(),
        ))
        engine.add_request(
            "r", None,
            SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True),
            prompt_token_ids=list(range(3, 40)),
        )
        tokens: list[int] = []
        for _ in range(100):
            if not engine.has_unfinished_requests():
                break
            for out in engine.step():
                tokens = list(out.outputs[0].token_ids)
        return tokens, engine.runner.caches[0].shape[-1]

    plain, width = serve()
    assert width == 16 and len(plain) == 8
    monkeypatch.setattr(
        kv_quant, "cache_head_dim", lambda dh, device=None: 128
    )
    padded, width = serve()
    assert width == 128
    assert padded == plain


# ---------------------------------------------------------------------------
# Falcon-H1: a Mamba-2 mixer beside attention, state beside the pool
# ---------------------------------------------------------------------------


def _falcon_h1_34b_l6():
    """``benchmark/configs/falcon-h1-34b-instruct-l6.json``: the
    published widths of Falcon-H1-34B-Instruct at six layers."""
    import json
    from pathlib import Path

    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

    path = (Path(__file__).resolve().parent.parent / "benchmark" / "configs"
            / "falcon-h1-34b-instruct-l6.json")
    return ModelConfig.from_hf_config(
        "falcon-h1-34b-instruct-l6", json.loads(path.read_text()),
        max_model_len=4096, dtype="bfloat16",
    )


def _falcon_on_chip(one_chip, cfg, rows=64):
    """The model, its parameters' shapes, and its caches as the runner
    builds them: the page pool ``resolve_num_blocks`` would size after
    the weights AND the state slab, then the slab."""
    from vllm_tgis_adapter_tpu.models.falcon_h1 import FalconH1ForCausalLM

    model = FalconH1ForCausalLM(cfg)

    def on_chip_shapes(tree):
        return jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=one_chip),
            tree,
        )

    params = on_chip_shapes(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    weight_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params))
    state_bytes = rows * cfg.mixer.state_bytes_per_row(cfg.num_layers, 2)
    page_bytes = 2 * cfg.num_layers * cfg.num_kv_heads * BLOCK * 128 * 2
    pages = (int(HBM_BYTES * 0.9) - weight_bytes - state_bytes) // page_bytes
    # beside weights and slab the pool is budget-bound: at this file's
    # 16e9-byte chip 11.5k pages (185k tokens), at the 15.75 GiB a v5e
    # reports ~15.6k, under the 16,384 (64 rows x 4,096 tokens) it would
    # be capped at (PERF.md section 4); every row can hold half of
    # max_model_len at once even here
    assert pages * BLOCK >= 64 * 2048, pages
    caches = on_chip_shapes(jax.eval_shape(
        lambda: (*model.make_kv_caches(pages * BLOCK, cfg.dtype),
                 model.make_state(rows))))
    held = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(caches))
    assert held == 2 * caches[0].size * 2 + state_bytes
    return model, params, caches, held


def _assert_state_in_place_and_fits(compiled, held):
    """Pool and slab are both donated and updated in place, the update
    kernel is in the program under its own name, and weights + pool +
    slab + temporaries fit one 16 GB chip."""
    from vllm_tgis_adapter_tpu.ops.ssm_scan import DECODE_KERNEL_NAME

    import re

    text = compiled.as_text()
    # the kernel itself, by the name the device trace will show (a
    # named_scope of the same name is in every op's metadata)
    assert re.search(
        rf"%{DECODE_KERNEL_NAME}[.\w]* = [^\n]*custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"', text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == held
    total = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert total < HBM_BYTES, (total, mem.temp_size_in_bytes)
    return mem


@pytest.mark.parametrize("t", [512, 4096])
def test_falcon_h1_ragged_step_compiles_and_fits(topo, one_chip, on_chip, t):
    """The mixed step of Falcon-H1-34B at its published widths and six
    layers: attention at head dim 128 over the unpadded pool, the
    one-token update kernel over the slab, and the chunked scan's loop
    over pieces, in one program that fits beside pool and slab."""
    cfg = _falcon_h1_34b_l6()
    model, params, caches, held = _falcon_on_chip(one_chip, cfg)
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    seqs = 64
    step = jax.jit(
        functools.partial(model.ragged_forward, block_size=BLOCK),
        donate_argnums=(1,),
    )
    compiled = step.lower(
        params, caches, spec((t,), jnp.int32), spec((t,), jnp.int32),
        spec((t,), jnp.int32), spec((seqs + 1,), jnp.int32),
        spec((seqs,), jnp.int32), spec((), jnp.int32),
        spec((seqs, 4096 // BLOCK), jnp.int32), spec((seqs,), jnp.int32),
        work=spec((ra.work_rows(BLOCK), 512), jnp.int32),
        n_items=spec((), jnp.int32),
        state_slots=spec((seqs,), jnp.int32),
    ).compile(compiler_options=TPU_STEP_COMPILER_OPTIONS)
    _assert_state_in_place_and_fits(compiled, held)


def test_falcon_h1_decode_wave_compiles_and_fits(topo, one_chip, on_chip):
    """The fused wave of Falcon-H1-34B at six layers: the loop of traced
    length carries pool and slab in place; each step one attention call
    and one ``ssm_decode_update`` a layer."""
    cfg = _falcon_h1_34b_l6()
    model, params, caches, held = _falcon_on_chip(one_chip, cfg)
    b, max_blocks = 64, 4096 // BLOCK

    def wave(params, caches, tokens, pos0, limits, tables, rows, num_steps):
        def step(k, carry):
            caches, tokens = carry
            pos = pos0 + k
            blk = jnp.take_along_axis(
                tables, (pos // BLOCK)[:, None], axis=1)[:, 0]
            slot = jnp.where(pos <= limits, blk * BLOCK + pos % BLOCK, -1)
            logits, caches = model.decode(
                params, caches, tokens, pos, slot, tables, pos + 1, BLOCK,
                state_slots=rows,
            )
            return caches, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return jax.lax.fori_loop(0, num_steps, step, (caches, tokens))

    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(wave, donate_argnums=(1,)).lower(
        params, caches, spec((b,), jnp.int32), spec((b,), jnp.int32),
        spec((b,), jnp.int32), spec((b, max_blocks), jnp.int32),
        spec((b,), jnp.int32), spec((), jnp.int32),
    ).compile(compiler_options=TPU_STEP_COMPILER_OPTIONS)
    mem = _assert_state_in_place_and_fits(compiled, held)
    assert mem.temp_size_in_bytes < 512 << 20


# ---- DeepSeek-V2 at its published widths, one dense and seven expert
# layers, one group of 20 experts held (models/deepseek_v2.py)


def _deepseek_v2_ep8_l8():
    """``benchmark/configs/deepseek-v2-ep8-l8.json``: the router's 160
    outputs are read from the checkpoint, which this file has none of."""
    import dataclasses
    import json
    from pathlib import Path

    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

    path = (Path(__file__).resolve().parent.parent / "benchmark" / "configs"
            / "deepseek-v2-ep8-l8.json")
    cfg = ModelConfig.from_hf_config(
        "deepseek-v2-ep8-l8", json.loads(path.read_text()),
        max_model_len=4096, dtype="bfloat16",
    )
    assert (cfg.num_layers, cfg.experts.num_held) == (8, 20)
    return dataclasses.replace(cfg, experts=dataclasses.replace(
        cfg.experts, num_routed=160))


def _deepseek_on_chip(one_chip, cfg):
    """The model, its parameters' shapes and its latent pool as
    ``resolve_num_blocks`` would size it after the weights: 640 lanes a
    token a layer, capped at 64 rows x 4,096 tokens."""
    from vllm_tgis_adapter_tpu.models.deepseek_v2 import (
        DeepseekV2ForCausalLM,
    )

    model = DeepseekV2ForCausalLM(cfg)

    def on_chip_shapes(tree):
        return jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=one_chip),
            tree,
        )

    params = on_chip_shapes(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    weight_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params))
    # 12.14 GB: embedding and head 2.10, the dense layer 0.68, seven
    # expert layers of 1.338 (ISSUE 43's arithmetic)
    assert 12.1e9 < weight_bytes < 12.2e9, weight_bytes
    assert cfg.mla.row_width == 640
    token_bytes = cfg.num_layers * cfg.mla.row_width * 2
    tokens = min((int(HBM_BYTES * 0.9) - weight_bytes) // token_bytes,
                 64 * 4096) // BLOCK * BLOCK
    assert tokens >= 64 * 2048, tokens
    caches = on_chip_shapes(jax.eval_shape(
        lambda: model.make_kv_caches(tokens, cfg.dtype)))
    assert caches[0].shape == (8, tokens, 640)
    # the two running counts take one 512-byte allocation
    return model, params, caches, caches[0].size * 2 + 512


def _assert_latent_in_place_and_fits(compiled, held, temp_limit):
    """The pool is donated and updated in place, the latent kernel is in
    the program under its own name beside the grouped matmul, and
    weights + pool + temporaries fit one 16 GB chip."""
    import re

    text = compiled.as_text()
    assert re.search(
        r"%mla_ragged_attention[.\w]* = [^\n]*custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"', text)
    # the grouped matmul, by the name the trace's readers match
    assert re.search(
        r"%gmm[.\w]* = [^\n]*custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"', text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == held
    total = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert total < HBM_BYTES, (total, mem.temp_size_in_bytes)
    # read here (PR 43): 7.6 MB at 64 tokens, 91 MB at 512, 344 MB at
    # 1,024, 9.6 MB for the wave
    assert mem.temp_size_in_bytes < temp_limit, mem.temp_size_in_bytes


@pytest.mark.parametrize("t,work_w", [(64, 2048), (512, 4096),
                                      (1024, 8192)])
def test_deepseek_v2_ragged_step_compiles_and_fits(topo, one_chip, on_chip,
                                                   t, work_w):
    """The mixed step of DeepSeek-V2 at its published widths, 8 layers
    and 20 held experts: 128 heads over rows of 640 lanes at a query
    block of 4 tokens, the grouped matmul over the held experts' stacks,
    in one program that fits beside the pool.  1,024 tokens is the
    planner's widest step for this model (512 of a prompt and every
    row's one) and 8,192 items the widest schedule the kernel's scalar
    memory takes (``ops/mla_attention.py`` ``MAX_WORK_WIDTH``: the chip
    refused 16,384)."""
    from vllm_tgis_adapter_tpu.ops import mla_attention as mla

    cfg = _deepseek_v2_ep8_l8()
    assert mla.MAX_WORK_WIDTH == 8192
    assert mla.max_step_prefill_tokens(4096, 64, BLOCK) == 512
    model, params, caches, held = _deepseek_on_chip(one_chip, cfg)
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    seqs = 64
    step = jax.jit(
        functools.partial(model.ragged_forward, block_size=BLOCK),
        donate_argnums=(1,),
    )
    compiled = step.lower(
        params, caches, spec((t,), jnp.int32), spec((t,), jnp.int32),
        spec((t,), jnp.int32), spec((seqs + 1,), jnp.int32),
        spec((seqs,), jnp.int32), spec((), jnp.int32),
        spec((seqs, 4096 // BLOCK), jnp.int32), spec((seqs,), jnp.int32),
        work=spec((ra.work_rows(BLOCK), work_w), jnp.int32),
        n_items=spec((), jnp.int32),
    ).compile(compiler_options=TPU_STEP_COMPILER_OPTIONS)
    _assert_latent_in_place_and_fits(compiled, held, 512 << 20)


def test_deepseek_v2_decode_wave_compiles_and_fits(topo, one_chip, on_chip):
    """The fused wave: the loop of traced length carries the pool in
    place; each step one latent attention call a layer over the
    in-trace schedule at a query block of one token, and three grouped
    matmuls an expert layer."""
    cfg = _deepseek_v2_ep8_l8()
    model, params, caches, held = _deepseek_on_chip(one_chip, cfg)
    b, max_blocks = 64, 4096 // BLOCK

    def wave(params, caches, tokens, pos0, limits, tables, num_steps):
        def step(k, carry):
            caches, tokens = carry
            pos = pos0 + k
            blk = jnp.take_along_axis(
                tables, (pos // BLOCK)[:, None], axis=1)[:, 0]
            slot = jnp.where(pos <= limits, blk * BLOCK + pos % BLOCK, -1)
            logits, caches = model.decode(
                params, caches, tokens, pos, slot, tables, pos + 1, BLOCK)
            return caches, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return jax.lax.fori_loop(0, num_steps, step, (caches, tokens))

    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(wave, donate_argnums=(1,)).lower(
        params, caches, spec((b,), jnp.int32), spec((b,), jnp.int32),
        spec((b,), jnp.int32), spec((b, max_blocks), jnp.int32),
        spec((), jnp.int32),
    ).compile(compiler_options=TPU_STEP_COMPILER_OPTIONS)
    _assert_latent_in_place_and_fits(compiled, held, 512 << 20)
