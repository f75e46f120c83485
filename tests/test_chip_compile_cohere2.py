"""Compile gates for Command A+ (``cohere2_moe``) at its published
widths, one period of four layers and 16 held experts, for a DESCRIBED
TPU v5e: the mixed step and the fused wave, each with the window layers'
and the full layer's kernel calls under their own names and schedules,
at 16 query heads a KV head.  Nothing runs (tests/test_chip_compile.py
has the rules; this model's cases are a file of their own so that
another xdist worker takes them)."""

from __future__ import annotations

import functools
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from vllm_tgis_adapter_tpu.engine.runner import TPU_STEP_COMPILER_OPTIONS
from vllm_tgis_adapter_tpu.ops import attention as attn_ops
from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

HBM_BYTES = 16 * 10**9  # one v5e chip
BLOCK = 128  # the page size the server derives for this model
MAX_LEN = 16384
ROWS = 64  # the server's default --max-num-seqs


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
    monkeypatch.setattr(attn_ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(attn_ops, "_pallas_interpret", lambda: False)
    monkeypatch.setattr(ra, "_use_pallas", lambda: True)
    monkeypatch.setattr(ra, "_pallas_interpret", lambda: False)


def _command_a_plus_ep8_l4():
    """``benchmark/configs/command-a-plus-ep8-l4.json``: the router's 128
    outputs are read from the checkpoint, which this file has none of."""
    import dataclasses

    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

    path = (Path(__file__).resolve().parent.parent / "benchmark" / "configs"
            / "command-a-plus-ep8-l4.json")
    cfg = ModelConfig.from_hf_config(
        "command-a-plus-ep8-l4", json.loads(path.read_text()),
        max_model_len=MAX_LEN, dtype="bfloat16",
    )
    assert (cfg.num_layers, cfg.experts.num_held) == (4, 16)
    assert cfg.layer_windows == (4096, 4096, 4096, 0)
    return dataclasses.replace(cfg, experts=dataclasses.replace(
        cfg.experts, num_routed=128))


def _on_chip(one_chip, cfg):
    """The model, its parameters' shapes, and the two pools as the boot
    would size them after the weights (engine/kv_cache.py): the same
    number of sequences at ``max_model_len`` in each group."""
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.kv_cache import (
        per_block_bytes,
        window_band_blocks,
        window_block_bytes,
        window_blocks_for,
    )
    from vllm_tgis_adapter_tpu.models.cohere2_moe import (
        Cohere2MoeForCausalLM,
    )

    model = Cohere2MoeForCausalLM(cfg)
    assert model.ragged_block_q == 64  # 16 heads a KV head: 1,024 rows

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
    # 11.35 GB: the tied embedding 2.15, four layers of 2.299 (ISSUE
    # 49's arithmetic)
    assert 11.3e9 < weight_bytes < 11.4e9, weight_bytes
    config = EngineConfig(
        model_config=cfg,
        cache_config=CacheConfig(block_size=BLOCK, num_blocks=1,
                                 cache_dtype=cfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=ROWS, max_num_batched_tokens=2048),
        parallel_config=ParallelConfig(), lora_config=LoRAConfig(),
    )
    assert per_block_bytes(config) == 1 * 2 * 8 * BLOCK * 128 * 2
    assert window_block_bytes(config) == 3 * per_block_bytes(config)
    band = window_band_blocks(config)
    assert band == (4096 + 2048) // BLOCK + 1 + 1
    page = per_block_bytes(config) + -(
        -window_block_bytes(config) * band // (MAX_LEN // BLOCK))
    pages = (int(HBM_BYTES * 0.9) - weight_bytes) // page
    window_pages = window_blocks_for(config, pages)
    # 20 sequences of 16 k tokens: 343 k tokens in the full group, the
    # window group's 134 k their bands
    assert pages * BLOCK // MAX_LEN == 20, pages
    assert window_pages == -(-pages * band // (MAX_LEN // BLOCK))
    caches = on_chip_shapes(jax.eval_shape(
        lambda: model.make_kv_caches(
            pages * BLOCK, cfg.dtype, window_slots=window_pages * BLOCK)))
    assert caches[0].shape == (1, 8, pages * BLOCK, 128)
    assert caches[2].shape == (3, 8, window_pages * BLOCK, 128)
    held = sum(c.size * c.dtype.itemsize for c in caches)
    return model, params, caches, held


def _assert_in_place_and_fits(compiled, held, temp_limit):
    """Both pools are donated and updated in place; the two kernels are
    in the program under their own names beside the grouped matmul; and
    weights + pools + temporaries fit one 16 GB chip."""
    text = compiled.as_text()
    for name in ("window_ragged_attention", "full_ragged_attention", "gmm"):
        assert re.search(
            rf"%{name}[.\w]* = [^\n]*custom-call\([^\n]*"
            r'custom_call_target="tpu_custom_call"', text), name
    mem = compiled.memory_analysis()
    # the two running counts take one 512-byte allocation
    assert abs(mem.alias_size_in_bytes - held) <= 512
    total = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert total < HBM_BYTES, (total, mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < temp_limit, mem.temp_size_in_bytes


@pytest.mark.parametrize("t,work_w,window_w", [(128, 4096, 2048),
                                               (2048, 8192, 4096)])
def test_ragged_step_compiles_and_fits(topo, one_chip, on_chip, t, work_w,
                                       window_w):
    """The mixed step: three window layers and a full one, each kind's
    prompt chunks at a query block of 64 tokens x 16 heads a KV head
    under a host-built schedule of its own width (8,192 items is the
    widest the kernel's scalar memory takes) and its running rows'
    one-token spans at the wave's query block under a schedule built in
    the program (64 rows x 128 runs: 8,192 items too), the grouped
    matmul over 16 experts of width 4,096,
    and one SwiGLU of 16,384 for the shared experts, in one program
    beside both pools."""
    cfg = _command_a_plus_ep8_l4()
    model, params, caches, held = _on_chip(one_chip, cfg)
    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    step = jax.jit(
        functools.partial(model.ragged_forward, block_size=BLOCK),
        donate_argnums=(1,),
    )
    tables = spec((ROWS, MAX_LEN // BLOCK), jnp.int32)
    compiled = step.lower(
        params, caches, spec((t,), jnp.int32), spec((t,), jnp.int32),
        spec((t,), jnp.int32), spec((ROWS + 1,), jnp.int32),
        spec((ROWS,), jnp.int32), spec((), jnp.int32), tables,
        spec((ROWS,), jnp.int32),
        window_slots=spec((t,), jnp.int32), window_tables=tables,
        work=spec((ra.work_rows(BLOCK), work_w), jnp.int32),
        n_items=spec((), jnp.int32),
        window_work=spec((ra.work_rows(BLOCK), window_w), jnp.int32),
        window_n_items=spec((), jnp.int32),
        unit_rows=spec((), jnp.int32),
    ).compile(compiler_options=TPU_STEP_COMPILER_OPTIONS)
    _assert_in_place_and_fits(compiled, held, 1 << 30)


def test_decode_wave_compiles_and_fits(topo, one_chip, on_chip):
    """The fused wave: the loop of traced length carries both pools in
    place; each step builds one schedule a layer kind in the trace (the
    window layers' over a band of 33 pages of 128 tokens, not 128) and calls
    each
    kernel under its name."""
    cfg = _command_a_plus_ep8_l4()
    model, params, caches, held = _on_chip(one_chip, cfg)
    b, max_blocks = ROWS, MAX_LEN // BLOCK

    def wave(params, caches, tokens, pos0, limits, tables, window_tables,
             num_steps):
        def step(k, carry):
            caches, tokens = carry
            pos = pos0 + k
            at = (pos // BLOCK)[:, None]
            blk = jnp.take_along_axis(tables, at, axis=1)[:, 0]
            wblk = jnp.take_along_axis(window_tables, at, axis=1)[:, 0]
            live = pos <= limits
            logits, caches = model.decode(
                params, caches, tokens, pos,
                jnp.where(live, blk * BLOCK + pos % BLOCK, -1), tables,
                pos + 1, BLOCK,
                window_slots=jnp.where(live, wblk * BLOCK + pos % BLOCK, -1),
                window_tables=window_tables)
            return caches, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return jax.lax.fori_loop(0, num_steps, step, (caches, tokens))

    spec = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(wave, donate_argnums=(1,)).lower(
        params, caches, spec((b,), jnp.int32), spec((b,), jnp.int32),
        spec((b,), jnp.int32), spec((b, max_blocks), jnp.int32),
        spec((b, max_blocks), jnp.int32), spec((), jnp.int32),
    ).compile(compiler_options=TPU_STEP_COMPILER_OPTIONS)
    _assert_in_place_and_fits(compiled, held, 512 << 20)
