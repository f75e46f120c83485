"""Ragged paged attention (--attention-backend=ragged): kernel parity,
backend equivalence, compile-lattice collapse, and chaos recovery.

The contract under test (docs/ATTENTION.md): the unified ragged path
produces PER-ROW outputs numerically identical (same dtype, same
reduction discipline → exact or within float tolerance) to the bucketed
solo-prefill, packed-prefill and fused-decode paths, across mixed
batches including sliding-window, LoRA-slot and prefix-cache-hit rows —
while compiling strictly fewer programs and reporting its padding
honestly (fill ratio ~1 whenever backlog exists).
"""

from __future__ import annotations

import ast
import asyncio
from pathlib import Path

import numpy as np
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------- kernel


def _build_mixed_case(rng, cases, *, hkv=2, g=2, dh=16, bs=4, max_blocks=8,
                      t_pad=None, num_blocks=32, shuffle=False, stale=0.0):
    """Build a paged cache + flat mixed stream from (ctx_before, n_new)
    span specs; returns everything the ragged kernel consumes plus the
    per-sequence pieces the reference needs.  ``shuffle`` hands out the
    pool's pages in a drawn order (a sequence's physical pages then
    neither follow one another nor ascend); ``stale`` is what every slot
    holds that no context wrote (the tail of a sequence's last page,
    which a short run's repeated places read again)."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops.attention import write_kv

    h = hkv * g
    kc = jnp.full((hkv, num_blocks * bs, dh), stale, jnp.float32)
    vc = jnp.full_like(kc, stale)
    tables = np.zeros((len(cases), max_blocks), np.int32)
    spans, pos_base, flat_q, flat_pos, per_seq = [], [], [], [], []
    order = rng.permutation(num_blocks) if shuffle else np.arange(num_blocks)
    next_block, row = 0, 0
    for s, (ctx_before, n_new) in enumerate(cases):
        total = ctx_before + n_new
        nb = -(-total // bs)
        blocks = [int(b) for b in order[next_block: next_block + nb]]
        next_block += nb
        tables[s, :nb] = blocks
        k_seq = rng.standard_normal((total, hkv, dh)).astype(np.float32)
        v_seq = rng.standard_normal((total, hkv, dh)).astype(np.float32)
        slots = [blocks[p // bs] * bs + p % bs for p in range(total)]
        kc, vc = write_kv(
            kc, vc, jnp.asarray(k_seq), jnp.asarray(v_seq),
            jnp.asarray(slots, jnp.int32),
        )
        q_new = rng.standard_normal((n_new, h, dh)).astype(np.float32)
        flat_q.append(q_new)
        flat_pos += list(range(ctx_before, total))
        spans.append((row, n_new, ctx_before))
        pos_base.append(ctx_before)
        per_seq.append((q_new, ctx_before, n_new))
        row += n_new
    t = row
    t_pad = t_pad or (16 if t <= 16 else 32)
    q = np.zeros((t_pad, h, dh), np.float32)
    q[:t] = np.concatenate(flat_q)
    positions = np.zeros(t_pad, np.int32)
    positions[:t] = flat_pos
    s_pad = len(cases) + 1
    seq_starts = np.full(s_pad + 1, t_pad, np.int32)
    for s, (start, _, _) in enumerate(spans):
        seq_starts[s] = start
    seq_starts[len(spans)] = t
    pb = np.zeros(s_pad, np.int32)
    pb[: len(pos_base)] = pos_base
    bt = np.zeros((s_pad, max_blocks), np.int32)
    bt[: len(cases)] = tables
    return dict(
        kc=kc, vc=vc, q=q, t=t, t_pad=t_pad, positions=positions,
        seq_starts=seq_starts, pos_base=pb, block_tables=bt,
        spans=spans, per_seq=per_seq, bs=bs, scale=dh**-0.5, h=h,
    )


@pytest.mark.parametrize("window", [0, 4])
def test_ragged_xla_matches_decode_reference(window):
    """Every ragged row == the pinned decode formulation of the same
    (query, paged context) — prefill chunks, decode rows and
    prefix-resume chunks alike."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra
    from vllm_tgis_adapter_tpu.ops.attention import (
        paged_decode_attention_xla,
    )

    rng = np.random.default_rng(0)
    case = _build_mixed_case(rng, [(0, 7), (9, 1), (3, 5)])
    out = ra.ragged_attention_xla(
        jnp.asarray(case["q"]), case["kc"], case["vc"],
        jnp.asarray(case["positions"]), jnp.asarray(case["seq_starts"]),
        jnp.asarray(case["t"]), jnp.asarray(case["block_tables"]),
        case["bs"], case["scale"], window=window,
    )
    row = 0
    for s, (q_new, ctx_before, n_new) in enumerate(case["per_seq"]):
        ctx = jnp.arange(ctx_before + 1, ctx_before + n_new + 1,
                         dtype=jnp.int32)
        tb = jnp.broadcast_to(
            jnp.asarray(case["block_tables"][s])[None],
            (n_new, case["block_tables"].shape[1]),
        )
        ref = paged_decode_attention_xla(
            jnp.asarray(q_new), case["kc"], case["vc"], tb, ctx,
            case["bs"], case["scale"], window=window,
        )
        np.testing.assert_allclose(
            np.asarray(out[row: row + n_new]), np.asarray(ref),
            rtol=1e-5, atol=1e-5,
        )
        row += n_new


#: what a run of pages can get wrong, by block size (a work item reads
#: ``P = 128 // bs`` pages = 128 KV tokens; at block size 128 a run IS a
#: page).  Contexts of 1, 127, 128 and 129 tokens sit on both sides of a
#: run's end; 300 tokens are three runs, the last one short.  The pool's
#: pages are dealt in a drawn order and every slot no context wrote
#: holds 1e4: the repeated places of a short run read such slots again.
_RUN_EDGES = {
    # id: (bs, max_blocks, pool pages)
    "pages-of-4": (4, 80, 256),
    "pages-of-16": (16, 20, 64),
    "pages-of-128": (128, 3, 12),
}


@pytest.mark.parametrize("window", [0, 4])
@pytest.mark.parametrize("use_alibi", [False, True])
@pytest.mark.parametrize("schedule", ["sparse", "decode"])
@pytest.mark.parametrize("shape", ["one-run", *_RUN_EDGES])
def test_ragged_pallas_matches_xla(window, use_alibi, schedule, shape):
    """The Pallas kernel (interpret mode) matches the XLA reference for
    both the host-built sparse schedule (mixed engine steps, multi-row
    spans) and the in-trace compact schedule with its traced grid
    extent (the fused decode wave — single-row spans by contract, at a
    row count that block_q=8 divides exactly): for short contexts that
    one run covers, and on the edges of a run at every block size."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    rng = np.random.default_rng(1)
    if shape == "one-run":
        case = _build_mixed_case(
            rng,
            [(0, 6), (9, 1), (3, 4), (5, 1)]
            if schedule == "sparse"
            # decode contract: every span one row (seq s IS row s)
            else [(i % 11, 1) for i in range(16)],
        )
    else:
        bs, max_blocks, num_blocks = _RUN_EDGES[shape]
        case = _build_mixed_case(
            rng,
            # a decode row on each edge, a chunk whose rows see 121-132
            # tokens (its later rows a second run), a fresh prompt
            [(0, 1), (126, 1), (127, 1), (128, 1), (120, 12), (299, 1),
             (0, 5)]
            if schedule == "sparse"
            else [(c - 1, 1) for c in (1, 127, 128, 129, 300, 2, 256, 17)],
            bs=bs, max_blocks=max_blocks, num_blocks=num_blocks,
            shuffle=True, stale=1e4,
        )
    slopes = (
        jnp.asarray(rng.standard_normal(case["h"]).astype(np.float32) * 0.1)
        if use_alibi
        else None
    )
    ref = ra.ragged_attention_xla(
        jnp.asarray(case["q"]), case["kc"], case["vc"],
        jnp.asarray(case["positions"]), jnp.asarray(case["seq_starts"]),
        jnp.asarray(case["t"]), jnp.asarray(case["block_tables"]),
        case["bs"], case["scale"], window=window, alibi_slopes=slopes,
    )
    n_items = None
    if schedule == "sparse":
        work = jnp.asarray(ra.build_work_schedule(
            case["spans"], case["block_tables"],
            block_size=case["bs"], block_q=8, t_pad=case["t_pad"],
        )[0])
    else:
        t = case["t"]  # S == T: the pad descriptor slot stays out
        work, n_items = ra.decode_work_schedule(
            jnp.asarray(case["pos_base"][:t]), jnp.ones(t, bool),
            jnp.asarray(case["block_tables"][:t]), block_size=case["bs"],
        )
    out = ra._ragged_attention_pallas(
        jnp.asarray(case["q"]), case["kc"], case["vc"],
        jnp.asarray(case["seq_starts"]), jnp.asarray(case["pos_base"]),
        work, case["bs"], case["scale"], block_q=8, window=window,
        alibi_slopes=slopes, interpret=True, n_items=n_items,
    )
    np.testing.assert_allclose(
        np.asarray(out[: case["t"]]), np.asarray(ref[: case["t"]]),
        rtol=1e-5, atol=1e-5,
    )


#: tiles by their rows (G query heads a KV head x block_q rows a query
#: block) and the axis ``tile_stats`` puts their statistics on: the
#: served chunk tiles (512 granite, 640 falcon, 1,024 Command A+), the
#: 128-row wave tile of Command A+, and the waves' 32 and 40 rows
_TILES = {
    "128-rows": (16, 8, "sublanes"),
    "512-rows": (4, 128, "sublanes"),
    "640-rows": (5, 128, "sublanes"),
    "1024-rows": (16, 64, "sublanes"),
    "32-rows": (4, 8, "lanes"),
    "40-rows": (5, 8, "lanes"),
}


def _quantized(kc, vc, bs, scheme):
    """One layer's float ``[Hkv, slots, Dh]`` caches as quantized pages
    and their scale sidecars, written as the step programs write them."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import kv_quant

    slots = jnp.arange(kc.shape[1], dtype=jnp.int32)
    pools = [
        kv_quant.scatter_layer(
            kv_quant.make_kv_cache(
                (1, *cache.shape), jnp.float32, scheme, bs),
            0, slots, jnp.swapaxes(cache, 0, 1))
        for cache in (kc, vc)
    ]
    return (*(kv_quant.layer_data(pool, 0) for pool in pools),
            kv_quant.layer_scales(*pools, 0))


@pytest.mark.parametrize("variant", ["plain", "window", "alibi", "int8"])
@pytest.mark.parametrize("tile", _TILES)
def test_tile_layouts_match_xla(tile, variant):
    """The kernel (interpret mode) is the XLA reference's to 1e-5 with
    its statistics on either axis: a fresh prompt that crosses a query
    block, decode rows on both sides of a run's end, a chunk whose later
    rows read a second run, a row three runs deep; plain, under a band
    that cuts into a run, with ALiBi, and over quantized pages."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    g, block_q, stats = _TILES[tile]
    assert ra.tile_stats(g, block_q) == stats
    rng = np.random.default_rng(5)
    case = _build_mixed_case(
        rng, [(0, 70), (126, 1), (127, 1), (128, 1), (120, 12), (299, 1)],
        hkv=1, g=g, bs=16, max_blocks=20, num_blocks=64, t_pad=128,
        shuffle=True, stale=0.0 if variant == "int8" else 1e4,
    )
    window = 40 if variant == "window" else 0
    slopes = (
        jnp.asarray(rng.standard_normal(case["h"]).astype(np.float32) * 0.1)
        if variant == "alibi" else None
    )
    kc, vc, scales = case["kc"], case["vc"], None
    if variant == "int8":
        kc, vc, scales = _quantized(kc, vc, case["bs"], "int8")
    ref = ra.ragged_attention_xla(
        jnp.asarray(case["q"]), kc, vc,
        jnp.asarray(case["positions"]), jnp.asarray(case["seq_starts"]),
        jnp.asarray(case["t"]), jnp.asarray(case["block_tables"]),
        case["bs"], case["scale"], window=window, alibi_slopes=slopes,
        kv_scales=scales,
    )
    work, n_items = ra.build_work_schedule(
        case["spans"], case["block_tables"], block_size=case["bs"],
        block_q=block_q, t_pad=case["t_pad"],
    )
    out = ra._ragged_attention_pallas(
        jnp.asarray(case["q"]), kc, vc,
        jnp.asarray(case["seq_starts"]), jnp.asarray(case["pos_base"]),
        jnp.asarray(work), case["bs"], case["scale"], block_q=block_q,
        window=window, alibi_slopes=slopes, interpret=True,
        kv_scales=scales, n_items=jnp.asarray(n_items, jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(out[: case["t"]]), np.asarray(ref[: case["t"]]),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("tile", ["1024-rows", "32-rows"])
def test_masked_rows_and_padded_blocks_give_zeros_not_nan(tile):
    """Rows past the stream's end share a query block with real rows
    (every score of theirs masked: m stays -inf), and the stream's
    padding has query blocks of its own (one dead item each): both come
    out as zeros under either layout, never NaN."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    g, block_q, _ = _TILES[tile]
    rng = np.random.default_rng(6)
    case = _build_mixed_case(
        rng, [(130, 9), (0, 3)], hkv=1, g=g, bs=16, max_blocks=20,
        num_blocks=64, t_pad=4 * block_q, stale=1e4,
    )
    work, n_items = ra.build_work_schedule(
        case["spans"], case["block_tables"], block_size=case["bs"],
        block_q=block_q, t_pad=case["t_pad"],
    )
    assert n_items > 3 and (work[6, :n_items] == 0).sum() >= 2
    out = np.asarray(ra._ragged_attention_pallas(
        jnp.asarray(case["q"]), case["kc"], case["vc"],
        jnp.asarray(case["seq_starts"]), jnp.asarray(case["pos_base"]),
        jnp.asarray(work), case["bs"], case["scale"], block_q=block_q,
        window=0, interpret=True, n_items=jnp.asarray(n_items, jnp.int32),
    ))
    assert np.isfinite(out).all()
    assert np.abs(out[: case["t"]]).max(axis=(1, 2)).min() > 0
    assert not out[case["t"]:].any()


@pytest.mark.parametrize("tile", _TILES)
def test_counter_label_is_the_layout_the_kernel_call_takes(tile,
                                                           monkeypatch):
    """``tgis_tpu_ragged_items_total``'s label for a call and the layout
    the kernel's wrapper hands its body are one predicate's answer: the
    host asks ``tile_stats`` with the model's query heads a KV head and
    the plan's query block, the wrapper with the operands' own."""
    import types

    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    g, block_q, stats = _TILES[tile]
    taken = []
    kernel = ra._ragged_kernel

    def spy(*refs, **static):
        taken.append(static["stats"])
        return kernel(*refs, **static)

    monkeypatch.setattr(ra, "_ragged_kernel", spy)
    case = _build_mixed_case(
        np.random.default_rng(8), [(3, 5)], hkv=1, g=g, t_pad=block_q)
    work, n_items = ra.build_work_schedule(
        case["spans"], case["block_tables"], block_size=case["bs"],
        block_q=block_q, t_pad=case["t_pad"])
    ra._ragged_attention_pallas(
        jnp.asarray(case["q"]), case["kc"], case["vc"],
        jnp.asarray(case["seq_starts"]), jnp.asarray(case["pos_base"]),
        jnp.asarray(work), case["bs"], case["scale"], block_q=block_q,
        window=0, interpret=True, n_items=jnp.asarray(n_items, jnp.int32))
    engine = types.SimpleNamespace(config=types.SimpleNamespace(
        model_config=types.SimpleNamespace(
            has_latent_cache=False, q_per_kv=g)))
    raised = LLMEngine._items_by_stats(engine, [(block_q, n_items)])
    assert set(taken) == set(raised) == {stats}
    assert raised[stats] == n_items
    # a model with another attention kernel raises neither label
    engine.config.model_config.has_latent_cache = True
    assert not LLMEngine._items_by_stats(engine, [(block_q, n_items)])


def _decode_case(rng, ctx, *, bs=4, max_blocks=8, hkv=2, g=2, dh=16,
                 cache_dtype=None, page_gain=1.0):
    """A decode wave's attention inputs: row i one token at position
    ``ctx[i] - 1``; ``ctx[i] == 0`` marks a dead row (slot -1: empty
    table, context 1, as prepare_decode pads a batch)."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import kv_quant
    from vllm_tgis_adapter_tpu.ops.ragged_attention import pages_per_item

    ctx = np.asarray(ctx, np.int32)
    b = len(ctx)
    run = pages_per_item(bs)
    num_pages = b * max_blocks
    tables = np.zeros((b, max_blocks), np.int32)
    pages = rng.permutation(num_pages)
    used = 0
    for i, c in enumerate(ctx):
        nb = -(-int(c) // bs)
        tables[i, :nb] = pages[used: used + nb]
        used += nb
    shape = (hkv, num_pages * bs, dh)
    kc = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    vc = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    if page_gain != 1.0:
        # neighbouring pages (the places of one run) hold values, and
        # so dequant scales, ``page_gain`` apart
        gain = jnp.repeat(
            jnp.where(jnp.arange(num_pages) % 2 == 1, page_gain, 1.0), bs
        )[None, :, None]
        kc, vc = kc * gain, vc * gain
    scales = None
    if cache_dtype is not None:
        kc, vc, scales = _quantized(kc, vc, bs, cache_dtype)
    return dict(
        q=jnp.asarray(rng.standard_normal((b, hkv * g, dh)), jnp.float32),
        kc=kc, vc=vc, scales=scales, tables=jnp.asarray(tables),
        pos=jnp.asarray(np.maximum(ctx, 1) - 1), live=ctx > 0,
        starts=jnp.arange(b + 1, dtype=jnp.int32), bs=bs, scale=dh**-0.5,
        # a row's pages in runs of ``run``: the schedule's width (every
        # row at full context), what is live, and the pages under it
        run=run, width=b * -(-max_blocks // run),
        live_items=int((-(-(-(-ctx // bs)) // run)).sum()),
        live_pages=int((-(-ctx // bs)).sum()),
    )


@pytest.mark.parametrize(
    "ctx,cache_dtype,shape",
    [
        pytest.param([0] * 16, None, None, id="all-rows-dead"),
        pytest.param([0] * 23 + [9], None, None,
                     id="one-live-row-in-last-block"),
        pytest.param([4, 8, 1, 32, 5, 0, 12, 16], None, None,
                     id="context-ends-on-page-boundary"),
        pytest.param([32] * 64, None, None, id="64-rows-full-context"),
        pytest.param([(5 * i) % 33 for i in range(21)], None, None,
                     id="non-pow2-stream"),
        pytest.param([7, 0, 19, 32, 1, 0, 0, 3, 0, 0, 0, 0, 0, 0, 0, 0, 11],
                     "int8", None, id="int8-pages"),
        # runs of 8 pages of 16 tokens: contexts on both sides of a
        # run's end, three runs with a short last one, dead rows among
        # them and a query block (rows 8-15) with no live row
        pytest.param([1, 127, 128, 129, 300, 0, 16, 0] + [0] * 8 + [255],
                     None, {"bs": 16, "max_blocks": 20},
                     id="runs-of-8-pages"),
        # block size 128: a run IS a page, through the same code
        pytest.param([1, 127, 128, 129, 300, 0, 256, 0, 384],
                     None, {"bs": 128, "max_blocks": 3},
                     id="block-128-runs-of-1-page"),
        # pages of one run whose dequant scales lie 100x apart
        pytest.param([1, 127, 128, 129, 300, 0, 16, 77], "int8",
                     {"bs": 16, "max_blocks": 20, "page_gain": 100.0},
                     id="int8-runs-scales-100x-apart"),
        pytest.param([1, 127, 128, 129, 300, 0, 16, 77], "fp8",
                     {"bs": 16, "max_blocks": 20, "page_gain": 100.0},
                     id="fp8-runs-scales-100x-apart"),
    ],
)
def test_decode_schedule_visits_only_live_work(ctx, cache_dtype, shape):
    """The fused wave's attention under its compact schedule and a
    TRACED ``n_items``: the grid covers the live (row, run of pages)
    items and one dead item per empty query block, live rows equal the
    XLA reference, dead rows come out finite (zeros)."""
    import jax
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    case = _decode_case(np.random.default_rng(7), ctx,
                        cache_dtype=cache_dtype, **(shape or {}))
    b, live = len(ctx), case["live"]
    block_q = ra.decode_block_q(b)

    @jax.jit
    def attend(pos, active, tables):
        work, n_items = ra.decode_work_schedule(
            pos, active, tables, block_size=case["bs"]
        )
        out = ra._ragged_attention_pallas(
            case["q"], case["kc"], case["vc"], case["starts"], pos, work,
            case["bs"], case["scale"], block_q=block_q, window=0,
            interpret=True, kv_scales=case["scales"], n_items=n_items,
        )
        return out, work, n_items

    out, work, n_items = attend(
        case["pos"], jnp.asarray(live), case["tables"]
    )
    work, n_items = np.asarray(work), int(n_items)
    run = case["run"]
    assert work.shape == (ra.WORK_FIELDS + run, case["width"])
    nq = -(-b // block_q)
    empty = sum(
        not live[i * block_q: (i + 1) * block_q].any() for i in range(nq)
    )
    assert n_items == case["live_items"] + empty
    head = work[:, :n_items]
    assert head[6].sum() == case["live_items"]
    assert head[2].sum() == case["live_pages"]
    # every query block is initialised once and finalised once, in order
    assert head[4].sum() == head[5].sum() == nq
    assert (np.diff(head[0]) >= 0).all()
    assert sorted(set(head[0])) == list(range(nq))
    # a live item names consecutive logical pages of its own row's
    # table, from a multiple of the run's length on; a short run
    # repeats its last real page
    tables = np.asarray(case["tables"])
    on = head[6] == 1
    row, real, page0 = head[1][on], head[2][on], head[3][on]
    assert (page0 % run == 0).all() and (real >= 1).all()
    assert (real <= run).all()
    for i in range(run):
        assert (
            head[ra.WORK_FIELDS + i][on]
            == tables[row, page0 + np.minimum(i, real - 1)]
        ).all()

    ref = ra.ragged_attention_xla(
        case["q"], case["kc"], case["vc"], case["pos"], case["starts"],
        jnp.asarray(b), case["tables"], case["bs"], case["scale"],
        kv_scales=case["scales"],
    )
    out = np.asarray(out)
    assert np.isfinite(out).all()
    assert (out[~live] == 0).all()
    np.testing.assert_allclose(
        out[live], np.asarray(ref)[live], rtol=1e-5, atol=1e-5
    )


def test_ragged_decode_schedule_non_pow2_stream(monkeypatch):
    """The decode dispatch at a non-power-of-two stream width: the
    in-trace schedule must cover exactly the kernel's cdiv query-block
    grid.  A wider (pow2) schedule emits block indices past the output
    grid whose first/last flags re-init and finalise the clamped last
    real block with zeros — silently zeroing the tail rows of every
    non-pow2 decode wave."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    monkeypatch.setattr(ra, "_use_pallas", lambda: True)
    monkeypatch.setattr(ra, "_pallas_interpret", lambda: True)

    rng = np.random.default_rng(3)
    # 24 single-row spans: T=24 gives pow2_ceil(T)=32 but cdiv(24,8)*8=24
    case = _build_mixed_case(rng, [(i % 3, 1) for i in range(24)])
    t = case["t"]
    assert t == 24
    ref = ra.ragged_attention_xla(
        jnp.asarray(case["q"][:t]), case["kc"], case["vc"],
        jnp.asarray(case["positions"][:t]), jnp.asarray(case["seq_starts"]),
        jnp.asarray(t), jnp.asarray(case["block_tables"]),
        case["bs"], case["scale"],
    )
    work, n_items = ra.decode_work_schedule(
        jnp.asarray(case["pos_base"][:t]), jnp.ones(t, bool),
        jnp.asarray(case["block_tables"][:t]), block_size=case["bs"],
    )
    out = ra.ragged_paged_attention(
        jnp.asarray(case["q"][:t]), case["kc"], case["vc"],
        jnp.asarray(case["positions"][:t]), jnp.asarray(case["seq_starts"]),
        jnp.asarray(case["pos_base"]), jnp.asarray(t),
        jnp.asarray(case["block_tables"]), case["bs"], case["scale"],
        work=work, n_items=n_items, block_q=ra.decode_block_q(t),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


# ------------------------------------- the pool as one operand, by layer


def _pool_case(rng, cache_dtype, *, layers=3, hkv=2, g=2, dh=16):
    """A mixed ragged batch (prefill chunk, decode rows, a resumed
    chunk) over a WHOLE ``[L, Hkv, slots, Dh]`` pool whose layers hold
    different data, written through ``scatter_layer`` as the step
    programs write it (so quantized pools carry their sidecars).

    ``<dtype>-runs`` is the served form of the thing: pages of 16
    tokens read in runs of 8, contexts of 127-300 tokens (so runs end
    inside, on and past a context, and a 300-token row owns three),
    and the values of neighbouring pages — the places of one run, each
    with its own dequant scale — 100x apart."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import kv_quant

    cache_dtype, _, runs = cache_dtype.partition("-")
    if runs:
        bs, max_blocks, num_pages = 16, 20, 64
        spans_spec = [(0, 6), (126, 1), (127, 1), (128, 1), (299, 1)]
    else:
        bs, max_blocks, num_pages = 4, 8, 32
        spans_spec = [(0, 6), (9, 1), (3, 4), (5, 1), (13, 1)]
    gain = jnp.repeat(
        jnp.where(jnp.arange(num_pages) % 2 == 1, 100.0, 1.0)
        if runs else jnp.ones(num_pages), bs,
    )[:, None, None]
    scheme = cache_dtype if cache_dtype in ("int8", "fp8") else "none"
    store = jnp.bfloat16 if cache_dtype == "bf16" else jnp.float32
    k_pool = kv_quant.make_kv_cache(
        (layers, hkv, num_pages * bs, dh), store, scheme, bs
    )
    v_pool = kv_quant.make_kv_cache(
        (layers, hkv, num_pages * bs, dh), store, scheme, bs
    )
    slots = jnp.arange(num_pages * bs, dtype=jnp.int32)
    for layer in range(layers):
        k_pool = kv_quant.scatter_layer(
            k_pool, layer, slots, gain * jnp.asarray(
                rng.standard_normal((num_pages * bs, hkv, dh)), jnp.float32
            ),
        )
        v_pool = kv_quant.scatter_layer(
            v_pool, layer, slots, gain * jnp.asarray(
                rng.standard_normal((num_pages * bs, hkv, dh)), jnp.float32
            ),
        )
    s_pad = len(spans_spec) + 1
    tables = np.zeros((s_pad, max_blocks), np.int32)
    pages = rng.permutation(num_pages)
    spans, positions, used, row = [], [], 0, 0
    for s, (ctx_before, n_new) in enumerate(spans_spec):
        nb = -(-(ctx_before + n_new) // bs)
        tables[s, :nb] = pages[used: used + nb]
        used += nb
        spans.append((row, n_new, ctx_before))
        positions += list(range(ctx_before, ctx_before + n_new))
        row += n_new
    t, t_pad = row, 16
    pos = np.zeros(t_pad, np.int32)
    pos[:t] = positions
    starts = np.full(s_pad + 1, t_pad, np.int32)
    starts[: len(spans)] = [sp[0] for sp in spans]
    starts[len(spans)] = t
    base = np.zeros(s_pad, np.int32)
    base[: len(spans)] = [sp[2] for sp in spans]
    q = np.zeros((t_pad, hkv * g, dh), np.float32)
    q[:t] = rng.standard_normal((t, hkv * g, dh))
    return dict(
        k_pool=k_pool, v_pool=v_pool, q=jnp.asarray(q), t=t, t_pad=t_pad,
        positions=jnp.asarray(pos), starts=jnp.asarray(starts),
        base=jnp.asarray(base), tables=tables, spans=spans, bs=bs,
        scale=dh**-0.5, h=hkv * g, layers=layers,
    )


@pytest.mark.parametrize("use_alibi", [False, True],
                         ids=["no-alibi", "alibi"])
@pytest.mark.parametrize("window", [0, 4], ids=["full", "window4"])
@pytest.mark.parametrize(
    "cache_dtype", ["bf16", "int8", "fp8", "bf16-runs", "int8-runs",
                    "fp8-runs"],
)
@pytest.mark.parametrize("layer", [0, 1, 2],
                         ids=["first", "middle", "last"])
def test_pool_operand_is_addressed_by_layer(
    layer, cache_dtype, window, use_alibi, monkeypatch
):
    """The kernel is handed the whole K and V pools (and, quantized,
    the whole scale sidecars) and the layer to read: its output is the
    XLA reference's over that layer's own slice — for the first, a
    middle and the last layer, so an index map that ignored the layer
    or addressed a neighbour shows."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import kv_quant
    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    rng = np.random.default_rng(31)
    case = _pool_case(rng, cache_dtype)
    slopes = (
        jnp.asarray(rng.standard_normal(case["h"]).astype(np.float32) * 0.1)
        if use_alibi
        else None
    )
    k_pool, v_pool = case["k_pool"], case["v_pool"]
    ref = ra.ragged_attention_xla(
        case["q"], kv_quant.layer_data(k_pool, layer),
        kv_quant.layer_data(v_pool, layer), case["positions"],
        case["starts"], jnp.asarray(case["t"]), jnp.asarray(case["tables"]),
        case["bs"], case["scale"], window=window, alibi_slopes=slopes,
        kv_scales=kv_quant.layer_scales(k_pool, v_pool, layer),
    )
    work, n_items = ra.build_work_schedule(
        case["spans"], case["tables"], block_size=case["bs"], block_q=8,
        t_pad=case["t_pad"],
    )
    monkeypatch.setattr(ra, "_use_pallas", lambda: True)
    monkeypatch.setattr(ra, "_pallas_interpret", lambda: True)
    out = ra.ragged_paged_attention(
        case["q"], *kv_quant.pool_data(k_pool, v_pool), case["positions"],
        case["starts"], case["base"], jnp.asarray(case["t"]),
        jnp.asarray(case["tables"]), case["bs"], case["scale"],
        layer=layer, work=jnp.asarray(work),
        n_items=jnp.asarray(n_items, jnp.int32), block_q=8,
        window=window, alibi_slopes=slopes,
        kv_scales=kv_quant.pool_scales(k_pool, v_pool),
    )
    t = case["t"]
    np.testing.assert_allclose(
        np.asarray(out[:t]), np.asarray(ref[:t]), rtol=2e-5, atol=2e-5
    )
    if layer:  # the layers do differ: a wrong address could not pass
        other = ra.ragged_attention_xla(
            case["q"], kv_quant.layer_data(k_pool, layer - 1),
            kv_quant.layer_data(v_pool, layer - 1), case["positions"],
            case["starts"], jnp.asarray(case["t"]),
            jnp.asarray(case["tables"]), case["bs"], case["scale"],
            window=window, alibi_slopes=slopes,
            kv_scales=kv_quant.layer_scales(k_pool, v_pool, layer - 1),
        )
        assert not np.allclose(
            np.asarray(other[:t]), np.asarray(ref[:t]), atol=1e-2
        )


# --------------------------- the mixed step's grid ends at its last item


def _expected_items(spans, *, bs, block_q, t_pad):
    """The schedule's real-item count and the pages under it, reckoned
    apart from the builder: per query block the pages each of its
    sequences can see there, in runs of ``128 // bs``, and one dead
    item for a block with none."""
    run = max(1, 128 // bs)
    total = pages = 0
    for qb in range(t_pad // block_q):
        lo, hi = qb * block_q, (qb + 1) * block_q - 1
        items = 0
        for start, n_rows, pos0 in spans:
            first, last = max(start, lo), min(start + n_rows - 1, hi)
            if first <= last:
                seen = (pos0 + last - start) // bs + 1
                pages += seen
                items += -(-seen // run)
        total += items or 1
    return total, pages


@pytest.mark.parametrize(
    "cases,t_pad,width,shape",
    [
        # 11 real rows of a 32-row bucket: query blocks 2 and 3 are all
        # padding and own one dead item each, the schedule's last two
        pytest.param([(0, 6), (9, 1), (3, 4)], 32, 64, None,
                     id="all-padding-query-blocks"),
        # 8 items, one of them dead: the real count IS the (pow2) width
        pytest.param([(0, 8), (31, 1), (8, 7), (2, 1), (0, 2), (7, 1),
                      (1, 3)], 32, None, None,
                     id="real-count-equals-width"),
        # the served shape of the thing: few items, a width far above
        pytest.param([(5, 1), (17, 1), (2, 9), (30, 1)], 16, 256, None,
                     id="width-far-above"),
        # pages of 16 tokens in runs of 8: a context that ends a run, a
        # chunk over two query blocks whose second block sees a second
        # run, three runs with a short last one; 11 items where a page
        # each made 52
        pytest.param([(127, 1), (120, 12), (299, 1), (0, 3)], 32, 64,
                     {"bs": 16, "max_blocks": 20, "num_blocks": 64},
                     id="runs-of-8-pages"),
        # block size 128: a run is a page and an item a page, as before
        pytest.param([(127, 1), (120, 12), (299, 1), (0, 3)], 32, 64,
                     {"bs": 128, "max_blocks": 3, "num_blocks": 12},
                     id="block-128-runs-of-1-page"),
    ],
)
def test_mixed_grid_ends_at_its_last_real_item(cases, t_pad, width, shape):
    """A host-built schedule run to ``n_items`` equals the same
    schedule run whole, and the steps past ``n_items`` are NOT visited:
    padding rewritten into items that would wipe the last query block
    (init + finalise with nothing accumulated) changes nothing when the
    grid ends at the real count, and does when it runs the width."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    rng = np.random.default_rng(5)
    case = _build_mixed_case(
        rng, cases, t_pad=t_pad, shuffle=True, stale=1e4, **(shape or {})
    )
    block_q = 8
    work, n_items = ra.build_work_schedule(
        case["spans"], case["block_tables"], block_size=case["bs"],
        block_q=block_q, t_pad=t_pad, w_bucket=width,
    )
    assert (n_items, work[2, :n_items].sum()) == _expected_items(
        case["spans"], bs=case["bs"], block_q=block_q, t_pad=t_pad
    )
    assert work.shape[0] == ra.work_rows(case["bs"])
    assert work[5, n_items - 1] == 1  # the last real item finalises
    assert not work[4:, n_items:].any()  # the rest is flagless padding
    if width is None:
        assert work.shape[1] == n_items
    else:
        assert work.shape[1] == width > n_items

    def run(work, n_items):
        out = ra._ragged_attention_pallas(
            jnp.asarray(case["q"]), case["kc"], case["vc"],
            jnp.asarray(case["seq_starts"]), jnp.asarray(case["pos_base"]),
            jnp.asarray(work), case["bs"], case["scale"], block_q=block_q,
            window=0, interpret=True,
            n_items=None if n_items is None
            else jnp.asarray(n_items, jnp.int32),
        )
        return np.asarray(out[: case["t"]])

    ref = np.asarray(ra.ragged_attention_xla(
        jnp.asarray(case["q"]), case["kc"], case["vc"],
        jnp.asarray(case["positions"]), jnp.asarray(case["seq_starts"]),
        jnp.asarray(case["t"]), jnp.asarray(case["block_tables"]),
        case["bs"], case["scale"],
    )[: case["t"]])
    whole, bounded = run(work, None), run(work, n_items)
    np.testing.assert_array_equal(bounded, whole)
    np.testing.assert_allclose(bounded, ref, rtol=1e-5, atol=1e-5)

    if width is not None:
        wiped = work.copy()
        wiped[0, n_items:] = 0  # query block 0 holds real rows
        wiped[4:6, n_items:] = 1  # init + finalise, nothing accumulated
        np.testing.assert_array_equal(run(wiped, n_items), whole)
        assert not np.allclose(run(wiped, None), whole, atol=1e-3)


# ------------------------------------------------------------ engine pair


def _make_engine(model_dir, backend, *, num_blocks=128, max_num_seqs=8,
                 prefix_caching=False, lora=False, seed=0):
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
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16, num_blocks=num_blocks, cache_dtype=mcfg.dtype,
            enable_prefix_caching=prefix_caching,
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=max_num_seqs, prefill_buckets=(32, 64, 128),
        ),
        parallel_config=ParallelConfig(),
        lora_config=(
            LoRAConfig(enabled=True, max_loras=2, max_lora_rank=8)
            if lora
            else LoRAConfig()
        ),
        seed=seed,
        attention_backend=backend,
    )
    return LLMEngine.from_config(config)


def _legacy_engine(model_dir, **kwargs):
    """Same config, but planned through the surviving LEGACY
    solo-prefill/fused-decode alternation (the pp>1 / sp>1 /
    prompt-logprob path) — the independent planner the ragged path's
    token-identity is anchored against now that the bucketed backend is
    retired."""
    engine = _make_engine(model_dir, "ragged", **kwargs)
    engine.scheduler.ragged = False
    return engine


def _run_requests(engine, requests):
    """requests: (rid, prompt_ids, sampling_kwargs, add_kwargs)."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    for rid, ids, skw, akw in requests:
        engine.add_request(
            rid, None, SamplingParams(**skw), prompt_token_ids=list(ids),
            **akw,
        )
    outs = {}
    for _ in range(1000):
        if not engine.has_unfinished_requests():
            break
        for o in engine.step():
            outs[o.request_id] = o
    assert not engine.has_unfinished_requests(), "engine did not drain"
    return {k: list(v.outputs[0].token_ids) for k, v in outs.items()}


def _mixed_requests(rng, n=6, greedy=True):
    reqs = []
    for i in range(n):
        ids = rng.integers(3, 500, size=int(rng.integers(4, 60))).tolist()
        skw = dict(max_tokens=int(rng.integers(3, 14)), ignore_eos=True)
        if greedy:
            skw["temperature"] = 0.0
        else:
            skw["temperature"] = 0.8
            skw["seed"] = 1234 + i
        reqs.append((f"r{i}", ids, skw, {}))
    return reqs


def test_ragged_equals_legacy_mixed_batch(tiny_model_dir):
    """Greedy mixed batch (staggered lengths/budgets): token-identical
    to the legacy solo-prefill/fused-decode composition."""
    rng = np.random.default_rng(7)
    reqs = _mixed_requests(rng)
    r_legacy = _run_requests(_legacy_engine(tiny_model_dir), reqs)
    r_ragged = _run_requests(_make_engine(tiny_model_dir, "ragged"), reqs)
    assert r_legacy == r_ragged


def test_ragged_equals_legacy_sampled_rows(tiny_model_dir):
    """Seeded (temperature > 0) rows: the sampler consumes identical
    logits and per-row PRNG streams on both planner paths."""
    rng = np.random.default_rng(11)
    reqs = _mixed_requests(rng, n=4, greedy=False)
    r_legacy = _run_requests(_legacy_engine(tiny_model_dir), reqs)
    r_ragged = _run_requests(_make_engine(tiny_model_dir, "ragged"), reqs)
    assert r_legacy == r_ragged


@pytest.fixture(scope="module")
def tiny_mistral_dir(tmp_path_factory):
    from tests.fixture_models import build_tiny_mistral

    return build_tiny_mistral(
        str(tmp_path_factory.mktemp("tiny-mistral")), sliding_window=8
    )


def test_ragged_equals_legacy_sliding_window(tiny_mistral_dir):
    """Sliding-window rows: the ragged kernel's band mask matches the
    legacy prefill/decode band masks."""
    rng = np.random.default_rng(13)
    reqs = _mixed_requests(rng, n=4)
    r_legacy = _run_requests(_legacy_engine(tiny_mistral_dir), reqs)
    r_ragged = _run_requests(
        _make_engine(tiny_mistral_dir, "ragged"), reqs
    )
    assert r_legacy == r_ragged


@pytest.fixture(scope="module")
def tiny_lora_dir(tmp_path_factory):
    from tests.fixture_models import build_tiny_lora_adapter

    return build_tiny_lora_adapter(
        str(tmp_path_factory.mktemp("tiny-lora"))
    )


def test_ragged_equals_legacy_lora_rows(tiny_model_dir, tiny_lora_dir):
    """Mixed adapter/base rows: the ragged per-row LoRA gather matches
    the legacy per-sequence/per-row delta paths."""
    results = {}
    for backend in ("legacy", "ragged"):
        engine = (
            _legacy_engine(tiny_model_dir, lora=True)
            if backend == "legacy"
            else _make_engine(tiny_model_dir, backend, lora=True)
        )
        asyncio.run(
            engine.lora_manager.load_lora_adapter("tl", tiny_lora_dir)
        )
        rng = np.random.default_rng(17)
        reqs = []
        for i in range(4):
            ids = rng.integers(3, 500, size=20).tolist()
            akw = {"lora_name": "tl"} if i % 2 else {}
            reqs.append((
                f"r{i}", ids,
                dict(temperature=0.0, max_tokens=6, ignore_eos=True),
                akw,
            ))
        results[backend] = _run_requests(engine, reqs)
    assert results["legacy"] == results["ragged"]
    # the adapter actually did something (otherwise the case is vacuous)
    assert results["ragged"]["r0"] != results["ragged"]["r1"]


def test_ragged_equals_legacy_prefix_cache_hit(tiny_model_dir):
    """Prefix-cache-hit rows: the ragged span starts mid-prompt
    (start_pos = matched tokens) and attends through the adopted pages,
    matching the legacy chunked-resume path."""
    rng = np.random.default_rng(19)
    shared = rng.integers(3, 500, size=40).tolist()
    other = rng.integers(3, 500, size=24).tolist()
    results = {}
    for backend in ("legacy", "ragged"):
        engine = (
            _legacy_engine(tiny_model_dir, prefix_caching=True)
            if backend == "legacy"
            else _make_engine(tiny_model_dir, backend, prefix_caching=True)
        )
        skw = dict(temperature=0.0, max_tokens=6, ignore_eos=True)
        first = _run_requests(engine, [("warm", shared, skw, {})])
        hits0 = engine.scheduler.allocator.prefix_hits
        second = _run_requests(
            engine,
            [("hit", shared, skw, {}), ("miss", other, skw, {})],
        )
        assert engine.scheduler.allocator.prefix_hits > hits0, (
            f"{backend}: prefix cache never hit — the case is vacuous"
        )
        assert second["hit"] == first["warm"]
        results[backend] = (first, second)
    assert results["legacy"] == results["ragged"]


def test_ragged_prompt_logprobs_legacy_fallback(tiny_model_dir):
    """A waiting head bearing prompt_logprobs is served by the legacy
    solo-prefill path even under the ragged backend (full-bucket logits
    rows; docs/ATTENTION.md "Limits"), interleaved with ragged planning
    for everything else — arriving mid-stream against running decode
    rows so the alternation branch actually runs.  Tokens and the
    prompt-logprob table must match the legacy planner."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams
    from vllm_tgis_adapter_tpu.engine.scheduler import (
        PrefillPlan,
        RaggedPlan,
    )

    rng = np.random.default_rng(37)
    lp_ids = rng.integers(3, 500, size=20).tolist()
    plain = [
        rng.integers(3, 500, size=int(n)).tolist() for n in (12, 44, 7)
    ]

    results = {}
    for backend in ("legacy", "ragged"):
        engine = (
            _legacy_engine(tiny_model_dir)
            if backend == "legacy"
            else _make_engine(tiny_model_dir, backend)
        )
        plans = []
        orig = engine.scheduler.schedule

        def spy(**kwargs):
            plan = orig(**kwargs)
            plans.append(plan)
            return plan

        engine.scheduler.schedule = spy
        for i, ids in enumerate(plain):
            engine.add_request(
                f"p{i}", None,
                SamplingParams(
                    temperature=0.0, max_tokens=8, ignore_eos=True
                ),
                prompt_token_ids=ids,
            )
        outs = {}
        for _ in range(3):  # plain rows reach decode before lp arrives
            for o in engine.step():
                outs[o.request_id] = o
        engine.add_request(
            "lp", None,
            SamplingParams(
                temperature=0.0, max_tokens=4, prompt_logprobs=2,
                logprobs=2, ignore_eos=True,
            ),
            prompt_token_ids=list(lp_ids),
        )
        for _ in range(400):
            if not engine.has_unfinished_requests():
                break
            for o in engine.step():
                outs[o.request_id] = o
        assert not engine.has_unfinished_requests(), "engine did not drain"
        if backend == "ragged":
            assert any(isinstance(p, RaggedPlan) for p in plans)
            assert any(
                isinstance(p, PrefillPlan) and p.seq.request_id == "lp"
                for p in plans
            ), "lp head never took the legacy solo path"
        lp = outs["lp"]
        assert lp.prompt_logprobs is not None
        assert lp.prompt_logprobs[0] is None
        assert len(lp.prompt_logprobs) == len(lp_ids)
        results[backend] = (
            {k: list(v.outputs[0].token_ids) for k, v in outs.items()},
            lp.prompt_logprobs,
        )
    assert results["legacy"][0] == results["ragged"][0]
    for a, b in zip(
        results["legacy"][1][1:], results["ragged"][1][1:]
    ):
        assert set(a) == set(b)
        for tid in a:
            assert abs(a[tid].logprob - b[tid].logprob) < 1e-4


# -------------------------------------------------- lattice + observability


def test_ragged_compile_lattice_is_smaller(tiny_model_dir):
    """precompile() compiles strictly fewer programs than the retired
    PR 6 bucketed ladder at the same serving config
    (docs/ATTENTION.md documents the expected counts)."""
    from vllm_tgis_adapter_tpu import compile_tracker

    engine = _make_engine(
        tiny_model_dir, "ragged", num_blocks=256, max_num_seqs=8
    )
    compile_tracker.reset()
    engine.precompile()
    shapes = compile_tracker.num_shapes()
    compiles = compile_tracker.total_recompiles()
    shape_list = list(compile_tracker.shapes())
    compile_tracker.reset()
    # the bucketed ladder at this config (buckets 32/64/128, widths
    # 1/2/4/8, topn x2, solo+packed+chained entry points) measured 16
    # distinct shapes / 26 compiles before its retirement (PR 6 / PR 12
    # evidence, docs/ATTENTION.md "Compile lattice") — the consolidated
    # lattice must stay STRICTLY below both
    assert shapes < 16, shapes
    assert compiles < 26, compiles
    # and every mixed-step shape keys on a scheduler flat bucket
    buckets = set(engine.scheduler.ragged_buckets)
    for fn, shape in shape_list:
        if fn == "ragged_step":
            tokens = int(shape.split(",")[0].split("=")[1])
            assert tokens in buckets, (fn, shape)


def test_ragged_fill_ratio_and_plan_description(tiny_model_dir):
    """The padding gauges read from the RAGGED plan: a backlogged mixed
    step reports fill ratio 1.0 / waste 0.0, and describe_plan renders
    the ragged batch for /debug/state."""
    from vllm_tgis_adapter_tpu import metrics
    from vllm_tgis_adapter_tpu.engine.core import describe_plan
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine = _make_engine(tiny_model_dir, "ragged")
    rng = np.random.default_rng(23)
    # enough backlog to cover a bucket: the slice-to-fit planner must
    # dispatch an exactly-full flat bucket
    for i in range(6):
        engine.add_request(
            f"r{i}", None,
            SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
            prompt_token_ids=rng.integers(3, 500, size=50).tolist(),
        )
    outputs, plan, prepared = engine.plan_step()
    desc = describe_plan(plan)
    assert desc["kind"] == "ragged"
    assert desc["total_tokens"] == desc["bucket"]  # exactly full
    assert desc["num_prefill"] >= 1
    assert metrics.ragged_batch_fill_ratio._value.get() == 1.0
    assert metrics.prefill_padding_waste._value.get() == 0.0
    # drain so the module-scoped engine state is clean
    engine.commit_step(
        plan, engine.execute_step(plan, prepared), prepared
    )
    while engine.has_unfinished_requests():
        engine.step()


def test_ragged_work_schedule_width_is_per_bucket_stable(
    tiny_model_dir, monkeypatch
):
    """The Pallas work-schedule width is a compile shape of the jitted
    ragged step: every dispatch at a given flat bucket must reuse ONE
    quantized width (pow2 high-water, floored), not retrace at every
    distinct (item count) the batch mix happens to produce."""
    from vllm_tgis_adapter_tpu.ops import attention as attn_ops

    monkeypatch.setattr(attn_ops, "_use_pallas", lambda: True)
    engine = _make_engine(tiny_model_dir, "ragged")
    runner = engine.runner
    orig = runner.prepare_ragged
    seen: list[tuple[int, int]] = []

    def spy(plan):
        prep = orig(plan)
        assert prep.work is not None
        seen.append((prep.bucket, prep.work.shape[1]))
        return prep

    monkeypatch.setattr(runner, "prepare_ragged", spy)
    rng = np.random.default_rng(29)
    _run_requests(engine, _mixed_requests(rng))
    assert seen
    by_bucket: dict[int, set[int]] = {}
    for bucket, width in seen:
        by_bucket.setdefault(bucket, set()).add(width)
    for bucket, widths in by_bucket.items():
        assert len(widths) == 1, (bucket, widths)
        (w,) = widths
        assert w >= 64 and (w & (w - 1)) == 0
    assert runner._ragged_work_hwm == {
        b: max(ws) for b, ws in by_bucket.items()
    }


def test_ragged_precompile_warms_decode_heavy_tail_bucket(tiny_model_dir):
    """Flat buckets past the chunk budget are reachable only when a
    large running batch pushes the planner over it (bucket =
    max(floor_bucket, _ragged_bucket(base+1))); precompile's mixed
    tail phase must warm exactly the reachable ones and skip the rest."""
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    engine = LLMEngine.from_config(EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16, num_blocks=256, cache_dtype=mcfg.dtype,
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=40, prefill_buckets=(32,),
            max_num_batched_tokens=32,
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
        attention_backend="ragged",
    ))
    sched = engine.scheduler
    assert sched.chunk_budget == 32
    assert sched.ragged_buckets == [16, 32, 64, 128]

    buckets: list[int] = []
    orig = engine.runner.prepare_ragged

    def spy(plan):
        prep = orig(plan)
        buckets.append(prep.bucket)
        return prep

    engine.runner.prepare_ragged = spy
    engine.precompile("max")
    assert not engine.has_unfinished_requests()
    # 64 needs base > 32 running rows (prompt warmups cap at the 32
    # chunk budget): only the mixed tail phase reaches it
    assert 64 in buckets
    # 128 is unreachable at this config (base <= 40, chunk <= 32 ->
    # desired <= 72; _ragged_bucket(41) = 64): must be skipped
    assert 128 not in buckets


def test_ragged_precompile_tail_skips_full_batch_prev_route(tiny_model_dir):
    """prev == max_num_seqs must NOT take the prev-route: parking
    max_num_seqs one-token rows leaves zero free slots, so the filler
    prompt could never be admitted and the warm cycle was a guaranteed
    miss (park + drain paid for nothing).  The bucket is unreachable at
    serving time too (base <= 63 with a prefill slot caps the plan at
    bucket 64), so the right behavior is a silent skip."""
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    engine = LLMEngine.from_config(EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16, num_blocks=256, cache_dtype=mcfg.dtype,
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=64, prefill_buckets=(32,),
            max_num_batched_tokens=32,
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
        attention_backend="ragged",
    ))
    sched = engine.scheduler
    assert sched.ragged_buckets == [16, 32, 64, 128]

    buckets: list[int] = []
    warm_ids: list[str] = []
    orig_prep = engine.runner.prepare_ragged
    orig_add = engine.add_request

    def spy_prep(plan):
        prep = orig_prep(plan)
        buckets.append(prep.bucket)
        return prep

    def spy_add(request_id, *args, **kwargs):
        warm_ids.append(request_id)
        return orig_add(request_id, *args, **kwargs)

    engine.runner.prepare_ragged = spy_prep
    engine.add_request = spy_add
    engine.precompile("max")
    assert not engine.has_unfinished_requests()
    # bucket 64 warms via the prev=32 route as before
    assert 64 in buckets
    # bucket 128: prev == max_num_seqs == 64 — no rows may be parked
    # for a warm that cannot admit its filler
    assert 128 not in buckets
    assert not [r for r in warm_ids if r.startswith("__warmup_mix_128")]


def test_ragged_seen_seed_pad_ignores_decode_rows(tiny_model_dir):
    """Only finishing prompts seed the seen matrix, so the seeding pad
    width must track the seeding prompts — not a decode row whose
    all_token_ids has grown past the largest prefill bucket (that would
    retrace jitted set_seen_rows at every quantum the longest running
    generation crosses, with an ever-larger host transfer)."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    rng = np.random.default_rng(31)
    long_ids = rng.integers(3, 500, size=120).tolist()
    shorts = [rng.integers(3, 500, size=8).tolist() for _ in range(4)]

    def run(backend, widths=None):
        engine = _make_engine(tiny_model_dir, backend)
        if widths is not None:
            orig = engine.runner.prepare_ragged

            def spy(plan):
                prep = orig(plan)
                widths.append(prep.seed_tokens.shape[1])
                return prep

            engine.runner.prepare_ragged = spy
        engine.add_request(
            "long", None,
            SamplingParams(temperature=0.0, max_tokens=60, ignore_eos=True),
            prompt_token_ids=list(long_ids),
        )
        outs = {}
        pending = list(enumerate(shorts))
        # stagger the short prompts into the long request's decode
        # phase, after its total length has crossed the largest bucket
        for step in range(1000):
            if pending and step >= 12 and step % 6 == 0:
                i, ids = pending.pop(0)
                engine.add_request(
                    f"s{i}", None,
                    SamplingParams(
                        temperature=0.0, max_tokens=2, ignore_eos=True
                    ),
                    prompt_token_ids=list(ids),
                )
            for o in engine.step():
                outs[o.request_id] = list(o.outputs[0].token_ids)
            if not engine.has_unfinished_requests() and not pending:
                break
        assert not engine.has_unfinished_requests()
        assert not pending
        return outs

    widths: list[int] = []
    r_ragged = run("ragged", widths)
    assert len(r_ragged) == 5
    # the longest SEEDING prompt is 120 tokens (pad 128); the long
    # request's 120+60-token decode rows must not widen it to 256
    assert widths and max(widths) <= 128


def test_runner_jits_are_compile_tracker_wrapped():
    """Every jax.jit in runner.py is wrapped in track_jit, and
    ops/ragged_attention.py introduces no untracked module-level jit —
    its entry points compile inside the runner's tracked programs (the
    tpulint registry carries ragged_forward for the same reason)."""
    runner_src = (
        REPO_ROOT / "vllm_tgis_adapter_tpu" / "engine" / "runner.py"
    ).read_text()
    tree = ast.parse(runner_src)

    def is_jit(node):
        return (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "jit"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "jax"
        )

    def jit_descendants(node):
        return [n for n in ast.walk(node) if is_jit(n)]

    tracked = set()
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "track_jit"
        ):
            for arg in node.args:
                tracked.update(id(j) for j in jit_descendants(arg))

    def is_boot_time(jit_call):
        # jax.jit(lambda: ...) with no params is a one-shot boot-time
        # allocator (the sharded cache build), not a serving entry
        # point — same exemption tpulint's TPL104 applies
        return any(
            isinstance(a, ast.Lambda) and not a.args.args
            for a in jit_call.args
        )

    untracked = [
        j.lineno for j in jit_descendants(tree)
        if id(j) not in tracked and not is_boot_time(j)
    ]
    assert not untracked, (
        f"runner.py has jax.jit calls outside track_jit at lines "
        f"{untracked} — every jitted entry point must be "
        f"compile-tracker-wrapped"
    )

    ragged_src = (
        REPO_ROOT / "vllm_tgis_adapter_tpu" / "ops" / "ragged_attention.py"
    ).read_text()
    ragged_tree = ast.parse(ragged_src)
    assert not jit_descendants(ragged_tree), (
        "ops/ragged_attention.py must not jit its own entry points — "
        "they compile inside the runner's tracked programs"
    )

    # the tpulint registry knows the ragged entry point (satellite
    # contract: new jit-registry entries ride along with the kernel)
    import sys

    sys.path.insert(0, str(REPO_ROOT))
    try:
        from tools.tpulint import config as tpulint_config
    finally:
        sys.path.pop(0)
    assert "LlamaForCausalLM.ragged_forward" in tpulint_config.JIT_REGISTRY[
        "models/llama.py"
    ]


# ------------------------------------------------------------------ chaos


def test_ragged_dispatch_failpoint_replays_onto_ragged_path(tiny_model_dir):
    """Chaos case: a failpoint in the ragged dispatch kills the step
    loop before any token is emitted; the supervisor must replay the
    requests into the rebuilt engine and finish them ON the ragged path
    (token-identical to an uncrashed ragged run)."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        FrontdoorConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams
    from vllm_tgis_adapter_tpu.supervisor import failpoints

    def build():
        mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
        config = EngineConfig(
            model_config=mcfg,
            cache_config=CacheConfig(
                block_size=16, num_blocks=64, cache_dtype=mcfg.dtype
            ),
            scheduler_config=SchedulerConfig(
                max_num_seqs=4, prefill_buckets=(32, 64)
            ),
            parallel_config=ParallelConfig(),
            lora_config=LoRAConfig(),
            max_engine_restarts=3,
            engine_restart_backoff_s=0.02,
            frontdoor=FrontdoorConfig(enabled=True),
            attention_backend="ragged",
        )
        return AsyncLLMEngine.from_config(config)

    async def run(engine):
        async def one(i):
            final = None
            async for out in engine.generate(
                prompt=None,
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=6, ignore_eos=True
                ),
                request_id=f"r{i}",
                prompt_token_ids=[5 + i] * 12,
            ):
                final = out
            return list(final.outputs[0].token_ids)

        await engine.start()
        try:
            return await asyncio.gather(*[one(i) for i in range(3)])
        finally:
            await engine.stop()

    failpoints.disarm()
    baseline = asyncio.run(run(build()))

    engine = build()
    failpoints.arm("runner.dispatch_ragged=raise:1")
    try:
        replayed = asyncio.run(run(engine))
        fired = failpoints.fired("runner.dispatch_ragged")
    finally:
        failpoints.disarm()
    assert fired == 1, "failpoint never fired — the chaos case is vacuous"
    assert replayed == baseline
    assert engine.supervisor is not None
    assert engine.supervisor.restart_history, "no supervised restart ran"
