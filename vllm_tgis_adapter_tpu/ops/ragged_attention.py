"""Ragged paged attention: one kernel, one dispatch, zero bucket padding.

The bucketed data path runs THREE attention families per engine step —
solo/packed flash prefill over a padded prompt bucket, chunked prefill
against the paged cache, and a per-batch-width decode ladder
(folded → perhead → xla).  Every family carries its own compile lattice
and its own padding.  This module collapses them into ONE computation
(PAPERS.md: *Ragged Paged Attention — A High-Performance and Flexible
LLM Inference Kernel for TPU*): the engine hands the kernel a FLAT token
stream in which each sequence owns a contiguous span — a whole prompt, a
prefill chunk, or a single decode token — plus per-sequence descriptors,
and every row attends causally to its sequence's paged KV context.  A
mixed prefill+decode batch is one dispatch with no per-prompt bucket
padding; the only pad is the tail of the single flat-length bucket.

Layout contract (shared with ops/attention.py):
* KV cache per layer is head-leading ``[Hkv, num_slots, Dh]`` — a page is
  a contiguous ``(block_size, Dh)`` Mosaic-legal tile; the serving
  programs hand the kernel the whole ``[L, Hkv, num_slots, Dh]`` pool
  and the layer, which the page's index map addresses: no call is fed a
  copy of its layer;
* the caller scatters this step's K/V into the cache BEFORE attention,
  so prefill rows see their own chunk and decode rows see their token
  through the same paged read path — that unification is what removes
  the separate prefill/decode kernels.

Descriptors (all device arrays; S = padded sequence-descriptor width):
* ``seq_starts [S+1]`` — flat row where sequence s's span begins; spans
  are contiguous and sorted; unused/pad entries hold the padded stream
  length, so a span's membership test is just its two bounds;
* ``pos_base [S]`` — global position of sequence s's first row (chunk
  ``start_pos``; ``num_tokens - 1`` for a decode row);
* ``block_tables [S, max_blocks]`` — page table per sequence;
* ``positions [T]`` — global position per row (redundant with
  pos_base/seq_starts; the XLA path uses it directly, the Pallas kernel
  re-derives it from SMEM scalars to avoid vector gathers).

Pallas kernel: grid ``(kv_head, work_item)`` over a precomputed WORK
SCHEDULE — one item per (query block, sequence, RUN OF PAGES) triple
that actually overlaps, exactly the ragged-friendly formulation the
paper's kernel uses instead of a dense (batch, page) grid.  A run is up
to ``pages_per_item(block_size)`` consecutive LOGICAL pages of one
sequence — 128 KV tokens, one lane width of the score matrix — so a
grid step's fixed cost (the query block's load, the masks, the
online-softmax rescale) is paid once per 128 tokens, not once per page;
the run's physical pages are wherever the page table put them.  The
schedule rides scalar prefetch; each page of the run DMAs straight out
of the paged cache via its own BlockSpec index map over the one pool
operand (the gather happens in the memory system).  A
schedule is an array of fixed shape with its real items first and their
count ``n_items`` as the grid's TRACED second extent, so one program
visits only what some row reads.  Mixed engine steps pass a host-built
sparse schedule (``build_work_schedule``; its width is a compile shape
that only grows, its count is this step's own); the fused decode wave
builds its own in-trace, once per step (``decode_work_schedule``).
Numerics: f32 online-softmax
accumulation, masking identical to ``paged_decode_attention_xla`` — the
XLA path below IS that function, so parity is pinned to the same
reference the bucketed kernels are.  A tile whose rows fill whole lane
widths is computed transposed, cached tokens on sublanes, so that the
softmax's statistics are element-wise work and not reductions across
lanes (``tile_stats``): the same float32 products and sums in another
order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax import shard_map
from vllm_tgis_adapter_tpu.ops.attention import (
    NEG_INF,
    _pallas_interpret,
    _use_pallas,
    paged_decode_attention_xla,
)

#: work-schedule row layout ([WORK_FIELDS + P, W] i32, P =
#: ``pages_per_item(block_size)``): query-block index, sequence id, real
#: pages in the item's run (1..P; 0 for a dead item), logical index of
#: the run's first page within the sequence, first-item-of-block flag,
#: last-item-of-block flag, live flag (0 = padding/masked item: no
#: compute, accumulators only); then the run's P physical page ids (the
#: DMA targets) — a short run repeats its last real page, so every
#: target is a page of the same sequence.
WORK_FIELDS = 7

#: KV tokens of one work item: one lane width of the score matrix
KV_TILE = 128


def pages_per_item(block_size: int) -> int:
    """Pages in one work item's run: as many as make a ``KV_TILE``-token
    KV tile (8 at block size 16, 1 from block size 128 up)."""
    return max(1, KV_TILE // block_size)


def work_rows(block_size: int) -> int:
    """Rows of a work schedule: the fields, then a run's page ids."""
    return WORK_FIELDS + pages_per_item(block_size)


def query_block(block_q: int, num_rows: int) -> int:
    """The query block a call over a stream of ``num_rows`` rows runs
    at: its ``block_q``, or the stream's padded length where that is
    shorter."""
    return min(block_q, _pow2_ceil(num_rows))


def tile_stats(g: int, block_q: int) -> str:
    """Which axis of a call's score tile the online softmax's statistics
    run along, from the tile's static shape: ``g`` query heads a KV head
    times ``block_q`` rows a query block (``query_block``) against
    ``KV_TILE`` cached tokens.

    ``"sublanes"``: a tile whose rows fill whole lane widths is computed
    transposed, cached tokens on sublanes and query rows on lanes, so
    the row maximum and the row sum are element-wise work over the
    tile's sublane groups and one 8-to-1 reduce a lane tile.  Across
    lanes they are one cross-lane reduction a vreg, twice, and their
    results are broadcast back across lanes: 1.2 of a 1,024-row tile's
    2.4 us (docs/ATTENTION.md "What a grid step costs").  A tile of
    exactly 128 rows (16 query heads a KV head in a wave) holds as many
    vregs either way and is a quarter faster so: the rule is a multiple
    of 128, not 256 and up.

    ``"lanes"``: the tile as ``[rows, KV_TILE]``.  The wave's tiles of
    32 or 40 rows would pad their lanes to 128 when transposed, and
    their cost is the pages' DMAs, not the reductions.
    """
    return "sublanes" if (g * block_q) % 128 == 0 else "lanes"


#: lane width of the scale-sidecar block the quantized kernel DMAs (the
#: TPU's minor tile dimension)
SCALE_LANES = 128


#: widest work schedule a kernel takes: the schedule rides scalar
#: prefetch, 16 rows of 4 bytes an item in the chip's 1 MB of scalar
#: memory, and 16,384 items do not fit beside the other prefetched
#: operands (the chip's compiler refuses the program)
MAX_WORK_WIDTH = 8192


def max_step_prefill_tokens(max_model_len: int, max_num_seqs: int,
                            block_size: int, block_q: int) -> int:
    """The most prompt tokens a mixed step may hold so that its schedule
    fits ``MAX_WORK_WIDTH`` whatever the contexts: every running row's
    one token and every query block of the chunk may each see
    ``max_model_len`` tokens, an item a run of 128.  A power of two (the
    planner's chunks are)."""
    run_tokens = pages_per_item(block_size) * block_size
    runs = -(-max_model_len // run_tokens)
    blocks = MAX_WORK_WIDTH // runs - max_num_seqs
    if blocks < 1:
        raise ValueError(
            f"max_model_len {max_model_len} with {max_num_seqs} rows: one "
            f"step's work schedule would not fit {MAX_WORK_WIDTH} items")
    tokens = blocks * block_q
    return 1 << (tokens.bit_length() - 1)


def _pow2_ceil(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


# ------------------------------------------------------------- schedules


def build_work_schedule(
    spans: list[tuple[int, int, int]],  # per seq: (start_row, n_rows, pos_base)
    block_tables: "np.ndarray",  # [S, max_blocks] int32
    *,
    block_size: int,
    block_q: int,
    t_pad: int,
    w_bucket: int | None = None,
    window: int = 0,
) -> tuple["np.ndarray", int]:
    """Host-side sparse schedule for a mixed ragged batch.

    Emits one work item per (query block, sequence, run of pages)
    triple: the logical pages that could be causally visible to some
    row of that sequence in that block — the exact page set, so the
    kernel never DMAs a page no row reads — cut into runs of
    ``pages_per_item(block_size)``.  A schedule serves the layers of one
    kind: with ``window`` 0 it covers the full causal span (the band of
    a model whose layers all share this one schedule is cut by the
    kernel's mask alone); with ``window`` > 0 it is the window layers'
    own and leaves out every run of pages that lies wholly below the
    band of the block's first row — the runs start at the band's first
    page, and the kernel's band mask cuts into that first run.  A page
    the cache no longer holds (table entry < 0: freed behind the band)
    is never named: its place repeats a live page of the same sequence,
    whose columns the band mask discards.  Every query
    block gets at least one item (a dead one if the block is all
    padding) so its output block is always initialised and finalised.

    Returns ``(work, n_items)``: ``work`` is ``[WORK_FIELDS + P, W]``
    int32 with W padded to a power of two (``w_bucket`` overrides) — the
    schedule width is a compile shape — and ``n_items`` counts the real
    items at its head (the dead items of empty query blocks included).
    The last of them carries its block's ``last`` flag, so a grid that
    ends at ``n_items`` finalises every output block.
    """
    run = pages_per_item(block_size)
    nq = t_pad // block_q
    # per item: (seq, real pages, first logical page, live, page ids)
    dead = (0, 0, 0, 0, [0] * run)
    per_block: list[list[tuple]] = [[] for _ in range(nq)]
    for s, (start, n_rows, pos0) in enumerate(spans):
        if n_rows <= 0:
            continue
        lo_block = start // block_q
        hi_block = (start + n_rows - 1) // block_q
        for qb in range(lo_block, hi_block + 1):
            # deepest position any of this sequence's rows in block qb
            # can see: its last row's own position
            row_hi = min(start + n_rows - 1, (qb + 1) * block_q - 1)
            max_pos = pos0 + (row_hi - start)
            first, last = band_pages(
                pos0 + (max(start, qb * block_q) - start), max_pos,
                block_size, window)
            pages = block_tables[s, first: last + 1].tolist()
            live = pages[-1]  # the newest page is never freed
            pages = [p if p >= 0 else live for p in pages]
            for j in range(0, len(pages), run):
                ids = pages[j: j + run]
                # a short run repeats its last real page: the causal
                # mask already excludes those columns
                per_block[qb].append((
                    s, len(ids), first + j, 1,
                    ids + ids[-1:] * (run - len(ids)),
                ))
    items: list[list[int]] = []
    for qb in range(nq):
        blk = per_block[qb] or [dead]  # dead item: zeros the block
        for i, (s, n_pages, j, live, ids) in enumerate(blk):
            items.append([
                qb, s, n_pages, j,
                1 if i == 0 else 0,
                1 if i == len(blk) - 1 else 0,
                live, *ids,
            ])
    w = len(items)
    width = w_bucket or _pow2_ceil(w)
    work = np.zeros((work_rows(block_size), width), np.int32)
    work[:, :w] = np.asarray(items, np.int32).T
    if width > w:
        # pads keep the final real block's index so the output pipeline
        # never revisits an earlier block; flags all zero = no-ops
        work[0, w:] = items[-1][0]
    return work, w


def band_pages(pos_lo: int, pos_hi: int, block_size: int,
               window: int) -> tuple[int, int]:
    """``(first, last)`` logical pages some row at a position in
    ``[pos_lo, pos_hi]`` reads: down from ``pos_hi``'s own page to page
    0, or with a window to the page of the oldest token ``pos_lo``
    sees."""
    first = max(0, pos_lo - window + 1) // block_size if window > 0 else 0
    return first, pos_hi // block_size


#: query-block height of the fused decode wave's schedule: every span
#: is one row, so a taller block only multiplies masked rows per item
DECODE_BLOCK_Q = 8


def decode_block_q(num_rows: int) -> int:
    """The ``block_q`` a ``decode_work_schedule`` over ``num_rows`` rows
    is laid out for; the attention call must be given the same."""
    return min(DECODE_BLOCK_Q, _pow2_ceil(num_rows))


def decode_work_schedule(
    pos_base: jax.Array,  # [S] i32: position of each row's one token
    active: jax.Array,  # [S] bool: row is live in this step
    block_tables: jax.Array,  # [S, max_blocks] i32
    *,
    block_size: int,
    block_q: int | None = None,
    window: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """In-trace schedule for the fused decode wave, where every span is
    exactly ONE row (``seq_starts = arange(S+1)``): sequence *s* IS flat
    row *s*, so its items live only in query block
    ``s // decode_block_q(S)`` (or ``s // block_q`` for a kernel with a
    query block of its own, ops/mla_attention.py).

    Returns ``(work, n_items)``.  ``work`` keeps the static shape
    ``[WORK_FIELDS + P, S * cdiv(max_blocks, P)]`` (every row at full
    context, ``P = pages_per_item(block_size)``), but only its first
    ``n_items`` entries mean anything: the live (row, run of pages)
    items in (query block, row, run) order — a row owns
    ``pos_base // block_size + 1`` pages cut into runs of P, a dead row
    none — plus one dead item for each query block none of whose rows is
    live, so every output block is still initialised and finalised.  The
    kernel's grid ends at ``n_items`` (a traced scalar): what is not
    live costs no grid step.

    With ``window`` > 0 (the window layers' own schedule) a row owns
    only the pages from its band's first one on (``band_pages``), its
    runs start there, and the static width shrinks to the band's runs.
    """
    s_count, max_blocks = block_tables.shape
    run = pages_per_item(block_size)
    band = max_blocks
    if window > 0:
        # a band covers window tokens from anywhere in a page
        band = min(max_blocks, -(-window // block_size) + 1)
    max_runs = -(-band // run)
    block_q = block_q or decode_block_q(s_count)
    nq = -(-s_count // block_q)
    rows = jnp.arange(s_count, dtype=jnp.int32)
    last_page = jnp.clip(pos_base // block_size, 0, max_blocks - 1)
    first_page = (
        jnp.maximum(pos_base - window + 1, 0) // block_size
        if window > 0 else jnp.zeros_like(last_page)
    ).astype(jnp.int32)
    pages = jnp.where(active, last_page + 1 - first_page, 0).astype(
        jnp.int32)
    runs = -(-pages // run)
    block_runs = jnp.sum(
        jnp.pad(runs, (0, nq * block_q - s_count)).reshape(nq, block_q),
        axis=1,
    )
    # the dead item of an empty block rides on the block's first row
    empty = (rows % block_q == 0) & (
        jnp.take(block_runs, rows // block_q) == 0
    )
    items = runs + empty.astype(jnp.int32)
    ends = jnp.cumsum(items)
    n_items = ends[-1]
    w = jnp.arange(s_count * max_runs, dtype=jnp.int32)
    # entries past n_items clamp onto the last row: never visited
    s = jnp.minimum(
        jnp.searchsorted(ends, w, side="right").astype(jnp.int32),
        s_count - 1,
    )
    r = w - (jnp.take(ends, s) - jnp.take(items, s))
    row_pages = jnp.take(pages, s)
    live = r < jnp.take(runs, s)
    row_first = jnp.take(first_page, s)
    j = r * run  # the run's first page, counted from the row's first
    n_pages = jnp.where(live, jnp.minimum(row_pages - j, run), 0)
    # a short run repeats its last real page, and a dead item's DMA
    # indices clamp to a page of its row's table: the causal mask
    # already excludes those columns
    j_eff = row_first[None, :] + jnp.clip(
        j[None, :] + jnp.arange(run, dtype=jnp.int32)[:, None],
        0, jnp.maximum(row_pages - 1, 0)[None, :],
    )
    page_ids = jnp.clip(block_tables[s[None, :], j_eff], 0, None)
    j = row_first + j  # the run's first LOGICAL page
    qb = s // block_q
    # first/last flags on the block TRANSITIONS: blocks hold ragged
    # item counts, and every block's accumulators must init exactly
    # once and finalise on its true final item
    step = qb[1:] != qb[:-1]
    edge = jnp.ones(1, bool)
    first = jnp.concatenate([edge, step])
    last = jnp.concatenate([step, edge]) | (w == n_items - 1)
    work = jnp.concatenate([
        jnp.stack([
            qb, s, n_pages, j, first.astype(jnp.int32),
            last.astype(jnp.int32), live.astype(jnp.int32),
        ]),
        page_ids,
    ])
    return work, n_items


# ----------------------------------------------------------------- kernel


def _ragged_kernel(
    # scalar prefetch
    work_ref,  # [WORK_FIELDS + P, W] SMEM work schedule
    starts_ref,  # [S+1] SMEM flat span starts (pads = padded length)
    base_ref,  # [S] SMEM global position of each span's first row
    alibi_ref,  # [H] f32 SMEM slopes; unused unless use_alibi
    # blocks: q_ref [1, G*bq, Dh]; P k refs then P v refs, each
    # [1, block_size, Dh] (one page of the run, picked out of the
    # layer's pool by its own index_map); then — quantized caches only —
    # P ks refs and P vs refs, each [Hkv, SCALE_LANES] f32 (the lane
    # tile of the layer's scale sidecar that holds that page's dequant
    # scale); then o_ref [1, G*bq, Dh] and the three f32 scratch
    # accumulators (m [G*bq, 1], l [G*bq, 1], acc [G*bq, Dh]; with the
    # statistics on sublanes m [1, G*bq], l [1, G*bq], acc [Dh, G*bq])
    q_ref,
    *refs,
    scale: float,
    block_size: int,
    block_q: int,
    g: int,
    window: int,
    use_alibi: bool,
    quantized: bool,
    run: int,
    stats: str,
):
    # the axes of the score tile that query rows and cached tokens lie
    # on (``tile_stats``): the online softmax's maximum and sum run
    # along the cached tokens' axis
    transposed = stats == "sublanes"
    q_axis, kv_axis = (1, 0) if transposed else (0, 1)
    k_refs, v_refs, refs = refs[:run], refs[run: 2 * run], refs[2 * run:]
    ks_refs = vs_refs = None
    if quantized:
        ks_refs, vs_refs = refs[:run], refs[run: 2 * run]
        refs = refs[2 * run:]
    o_ref, m_ref, l_ref, acc_ref = refs
    h = pl.program_id(0)
    w = pl.program_id(1)
    seq = work_ref[1, w]
    first_page = work_ref[3, w]

    @pl.when(work_ref[4, w] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _tile(page_refs, scale_refs):
        """The run's pages as ONE ``[P * bs, Dh]`` f32 tile."""
        pages = []
        for i, page_ref in enumerate(page_refs):
            page = page_ref[0].astype(jnp.float32)  # [bs, Dh]
            if scale_refs is not None:
                # in-register dequant: each page segment of the tile
                # has ONE per-(kv head, page) scale (ops/kv_quant.py
                # sidecar).  Mosaic takes no 1x1 block of the
                # [Hkv, pages] sidecar, so the block is the page's
                # whole lane tile and the scalar is picked out of it by
                # a masked reduce (one vreg for Hkv<=8)
                tile = scale_refs[i].shape
                pick = (
                    jax.lax.broadcasted_iota(jnp.int32, tile, 0) == h
                ) & (
                    jax.lax.broadcasted_iota(jnp.int32, tile, 1)
                    == work_ref[WORK_FIELDS + i, w] % SCALE_LANES
                )
                page = page * jnp.sum(
                    jnp.where(pick, scale_refs[i][...], 0.0)
                )
            pages.append(page)
        return pages[0] if run == 1 else jnp.concatenate(pages, axis=0)

    @pl.when(work_ref[6, w] == 1)
    def _item():
        q = q_ref[0].astype(jnp.float32)  # [G*bq, Dh]
        k = _tile(k_refs, ks_refs)  # [P*bs, Dh]
        v = _tile(v_refs, vs_refs)
        # [G*bq, P*bs], or k @ q^T = [P*bs, G*bq] with the statistics on
        # sublanes: the same NT product, operands swapped
        s_mat = jax.lax.dot_general(
            *((k, q) if transposed else (q, k)), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        # rows are (g, i) flattened row-major (chunked-kernel layout):
        # flat token index = qb*bq + row % bq
        row = jax.lax.broadcasted_iota(
            jnp.int32, s_mat.shape, dimension=q_axis)
        tok = work_ref[0, w] * block_q + row % block_q
        # the item already names its sequence, and spans are contiguous
        # and sorted — membership and global position are two SMEM
        # scalar reads of the span bounds, not a scan over every
        # descriptor slot (no vector gathers either way; rows outside
        # the span mask out, so their garbage pos_row never matters)
        start = starts_ref[seq]
        pos_row = base_ref[seq] + tok - start
        col = jax.lax.broadcasted_iota(
            jnp.int32, s_mat.shape, dimension=kv_axis)
        # the run's pages are consecutive LOGICAL pages, so a column's
        # position is its offset from the run's first page; the places
        # of a short run lie past every row's own position
        k_pos = first_page * block_size + col
        keep = (
            (tok >= start)
            & (tok < starts_ref[seq + 1])
            & (k_pos <= pos_row)
        )
        if window > 0:
            keep &= pos_row - k_pos < window
        if use_alibi:
            # query head = h·G + (row // bq); 2-D selects, no 1-D gathers
            slopes = jnp.full(s_mat.shape, alibi_ref[h * g], jnp.float32)
            for gi in range(1, g):
                slopes = jnp.where(
                    row // block_q == gi, alibi_ref[h * g + gi], slopes
                )
            s_mat = s_mat + slopes * k_pos.astype(jnp.float32)
        s_mat = jnp.where(keep, s_mat, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(
            m_prev, jnp.max(s_mat, axis=kv_axis, keepdims=True))
        # fully masked rows keep m == -inf; pin the shift finite so exp
        # stays NaN-free (house convention, see _prefill_kernel)
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s_mat - shift)
        alpha = jnp.exp(
            jnp.where(jnp.isfinite(m_prev), m_prev, shift) - shift
        )
        l_ref[...] = alpha * l_ref[...] + jnp.sum(
            p, axis=kv_axis, keepdims=True)
        # p @ v = [G*bq, Dh], or v^T @ p = [Dh, G*bq]
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            *((v, p) if transposed else (p, v)),
            (((kv_axis,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(work_ref[5, w] == 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        # one transpose a (query block, sequence), not one an item
        o_ref[0] = (out.T if transposed else out).astype(o_ref.dtype)


def _pool_view(k_cache, v_cache, kv_scales, layer):
    """``(k_pool, v_pool, scales, layer)`` in the one form the kernel
    reads: the whole ``[L, Hkv, slots, Dh]`` pools (sidecars
    ``[L, Hkv, pages]``) and the layer to address.  One layer's own
    arrays (``layer`` None) are the pool of a single layer — a leading
    axis of 1 is a bitcast, not a copy."""
    if layer is not None:
        return k_cache, v_cache, kv_scales, layer
    if kv_scales is not None:
        kv_scales = tuple(s[None] for s in kv_scales)
    return k_cache[None], v_cache[None], kv_scales, 0


def _ragged_attention_pallas(
    q: jax.Array,  # [T, H, Dh] flat mixed stream
    k_cache: jax.Array,  # [L, Hkv, num_slots, Dh] with ``layer``, or
    v_cache: jax.Array,  # one layer's [Hkv, num_slots, Dh] without
    seq_starts: jax.Array,  # [S+1]
    pos_base: jax.Array,  # [S]
    work: jax.Array,  # [WORK_FIELDS + P, W]
    block_size: int,
    scale: float,
    *,
    block_q: int,
    window: int,
    alibi_slopes: jax.Array | None = None,
    interpret: bool,
    kv_scales: tuple | None = None,  # quantized only, f32 x2:
    #     [L, Hkv, pages] with ``layer``, one layer's [Hkv, pages] without
    n_items: jax.Array | None = None,  # traced scalar: the grid visits
    #     only work[:, :n_items] (None = the whole schedule)
    layer: int | None = None,  # static: which layer of the pool to read
    name: str | None = None,  # the call's name in a device trace
) -> jax.Array:
    t, num_heads, head_dim = q.shape
    k_cache, v_cache, kv_scales, layer = _pool_view(
        k_cache, v_cache, kv_scales, layer
    )
    num_kv = k_cache.shape[1]
    g = num_heads // num_kv
    run = pages_per_item(block_size)
    if work.shape[0] != work_rows(block_size):
        raise ValueError(
            f"work schedule of {work.shape[0]} rows: block size "
            f"{block_size} takes {WORK_FIELDS} fields and {run} page ids"
        )
    block_q = query_block(block_q, t)
    nq = pl.cdiv(t, block_q)
    t_pad = nq * block_q

    # [Hkv, nq·G·bq, Dh] with each q block laid out (G, bq) row-major —
    # the chunked-prefill kernel's layout: one page DMA serves the whole
    # GQA group of the block
    qp = jnp.pad(q, ((0, t_pad - t), (0, 0), (0, 0)))
    qh = jnp.transpose(
        qp.reshape(nq, block_q, num_kv, g, head_dim), (2, 0, 3, 1, 4)
    ).reshape(num_kv, nq * g * block_q, head_dim)

    slopes = (
        jnp.zeros(num_heads, jnp.float32)
        if alibi_slopes is None
        else alibi_slopes.astype(jnp.float32)
    )
    num_work = work.shape[1] if n_items is None else n_items
    quantized = kv_scales is not None
    stats = tile_stats(g, block_q)
    rows = g * block_q
    stat_shape, acc_shape = {
        "lanes": ((rows, 1), (rows, head_dim)),
        "sublanes": ((1, rows), (head_dim, rows)),
    }[stats]
    in_specs = [
        pl.BlockSpec(
            (1, g * block_q, head_dim),
            lambda h, w, wk, st, bs_, al: (h, wk[0, w], 0),
        ),
    ]
    # the pool is ONE array, never sliced: the layer is a static index
    # in a page's index map, so the call moves only the pages its items
    # name (a ``cache[layer]`` operand is a pool-sized copy a call).  It
    # is passed once per place of the run — by reference, no copy — and
    # each place reads its own row of the schedule
    page_specs = [
        pl.BlockSpec(
            (None, 1, block_size, head_dim),
            lambda h, w, wk, st, bs_, al, i=i: (
                layer, h, wk[WORK_FIELDS + i, w], 0
            ),
        )
        for i in range(run)
    ]
    in_specs += page_specs * 2
    operands = [qh, *[k_cache] * run, *[v_cache] * run]
    if quantized:
        # the layer's [Hkv, pages] scale sidecar travels as the
        # tile-legal (Hkv, SCALE_LANES) block holding each place's
        # physical page; consecutive items in one lane tile re-use the
        # resident block
        scale_specs = [
            pl.BlockSpec(
                (None, num_kv, SCALE_LANES),
                lambda h, w, wk, st, bs_, al, i=i: (
                    layer, 0, wk[WORK_FIELDS + i, w] // SCALE_LANES
                ),
            )
            for i in range(run)
        ]
        in_specs += scale_specs * 2
        operands += [kv_scales[0].astype(jnp.float32)] * run
        operands += [kv_scales[1].astype(jnp.float32)] * run
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(num_kv, num_work),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, g * block_q, head_dim),
            lambda h, w, wk, st, bs_, al: (h, wk[0, w], 0),
        ),
        scratch_shapes=[
            pltpu.VMEM(stat_shape, jnp.float32),
            pltpu.VMEM(stat_shape, jnp.float32),
            pltpu.VMEM(acc_shape, jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel, scale=scale, block_size=block_size,
            block_q=block_q, g=g, window=window,
            use_alibi=alibi_slopes is not None, quantized=quantized,
            run=run, stats=stats,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (num_kv, nq * g * block_q, head_dim), q.dtype
        ),
        interpret=interpret,
        **({} if name is None else {"name": name}),
    )(work, seq_starts.astype(jnp.int32), pos_base.astype(jnp.int32),
      slopes, *operands)
    return jnp.transpose(
        out.reshape(num_kv, nq, g, block_q, head_dim), (1, 3, 0, 2, 4)
    ).reshape(t_pad, num_heads, head_dim)[:t]


# --------------------------------------------------------------- dispatch


def ragged_attention_xla(
    q: jax.Array,  # [T, H, Dh] flat mixed stream
    k_cache: jax.Array,  # [Hkv, num_slots, Dh]
    v_cache: jax.Array,
    positions: jax.Array,  # [T] global position per row
    seq_starts: jax.Array,  # [S+1] flat span starts (pads = T)
    total_tokens: jax.Array,  # scalar: real rows in the stream
    block_tables: jax.Array,  # [S, max_blocks]
    block_size: int,
    scale: float,
    *,
    window: int = 0,
    alibi_slopes: jax.Array | None = None,
    kv_scales: tuple | None = None,
) -> jax.Array:
    """XLA reference: every ragged row IS a decode row with context
    length ``position + 1`` against its sequence's page table — the
    formulation the bucketed chunked-prefill fallback already pins its
    numerics to, generalised to a mixed multi-sequence stream."""
    t = q.shape[0]
    num_seqs = block_tables.shape[0]
    rows = jnp.arange(t, dtype=jnp.int32)
    seq = jnp.sum(
        rows[:, None] >= seq_starts[None, :num_seqs].astype(jnp.int32),
        axis=1,
    ) - 1
    seq = jnp.clip(seq, 0, num_seqs - 1)
    tables = jnp.take(block_tables, seq, axis=0)  # [T, max_blocks]
    ctx = jnp.where(rows < total_tokens, positions.astype(jnp.int32) + 1, 1)
    return paged_decode_attention_xla(
        q, k_cache, v_cache, tables, ctx, block_size, scale,
        window=window, alibi_slopes=alibi_slopes, kv_scales=kv_scales,
    )


def ragged_paged_attention(
    q: jax.Array,  # [T, H, Dh] flat mixed stream
    k_cache: jax.Array,  # the [L, Hkv, num_slots, Dh] pool with
    v_cache: jax.Array,  # ``layer``; one layer's [Hkv, ...] without
    positions: jax.Array,  # [T]
    seq_starts: jax.Array,  # [S+1]
    pos_base: jax.Array,  # [S]
    total_tokens: jax.Array,  # scalar
    block_tables: jax.Array,  # [S, max_blocks]
    block_size: int,
    scale: float,
    *,
    work: jax.Array | None = None,  # [WORK_FIELDS + P, W]; the Pallas path's
    n_items: jax.Array | None = None,  # scalar: live prefix of ``work``
    mesh=None,
    window: int = 0,
    alibi_slopes: jax.Array | None = None,  # [H] f32 (bloom lineage)
    block_q: int = 128,
    kv_scales: tuple | None = None,  # f32 x2 quantized-KV sidecars:
    #     [L, Hkv, pages] with ``layer``, one layer's [Hkv, pages] without
    layer: int | None = None,  # static: the pool's layer this call reads
    name: str | None = None,  # the kernel call's name in a device trace
    #     (None = the kernel function's own, as every trace so far has it)
) -> jax.Array:
    """One causal paged-attention dispatch over a mixed ragged stream.

    The caller must have scattered this step's K/V into the cache first.
    TPU runs the Pallas work-schedule kernel over the first ``n_items``
    entries of ``work`` (all of it when ``n_items`` is None): a
    host-built ``build_work_schedule`` with its real-item count (mixed
    steps), or a ``decode_work_schedule`` built once per decode step,
    with ``block_q = decode_block_q(T)``.  The serving programs hand
    over the WHOLE pool and the ``layer`` to read: the kernel addresses
    the layer in its index map, so no per-layer slice of the pool is
    ever made.  Elsewhere the XLA reference runs on the layer's slice
    and ``work`` is ignored entirely — it never becomes an operand, so
    schedule-width shape variety cannot retrace the CPU path.

    ``kv_scales`` marks the caches as quantized pages (ops/kv_quant.py):
    the Pallas kernel dequantizes each page of an item's run in-register
    against its one per-(kv head, page) scale, the XLA path right after
    its gather.

    Under a TP mesh the kernel runs inside shard_map over the head axis,
    the pool sharded on its kv-head axis — each device addresses the
    layer in its own shard.
    """
    if not _use_pallas():
        if layer is not None:
            k_cache, v_cache = k_cache[layer], v_cache[layer]
            if kv_scales is not None:
                kv_scales = tuple(s[layer] for s in kv_scales)
        return ragged_attention_xla(
            q, k_cache, v_cache, positions, seq_starts, total_tokens,
            block_tables, block_size, scale,
            window=window, alibi_slopes=alibi_slopes, kv_scales=kv_scales,
        )
    if work is None:
        raise ValueError(
            "the Pallas ragged kernel needs a work schedule: "
            "build_work_schedule or decode_work_schedule"
        )
    k_cache, v_cache, kv_scales, layer = _pool_view(
        k_cache, v_cache, kv_scales, layer
    )
    kernel = functools.partial(
        _ragged_attention_pallas,
        block_size=block_size,
        scale=scale,
        block_q=block_q,
        window=window,
        interpret=_pallas_interpret(),
        layer=layer,
        name=name,
    )
    # what only some calls carry
    extras = {
        name: value
        for name, value in (
            ("kv_scales", kv_scales),
            ("alibi_slopes", alibi_slopes),
            ("n_items", n_items),
        )
        if value is not None
    }
    if mesh is None:
        return kernel(q, k_cache, v_cache, seq_starts, pos_base, work,
                      **extras)
    from jax.sharding import PartitionSpec as P

    heads = P(None, "tp", None)
    cache = P(None, "tp", None, None)
    # scale sidecars shard with the kv-head axis like the pools they
    # dequantize, slopes with the heads; the grid's extent is
    # replicated, like the schedule it bounds
    extra_specs = {
        "kv_scales": (P(None, "tp", None), P(None, "tp", None)),
        "alibi_slopes": P("tp"),
        "n_items": P(),
    }
    return shard_map(
        lambda q, kc, vc, st, pb, wk, extras: kernel(
            q, kc, vc, st, pb, wk, **extras
        ),
        mesh=mesh,
        in_specs=(heads, cache, cache, P(), P(), P(),
                  {name: extra_specs[name] for name in extras}),
        out_specs=heads, check_vma=False,
    )(q, k_cache, v_cache, seq_starts, pos_base, work, extras)
