"""Attention over the paged KV cache.

TPU-native replacement for the paged-attention CUDA kernels the reference
stack executes inside vLLM (SURVEY.md §2.2/§2.3).  This module holds the
XLA-composed implementations: dense causal prefill attention and the
gather-based paged decode formulation.  They are correct on every backend
(CPU tests included) and serve as the numerical reference for the Pallas
TPU kernels in ``pallas_attention.py`` / ``ragged_attention.py``, which
are swapped in at engine boot when running on real TPU hardware.  Decode
itself serves through the unified RAGGED kernel (ops/ragged_attention.py)
— the bucketed folded/perhead decode variant ladder is retired
(docs/ATTENTION.md); ``paged_decode_attention_xla`` below remains as the
shared numerical reference and CPU path.

Layout choices (TPU-first):
* KV cache is one array per K/V of shape ``[num_layers, kv_heads, num_slots,
  head_dim]`` where ``num_slots = num_blocks * block_size`` — head-leading
  so a KV page is a contiguous ``(block_size, head_dim)`` tile, the layout
  Mosaic can DMA as a legal (sublane, lane) block (see
  pallas_attention.py's module docstring); the flat slot dimension keeps
  page writes as scatters and page reads as gathers with plain integer
  indices (no data-dependent shapes, jit-stable).
* softmax runs in float32 regardless of cache dtype (MXU-friendly bf16 in,
  f32 accumulate).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import shard_map

NEG_INF = float("-inf")

# ATTENTION_BACKEND=pallas|xla|auto (auto: Pallas kernels on TPU, XLA
# fallbacks elsewhere; pallas on a non-TPU backend runs the kernels in
# interpreter mode — slow, tests only)
_BACKEND_ENV = "ATTENTION_BACKEND"

# pallas_call is an opaque custom call the GSPMD partitioner cannot split,
# so under a TP mesh the kernels are wrapped in shard_map over the
# head-sharded axis.  The mesh travels explicitly on the call path
# (model -> dispatch), never via process state: two engines with
# different meshes in one process must not affect each other's retraces.


def _use_pallas() -> bool:
    mode = os.environ.get(_BACKEND_ENV, "auto")
    if mode == "xla":
        return False
    if mode == "pallas":
        return True
    return jax.default_backend() == "tpu"


def _pallas_interpret() -> bool:
    return jax.default_backend() != "tpu"


def write_kv(
    k_cache: jax.Array,  # [Hkv, num_slots, Dh] head-leading
    v_cache: jax.Array,
    k: jax.Array,  # [T, Hkv, Dh]
    v: jax.Array,
    slot_mapping: jax.Array,  # [T] int32 flat slot per token; -1 = drop
) -> tuple[jax.Array, jax.Array]:
    """Scatter new K/V rows into their assigned cache slots.

    Padding tokens carry slot -1; JAX's scatter mode='drop' only discards
    out-of-bounds *positive* indices (negatives wrap), so negatives are
    remapped to num_slots first and then dropped.  A single advanced index
    keeps the indexed dim in place — ``cache[:, safe]`` is ``[Hkv, T, Dh]``
    — so ``k``/``v`` are swapped to head-leading before the scatter.
    """
    k = k.astype(k_cache.dtype)
    v = v.astype(v_cache.dtype)
    safe = jnp.where(slot_mapping < 0, k_cache.shape[1], slot_mapping)
    k_cache = k_cache.at[:, safe].set(jnp.swapaxes(k, 0, 1), mode="drop")
    v_cache = v_cache.at[:, safe].set(jnp.swapaxes(v, 0, 1), mode="drop")
    return k_cache, v_cache


def prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: float,
    valid_len: jax.Array | None = None,
    mesh=None,
    window: int = 0,
    alibi_slopes: jax.Array | None = None,  # [H] f32 (bloom lineage)
    seg_starts: jax.Array | None = None,  # [max_segs] i32 packed-prefill
    sp_mode: str = "ring",  # "ring" | "ulysses" sequence-parallel style
) -> jax.Array:
    """Dispatch: flash Pallas kernel on TPU, XLA fallback elsewhere.

    Under a TP mesh the kernel runs inside shard_map over the head axis
    (each shard attends with its local query/kv heads; GQA grouping is
    preserved because tp divides both H and Hkv, parallel/sharding.py).
    Under an sp mesh axis > 1 the sequence axis is sharded instead and
    K/V chunks rotate around the ring (ops/ring_attention.py) — the
    long-context path.

    ``seg_starts`` enables packed (batched) prefill: several prompts are
    concatenated along the token axis and ``seg_starts[b]`` is the flat
    start index of segment b (entry 0 is 0; unused entries pad with T).
    Queries then attend only within their own segment (block-diagonal
    causal mask).  The scheduler only packs on the plain causal path, so
    seg_starts never combines with window/ALiBi/sp.
    """
    if seg_starts is not None and (
        window > 0
        or alibi_slopes is not None
        or (mesh is not None and dict(mesh.shape).get("sp", 1) > 1)
    ):
        raise NotImplementedError(
            "packed prefill (seg_starts) composes only with plain causal "
            "attention; the block-diagonal mask survives as ops-level "
            "machinery only — the serving planner is ragged "
            "(docs/ATTENTION.md)"
        )
    if mesh is not None and dict(mesh.shape).get("sp", 1) > 1:
        # window/ALiBi ride through both sp styles: the ring carries the
        # band mask / position bias in GLOBAL coordinates across hops
        # (ops/ring_attention.py _chunk_attention), ulysses head-slices
        # the slopes to follow its all-to-all repartition
        vl = (
            jnp.asarray(q.shape[0], jnp.int32)
            if valid_len is None
            else valid_len
        )
        if sp_mode == "ulysses":
            from vllm_tgis_adapter_tpu.ops.ulysses_attention import (
                ulysses_prefill_attention,
            )

            return ulysses_prefill_attention(
                q, k, v, scale, vl, mesh, window=window,
                alibi_slopes=alibi_slopes,
            )
        from vllm_tgis_adapter_tpu.ops.ring_attention import (
            ring_prefill_attention,
        )

        return ring_prefill_attention(
            q, k, v, scale, vl, mesh, window=window,
            alibi_slopes=alibi_slopes,
        )
    if _use_pallas():
        from vllm_tgis_adapter_tpu.ops import pallas_attention

        vl = (
            jnp.asarray(q.shape[0], jnp.int32)
            if valid_len is None
            else valid_len
        )
        kernel = functools.partial(
            pallas_attention.prefill_attention,
            scale=scale,
            window=window,
            interpret=_pallas_interpret(),
        )
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            heads = P(None, "tp", None)
            # slopes (when present) shard with the query heads: each tp
            # shard's kernel sees exactly its local heads' slopes
            operands = [q, k, v, vl]
            specs = [heads, heads, heads, P()]
            if alibi_slopes is not None:
                operands.append(("alibi", alibi_slopes, P("tp")))
            if seg_starts is not None:
                operands.append(("segs", seg_starts, P()))
            tagged = [op for op in operands if isinstance(op, tuple)]
            operands = operands[:4] + [op[1] for op in tagged]
            specs = specs + [op[2] for op in tagged]
            names = [op[0] for op in tagged]

            def wrapped(q, k, v, vl, *rest):
                by_name = dict(zip(names, rest))
                return kernel(q, k, v, valid_len=vl,
                              alibi_slopes=by_name.get("alibi"),
                              seg_starts=by_name.get("segs"))

            return shard_map(
                wrapped, mesh=mesh, in_specs=tuple(specs),
                out_specs=heads, check_vma=False,
            )(*operands)
        return kernel(q, k, v, valid_len=vl, alibi_slopes=alibi_slopes,
                      seg_starts=seg_starts)
    return prefill_attention_xla(q, k, v, scale, valid_len, window=window,
                                 alibi_slopes=alibi_slopes,
                                 seg_starts=seg_starts)


def prefill_attention_xla(
    q: jax.Array,  # [T, H, Dh]
    k: jax.Array,  # [T, Hkv, Dh]
    v: jax.Array,  # [T, Hkv, Dh]
    scale: float,
    valid_len: jax.Array | None = None,  # scalar int: tokens < valid_len attend
    window: int = 0,  # >0: attend to at most the previous `window` tokens
    alibi_slopes: jax.Array | None = None,  # [H] f32 per-head bias slopes
    seg_starts: jax.Array | None = None,  # [max_segs] i32 packed-prefill starts
) -> jax.Array:
    """Causal self-attention over a single (padded) prompt.

    Prompts are padded up to a bucket length; padding tokens still flow
    through the math (static shapes) but their K/V are masked out for real
    tokens' queries via the causal mask, and their own outputs are discarded
    by the caller.

    With ``seg_starts`` (packed prefill) the mask is block-diagonal
    causal: token p belongs to segment ``sum(p >= seg_starts) `` and only
    attends within it.  Padding tokens land in the last segment, but
    their keys are already excluded by valid_len and their query rows are
    discarded by the caller.
    """
    t, num_heads, head_dim = q.shape
    num_kv = k.shape[1]
    q_per_kv = num_heads // num_kv

    qh = q.reshape(t, num_kv, q_per_kv, head_dim).astype(jnp.float32)
    kh = k.astype(jnp.float32)
    vh = v.astype(jnp.float32)

    # [num_kv, q_per_kv, Tq, Tk]
    scores = jnp.einsum("tkgd,skd->kgts", qh, kh) * scale
    if alibi_slopes is not None:
        # HF bloom convention: score(q_i, k_j) += slope_h * j (the
        # row-constant -slope_h*i term cancels in the softmax)
        slopes = alibi_slopes.reshape(num_kv, q_per_kv).astype(jnp.float32)
        scores = scores + (
            slopes[:, :, None, None]
            * jnp.arange(t, dtype=jnp.float32)[None, None, None, :]
        )
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    mask = causal
    if window > 0:
        # band mask: query i sees keys (i-window, i] (HF mistral
        # convention — the diagonal plus window-1 predecessors)
        offsets = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        mask = mask & (offsets < window)
    if seg_starts is not None:
        # segment of token p = how many segment starts are <= p
        seg = (
            jnp.arange(t)[:, None] >= seg_starts[None, :].astype(jnp.int32)
        ).sum(axis=1)
        mask = mask & (seg[:, None] == seg[None, :])
    if valid_len is not None:
        mask = mask & (jnp.arange(t) < valid_len)[None, :]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (bucket padding beyond valid_len+window) softmax
    # to NaN, and 0·NaN in the value contraction would poison EVERY row
    # at the next layer (padding rows feed layer n+1's K/V); exact zeros
    # keep padding outputs finite (0) and valid rows untouched
    probs = jnp.where(mask[None, None], probs, 0.0)
    out = jnp.einsum("kgts,skd->tkgd", probs, vh)
    return out.reshape(t, num_heads, head_dim).astype(q.dtype)


def chunked_prefill_attention(
    q: jax.Array,  # [T, H, Dh] one chunk's queries
    k_cache: jax.Array,  # [Hkv, num_slots, Dh]
    v_cache: jax.Array,
    block_table: jax.Array,  # [max_blocks] this sequence's page table
    start_pos: jax.Array,  # scalar: context tokens before this chunk
    valid_len: jax.Array,  # scalar: real tokens in the chunk
    block_size: int,
    scale: float,
    mesh=None,
    window: int = 0,
    alibi_slopes: jax.Array | None = None,  # [H] f32 (bloom lineage)
    kv_scales: tuple | None = None,  # ([Hkv, pages] f32 x2) quantized KV
) -> jax.Array:
    """Causal chunk-vs-paged-context attention (the chunked-prefill and
    prefix-cache-resume hot path).

    TPU: dedicated Pallas kernel — each context page is read once per
    (kv head, query block) instead of once per query token.  Fallback:
    the decode formulation (each query as a batch row with its own
    context length), which is what the kernel's numerics are pinned to.
    With quantized KV (``kv_scales`` set, ops/kv_quant.py) the gather
    formulation runs everywhere — this is the legacy solo planner's
    path only (prompt-logprob heads); the ragged serving kernel has its
    own in-register dequant.
    """
    if _use_pallas() and kv_scales is None:
        from vllm_tgis_adapter_tpu.ops import pallas_attention

        kernel = functools.partial(
            pallas_attention.chunked_prefill_attention,
            block_size=block_size,
            scale=scale,
            window=window,
            interpret=_pallas_interpret(),
        )
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            heads = P(None, "tp", None)
            cache = P("tp", None, None)
            operands = [q, k_cache, v_cache, block_table,
                        jnp.asarray(start_pos, jnp.int32),
                        jnp.asarray(valid_len, jnp.int32)]
            specs = [heads, cache, cache, P(), P(), P()]
            if alibi_slopes is not None:
                operands.append(alibi_slopes)
                specs.append(P("tp"))

            def wrapped(q, kc, vc, bt, sp, vl, *rest):
                return kernel(q, kc, vc, bt, sp, vl,
                              alibi_slopes=rest[0] if rest else None)

            return shard_map(
                wrapped, mesh=mesh, in_specs=tuple(specs),
                out_specs=heads, check_vma=False,
            )(*operands)
        return kernel(q, k_cache, v_cache, block_table, start_pos,
                      valid_len, alibi_slopes=alibi_slopes)
    # XLA fallback: every chunk query becomes a decode row with context
    # length position+1 (exact same semantics, gather-based)
    t = q.shape[0]
    local = jnp.arange(t, dtype=jnp.int32)
    positions = jnp.asarray(start_pos, jnp.int32) + local
    ctx_lens = jnp.where(local < valid_len, positions + 1, 1)
    tables = jnp.broadcast_to(block_table[None, :], (t, block_table.shape[0]))
    return paged_decode_attention_xla(
        q, k_cache, v_cache, tables, ctx_lens, block_size, scale,
        window=window, alibi_slopes=alibi_slopes, kv_scales=kv_scales,
    )


def paged_decode_attention_xla(
    q: jax.Array,  # [B, H, Dh]
    k_cache: jax.Array,  # [Hkv, num_slots, Dh] head-leading
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks] int32 page ids (-1 pad)
    context_lens: jax.Array,  # [B] int32, tokens of context incl. current
    block_size: int,
    scale: float,
    window: int = 0,  # >0: attend to at most the last `window` tokens
    alibi_slopes: jax.Array | None = None,  # [H] f32 per-head bias slopes
    kv_scales: tuple | None = None,  # ([Hkv, pages] f32 x2) quantized KV
) -> jax.Array:
    """One-token-per-sequence attention against the paged cache.

    Gather-based XLA implementation: materialises each sequence's pages as
    ``[B, max_blocks * block_size]`` rows, masks beyond ``context_len``.
    With quantized KV (``kv_scales`` from ops/kv_quant.py) the gathered
    page values multiply by their per-(head, page) scale right after the
    gather — the dequant stays on the gathered working set, never the
    whole cache.
    """
    b, num_heads, head_dim = q.shape
    max_blocks = block_tables.shape[1]
    num_kv = k_cache.shape[0]
    q_per_kv = num_heads // num_kv
    s = max_blocks * block_size

    # [B, S] flat slot index per in-context token position
    slot_idx = (
        block_tables[:, :, None] * block_size
        + jnp.arange(block_size)[None, None, :]
    ).reshape(b, s)
    # pages with id -1 produce negative slots; take(mode='fill') would give
    # garbage — clamp and rely on the length mask instead
    gather_idx = jnp.clip(slot_idx, 0, k_cache.shape[1] - 1)

    keys = jnp.take(k_cache, gather_idx, axis=1).astype(jnp.float32)  # [Hkv,B,S,Dh]
    values = jnp.take(v_cache, gather_idx, axis=1).astype(jnp.float32)
    if kv_scales is not None:
        k_scale, v_scale = kv_scales
        page_idx = gather_idx // block_size  # [B, S] physical page ids
        keys = keys * jnp.take(
            k_scale.astype(jnp.float32), page_idx, axis=1
        )[..., None]
        values = values * jnp.take(
            v_scale.astype(jnp.float32), page_idx, axis=1
        )[..., None]

    qh = q.reshape(b, num_kv, q_per_kv, head_dim).astype(jnp.float32)
    scores = jnp.einsum("bkgd,kbsd->bkgs", qh, keys) * scale
    if alibi_slopes is not None:
        # position index s IS the sequence position (block j of the table
        # covers positions [j*bs, (j+1)*bs)); same bias as prefill
        slopes = alibi_slopes.reshape(num_kv, q_per_kv).astype(jnp.float32)
        scores = scores + (
            slopes[None, :, :, None]
            * jnp.arange(s, dtype=jnp.float32)[None, None, None, :]
        )
    length_mask = jnp.arange(s)[None, :] < context_lens[:, None]  # [B, S]
    if window > 0:
        # sliding window: only the last `window` in-context positions
        length_mask = length_mask & (
            jnp.arange(s)[None, :] >= context_lens[:, None] - window
        )
    scores = jnp.where(length_mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,kbsd->bkgd", probs, values)
    return out.reshape(b, num_heads, head_dim).astype(q.dtype)
