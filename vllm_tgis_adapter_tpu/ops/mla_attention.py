"""Latent attention (MLA) over a paged pool of latent rows, in the
absorbed form: every head of a token is a query ROW over ONE cached row
a token a layer.

A cached row is ``[c_kv (kv_lora_rank) | k_pe (rope dim, padded to a
lane tile)]`` (docs/LATENT_ATTENTION.md): with ``kv_b_proj`` split a head
into ``W_k`` and ``W_v``, ``q_lat = q_nope W_k`` and

    score = q_lat . c_kv + q_pe . k_pe,   o_lat = softmax(score) . c_kv,

so one ``[128, row]`` tile of the pool is the keys and, in its first
``value_dim`` columns, the values.  ``q_lat`` and ``o_head = o_lat W_v``
are the model's batched matmuls outside the kernel
(models/deepseek_v2.py).

The Pallas kernel walks the SAME work schedule as
ops/ragged_attention.py: one grid step per live (query block, sequence,
run of up to 128 KV tokens) item, the grid ending at the last live item,
in the mixed step (``build_work_schedule``, host-built) and in the fused
wave (``decode_work_schedule``, in-trace) alike.  There is no KV-head
axis: the grid is the items alone, and a query block's rows are its
``block_q`` tokens x all heads, token-major, which is ``q``'s own
memory order.  The latent tile is read once a grid step and used for
scores and for values.  Matmul operands stay in the pool's dtype;
scores, the online softmax and both accumulations are float32.

Off the TPU the XLA path is ``ragged_attention_xla`` on the pool as one
KV head whose values are its keys: the same reference the ragged
kernel's parity is pinned to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_tgis_adapter_tpu.ops import attention as attn_ops
from vllm_tgis_adapter_tpu.ops import ragged_attention
from vllm_tgis_adapter_tpu.ops.attention import NEG_INF
from vllm_tgis_adapter_tpu.ops.ragged_attention import (
    WORK_FIELDS,
    _pow2_ceil,
    pages_per_item,
    ragged_attention_xla,
    work_rows,
)

#: tokens of a mixed step's query block: x 128 heads, 512 query rows a
#: grid step.  A decode row in a mixed step shares its block with three
#: other sequences' rows, whose work on its item is masked away, so a
#: taller block wastes more of the MXU on decode rows than it saves a
#: prompt in re-read tiles (docs/LATENT_ATTENTION.md)
MIXED_BLOCK_Q = 4

#: the fused wave's: every span is one token, whose heads alone fill the
#: MXU's 128 rows
DECODE_BLOCK_Q = 1


#: widest work schedule the kernel takes (the ragged kernel's own bound)
MAX_WORK_WIDTH = ragged_attention.MAX_WORK_WIDTH


def max_step_prefill_tokens(max_model_len: int, max_num_seqs: int,
                            block_size: int) -> int:
    """The most prompt tokens a mixed step may hold so that its schedule
    fits ``MAX_WORK_WIDTH`` whatever the contexts, at this kernel's
    query block (ops/ragged_attention.py has the rule)."""
    return ragged_attention.max_step_prefill_tokens(
        max_model_len, max_num_seqs, block_size, MIXED_BLOCK_Q)


def attended_pairs(n_rows: int, pos0: int) -> int:
    """(query row, cached token) pairs under the causal mask of a span
    of ``n_rows`` tokens whose first sits at position ``pos0``: row ``r``
    sees ``pos0 + r + 1`` tokens."""
    return n_rows * pos0 + n_rows * (n_rows + 1) // 2


def attention_flops(pairs: int, num_heads: int, latent_dim: int,
                    value_dim: int) -> int:
    """Floating-point operations of the kernel for ``pairs`` attended
    (row, token) pairs of ONE layer: a score over the latent and the
    rotary key, and a value sum over the latent, a head.  ``q_lat`` and
    ``o_head`` are outside the kernel and outside this count."""
    return pairs * num_heads * 2 * (latent_dim + value_dim)


def _mla_kernel(
    # scalar prefetch
    work_ref,  # [WORK_FIELDS + P, W] SMEM work schedule
    starts_ref,  # [S+1] SMEM flat span starts (pads = padded length)
    base_ref,  # [S] SMEM global position of each span's first row
    # blocks: q_ref [bq*H, D]; P page refs, each [block_size, D] (one
    # page of the run, picked out of the layer's pool by its own
    # index_map); o_ref [bq*H, Dv]; f32 scratch m, l [bq*H, 1] and acc
    # [bq*H, Dv]
    q_ref,
    *refs,
    scale: float,
    block_size: int,
    block_q: int,
    num_heads: int,
    value_dim: int,
    run: int,
):
    page_refs, (o_ref, m_ref, l_ref, acc_ref) = refs[:run], refs[run:]
    w = pl.program_id(0)
    seq = work_ref[1, w]
    first_page = work_ref[3, w]

    @pl.when(work_ref[4, w] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(work_ref[6, w] == 1)
    def _item():
        q = q_ref[...]  # [bq*H, D]
        pages = [ref[...] for ref in page_refs]
        kv = pages[0] if run == 1 else jnp.concatenate(pages, axis=0)
        s_mat = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq*H, P*bs]
        # rows are (token, head) row-major: q's own order
        row = jax.lax.broadcasted_iota(jnp.int32, s_mat.shape, dimension=0)
        tok = work_ref[0, w] * block_q + row // num_heads
        start = starts_ref[seq]
        pos_row = base_ref[seq] + tok - start
        col = jax.lax.broadcasted_iota(jnp.int32, s_mat.shape, dimension=1)
        k_pos = first_page * block_size + col
        keep = (
            (tok >= start)
            & (tok < starts_ref[seq + 1])
            & (k_pos <= pos_row)
        )
        s_mat = jnp.where(keep, s_mat, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_mat, axis=-1, keepdims=True))
        # fully masked rows keep m == -inf; pin the shift finite so exp
        # stays NaN-free (ops/ragged_attention.py)
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s_mat - shift)
        alpha = jnp.exp(
            jnp.where(jnp.isfinite(m_prev), m_prev, shift) - shift
        )
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        # the same tile again, its latent columns as the values
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :value_dim],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(work_ref[5, w] == 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _mla_attention_pallas(
    q: jax.Array,  # [T, H, D]
    cache: jax.Array,  # [L, num_slots, D]
    seq_starts: jax.Array,  # [S+1]
    pos_base: jax.Array,  # [S]
    work: jax.Array,  # [WORK_FIELDS + P, W]
    n_items: jax.Array | None,
    *,
    layer: int,
    block_size: int,
    scale: float,
    value_dim: int,
    block_q: int,
    interpret: bool,
) -> jax.Array:
    t, num_heads, width = q.shape
    run = pages_per_item(block_size)
    if work.shape[0] != work_rows(block_size):
        raise ValueError(
            f"work schedule of {work.shape[0]} rows: block size "
            f"{block_size} takes {WORK_FIELDS} fields and {run} page ids"
        )
    block_q = min(block_q, _pow2_ceil(t))
    nq = pl.cdiv(t, block_q)
    t_pad = nq * block_q
    rows = block_q * num_heads
    qf = jnp.pad(q, ((0, t_pad - t), (0, 0), (0, 0))).reshape(
        t_pad * num_heads, width)
    num_work = work.shape[1] if n_items is None else n_items
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_work,),
        in_specs=[
            pl.BlockSpec((rows, width), lambda w, wk, st, bs_: (wk[0, w], 0)),
            # the pool is ONE array, never sliced: the layer is a static
            # index in a page's index map; it is passed once per place
            # of the run, by reference
            *[
                pl.BlockSpec(
                    (None, block_size, width),
                    lambda w, wk, st, bs_, i=i: (
                        layer, wk[WORK_FIELDS + i, w], 0
                    ),
                )
                for i in range(run)
            ],
        ],
        out_specs=pl.BlockSpec(
            (rows, value_dim), lambda w, wk, st, bs_: (wk[0, w], 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, value_dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _mla_kernel, scale=scale, block_size=block_size,
            block_q=block_q, num_heads=num_heads, value_dim=value_dim,
            run=run,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (t_pad * num_heads, value_dim), q.dtype),
        interpret=interpret,
        name="mla_ragged_attention",
    )(work, seq_starts.astype(jnp.int32), pos_base.astype(jnp.int32),
      qf, *[cache] * run)
    return out.reshape(t_pad, num_heads, value_dim)[:t]


def mla_ragged_attention(
    q: jax.Array,  # [T, H, D]: a head's [q_lat | q_pe | zeros] a row
    cache: jax.Array,  # [L, num_slots, D] the latent pool
    positions: jax.Array,  # [T]
    seq_starts: jax.Array,  # [S+1]
    pos_base: jax.Array,  # [S]
    total_tokens: jax.Array,  # scalar
    block_tables: jax.Array,  # [S, max_blocks]
    block_size: int,
    scale: float,
    *,
    layer: int,
    value_dim: int,
    work: jax.Array | None = None,
    n_items: jax.Array | None = None,
    block_q: int = MIXED_BLOCK_Q,
) -> jax.Array:
    """One causal latent-attention dispatch over a mixed ragged stream:
    ``[T, H, value_dim]``, each head's softmax-weighted sum of the
    cached latents its row may see.  The caller has written this step's
    rows into the pool first (ops/ragged_attention.py has the stream's
    contract)."""
    if not attn_ops._use_pallas():
        kv = cache[layer][None]  # one KV head; its values are its keys
        return ragged_attention_xla(
            q, kv, kv, positions, seq_starts, total_tokens, block_tables,
            block_size, scale,
        )[..., :value_dim]
    if work is None:
        raise ValueError(
            "the Pallas latent kernel needs a work schedule: "
            "build_work_schedule or decode_work_schedule"
        )
    return _mla_attention_pallas(
        q, cache, seq_starts, pos_base, work, n_items, layer=layer,
        block_size=block_size, scale=scale, value_dim=value_dim,
        block_q=block_q, interpret=attn_ops._pallas_interpret(),
    )
