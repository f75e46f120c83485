"""Ring attention: causal prefill sharded over the sequence axis.

Long-context scale-out path (SURVEY.md §5 "long-context"): when a prompt
exceeds one chip's HBM (activations + KV), the sequence axis is sharded
over the mesh's ``sp`` axis and K/V chunks rotate around the ring via
``ppermute`` while every device accumulates online-softmax partials for
its local queries.  Peak per-device memory is O(T/n) and the ring rides
the ICI neighbour links; compute overlaps the rotation because XLA
schedules the collective-permute asynchronously.

Causality over chunks: device d owns global positions [d·c, (d+1)·c); a
K/V chunk originating from device s is fully visible when s < d, fully
masked when s > d, and diagonally masked when s == d — so each hop does
full-block work and the mask only materialises on the diagonal hop.

Numerics mirror ops/attention.py:prefill_attention_xla (f32 softmax);
parity is pinned on the virtual CPU mesh in tests/test_ring_attention.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from vllm_tgis_adapter_tpu.parallel.mesh import SP_AXIS, TP_AXIS

NEG_INF = float("-inf")


def _chunk_attention(
    q: jax.Array,  # [C, Hkv, G, Dh] f32 local queries
    k: jax.Array,  # [C, Hkv, Dh] f32 visiting key chunk
    v: jax.Array,  # [C, Hkv, Dh] f32
    scale: float,
    q_pos: jax.Array,  # [C] global positions of local queries
    k_pos: jax.Array,  # [C] global positions of the visiting chunk
    valid_len: jax.Array,
    m: jax.Array,  # [Hkv, G, C, 1] running max
    l: jax.Array,  # [Hkv, G, C, 1] running denom
    acc: jax.Array,  # [Hkv, G, C, Dh] running numerator
    window: int = 0,  # >0: band mask over GLOBAL positions
    slopes: jax.Array | None = None,  # [Hkv, G] f32 ALiBi slopes
):
    s = jnp.einsum("ckgd,skd->kgcs", q, k) * scale  # [Hkv, G, C, C]
    if slopes is not None:
        # HF bloom convention (ops/attention.py prefill_attention_xla):
        # score += slope_h * j with j the GLOBAL key position — the
        # row-constant term cancels in softmax, and global positions
        # keep the bias identical across ring hops
        s = s + (
            slopes[:, :, None, None]
            * k_pos.astype(jnp.float32)[None, None, None, :]
        )
    mask = (k_pos[None, :] <= q_pos[:, None]) & (
        k_pos[None, :] < valid_len
    )  # [C, C]
    if window > 0:
        # band over global positions: query i sees keys (i-window, i];
        # hops entirely below the band contribute nothing (all -inf,
        # alpha carries prior partials through unchanged)
        mask = mask & (
            (q_pos[:, None] - k_pos[None, :]) < window
        )
    s = jnp.where(mask[None, None], s, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - shift)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m, shift) - shift)
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc = alpha * acc + jnp.einsum("kgcs,skd->kgcd", p, v)
    return m_new, l, acc


def ring_prefill_attention(
    q: jax.Array,  # [T, H, Dh] sequence-sharded on sp
    k: jax.Array,  # [T, Hkv, Dh]
    v: jax.Array,
    scale: float,
    valid_len: jax.Array,  # scalar int32 (global)
    mesh: Mesh,
    axis: str = SP_AXIS,
    window: int = 0,  # mistral-style sliding window (0 = full causal)
    alibi_slopes: jax.Array | None = None,  # [H] f32 (bloom lineage)
) -> jax.Array:
    """Causal attention with the sequence axis sharded over ``axis``.

    All inputs/outputs are global-view arrays; shard_map splits them so
    each device keeps only its T/n chunk resident.  On a joint sp×tp mesh
    the head axis is additionally split over tp, so every device holds a
    (T/sp, H/tp) tile — ring hops move only local-head K/V chunks.
    """
    n = mesh.shape[axis]
    if n == 1:
        from vllm_tgis_adapter_tpu.ops.attention import prefill_attention_xla

        return prefill_attention_xla(q, k, v, scale, valid_len,
                                     window=window,
                                     alibi_slopes=alibi_slopes)
    t, _, head_dim = q.shape
    if t % n:
        raise ValueError(f"sequence {t} not divisible by ring size {n}")
    c = t // n
    tp = dict(mesh.shape).get(TP_AXIS, 1)
    head_axis = TP_AXIS if tp > 1 else None

    def local_fn(q_loc, k_loc, v_loc, vl, slopes_loc):
        # q_loc [C, H/tp, Dh]; k_loc/v_loc [C, Hkv/tp, Dh]; vl [1];
        # slopes_loc [H/tp] (zero-size placeholder when ALiBi is off)
        d = jax.lax.axis_index(axis)
        num_heads = q_loc.shape[1]
        num_kv = k_loc.shape[1]
        g = num_heads // num_kv
        qf = q_loc.reshape(c, num_kv, g, head_dim).astype(jnp.float32)
        q_pos = d * c + jnp.arange(c)
        slopes = (
            slopes_loc.reshape(num_kv, g).astype(jnp.float32)
            if slopes_loc.size else None
        )

        m = jnp.full((num_kv, g, c, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((num_kv, g, c, 1), jnp.float32)
        acc = jnp.zeros((num_kv, g, c, head_dim), jnp.float32)

        k_cur = k_loc.astype(jnp.float32)
        v_cur = v_loc.astype(jnp.float32)
        # ring size is static (mesh shape): unrolled python loop lets XLA
        # pipeline each hop's ppermute under the previous hop's compute
        for i in range(n):
            src = (d - i) % n  # chunk currently visiting this device
            k_pos = src * c + jnp.arange(c)
            m, l, acc = _chunk_attention(
                qf, k_cur, v_cur, scale, q_pos, k_pos, vl[0], m, l, acc,
                window=window, slopes=slopes,
            )
            if i != n - 1:
                perm = [(j, (j + 1) % n) for j in range(n)]
                k_cur = jax.lax.ppermute(k_cur, axis, perm)
                v_cur = jax.lax.ppermute(v_cur, axis, perm)

        out = acc / jnp.maximum(l, 1e-30)  # [Hkv, G, C, Dh]
        out = jnp.transpose(out, (2, 0, 1, 3)).reshape(
            c, num_heads, head_dim
        )
        return out.astype(q_loc.dtype)

    seq = P(axis, head_axis, None)
    slopes_in = (
        jnp.zeros((0,), jnp.float32)
        if alibi_slopes is None
        else alibi_slopes.astype(jnp.float32)
    )
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(seq, seq, seq, P(), P(head_axis)),
        out_specs=seq,
        check_vma=False,
    )(q, k, v, jnp.asarray([valid_len], jnp.int32), slopes_in)
