"""Ulysses (DeepSpeed-style) sequence parallelism for prefill attention.

The alternative long-context scale-out to ring attention (SURVEY.md §2.4):
instead of rotating K/V chunks around the sp ring, two ``all_to_all``
collectives re-partition the activations so each device holds the FULL
sequence for a 1/sp slice of the heads, computes ordinary causal
attention locally, and swaps back:

    [T/sp, H/tp, Dh]  --all_to_all-->  [T, H/(tp·sp), Dh]
         (sequence-sharded)                (head-sharded)

Trade-off vs ring: two bulk all-to-alls (latency-bound, one shot) versus
sp-1 ppermute hops (bandwidth pipelined under compute); Ulysses keeps the
attention inner loop IDENTICAL to the single-device kernel — on TPU the
flash Pallas kernel runs unchanged on the gathered slice, where the ring
must re-implement online softmax across hops.  Requires sp to divide the
per-tp-shard head counts (validated at engine boot, engine/runner.py).

Numerics are pinned against ops/attention.py:prefill_attention_xla on the
virtual CPU mesh in tests/test_ulysses.py.
"""

from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from vllm_tgis_adapter_tpu.parallel.mesh import SP_AXIS, TP_AXIS


def ulysses_prefill_attention(
    q: jax.Array,  # [T, H, Dh] sequence-sharded on sp (global view)
    k: jax.Array,  # [T, Hkv, Dh]
    v: jax.Array,
    scale: float,
    valid_len: jax.Array,  # scalar int32 (global)
    mesh: Mesh,
    axis: str = SP_AXIS,
    window: int = 0,  # mistral-style sliding window (0 = full causal)
    alibi_slopes: jax.Array | None = None,  # [H] f32 (bloom lineage)
) -> jax.Array:
    """Causal prefill attention with the sequence axis sharded over
    ``axis``, computed via head/sequence all-to-all re-partitioning.

    All inputs/outputs are global-view arrays; shard_map splits them so
    each device keeps a (T/sp, H/tp) tile at rest and a (T, H/(tp·sp))
    tile during attention.
    """
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import attention as attn_ops

    n = mesh.shape[axis]
    if n == 1:
        return attn_ops.prefill_attention_xla(
            q, k, v, scale, valid_len, window=window,
            alibi_slopes=alibi_slopes,
        )
    t = q.shape[0]
    if t % n:
        raise ValueError(f"sequence {t} not divisible by sp size {n}")
    tp = dict(mesh.shape).get(TP_AXIS, 1)
    head_axis = TP_AXIS if tp > 1 else None

    def local_fn(q_loc, k_loc, v_loc, vl, slopes_loc):
        # [T/sp, H/tp, Dh] → [T, H/(tp·sp), Dh]
        if q_loc.shape[1] % n or k_loc.shape[1] % n:
            raise ValueError(
                f"ulysses needs sp={n} to divide the local head counts "
                f"(q {q_loc.shape[1]}, kv {k_loc.shape[1]})"
            )
        qt = jax.lax.all_to_all(
            q_loc, axis, split_axis=1, concat_axis=0, tiled=True
        )
        kt = jax.lax.all_to_all(
            k_loc, axis, split_axis=1, concat_axis=0, tiled=True
        )
        vt = jax.lax.all_to_all(
            v_loc, axis, split_axis=1, concat_axis=0, tiled=True
        )
        # the head all_to_all keeps chunk j of the local head slice on
        # sp-rank j — slice the slopes the same way so bias follows head
        slopes = None
        if slopes_loc.size:
            j = jax.lax.axis_index(axis)
            slopes = jax.lax.dynamic_slice_in_dim(
                slopes_loc, j * (slopes_loc.shape[0] // n),
                slopes_loc.shape[0] // n,
            )
        if attn_ops._use_pallas():
            from vllm_tgis_adapter_tpu.ops import pallas_attention

            out = pallas_attention.prefill_attention(
                qt, kt, vt, scale, jnp.asarray(vl[0], jnp.int32),
                window=window,
                alibi_slopes=slopes,
                interpret=attn_ops._pallas_interpret(),
            )
        else:
            out = attn_ops.prefill_attention_xla(
                qt, kt, vt, scale, vl[0], window=window,
                alibi_slopes=slopes,
            )
        # [T, H/(tp·sp), Dh] → [T/sp, H/tp, Dh]
        return jax.lax.all_to_all(
            out, axis, split_axis=0, concat_axis=1, tiled=True
        )

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
    )(q, k, v, jax.numpy.asarray([valid_len], jax.numpy.int32), slopes_in)
