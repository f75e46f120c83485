"""A dropless expert layer that is told which experts it holds.

Routing is over ALL of a layer's experts (the router's own width); the
(token, expert) pairs that fall on the held experts are sorted by
expert, a grouped matmul runs over the held experts' stacked weights
with the group sizes as data, and the weighted results are summed back
into their tokens.  What the absent experts would have added is left
out: the layer returns the held share's partial result, which is what a
chip of an expert-parallel deployment computes before its exchange
(docs/LATENT_ATTENTION.md).  No capacity, no dropped pair: the pair
buffer is sized for the worst case (every pair of every token on a held
expert), and rows past the last real pair belong to no group, so the
grouped matmul does not compute them.

Router scores, the top-k and the combine are float32; the expert
matmuls take the activations' dtype and accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_tgis_adapter_tpu.ops import attention as attn_ops

#: rows of one tile of the Pallas grouped matmul; the pair buffer is
#: padded to a multiple of it
GMM_TILE_M = 128


def group_limited_route(
    logits: jax.Array,  # [T, E] router outputs, any float dtype
    *,
    n_group: int,
    topk_group: int,
    top_k: int,
    scaling: float,
) -> tuple[jax.Array, jax.Array]:
    """Group-limited greedy routing: softmax over all experts in
    float32; a group's score is its largest; the ``topk_group`` best
    groups stay and every other group's scores are zeroed; the ``top_k``
    largest of what is left are the token's experts, weighted
    ``scaling x score`` with no renormalising.  Ties break to the lower
    index (``lax.top_k`` keeps the lower index first).  Returns
    ``(experts [T, k] i32, weights [T, k] f32)``."""
    t, e = logits.shape
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if n_group > 1:
        group_scores = jnp.max(
            scores.reshape(t, n_group, e // n_group), axis=-1)
        _, groups = jax.lax.top_k(group_scores, topk_group)
        keep = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], groups].set(True)
        scores = jnp.where(
            jnp.repeat(keep, e // n_group, axis=1), scores, 0.0)
    weights, experts = jax.lax.top_k(scores, top_k)
    return experts.astype(jnp.int32), weights * scaling


def sigmoid_route(
    logits: jax.Array,  # [T, E] router outputs, any float dtype
    *,
    top_k: int,
) -> tuple[jax.Array, jax.Array]:
    """Sigmoid routing (``cohere2_moe``): every expert's score is the
    sigmoid of its logit, in float32; the ``top_k`` largest are the
    token's experts, each weighted by its score over the sum of the
    chosen scores.  Ties break to the lower index.  Returns
    ``(experts [T, k] i32, weights [T, k] f32)``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    chosen, experts = jax.lax.top_k(scores, top_k)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights


def grouped_matmul(
    lhs: jax.Array,  # [P, k] rows sorted by group
    rhs: jax.Array,  # [G, k, n]
    group_sizes: jax.Array,  # [G] i32
) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for every group, float32
    accumulation, ``lhs``'s dtype out; rows past ``sum(group_sizes)``
    come back as they may (the caller masks them)."""
    if attn_ops._use_pallas():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        k, n = rhs.shape[1:]
        tiling = (GMM_TILE_M, min(k, 512), min(n, 1024))
        return gmm(
            lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
            tiling=tiling, interpret=attn_ops._pallas_interpret(),
        ).astype(lhs.dtype)
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
    ).astype(lhs.dtype)


def held_experts_mlp(
    x: jax.Array,  # [T, d]
    experts: jax.Array,  # [T, k] i32 published expert indices
    weights: jax.Array,  # [T, k] f32
    valid: jax.Array,  # [T] bool: padding rows are routed nowhere
    w_gate: jax.Array,  # [n_held, d, f]
    w_up: jax.Array,  # [n_held, d, f]
    w_down: jax.Array,  # [n_held, f, d]
    first_held: int,
) -> tuple[jax.Array, jax.Array]:
    """The held experts' share of the routed sum,
    ``sum_k w_k down_k(silu(gate_k x) * up_k x)`` over the pairs whose
    expert is held: ``([T, d] in x's dtype, counts [2] i32 = (local
    pairs, held experts hit))``."""
    t, top_k = experts.shape
    n_held = w_gate.shape[0]
    local = experts - first_held
    on = valid[:, None] & (local >= 0) & (local < n_held)
    # pairs off this share sort behind every held expert's
    key = jnp.where(on, local, n_held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    pairs = t * top_k
    padded = -(-pairs // GMM_TILE_M) * GMM_TILE_M
    order = jnp.pad(order, (0, padded - pairs))
    sorted_key = jnp.pad(key[order[:pairs]], (0, padded - pairs),
                         constant_values=n_held)
    token_of = order // top_k
    group_sizes = jnp.zeros((n_held,), jnp.int32).at[key].add(
        1, mode="drop")
    xs = x[token_of]  # [P, d]
    with jax.named_scope("moe_grouped_matmul"):
        gate = grouped_matmul(xs, w_gate, group_sizes)
        up = grouped_matmul(xs, w_up, group_sizes)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(x.dtype)
        out = grouped_matmul(act, w_down, group_sizes)  # [P, d]
    real = sorted_key < n_held
    w_sorted = jnp.where(real, weights.reshape(-1)[order], 0.0)
    # a row of no group holds whatever the kernel left there
    out = jnp.where(real[:, None], out.astype(jnp.float32), 0.0)
    y = jnp.zeros((t, x.shape[1]), jnp.float32).at[token_of].add(
        out * w_sorted[:, None])
    counts = jnp.stack([
        jnp.sum(group_sizes), jnp.sum((group_sizes > 0).astype(jnp.int32)),
    ])
    return y.astype(x.dtype), counts
