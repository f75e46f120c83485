"""Command A+'s (``cohere2_moe``) forward pass, plainly: one sequence,
float32, highest matmul precision, full ``[T, T]`` attention under each
layer's own mask over the whole sequence; no cache, no chunks, no
batching, no kernel.

After the published ``config.json``
(``CohereLabs/command-a-plus-05-2026``).  In every layer, with ``u =
LayerNorm(x)`` (mean-subtracting, a weight and no bias, eps
``layer_norm_eps``):

- ``q, k, v = q_proj u, k_proj u, v_proj u``: ``num_attention_heads``
  query heads over ``num_key_value_heads`` KV heads of ``head_dim``;
- ``layer_types[i] == "sliding_attention"``: RoPE on q and k over the
  whole head (``rotary_pct`` 1), interleaved pairs ``(x0, x1), (x2,
  x3), ...`` (``position_embedding_type`` ``rope_gptj``), ``theta =
  rope_theta``; row ``t`` sees ``s`` with ``0 <= t - s <
  sliding_window``;  ``"full_attention"``: no positional encoding at
  all, causal over everything;
- ``a = o_proj softmax(q k^T / sqrt(head_dim)) v``;
- ``s = sigmoid(gate u)`` over ALL experts; the ``num_experts_per_tok``
  largest are the token's experts, ``w_k = s_k / (sum of the chosen
  s)``; ``routed = sum_k w_k down_k(silu(gate_k u) * up_k u)``;
- ``shared = (1 / num_shared_experts) sum_j down_j(silu(gate_j u) *
  up_j u)``: the shared experts' MEAN;
- ``x' = x + a + routed + shared``: one norm feeds attention and
  experts (``use_parallel_block``).

``logits = logit_scale * embed_tokens LayerNorm(x_L)``: the head is the
embedding (``tie_word_embeddings``).

The parameters come as the checkpoint's own tensors, by name and in
``[out, in]`` orientation, so nothing of the server's loader (its
transposes, its regrouped rotary columns, its one wide shared SwiGLU) is
shared with what it is held to.

Departures from the published description, each of no effect where the
result is compared: everything is float32 whatever the checkpoint's
dtype; ``held`` names the experts ``[first, last)`` whose results are
summed, so that the reference gives the same share as a server that
holds those (the router still scores all), and ``shared`` and
``attention`` switch those branches off for the share test; ties in the
top-k break to the lower index (``lax.top_k``).  Inferred, not
published (the configuration file's ``assumed``): that the shared mean
is ADDED to the routed sum; the tensor names; that the router has no
bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_norm(x, w, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w


def rope_interleaved(x, theta: float):
    """``x [T, heads, d]`` rotated in interleaved pairs at positions
    ``0..T-1``."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def layer_mask(hf: dict, i: int, t: int):
    """``[T, T]`` bool: what row ``t`` of layer ``i`` may see."""
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    keep = cols <= rows
    if hf["layer_types"][i] == "sliding_attention":
        keep &= rows - cols < hf["sliding_window"]
    return keep


def attention(hf: dict, w, i: int, u):
    """``w(name)`` gives the layer's ``self_attn.<name>.weight``."""
    t = u.shape[0]
    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    dh = hf.get("head_dim") or hf["hidden_size"] // heads
    q = (u @ w("q_proj").T).reshape(t, heads, dh)
    k = (u @ w("k_proj").T).reshape(t, kv_heads, dh)
    v = (u @ w("v_proj").T).reshape(t, kv_heads, dh)
    if hf["layer_types"][i] == "sliding_attention":
        theta = float((hf.get("rope_parameters") or {}).get("rope_theta")
                      or hf["rope_theta"])
        q, k = rope_interleaved(q, theta), rope_interleaved(k, theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * dh ** -0.5
    scores = jnp.where(layer_mask(hf, i, t)[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(t, heads * dh)
    return out @ w("o_proj").T


def swiglu(w, prefix: str, v):
    gate = v @ w(f"{prefix}gate_proj").T
    return (jax.nn.silu(gate) * (v @ w(f"{prefix}up_proj").T)) @ w(
        f"{prefix}down_proj").T


def route(hf: dict, gate_weight, v):
    """``(experts [T, k], weights [T, k])`` over all the router's
    experts."""
    scores = jax.nn.sigmoid(v @ gate_weight.T)
    chosen, experts = jax.lax.top_k(scores, hf["num_experts_per_tok"])
    return experts, chosen / jnp.sum(chosen, -1, keepdims=True)


def experts_layer(hf: dict, w, v, held=None, shared: bool = True):
    """``sum_k w_k expert_k(v)`` over the chosen experts in ``held``
    (``[first, last)``; all the router scores when None), plus the MEAN
    of the shared experts when ``shared``.  ``w(name)`` gives
    ``mlp.<name>.weight``."""
    experts, weights = route(hf, w("gate"), v)
    lo, hi = held or (0, w("gate").shape[0])
    y = jnp.zeros_like(v)
    for e in range(lo, hi):  # expert by expert, every token's weight on it
        weight = jnp.sum(jnp.where(experts == e, weights, 0.0), -1)
        y = y + weight[:, None] * swiglu(w, f"experts.{e}.", v)
    n_shared = hf.get("num_shared_experts") or 0
    if shared and n_shared:
        y = y + sum(swiglu(w, f"shared_experts.{j}.", v)
                    for j in range(n_shared)) / n_shared
    return y


def block(hf: dict, tensors: dict, i: int, x, held=None, *,
          attention_on: bool = True, shared: bool = True):
    p = f"model.layers.{i}."

    def get(prefix):
        return lambda name: tensors[f"{p}{prefix}{name}.weight"].astype(
            jnp.float32)

    u = layer_norm(x, get("")("input_layernorm"), hf["layer_norm_eps"])
    out = x + experts_layer(hf, get("mlp."), u, held, shared)
    if attention_on:
        out = out + attention(hf, get("self_attn."), i, u)
    return out


def forward(hf: dict, tensors: dict, token_ids, held=None) -> jax.Array:
    """Logits ``[T, vocab]`` of one whole sequence; ``tensors`` the
    checkpoint's, by name; ``held`` the experts ``[first, last)`` whose
    share is computed (all when None)."""
    with jax.default_matmul_precision("highest"):
        embed = tensors["model.embed_tokens.weight"].astype(jnp.float32)
        x = embed[jnp.asarray(token_ids)]
        for i in range(hf["num_hidden_layers"]):
            x = block(hf, tensors, i, x, held)
        x = layer_norm(x, tensors["model.norm.weight"].astype(jnp.float32),
                       hf["layer_norm_eps"])
        return hf.get("logit_scale", 1.0) * (x @ embed.T)
