"""DeepSeek-V2's forward pass, plainly: one sequence, float32, highest
matmul precision, the NON-absorbed form of its latent attention (keys
and values expanded a head from the latent, full causal attention over
the whole sequence); no cache, no chunks, no batching.

After the published description (``deepseek-ai/DeepSeek-V2``
``config.json`` and its ``modeling_deepseek.py``).  With ``u =
RMSNorm(x)``, in every layer:

- ``c_q = RMSNorm(q_a_proj u)``; per head ``[q_nope | q_pe] = q_b_proj
  c_q``; ``[c_kv | k_pe] = kv_a_proj_with_mqa u``; ``c_kv =
  RMSNorm(c_kv)``; per head ``[k_nope | v] = kv_b_proj c_kv``;
- RoPE on ``q_pe`` and on the one ``k_pe`` all heads share: the rotary
  columns are first regrouped from interleaved pairs to halves, then
  halves are rotated; YaRN blends ``theta^(-2i/d)`` with that over
  ``factor`` along the linear ramp between the dimensions ``beta_fast``
  and ``beta_slow`` find at the original length; the cos / sin factor
  is ``yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
  mscale_all_dim)``;
- ``score = (q_nope . k_nope + q_pe . k_pe) (nope + rope)^-0.5 m^2``,
  ``m = yarn_get_mscale(factor, mscale_all_dim)``; causal softmax;
  ``x' = x + o_proj(concat_heads(softmax . v))``;
- the leading ``first_k_dense_replace`` layers: ``x'' = x' + down(silu(
  gate v) up v)``, ``v = RMSNorm(x')``; the others: ``s = softmax(gate
  v)`` over all experts; a group's score its largest ``s``; the
  ``topk_group`` best groups stay, every other group's ``s`` is zeroed;
  the ``num_experts_per_tok`` largest of what is left are the token's
  experts with weights ``routed_scaling_factor x s``; ``x'' = x' +
  sum_k w_k expert_k(v) + shared(v)``.

The parameters come as the checkpoint's own tensors, by Hugging Face's
names and in its ``[out, in]`` orientation, so nothing of the server's
loader (its transposes, its split of ``kv_b_proj``, its regrouped
rotary columns) is shared with what it is held to.

Departures from the published code, each of no effect on the result
where it is compared: everything is float32 whatever the checkpoint's
dtype; ``held`` names the experts ``[first, last)`` whose results are
summed, so that the reference gives the same share as a server that
holds those (the router still scores all); ties in either top-k break
to the lower index (``lax.top_k``), which the published ``torch.topk``
leaves open.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(hf: dict, t: int):
    """``(cos, sin) [T, rope_dim]`` of positions ``0..T-1`` under the
    config's YaRN scaling (plain RoPE without one)."""
    dim, theta = hf["qk_rope_head_dim"], float(hf.get("rope_theta", 10000.0))
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    factor_out = 1.0
    sc = hf.get("rope_scaling")
    if sc:
        factor, orig = sc["factor"], sc["original_max_position_embeddings"]

        def correction_dim(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip(
            (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
            0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        factor_out = _mscale(factor, sc.get("mscale", 1.0)) / _mscale(
            factor, sc.get("mscale_all_dim", 0.0))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)
    return jnp.cos(emb) * factor_out, jnp.sin(emb) * factor_out


def _rope(x, cos, sin):
    """x: [T, heads, rope_dim]: interleaved pairs regrouped to halves,
    then halves rotated."""
    t, h, d = x.shape
    x = x.reshape(t, h, d // 2, 2).swapaxes(-1, -2).reshape(t, h, d)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None, :] + rotated * sin[:, None, :]


def softmax_scale(hf: dict) -> float:
    scale = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    sc = hf.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        scale *= _mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def attention(hf: dict, w, u):
    """``w(name)`` gives the layer's tensor ``self_attn.<name>.weight``."""
    t = u.shape[0]
    heads, nope = hf["num_attention_heads"], hf["qk_nope_head_dim"]
    rope_dim, v_dim = hf["qk_rope_head_dim"], hf["v_head_dim"]
    rank, eps = hf["kv_lora_rank"], hf["rms_norm_eps"]
    c_q = _rms_norm(u @ w("q_a_proj").T, w("q_a_layernorm"), eps)
    q = (c_q @ w("q_b_proj").T).reshape(t, heads, nope + rope_dim)
    kv = u @ w("kv_a_proj_with_mqa").T
    c_kv = _rms_norm(kv[:, :rank], w("kv_a_layernorm"), eps)
    cos, sin = rope_tables(hf, t)
    q_pe = _rope(q[..., nope:], cos, sin)
    k_pe = _rope(kv[:, None, rank:], cos, sin)  # one for all heads
    expanded = (c_kv @ w("kv_b_proj").T).reshape(t, heads, nope + v_dim)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    scores = (jnp.einsum("thd,shd->hts", q[..., :nope], k_nope)
              + jnp.einsum("thd,sd->hts", q_pe, k_pe[:, 0])
              ) * softmax_scale(hf)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(t, heads * v_dim)
    return out @ w("o_proj").T


def swiglu(w, prefix: str, v):
    gate = v @ w(f"{prefix}gate_proj").T
    return (jax.nn.silu(gate) * (v @ w(f"{prefix}up_proj").T)) @ w(
        f"{prefix}down_proj").T


def route(hf: dict, gate_weight, v):
    """``(experts [T, k], weights [T, k])`` over all the router's
    experts."""
    t = v.shape[0]
    scores = jax.nn.softmax(v @ gate_weight.T, axis=-1)
    groups = hf.get("n_group") or 1
    if groups > 1:
        per = scores.shape[1] // groups
        best = jnp.max(scores.reshape(t, groups, per), -1)
        _, kept = jax.lax.top_k(best, hf["topk_group"])
        keep = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        scores = jnp.where(jnp.repeat(keep, per, axis=1), scores, 0.0)
    weights, experts = jax.lax.top_k(scores, hf["num_experts_per_tok"])
    return experts, weights * hf.get("routed_scaling_factor", 1.0)


def experts_layer(hf: dict, w, v, held=None, shared: bool = True):
    """``sum_k w_k expert_k(v)`` over the chosen experts in ``held``
    (``[first, last)``; all of them when None), plus the shared experts
    when ``shared``.  ``w(name)`` gives ``mlp.<name>.weight``."""
    experts, weights = route(hf, w("gate"), v)
    total = w("gate").shape[0]
    lo, hi = held or (0, total)
    y = jnp.zeros_like(v)
    for e in range(lo, hi):  # expert by expert, every token's weight on it
        weight = jnp.sum(jnp.where(experts == e, weights, 0.0), -1)
        y = y + weight[:, None] * swiglu(w, f"experts.{e}.", v)
    if shared and hf.get("n_shared_experts"):
        y = y + swiglu(w, "shared_experts.", v)
    return y


def block(hf: dict, tensors: dict, i: int, x, held=None, parts=None):
    p = f"model.layers.{i}."

    def get(prefix):
        return lambda name: tensors[f"{p}{prefix}{name}.weight"].astype(
            jnp.float32)

    eps = hf["rms_norm_eps"]
    a = attention(hf, get("self_attn."),
                  _rms_norm(x, get("")("input_layernorm"), eps))
    x = x + a
    v = _rms_norm(x, get("")("post_attention_layernorm"), eps)
    if i < hf.get("first_k_dense_replace", 0):
        m = swiglu(get("mlp."), "", v)
    else:
        m = experts_layer(hf, get("mlp."), v, held)
    if parts is not None:
        parts.append((a, m))
    return x + m


def forward(hf: dict, tensors: dict, token_ids, held=None,
            parts=None) -> jax.Array:
    """Logits ``[T, vocab]`` of one whole sequence; ``tensors`` the
    checkpoint's, by name; ``held`` the experts ``[first, last)`` whose
    share is computed (all when None)."""
    with jax.default_matmul_precision("highest"):
        x = tensors["model.embed_tokens.weight"].astype(jnp.float32)[
            jnp.asarray(token_ids)]
        for i in range(hf["num_hidden_layers"]):
            x = block(hf, tensors, i, x, held, parts)
        x = _rms_norm(x, tensors["model.norm.weight"].astype(jnp.float32),
                      hf["rms_norm_eps"])
        return x @ tensors["lm_head.weight"].astype(jnp.float32).T
