"""Falcon-H1's forward pass, plainly: one sequence, float32, highest
matmul precision, the Mamba-2 recurrence token by token, full causal
attention over the whole sequence; no cache, no chunks, no batching.

After the published description (``tiiuae/Falcon-H1-*`` ``config.json``
and Hugging Face's ``modeling_falcon_h1.py``).  In every layer, on the
same normed input ``u = RMSNorm(x)``:

- attention: ``q, k, v = Wq(u a_in), Wk(u a_in) k_mult, Wv(u a_in)``;
  RoPE (rotate-half) on q and k; causal softmax(``q k^T / sqrt(Dh)``) v
  with grouped KV heads; ``a = Wo(.) a_out``;
- mixer: ``[z | xBC | dt] = in_proj(u s_in) * mup_vector`` (``m[0]``
  over z, ``m[1]`` over x, ``m[2]`` over B, ``m[3]`` over C, ``m[4]``
  over dt); ``xBC = silu(conv(xBC) + bias)``, a causal depthwise
  convolution of ``d_conv`` taps whose left context is zeros; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h_t =
  exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t``, ``y_t = C_t . h_t + D x_t``;
  ``y = RMSNorm_grouped(y silu(z))`` (norm after the gate, over each
  group's columns, with weight); ``s = out_proj(y) s_out``;
- ``x' = x + a + s``; ``x'' = x' + down(silu(gate(v) g_mult) up(v))
  d_mult`` with ``v = RMSNorm(x')``.

Embeddings times ``embedding_multiplier``; final RMSNorm; logits times
``lm_head_multiplier``.

Departures from the published code, each of no effect on the result:
the parameters come as this repo's pytree (projections stored ``[in,
out]``, the convolution's weight ``[taps, channels]``), not as a
``state_dict``; ``mup_vector`` is built here from ``ssm_multipliers``
as Hugging Face builds its buffer; everything is float32 whatever the
checkpoint's dtype.  Configurations the loader refuses
(``mamba_norm_before_gate``, biases on the projections, scaled RoPE)
are not covered.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, heads, Dh], positions 0..T-1, rotate-half layout."""
    t, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = dh // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def mup_vector(mixer) -> jax.Array:
    """``ssm_multipliers`` laid over the columns of ``in_proj``."""
    m = mixer.ssm_multipliers
    bc = mixer.n_groups * mixer.d_state
    widths = (mixer.d_ssm, mixer.d_ssm, bc, bc, mixer.num_heads)
    return jnp.concatenate(
        [jnp.full((w,), v, jnp.float32) for w, v in zip(widths, m)])


def attention(cfg, layer, u):
    mx = cfg.mixer
    t = u.shape[0]
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ua = u * mx.attention_in_multiplier
    q = (ua @ layer["wq"]).reshape(t, h, dh)
    k = (ua @ layer["wk"] * mx.key_multiplier).reshape(t, kv, dh)
    v = (ua @ layer["wv"]).reshape(t, kv, dh)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * dh ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(t, h * dh)
    return out @ layer["wo"] * mx.attention_out_multiplier


def mixer(cfg, layer, u):
    mx = cfg.mixer
    t = u.shape[0]
    heads, p, n, g = mx.num_heads, mx.head_dim, mx.d_state, mx.n_groups
    proj = (u * mx.ssm_in_multiplier) @ layer["in_proj"] * mup_vector(mx)
    z = proj[:, :mx.d_ssm]
    xbc = proj[:, mx.d_ssm:mx.d_ssm + mx.conv_dim]
    dt = proj[:, mx.d_ssm + mx.conv_dim:]
    # causal depthwise convolution: tap j meets the input d_conv-1-j back
    taps = mx.d_conv
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, mx.conv_dim), xbc.dtype), xbc])
    xbc = layer["conv_b"] + sum(
        layer["conv_w"][j] * padded[j:j + t] for j in range(taps))
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :mx.d_ssm].reshape(t, heads, p)
    b = xbc[:, mx.d_ssm:mx.d_ssm + g * n].reshape(t, g, n)
    c = xbc[:, mx.d_ssm + g * n:].reshape(t, g, n)
    b = jnp.repeat(b, heads // g, axis=1)  # [T, H, N]
    c = jnp.repeat(c, heads // g, axis=1)
    dt = jax.nn.softplus(dt + layer["dt_bias"])  # [T, H]
    a = -jnp.exp(layer["a_log"])  # [H]
    h = jnp.zeros((heads, n, p), jnp.float32)
    ys = []
    for i in range(t):  # the recurrence, one token at a time
        h = (jnp.exp(dt[i] * a)[:, None, None] * h
             + (dt[i][:, None] * b[i])[:, :, None] * x[i][:, None, :])
        ys.append(jnp.einsum("hn,hnp->hp", c[i], h)
                  + layer["d"][:, None] * x[i])
    y = jnp.stack(ys).reshape(t, mx.d_ssm)
    y = y * jax.nn.silu(z)
    if mx.rms_norm:  # over each group's columns, then the weight
        y = y.reshape(t, g, mx.d_ssm // g)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, -1, keepdims=True) + cfg.rms_norm_eps)
        y = y.reshape(t, mx.d_ssm) * layer["mixer_norm"]
    return y @ layer["out_proj"] * mx.ssm_out_multiplier


def mlp(cfg, layer, v):
    gate_mult, down_mult = cfg.mixer.mlp_multipliers
    act = jax.nn.silu(v @ layer["w_gate"] * gate_mult) * (v @ layer["w_up"])
    return act @ layer["w_down"] * down_mult


def block(cfg, layer, x, parts=None):
    """One layer; ``parts``, when a list, collects the three branches'
    outputs (attention, mixer, MLP) for whoever wants their sizes."""
    u = _rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
    a, s = attention(cfg, layer, u), mixer(cfg, layer, u)
    x = x + a + s
    m = mlp(cfg, layer, _rms_norm(x, layer["pre_ff_norm"], cfg.rms_norm_eps))
    if parts is not None:
        parts.append((a, s, m))
    return x + m


def forward(cfg, params: dict, token_ids, parts=None) -> jax.Array:
    """Logits ``[T, vocab]`` of one whole sequence, float32."""
    params = jax.tree_util.tree_map(
        lambda w: jnp.asarray(w, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(token_ids)] * cfg.embedding_multiplier
        for layer in params["layers"]:
            x = block(cfg, layer, x, parts)
        x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return x @ params["lm_head"] * cfg.mixer.lm_head_multiplier
