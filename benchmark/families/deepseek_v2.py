"""The DeepSeek-V2 family: Hugging Face's ``deepseek_v2`` tensor names and
the block of latent attention (MLA) and group-routed experts beside
shared ones, with a chip's share of the routed experts.

    u = RMSNorm(x)
    c_q = RMSNorm(q_a u);  per head [q_nope | q_pe] = q_b c_q
    [c_kv | k_pe] = kv_a u;  c_kv = RMSNorm(c_kv)
    per head [k_nope | v] = kv_b c_kv          (the NON-absorbed form)
    RoPE on q_pe and on the one k_pe (interleaved pairs regrouped to
        halves, halves rotated, YaRN frequencies)
    x' = x + o(softmax((q_nope . k_nope + q_pe . k_pe) scale) v)
    s = softmax(gate v') over ALL experts, v' = RMSNorm(x'); the
        topk_group best groups (by their largest s) stay, every other
        group's s is zeroed; the 6 largest left, weights 16 s
    x'' = x' + sum over the chosen experts THAT ARE HELD of
        w_k down_k(silu(gate_k v') up_k v') + shared(v')
    (the leading first_k_dense_replace layers: a dense SwiGLU instead)

``tensors``, ``score`` and ``LOGPROB_TOLERANCE`` as ``families/llama.py``
documents them.  Nothing here imports JAX or the package.

**A configuration of this family is one chip's share.**  Its
``n_routed_experts`` counts the experts HELD (the guide's section 4): one
of the ``n_group`` groups, experts ``0 .. n_routed_experts - 1`` under
their published indices.  The router keeps every row: ``n_group x
n_routed_experts`` of them (8 x 20 = the published 160).  What the
absent experts would add is left out, here as in the server.

**The fills are part of the result** (PERF.md section 4 gives the
readings at the published widths).  Each tensor has a std of its own,
chosen so that

- the residual stream starts at RMS ~1 and the three branches of an
  expert layer (attention, shared experts, the held routed experts after
  the factor 16) each add a few tenths, none under a tenth of another;
- attention scores have a std near 1.5, so which token a row attends to
  matters, through the latent and through the rotary key alike;
- the router's logits have a std near 8: WIDE, so that routing is not
  decided by rounding.  Server (bfloat16) and reference (float32) see
  slightly different inputs, and where a token's sixth and seventh
  scores, or its third and fourth groups, lie closer than that
  difference they choose different experts: how often is a matter of
  relative margins, which no fill changes (about one (token, layer) set
  in twenty-five at any width).  What a flip COSTS is the weight of the
  expert that changes, and under a wide softmax the first one or two
  experts carry nearly all of the factor 16 while the sixth and the
  third group's best carry 1e-2 to 1e-9 of it: a flipped set moves a
  logprob by less than the rounding does (PERF.md section 6, PR 43: at
  a std of 2 one flipped group moved a logprob by 0.95);
- the logits' std is near 2, so the logprobs of a sequence's tokens
  range over ten tolerances and more.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

from reference import Weights, chosen_logprobs, rms_norm

#: Largest |server logprob - reference logprob| allowed for a generated
#: token.  The server multiplies in bfloat16 and keeps the softmax, the
#: router's scores and every sum in float32; the reference is float32 on
#: the same bf16 weights.  Beside the rounding that every family has
#: (here ~0.03 in the mean), this one has a discrete step: where a
#: token's sixth and seventh router scores, or its third and fourth
#: groups, lie closer than the rounding of the layer's input, server and
#: reference choose different experts for that token in that layer
#: (about one (token, layer) set in twenty, one in sixty on a held
#: expert), and the token's hidden state moves by the flipped expert's
#: weighted output.  The router's fill is wide so that this weight is
#: nearly always 1e-2 of the factor 16 or less (the module docstring),
#: but where a token's best groups score alike a flipped group carries
#: real weight: the differences have a tail.  The limit lies between
#: two readings at the published widths (PERF.md section 6, PR 43):
#: 0.365, the largest difference the server has shown on the chip over
#: eighteen runs of eleven seeds (0.080-0.365; this reference with
#: bfloat16 matmul operands differs from itself in float32 by 0.128 on
#: one of them, where the server read 0.149), and 3.09, the difference
#: of this same reference computed with float8 (e4m3) matmul operands,
#: the nearest precision below bfloat16, which so comes out as not
#: correct: 2.6 times over the one, 3.3 times under the other.  The
#: reference's logprobs of one run's 34 tokens range over 1.5-2.9: two
#: or three such limits, not the ten a family without the discrete step
#: reaches; the fine comparison is the CPU tests' (3e-5 on a logit,
#: tests/test_deepseek_v2.py), this one tells a server that computes the
#: model from one that does not.
LOGPROB_TOLERANCE = 0.95

ONES = ("ones",)

# stds, by tensor (the module docstring says what each is for)
EMBED = ("pool", 1.0)  # residual RMS ~1
HEAD = ("pool", 0.028)  # x 71.6 |h|: logit std ~2
Q_A = ("pool", 0.014)  # normed straight after: only its direction counts
Q_B = ("pool", 0.0255)  # x sqrt(1536): q std ~1
KV_A = ("pool", 0.014)  # x 71.6: k_pe std ~1 (c_kv is normed)
KV_B = ("pool", 0.0442)  # x sqrt(512): k_nope and v std ~1
O_PROJ = ("pool", 0.008)
GATE_UP = ("pool", 0.014)  # x 71.6: pre-activations std ~1
DENSE_DOWN = ("pool", 0.006)
SHARED_DOWN = ("pool", 0.012)
EXPERT_DOWN = ("pool", 0.003)
ROUTER = ("pool", 0.112)  # logits std ~8


def routed(cfg: dict) -> int:
    """Experts the router scores: every group's, of which this share
    holds one group's ``n_routed_experts``."""
    return cfg["n_group"] * cfg["n_routed_experts"]


def _swiglu(prefix: str, d: int, width: int, down) -> dict:
    return {f"{prefix}.gate_proj.weight": ((width, d), GATE_UP),
            f"{prefix}.up_proj.weight": ((width, d), GATE_UP),
            f"{prefix}.down_proj.weight": ((d, width), down)}


def tensors(cfg: dict) -> tuple[dict, list[dict]]:
    """(tensors outside the layers, tensors of each layer): name ->
    (shape, fill), Hugging Face's ``deepseek_v2`` names; of an expert
    layer's routed experts, the held ones 0 .. n_routed_experts - 1."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f = cfg["moe_intermediate_size"]
    top = {"model.embed_tokens.weight": ((cfg["vocab_size"], d), EMBED),
           "model.norm.weight": ((d,), ONES),
           "lm_head.weight": ((cfg["vocab_size"], d), HEAD)}
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        layer = {
            f"{p}.input_layernorm.weight": ((d,), ONES),
            f"{p}.post_attention_layernorm.weight": ((d,), ONES),
            f"{p}.self_attn.q_a_proj.weight": ((q_rank, d), Q_A),
            f"{p}.self_attn.q_a_layernorm.weight": ((q_rank,), ONES),
            f"{p}.self_attn.q_b_proj.weight": (
                (heads * (nope + rope_dim), q_rank), Q_B),
            f"{p}.self_attn.kv_a_proj_with_mqa.weight": (
                (kv_rank + rope_dim, d), KV_A),
            f"{p}.self_attn.kv_a_layernorm.weight": ((kv_rank,), ONES),
            f"{p}.self_attn.kv_b_proj.weight": (
                (heads * (nope + cfg["v_head_dim"]), kv_rank), KV_B),
            f"{p}.self_attn.o_proj.weight": (
                (d, heads * cfg["v_head_dim"]), O_PROJ),
        }
        if i < cfg["first_k_dense_replace"]:
            layer |= _swiglu(f"{p}.mlp", d, cfg["intermediate_size"],
                             DENSE_DOWN)
        else:
            layer[f"{p}.mlp.gate.weight"] = ((routed(cfg), d), ROUTER)
            layer |= _swiglu(f"{p}.mlp.shared_experts", d,
                             cfg["n_shared_experts"] * f, SHARED_DOWN)
            for e in range(cfg["n_routed_experts"]):
                layer |= _swiglu(f"{p}.mlp.experts.{e}", d, f, EXPERT_DOWN)
        layers.append(layer)
    return top, layers


def _silu(x: np.ndarray) -> np.ndarray:
    return x / (1.0 + np.exp(-x))


def _same(x: np.ndarray) -> np.ndarray:
    return x


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(cfg: dict, t: int):
    """cos and sin ``[T, rope_dim]`` of positions 0..T-1, YaRN applied."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg.get("rope_theta", 1e4))
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float32) / dim)
    scale = 1.0
    sc = cfg.get("rope_scaling")
    if sc:
        factor, orig = sc["factor"], sc["original_max_position_embeddings"]

        def correction_dim(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                       / (high - low), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        scale = _mscale(factor, sc.get("mscale", 1.0)) / _mscale(
            factor, sc.get("mscale_all_dim", 0.0))
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None, :]
    emb = np.concatenate([ang, ang], -1).astype(np.float32)
    return np.cos(emb) * scale, np.sin(emb) * scale


def _rope(x, cos, sin):
    """x: [T, heads, rope_dim]: interleaved pairs regrouped to halves,
    then halves rotated."""
    t, h, d = x.shape
    x = x.reshape(t, h, d // 2, 2).swapaxes(-1, -2).reshape(t, h, d)
    rotated = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None, :] + rotated * sin[:, None, :]


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        scale *= _mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def attention(u, get, cfg, cast=_same):
    """``get(name)`` gives ``self_attn.<name>.weight`` in float32."""
    t = u.shape[0]
    heads, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope_dim, v_dim = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    u = cast(u)
    c_q = rms_norm(u @ cast(get("q_a_proj")).T, get("q_a_layernorm"), eps)
    q = (cast(c_q) @ cast(get("q_b_proj")).T).reshape(
        t, heads, nope + rope_dim)
    kv = u @ cast(get("kv_a_proj_with_mqa")).T
    c_kv = rms_norm(kv[:, :rank], get("kv_a_layernorm"), eps)
    cos, sin = rope_tables(cfg, t)
    q_pe = _rope(q[..., nope:], cos, sin)
    k_pe = _rope(kv[:, None, rank:], cos, sin)[:, 0]  # one for all heads
    expanded = (cast(c_kv) @ cast(get("kv_b_proj")).T).reshape(
        t, heads, nope + v_dim)
    causal = np.tril(np.ones((t, t), bool))
    out = np.empty((t, heads, v_dim), np.float32)
    scale = softmax_scale(cfg)
    for h in range(heads):  # a head at a time: [T, T] scores
        scores = (cast(q[:, h, :nope]) @ cast(expanded[:, h, :nope]).T
                  + cast(q_pe[:, h]) @ cast(k_pe).T) * scale
        scores = np.where(causal, scores, -np.inf)
        scores -= scores.max(-1, keepdims=True)
        probs = np.exp(scores)
        probs /= probs.sum(-1, keepdims=True)
        out[:, h] = cast(probs) @ cast(expanded[:, h, nope:])
    return cast(out.reshape(t, heads * v_dim)) @ cast(get("o_proj")).T


def swiglu(v, get, prefix: str, cast=_same):
    v = cast(v)
    act = _silu(v @ cast(get(f"{prefix}gate_proj")).T) * (
        v @ cast(get(f"{prefix}up_proj")).T)
    return cast(act) @ cast(get(f"{prefix}down_proj")).T


def route(v, gate_weight, cfg, margins=None):
    """(experts [T, k], weights [T, k]) over all the router's experts,
    ties to the lower index; the router is float32 whatever ``cast`` is.
    ``margins``, when a list, collects each token's gap between its
    sixth and seventh score among the experts left."""
    t = v.shape[0]
    logits = v.astype(np.float32) @ gate_weight.T
    logits -= logits.max(-1, keepdims=True)
    scores = np.exp(logits)
    scores /= scores.sum(-1, keepdims=True)
    groups, k = cfg.get("n_group") or 1, cfg["num_experts_per_tok"]
    if groups > 1:
        per = scores.shape[1] // groups
        best = scores.reshape(t, groups, per).max(-1)
        kept = np.argsort(-best, axis=-1, kind="stable")[
            :, :cfg["topk_group"]]
        keep = np.zeros((t, groups), bool)
        np.put_along_axis(keep, kept, True, axis=-1)
        scores = np.where(np.repeat(keep, per, axis=1), scores, 0.0)
    order = np.argsort(-scores, axis=-1, kind="stable")
    experts = order[:, :k]
    weights = np.take_along_axis(scores, experts, axis=-1)
    if margins is not None:
        margins.append(weights[:, -1] - np.take_along_axis(
            scores, order[:, k:k + 1], axis=-1)[:, 0])
    return experts, weights * cfg.get("routed_scaling_factor", 1.0)


def experts_layer(v, get, cfg, cast=_same, routes=None, parts=None):
    """The held share of the routed sum, expert by expert, plus the
    shared experts.  ``get(name)`` gives ``mlp.<name>.weight``;
    ``routes``, when a list, collects ``(experts, margins)`` a layer."""
    margins = [] if routes is not None else None
    experts, weights = route(v, get("gate"), cfg, margins)
    if routes is not None:
        routes.append((experts, margins[0]))
    y = np.zeros_like(v)
    for e in range(cfg["n_routed_experts"]):  # the held ones: 0 ..
        weight = np.where(experts == e, weights, 0.0).sum(-1)
        rows = np.nonzero(weight)[0]
        if len(rows):
            y[rows] += weight[rows, None] * swiglu(
                v[rows], get, f"experts.{e}.", cast)
    shared = swiglu(v, get, "shared_experts.", cast)
    if parts is not None:
        parts.append((y, shared))
    return y + shared


def layer(x, w: Weights, i: int, cfg, cast=_same, routes=None, parts=None):
    """One block on one sequence; tensors are read as they are needed, an
    expert at a time."""
    p = f"model.layers.{i}."
    eps = cfg["rms_norm_eps"]

    def get(prefix):
        return lambda name: w.get(f"{p}{prefix}{name}.weight")

    a = attention(rms_norm(x, get("")("input_layernorm"), eps),
                  get("self_attn."), cfg, cast)
    x = x + a
    v = rms_norm(x, get("")("post_attention_layernorm"), eps)
    if i < cfg["first_k_dense_replace"]:
        m = swiglu(v, get("mlp."), "", cast)
    else:
        branch = [] if parts is not None else None
        m = experts_layer(v, get("mlp."), cfg, cast, routes, branch)
        if parts is not None:
            parts.append((a, *branch[0]))
    return x + m


#: rows of the head multiplied at a time: a float32 block of 8,192 x
#: 5,120 is 168 MB
HEAD_BLOCK = 8192


def _rows_of(model_dir: Path, name: str):
    """A checkpoint tensor as a safetensors slice, rows read as they are
    asked for: neither the embedding nor the head (102,400 x 5,120, 2.1
    GB each in float32) is ever held whole (PERF.md section 7)."""
    from safetensors import safe_open

    index = json.loads(
        (model_dir / "model.safetensors.index.json").read_text())["weight_map"]
    return safe_open(str(model_dir / index[name]),
                     framework="np").get_slice(name)


def score(model_dir: Path, cfg: dict, sequences: list[list[int]],
          prompt_lens: list[int], cast=_same, routes=None, parts=None
          ) -> list[list[float]]:
    """For each sequence (prompt ids + generated ids), the reference's
    logprob of every generated token given all tokens before it, layer by
    layer and expert by expert.  ``cast`` rounds every matmul operand but
    the router's (the lower-precision control; identity for the
    reference); ``routes`` and ``parts``, when lists, collect each expert
    layer's ``(experts, margins)`` and its three branches' outputs, a
    sequence."""
    f32 = np.float32
    embed = _rows_of(model_dir, "model.embed_tokens.weight")
    row = {i: np.asarray(embed[i:i + 1]).astype(f32)[0]
           for i in sorted({i for ids in sequences for i in ids})}
    xs = [np.stack([row[i] for i in ids]) for ids in sequences]
    del embed, row
    w = Weights(model_dir)
    for i in range(cfg["num_hidden_layers"]):
        xs = [layer(x, w, i, cfg, cast, routes, parts) for x in xs]
        w.close()
    norm = w.get("model.norm.weight")
    rows = [cast(rms_norm(x[n_prompt - 1:-1], norm, cfg["rms_norm_eps"]))
            for x, n_prompt in zip(xs, prompt_lens)]
    stacked = np.concatenate(rows)
    head = _rows_of(model_dir, "lm_head.weight")
    vocab = head.get_shape()[0]
    logits = np.concatenate([
        stacked @ cast(np.asarray(head[a:min(a + HEAD_BLOCK, vocab)])
                       .astype(f32)).T
        for a in range(0, vocab, HEAD_BLOCK)
    ], axis=1)
    out, at = [], 0
    for r, ids, n_prompt in zip(rows, sequences, prompt_lens):
        out.append(chosen_logprobs(logits[at:at + len(r)], ids[n_prompt:]))
        at += len(r)
    return out
