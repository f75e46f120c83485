"""The Command A+ family (``cohere2_moe``): window and full attention
layers in one stack, a parallel block under one weight-only LayerNorm,
sigmoid-routed experts of which a chip holds a share beside averaged
shared ones, and a tied head.

    u = LayerNorm(x)            mean-subtracting, a weight and no bias
    q, k, v = q_proj u, k_proj u, v_proj u     128 query heads, 8 KV heads
    layer_types[i] == "sliding_attention": RoPE on q and k over the whole
        head in interleaved pairs, theta 50,000; row t sees s with
        0 <= t - s < sliding_window
    layer_types[i] == "full_attention": no positional encoding; causal
    a = o_proj softmax(q k^T / sqrt(head_dim)) v
    s = sigmoid(gate u) over ALL experts, float32; the 8 largest are the
        token's experts, w_k = s_k / (sum of the 8 chosen s)
    x' = x + a + sum over the chosen experts THAT ARE HELD of
        w_k down_k(silu(gate_k u) up_k u)
        + (1/4) sum_j down_j(silu(gate_j u) up_j u)    the shared MEAN
    logits = logit_scale * embed_tokens LayerNorm(x_L)      the tied head

``tensors``, ``score`` and ``LOGPROB_TOLERANCE`` as ``families/llama.py``
documents them.  Nothing here imports JAX or the package.

**A configuration of this family is one chip's share.**  Its
``num_experts`` counts the experts HELD (the guide's section 4): experts
``0 .. num_experts - 1`` under their published indices.  The router
keeps every row: ``ROUTED`` of them (the published 128).  What the
absent experts would add is left out, here as in the server.  A file's
``layer_types`` is the published list; the first ``num_hidden_layers``
entries are the layers that are there.

**The fills are part of the result** (PERF.md section 4 gives the
readings at the published widths).  The head is the embedding, so the
embedding's std is what the logits need (0.031 x |LayerNorm(x)| = 64:
std ~2, logprobs range over many tolerances).  A token's own embedding
is in its residual stream, and a tied head scores it ``d sigma^2 /
RMS(x_L)`` above the rest: 87 at a stream of the embedding's own size,
where every request would repeat its last token at logprob 0 and the
comparison would hold whatever the layers computed (read here at a
width of 1,024: -53 for any other token).  So the branches are LARGE
against the embedding: each adds a few tenths to a stream that starts at
0.031 and ends near 1, which leaves the own token ~4 above the rest,
two of the logits' stds.  Every layer's input is normed, so beyond that
only the branches' sizes against one another count.  Each tensor has a
std of its own, chosen so that

- attention scores have a std near 1.5 (q and k at 1.22), so which token
  a row attends to matters, in a window layer through the rotation and
  in the full layer without any;
- the three branches of a layer add attention ~0.25, the shared
  experts' mean ~0.32, and the held routed experts ~0.055 a (token, held
  pair): a sixth of the largest and not less.  Not a third, and the
  chip gave the reason twice.  Under a sigmoid with renormalised
  weights every chosen expert weighs about an eighth whatever the
  router's width (the top 8 of 128 sigmoids lie within a few percent of
  one another at any logit std), so where a token's eighth and ninth
  scores lie closer than the rounding of the layer's input, server and
  reference choose different experts, and what that costs is one held
  pair's whole output.  How often is a matter of relative margins,
  which no fill changes (a router logit std of 2 keeps the sigmoids off
  both flat ends); what it costs is the fill's.  At a held pair of 0.11
  one flip moved a served logprob by 0.43 and by 0.62 (two seeds of two,
  PERF.md section 6) while float8 operands moved the reference by 1.46:
  a limit between them is 1.5 times from each.  At 0.055 the largest
  served reading over ten seeds is 0.193 and float8 reads 0.894: the
  precisions stand 4.6 apart, not 2.3, because rounding moves every
  branch and a flip only this one.  ``run.py`` holds every scored token
  to ONE limit and ``score`` never sees the served logprobs, so it
  cannot set a near-tie token aside (``routes`` collects the margins
  for whoever reads a failure), and a limit that clears one flipped
  pair also clears a pair that is wrong on every token, at either size:
  which expert computes what is held by the CPU tests at 1e-4 on a
  logit (tests/test_cohere2_moe.py), not by this comparison (PERF.md
  section 7 says what a ``benchmark`` PR would change);
- one chip holds 16 of 128 experts, so a token has one held pair in the
  mean and a flip touches a held expert one time in four.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from reference import Weights, chosen_logprobs

#: Largest |server logprob - reference logprob| allowed for a generated
#: token.  The server multiplies in bfloat16 and keeps the norm, the
#: softmax, the router's scores and every sum in float32; the reference
#: is float32 on the same bf16 weights.  Beside the rounding that every
#: family has, this one has the discrete step the module docstring
#: describes (a flipped expert at an eighth of its weight: with
#: bfloat16 matmul operands this reference flips 31 of 908 (token,
#: layer) expert sets against itself in float32).  The limit lies
#: between two readings at the published widths (PERF.md section 6,
#: PR 49): 0.193, the largest difference the server has shown on the
#: chip over ten runs of ten seeds at 48 and 145 prompt tokens
#: (0.038-0.193) and one at 4,600 and 9,000 (0.074, 0.031; this
#: reference with bfloat16 operands differs from itself in float32 by
#: 0.174), and 0.894, the difference of this same reference computed
#: with float8 (e4m3) matmul operands, the nearest precision below
#: bfloat16, which so comes out as not correct: 2.3 times over the one,
#: 2.0 times under the other.  The reference's logprobs of one run's 34
#: tokens range over 1.8-3.5: four to eight such limits; the fine
#: comparison is the CPU tests' (1e-4 on a logit,
#: tests/test_cohere2_moe.py).
LOGPROB_TOLERANCE = 0.45

#: experts the router scores (the published ``num_experts``), of which a
#: configuration's ``num_experts`` are held
ROUTED = 128

ONES = ("ones",)

# stds, by tensor (the module docstring says what each is for)
EMBED = ("pool", 0.031)  # x |LayerNorm| = 64: logit std ~2 (tied head)
Q_K = ("pool", 0.019)  # x 64: q and k std ~1.22, scores ~1.5
V_PROJ = ("pool", 0.0156)  # x 64: v std ~1
O_PROJ = ("pool", 0.006)  # x 128 x ~0.3: attention adds ~0.25
GATE_UP = ("pool", 0.0156)  # x 64: pre-activations std ~1
SHARED_DOWN = ("pool", 0.017)  # x 64 x 0.6 / 2 (a mean of 4): ~0.32
EXPERT_DOWN = ("pool", 0.0114)  # x 64 x 0.6 / 8 (one weight): ~0.055
ROUTER = ("pool", 0.031)  # x 64: logits std ~2


def _swiglu(prefix: str, d: int, width: int, down) -> dict:
    return {f"{prefix}.gate_proj.weight": ((width, d), GATE_UP),
            f"{prefix}.up_proj.weight": ((width, d), GATE_UP),
            f"{prefix}.down_proj.weight": ((d, width), down)}


def tensors(cfg: dict) -> tuple[dict, list[dict]]:
    """(tensors outside the layers, tensors of each layer): name ->
    (shape, fill); of a layer's routed experts, the held ones 0 ..
    num_experts - 1.  No ``lm_head``: the head is the embedding."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    f = cfg["intermediate_size"]
    top = {"model.embed_tokens.weight": ((cfg["vocab_size"], d), EMBED),
           "model.norm.weight": ((d,), ONES)}
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        layer = {
            f"{p}.input_layernorm.weight": ((d,), ONES),
            f"{p}.self_attn.q_proj.weight": ((heads * dh, d), Q_K),
            f"{p}.self_attn.k_proj.weight": ((kv * dh, d), Q_K),
            f"{p}.self_attn.v_proj.weight": ((kv * dh, d), V_PROJ),
            f"{p}.self_attn.o_proj.weight": ((d, heads * dh), O_PROJ),
            f"{p}.mlp.gate.weight": ((ROUTED, d), ROUTER),
        }
        for j in range(cfg["num_shared_experts"]):
            layer |= _swiglu(f"{p}.mlp.shared_experts.{j}", d, f, SHARED_DOWN)
        for e in range(cfg["num_experts"]):
            layer |= _swiglu(f"{p}.mlp.experts.{e}", d, f, EXPERT_DOWN)
        layers.append(layer)
    return top, layers


def _silu(x: np.ndarray) -> np.ndarray:
    return x / (1.0 + np.exp(-x))


def _same(x: np.ndarray) -> np.ndarray:
    return x


def layer_norm(x: np.ndarray, w: np.ndarray, eps: float) -> np.ndarray:
    x = x - x.mean(-1, keepdims=True)
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def rope_interleaved(x: np.ndarray, theta: float) -> np.ndarray:
    """x: [T, heads, d] at positions 0..T-1, rotated in interleaved
    pairs (x0, x1), (x2, x3), ..."""
    t, _, d = x.shape
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    out = np.empty_like(x)
    out[..., 0::2] = x[..., 0::2] * cos - x[..., 1::2] * sin
    out[..., 1::2] = x[..., 0::2] * sin + x[..., 1::2] * cos
    return out


#: query rows attended at a time: a float32 block of 512 x 16,384 scores
#: a head is 32 MB, so a sequence of any length fits
QUERY_BLOCK = 512


def attention(u, get, cfg, i: int, cast=_same):
    """``get(name)`` gives ``self_attn.<name>.weight`` in float32.  In
    query blocks; a window layer's block reads only the keys its rows
    may see."""
    t = u.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, group = cfg["head_dim"], heads // kv
    sliding = cfg["layer_types"][i] == "sliding_attention"
    window = cfg["sliding_window"] if sliding else t
    u = cast(u)
    q = (u @ cast(get("q_proj")).T).reshape(t, heads, dh)
    k = (u @ cast(get("k_proj")).T).reshape(t, kv, dh)
    v = (u @ cast(get("v_proj")).T).reshape(t, kv, dh)
    if sliding:
        theta = float((cfg.get("rope_parameters") or {}).get("rope_theta")
                      or cfg["rope_theta"])
        q, k = rope_interleaved(q, theta), rope_interleaved(k, theta)
    out = np.empty((t, heads, dh), np.float32)
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        first = max(0, lo - window + 1)
        rows = np.arange(lo, hi)[:, None]
        cols = np.arange(first, hi)[None, :]
        keep = (cols <= rows) & (rows - cols < window)
        for h in range(heads):  # a head at a time
            scores = (cast(q[lo:hi, h]) @ cast(k[first:hi, h // group]).T
                      ) * dh ** -0.5
            scores = np.where(keep, scores, -np.inf)
            scores -= scores.max(-1, keepdims=True)
            probs = np.exp(scores)
            probs /= probs.sum(-1, keepdims=True)
            out[lo:hi, h] = cast(probs) @ cast(v[first:hi, h // group])
    return cast(out.reshape(t, heads * dh)) @ cast(get("o_proj")).T


def swiglu(v, get, prefix: str, cast=_same):
    v = cast(v)
    act = _silu(v @ cast(get(f"{prefix}gate_proj")).T) * (
        v @ cast(get(f"{prefix}up_proj")).T)
    return cast(act) @ cast(get(f"{prefix}down_proj")).T


def route(u, gate_weight, cfg, margins=None):
    """(experts [T, k], weights [T, k]) over all the router's experts,
    ties to the lower index; the router is float32 whatever ``cast`` is.
    ``margins``, when a list, collects each token's gap between its
    eighth and ninth score."""
    scores = 1.0 / (1.0 + np.exp(-(u.astype(np.float32) @ gate_weight.T)))
    k = cfg["num_experts_per_tok"]
    order = np.argsort(-scores, axis=-1, kind="stable")
    experts = order[:, :k]
    chosen = np.take_along_axis(scores, experts, axis=-1)
    if margins is not None:
        margins.append(chosen[:, -1] - np.take_along_axis(
            scores, order[:, k:k + 1], axis=-1)[:, 0])
    return experts, chosen / chosen.sum(-1, keepdims=True)


def experts_layer(u, get, cfg, cast=_same, routes=None, parts=None):
    """The held share of the routed sum, expert by expert, plus the mean
    of the shared experts.  ``get(name)`` gives ``mlp.<name>.weight``;
    ``routes``, when a list, collects ``(experts, margins)`` a layer."""
    margins = [] if routes is not None else None
    experts, weights = route(u, get("gate"), cfg, margins)
    if routes is not None:
        routes.append((experts, margins[0]))
    y = np.zeros_like(u)
    for e in range(cfg["num_experts"]):  # the held ones: 0 ..
        weight = np.where(experts == e, weights, 0.0).sum(-1)
        rows = np.nonzero(weight)[0]
        if len(rows):
            y[rows] += weight[rows, None] * swiglu(
                u[rows], get, f"experts.{e}.", cast)
    n_shared = cfg["num_shared_experts"]
    shared = sum(swiglu(u, get, f"shared_experts.{j}.", cast)
                 for j in range(n_shared)) / n_shared
    if parts is not None:
        parts.append((y, shared))
    return y + shared


def layer(x, w: Weights, i: int, cfg, cast=_same, routes=None, parts=None):
    """One block on one sequence; tensors are read as they are needed, an
    expert at a time."""
    p = f"model.layers.{i}."

    def get(prefix):
        return lambda name: w.get(f"{p}{prefix}{name}.weight")

    u = layer_norm(x, get("")("input_layernorm"), cfg["layer_norm_eps"])
    a = attention(u, get("self_attn."), cfg, i, cast)
    branch = [] if parts is not None else None
    m = experts_layer(u, get("mlp."), cfg, cast, routes, branch)
    if parts is not None:
        parts.append((x, a, *branch[0]))
    return x + a + m


#: rows of the head multiplied at a time: a float32 block of 8,192 x
#: 4,096 is 134 MB
HEAD_BLOCK = 8192


def _rows_of(model_dir: Path, name: str):
    """A checkpoint tensor as a safetensors slice, rows read as they are
    asked for: the embedding (262,144 x 4,096, 4.3 GB in float32) is
    never held whole."""
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
    reference); ``routes`` and ``parts``, when lists, collect each
    layer's ``(experts, margins)`` and its input and three branches'
    outputs, a sequence."""
    f32 = np.float32
    embed = _rows_of(model_dir, "model.embed_tokens.weight")
    row = {i: np.asarray(embed[i:i + 1]).astype(f32)[0]
           for i in sorted({i for ids in sequences for i in ids})}
    xs = [np.stack([row[i] for i in ids]) for ids in sequences]
    del row
    w = Weights(model_dir)
    for i in range(cfg["num_hidden_layers"]):
        xs = [layer(x, w, i, cfg, cast, routes, parts) for x in xs]
        w.close()
    norm = w.get("model.norm.weight")
    rows = [cast(layer_norm(x[n_prompt - 1:-1], norm, cfg["layer_norm_eps"]))
            for x, n_prompt in zip(xs, prompt_lens)]
    stacked = np.concatenate(rows)
    vocab = embed.get_shape()[0]
    logits = cfg.get("logit_scale", 1.0) * np.concatenate([
        stacked @ cast(np.asarray(embed[a:min(a + HEAD_BLOCK, vocab)])
                       .astype(f32)).T
        for a in range(0, vocab, HEAD_BLOCK)
    ], axis=1)
    out, at = [], 0
    for r, ids, n_prompt in zip(rows, sequences, prompt_lens):
        out.append(chosen_logprobs(logits[at:at + len(r)], ids[n_prompt:]))
        at += len(r)
    return out
