"""The dense llama family (llama, granite, mistral): Hugging Face's
tensor names and the decoder block of RMSNorm, RoPE, grouped-query
softmax attention and SwiGLU, with granite's four multipliers.

A family module holds what is particular to one kind of model and is
found by the name a configuration file gives under ``"family"``:

- ``tensors(cfg)``: every tensor of the checkpoint by name, with its
  shape and its fill (``checkpoint.py`` says what a fill is);
- ``score(model_dir, cfg, sequences, prompt_lens)``: the plain float32
  numpy forward pass, the reference the served tokens are held to;
- ``LOGPROB_TOLERANCE``.

Nothing here imports JAX or the package.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from reference import Weights, chosen_logprobs, rms_norm, rope

#: Largest |server logprob - reference logprob| allowed for a generated
#: token.  The server multiplies in bfloat16 (8 bits of mantissa,
#: relative rounding 2**-9 per product, summed in float32) and the
#: reference in float32 on the same bf16 weights.  Measured on the chip
#: at published widths (my chip runs, PR 25): see PERF.md section 6; the
#: bound is about three times the largest difference seen.  A float8
#: (e4m3: 2**-4 per product) or int8 path errs some sixteen times more
#: per product than bf16 and lands well outside it.
LOGPROB_TOLERANCE = 0.03

ONES = ("ones",)  # norm weights
MATRIX = ("pool", 0.02)  # N(0, 0.02**2): every projection and embedding

LAYER_TENSORS = (
    "input_layernorm", "post_attention_layernorm", "self_attn.q_proj",
    "self_attn.k_proj", "self_attn.v_proj", "self_attn.o_proj",
    "mlp.gate_proj", "mlp.up_proj", "mlp.down_proj",
)


def tensors(cfg: dict) -> tuple[dict, list[dict]]:
    """(tensors outside the layers, tensors of each layer): name ->
    (shape, fill), in the order they are written; granite and mistral
    use llama's names."""
    d, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // heads
    top = {"model.embed_tokens.weight": ((cfg["vocab_size"], d), MATRIX),
           "model.norm.weight": ((d,), ONES)}
    if not cfg.get("tie_word_embeddings", False):
        top["lm_head.weight"] = ((cfg["vocab_size"], d), MATRIX)
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        layers.append({
            f"{p}.input_layernorm.weight": ((d,), ONES),
            f"{p}.post_attention_layernorm.weight": ((d,), ONES),
            f"{p}.self_attn.q_proj.weight": ((heads * dh, d), MATRIX),
            f"{p}.self_attn.k_proj.weight": ((kv * dh, d), MATRIX),
            f"{p}.self_attn.v_proj.weight": ((kv * dh, d), MATRIX),
            f"{p}.self_attn.o_proj.weight": ((d, heads * dh), MATRIX),
            f"{p}.mlp.gate_proj.weight": ((ffn, d), MATRIX),
            f"{p}.mlp.up_proj.weight": ((ffn, d), MATRIX),
            f"{p}.mlp.down_proj.weight": ((d, ffn), MATRIX),
        })
    return top, layers


def layer(x: np.ndarray, lw: dict, cfg: dict) -> np.ndarray:
    """One decoder block on one sequence; ``lw`` holds its tensors."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // heads
    eps = cfg["rms_norm_eps"]
    res = cfg.get("residual_multiplier", 1.0)
    scale = cfg.get("attention_multiplier", dh ** -0.5)
    t = x.shape[0]

    h = rms_norm(x, lw["input_layernorm"], eps)
    q = (h @ lw["self_attn.q_proj"].T).reshape(t, heads, dh)
    k = (h @ lw["self_attn.k_proj"].T).reshape(t, kv, dh)
    v = (h @ lw["self_attn.v_proj"].T).reshape(t, kv, dh)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = np.repeat(k, heads // kv, axis=1)
    v = np.repeat(v, heads // kv, axis=1)
    scores = np.einsum("thd,shd->hts", q, k) * scale
    scores = np.where(np.tril(np.ones((t, t), bool))[None], scores, -np.inf)
    scores -= scores.max(-1, keepdims=True)
    probs = np.exp(scores)
    probs /= probs.sum(-1, keepdims=True)
    attn = np.einsum("hts,shd->thd", probs, v).reshape(t, heads * dh)
    x = x + res * (attn @ lw["self_attn.o_proj"].T)

    h = rms_norm(x, lw["post_attention_layernorm"], eps)
    gate = h @ lw["mlp.gate_proj"].T
    up = h @ lw["mlp.up_proj"].T
    act = gate / (1.0 + np.exp(-gate)) * up  # SiLU(gate) * up
    return x + res * (act @ lw["mlp.down_proj"].T)


def score(model_dir: Path, cfg: dict, sequences: list[list[int]],
          prompt_lens: list[int]) -> list[list[float]]:
    """For each sequence (prompt ids + generated ids), the reference's
    logprob of every generated token given all tokens before it.  Streams
    one layer at a time from the checkpoint's safetensors, so it holds
    one layer in float32 at most."""
    w = Weights(model_dir)
    embed = w.get("model.embed_tokens.weight")
    xs = [
        embed[np.asarray(ids)] * cfg.get("embedding_multiplier", 1.0)
        for ids in sequences
    ]
    for i in range(cfg["num_hidden_layers"]):
        lw = {n: w.get(f"model.layers.{i}.{n}.weight") for n in LAYER_TENSORS}
        xs = [layer(x, lw, cfg) for x in xs]
        w.close()  # one layer's shard open at a time is enough
    head = embed if not w.has("lm_head.weight") else w.get("lm_head.weight")
    norm = w.get("model.norm.weight")
    out = []
    for x, ids, n_prompt in zip(xs, sequences, prompt_lens):
        rows = rms_norm(x[n_prompt - 1:-1], norm, cfg["rms_norm_eps"])
        logits = rows @ head.T / cfg.get("logits_scaling", 1.0)
        out.append(chosen_logprobs(logits, ids[n_prompt:]))
    return out
