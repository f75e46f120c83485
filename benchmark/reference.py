"""The plain reference: a float32 numpy forward pass of the dense decoder
block (RMSNorm, RoPE, grouped-query softmax attention, SwiGLU, and
granite's four multipliers), independent of the package: no cache, no
batching, no kernel.  It streams one layer at a time from the
checkpoint's safetensors, so it holds one layer in float32 at most.

``score`` takes whole sequences (prompt + the tokens the SERVER chose)
and returns the reference's logprob of each generated token: the first
comes from the server's prefill step, the rest from decode through its
paged cache, so both paths are held to the full forward pass.

Nothing here imports JAX.
"""

from __future__ import annotations

import json
from pathlib import Path

import ml_dtypes  # noqa: F401 - registers bfloat16 with numpy for safetensors
import numpy as np

#: Largest |server logprob - reference logprob| allowed for a generated
#: token.  The server multiplies in bfloat16 (8 bits of mantissa,
#: relative rounding 2**-9 per product, summed in float32) and the
#: reference in float32 on the same bf16 weights.  Measured on the chip
#: at published widths (my chip runs, PR 25): see PERF.md section 6; the
#: bound is about three times the largest difference seen.  A float8
#: (e4m3: 2**-4 per product) or int8 path errs some sixteen times more
#: per product than bf16 and lands well outside it.
LOGPROB_TOLERANCE = 0.03


class Weights:
    def __init__(self, model_dir: Path):
        from safetensors import safe_open

        index = json.loads(
            (model_dir / "model.safetensors.index.json").read_text()
        )["weight_map"]
        self._index = index
        self._dir = model_dir
        self._open = safe_open
        self._files: dict = {}

    def has(self, name: str) -> bool:
        return name in self._index

    def get(self, name: str) -> np.ndarray:
        shard = self._index[name]
        if shard not in self._files:
            self._files[shard] = self._open(
                str(self._dir / shard), framework="np"
            )
        return np.asarray(
            self._files[shard].get_tensor(name)
        ).astype(np.float32)


def rms_norm(x: np.ndarray, w: np.ndarray, eps: float) -> np.ndarray:
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x: np.ndarray, theta: float) -> np.ndarray:
    """x: [T, heads, Dh]; positions 0..T-1; the half-rotation layout of
    the Hugging Face llama family."""
    t, _, dh = x.shape
    inv = theta ** (-np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None, :]
    cos = np.concatenate([np.cos(ang), np.cos(ang)], -1)[:, None, :]
    sin = np.concatenate([np.sin(ang), np.sin(ang)], -1)[:, None, :]
    half = dh // 2
    rotated = np.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


LAYER_TENSORS = (
    "input_layernorm", "post_attention_layernorm", "self_attn.q_proj",
    "self_attn.k_proj", "self_attn.v_proj", "self_attn.o_proj",
    "mlp.gate_proj", "mlp.up_proj", "mlp.down_proj",
)


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
    logprob of every generated token given all tokens before it."""
    w = Weights(model_dir)
    embed = w.get("model.embed_tokens.weight")
    xs = [
        embed[np.asarray(ids)] * cfg.get("embedding_multiplier", 1.0)
        for ids in sequences
    ]
    for i in range(cfg["num_hidden_layers"]):
        lw = {n: w.get(f"model.layers.{i}.{n}.weight") for n in LAYER_TENSORS}
        xs = [layer(x, lw, cfg) for x in xs]
        w._files.clear()  # one layer's shard open at a time is enough
    head = embed if not w.has("lm_head.weight") else w.get("lm_head.weight")
    norm = w.get("model.norm.weight")
    out = []
    for x, ids, n_prompt in zip(xs, sequences, prompt_lens):
        rows = rms_norm(x[n_prompt - 1:-1], norm, cfg["rms_norm_eps"])
        logits = rows @ head.T / cfg.get("logits_scaling", 1.0)
        logits -= logits.max(-1, keepdims=True)
        logprobs = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        chosen = ids[n_prompt:]
        out.append([float(logprobs[j, tok]) for j, tok in enumerate(chosen)])
    return out


def main() -> None:
    """``python3 benchmark/reference.py <job.json>``: a job holds
    ``model_dir``, ``config``, ``sequences`` and ``prompt_lens``; prints
    the scores as one JSON line.  ``run.py`` starts this as a child with a
    few threads while the server warms up."""
    import sys

    job = json.loads(Path(sys.argv[1]).read_text())
    print(json.dumps(score(Path(job["model_dir"]), job["config"],
                           job["sequences"], job["prompt_lens"])))


if __name__ == "__main__":
    main()
