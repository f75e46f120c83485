"""The plain reference's command line and what its families share.

A family's reference is ``families/<family>.py`` ``score``: a float32
numpy forward pass of that family's block, independent of the package:
no cache, no batching, no kernel.  It takes whole sequences (prompt + the
tokens the SERVER chose) and returns the reference's logprob of each
generated token: the first comes from the server's prefill step, the
rest from decode through its paged cache, so both paths are held to the
full forward pass.

Here: the checkpoint's tensors by name (``Weights``), RMSNorm, RoPE and
the softmax tail, which are the same mathematics in every family so far.
The tolerance is the family's.

Nothing here imports JAX.
"""

from __future__ import annotations

import json
from pathlib import Path

import ml_dtypes  # noqa: F401 - registers bfloat16 with numpy for safetensors
import numpy as np


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

    def close(self) -> None:
        """Forget the shards opened so far (a family calls this between
        layers: one layer's shard open at a time is enough)."""
        self._files.clear()


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


def chosen_logprobs(logits: np.ndarray, chosen: list[int]) -> list[float]:
    """Row j of ``logits`` is the model's output before token ``chosen[j]``:
    the log-softmax of each row at its chosen token."""
    logits -= logits.max(-1, keepdims=True)
    logprobs = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    return [float(logprobs[j, tok]) for j, tok in enumerate(chosen)]


def main() -> None:
    """``python3 benchmark/reference.py <job.json>``: a job holds
    ``family``, ``model_dir``, ``config``, ``sequences`` and
    ``prompt_lens``; prints the scores of ``families/<family>.py`` as one
    JSON line.  ``run.py`` starts this as a child with a few threads
    while the server warms up."""
    import importlib
    import sys

    job = json.loads(Path(sys.argv[1]).read_text())
    family = importlib.import_module(f"families.{job['family']}")
    print(json.dumps(family.score(Path(job["model_dir"]), job["config"],
                                  job["sequences"], job["prompt_lens"])))


if __name__ == "__main__":
    main()
