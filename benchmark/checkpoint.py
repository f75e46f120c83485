"""Seeded checkpoint, tokenizer and prompts of an exact token length.

Copied in substance from ``chip_smoke.py`` (``write_granite_checkpoint``,
``make_prompts``) and ``tests/fixture_models.py`` (``build_tokenizer``,
``_CORPUS``), which ran on the chip in PR 23; kept here so that a later
PR cannot change what the benchmark serves.  Nothing in this file
imports JAX.

Which tensors a checkpoint holds, and how each is filled, is its
family's to say (``families/<family>.py`` ``tensors``): name -> (shape,
fill), where a fill is one of

- ``("ones",)``;
- ``("pool", std)``: N(0, std**2).  One pool of 2**26 standard normal
  values is drawn from the seed and every such tensor, of any rank, is a
  slice of it at an offset drawn per tensor, so writing 5-7.5 GB is bound
  by the disk and not by the random generator (chip_smoke drew every
  value: 36.8 s for granite-2b);
- ``("values", function)``: ``function(shape, generator)`` returns the
  values, for the few small tensors that are neither (a decay, a bias).
"""

from __future__ import annotations

import json
import math
import os
import random
import shutil
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "hello world, this is a tiny test corpus for a tiny tokenizer",
    "The capital of France is Paris. The capital of Italy is Rome.",
    "0 1 2 3 4 5 6 7 8 9 10 11 12 13 14 15 16 17 18 19 20",
    "def main():\n    print('hello')\n    return 0\n",
    '{"name": "value", "list": [1, 2, 3], "flag": true}',
    "to be or not to be, that is the question",
    "pack my box with five dozen liquor jugs",
]
MARKER = "COMPLETE"
POOL = 1 << 26
LAYERS_PER_SHARD = 4


def build_tokenizer(path: Path, vocab_size: int) -> None:
    """A byte-level BPE trained on CORPUS, padded with ``<filler_i>``
    tokens to exactly ``vocab_size`` so every model id is a token."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=vocab_size,
        special_tokens=["<unk>", "<s>", "</s>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False,
    )
    tok.train_from_iterator(CORPUS, trainer=trainer)
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok, unk_token="<unk>", bos_token="<s>",
        eos_token="</s>", pad_token="</s>",
    )
    missing = vocab_size - len(fast)
    if missing > 0:
        fast.add_tokens([f"<filler_{i}>" for i in range(missing)])
    fast.save_pretrained(str(path))


def write_checkpoint(path: Path, cfg: dict, seed: int, dtype: str,
                     tensors) -> None:
    """config.json + tokenizer + safetensors shards of seeded weights;
    ``tensors(cfg)`` is the family's table."""
    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file

    np_dtype = {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32}[dtype]
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    (path / "config.json").write_text(json.dumps(cfg, indent=2))

    top, layers = tensors(cfg)
    rng = np.random.default_rng([seed, 0])
    unit = rng.standard_normal(POOL, dtype=np.float32)
    pools = {}
    for std in {fill[1] for table in (top, *layers)
                for _, fill in table.values() if fill[0] == "pool"}:
        pool = (unit * std).astype(np_dtype)
        pools[std] = np.concatenate([pool, pool])  # a slice never wraps
    del unit

    def tensor(shape: tuple, fill: tuple, trng) -> "np.ndarray":
        if fill[0] == "ones":
            return np.ones(shape, np_dtype)
        if fill[0] == "values":
            return np.asarray(fill[1](shape, trng)).astype(np_dtype)
        if fill[0] != "pool":
            raise ValueError(f"unknown fill {fill!r}")
        rows, cols = shape[0], math.prod(shape[1:])
        step = max(1, POOL // cols)
        parts = []
        for lo in range(0, rows, step):
            n = (min(rows, lo + step) - lo) * cols
            off = int(trng.integers(POOL))
            parts.append(pools[fill[1]][off:off + n])
        flat = parts[0] if len(parts) == 1 else np.concatenate(parts)
        return flat.reshape(shape)

    groups = [layers[lo:lo + LAYERS_PER_SHARD]
              for lo in range(0, len(layers), LAYERS_PER_SHARD)]
    shards = [{k: v for layer in g for k, v in layer.items()} for g in groups]
    shards.append(top)
    names = [f"model-{i + 1:05d}-of-{len(shards):05d}.safetensors"
             for i in range(len(shards))]

    def write_shard(index: int) -> dict[str, str]:
        trng = np.random.default_rng([seed, 1, index])
        made = {name: tensor(shape, fill, trng)
                for name, (shape, fill) in shards[index].items()}
        save_file(made, str(path / names[index]))
        return {name: names[index] for name in made}

    weight_map: dict[str, str] = {}
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as workers:
        tokenizer = workers.submit(build_tokenizer, path, cfg["vocab_size"])
        for part in workers.map(write_shard, range(len(shards))):
            weight_map |= part
        tokenizer.result()
    (path / "model.safetensors.index.json").write_text(
        json.dumps({"metadata": {}, "weight_map": weight_map})
    )
    (path / MARKER).write_text(json.dumps({"seed": seed, "config": cfg}))


def ensure_checkpoint(work: Path, name: str, cfg: dict, seed: int,
                      dtype: str, tensors) -> tuple[Path, bool]:
    """The checkpoint of ``name`` under ``seed``, written from its
    family's ``tensors`` unless it is there whole; checkpoints of the same
    configuration under other seeds go first, so one is on disk at most.
    Returns (path, reused)."""
    path = work / f"{name}-seed{seed}"
    marker = path / MARKER
    want = {"seed": seed, "config": cfg}
    if marker.exists() and json.loads(marker.read_text()) == want:
        return path, True
    work.mkdir(parents=True, exist_ok=True)
    for other in work.glob(f"{name}-seed*"):
        shutil.rmtree(other, ignore_errors=True)
    write_checkpoint(path, cfg, seed, dtype, tensors)
    return path, False


class PromptMaker:
    """Prompts of an exact length in tokens of the checkpoint's own
    tokenizer, each from its own random atoms, so no two share a prefix
    beyond chance.  Every atom starts at a pre-tokenizer boundary, so
    token counts add up."""

    def __init__(self, model_dir: Path):
        from transformers import AutoTokenizer

        self.tok = AutoTokenizer.from_pretrained(str(model_dir))
        words = sorted({" " + w for line in CORPUS for w in line.split()})
        fillers = sorted(
            t for t in self.tok.get_added_vocab() if t.startswith("<filler_")
        )
        pick = random.Random(0).sample(fillers, min(len(fillers), 4096))
        self.atoms = {w: len(self.tok(w).input_ids) for w in words}
        self.atoms |= {f: 1 for f in pick}
        self.singles = [a for a, n in self.atoms.items() if n == 1]
        self.names = list(self.atoms)

    def make(self, length: int, rng: random.Random) -> str:
        parts, have = [], 0
        while have < length:
            atom = rng.choice(self.names)
            if have + self.atoms[atom] > length:
                atom = rng.choice(self.singles)
            parts.append(atom)
            have += self.atoms[atom]
        return "".join(parts)

    def ids(self, text: str) -> list[int]:
        return list(self.tok(text).input_ids)

    def make_all(self, lengths: list[int], seed: int) -> list[str]:
        rng = random.Random(seed)
        prompts = [self.make(n, rng) for n in lengths]
        for text, want in zip(prompts, lengths):
            got = len(self.ids(text))
            if got != want:
                raise RuntimeError(f"prompt built for {want} tokens has {got}")
        return prompts
