#!/usr/bin/env python3
"""The lower-precision control of a cell's ``correct``: the family's own
plain reference, computed with every matmul operand rounded to the
nearest precision below the one the configuration states, has to come
out as NOT correct by the comparison ``benchmark/run.py`` makes
(``judge``, the family's ``LOGPROB_TOLERANCE``); with operands rounded
to the configuration's own precision it has to pass.  Run by hand, on
the chip machine's CPU for a configuration at its published widths
(the checkpoint is the cell's own, written from ``--seed``):

    python3 tools/lower_precision_control.py --workload <cell> --seed <n>

Two sequences as long as the harness's own reference requests (48 and
145 prompt tokens, 17 scored tokens each), drawn from ``--seed``.  A
family takes part if its ``score`` takes ``cast`` (a function that
rounds an operand).  Prints one JSON line; exit code 1 unless the
configuration's precision passes and the one below fails.  Nothing here
imports JAX or starts a server.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import ml_dtypes
import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))

import run as harness  # noqa: E402  (benchmark/run.py)

#: the precision below each a configuration may state
BELOW = {"bfloat16": "float8_e4m3fn", "float16": "float8_e4m3fn",
         "float32": "bfloat16"}


def rounded_to(name: str):
    dtype = getattr(ml_dtypes, name, None) or np.dtype(name)
    return lambda a: a.astype(dtype).astype(np.float32)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    args = parser.parse_args()
    cell = harness.load("cells", args.workload)
    config = harness.load("configs", cell["config"])
    family = harness.load_family(config["family"])
    cfg = harness.served_config(config)
    # the cell's own checkpoint (found again if a run of this seed wrote
    # it); no server is started
    model_dir, reused = harness.checkpoint.ensure_checkpoint(
        harness.WORK, cell["config"], cfg, args.seed, config["dtype"],
        family.tensors)
    harness.say(phase="checkpoint", reused=reused)
    rng = np.random.default_rng(args.seed)
    lens = list(harness.REFERENCE_PROMPTS)
    sequences = [
        [int(t) for t in rng.integers(3, cfg["vocab_size"],
                                      n + harness.REFERENCE_TOKENS)]
        for n in lens]
    exact = family.score(model_dir, cfg, sequences, lens)
    own, below = config["dtype"], BELOW[config["dtype"]]
    out = {"tolerance": family.LOGPROB_TOLERANCE}
    for name in (own, below):
        low = family.score(model_dir, cfg, sequences, lens,
                           cast=rounded_to(name))
        out[name] = harness.judge(low, exact, family.LOGPROB_TOLERANCE)
        harness.say(phase="control", operands=name, **out[name])
    out["ok"] = bool(out[own]["ok"] and not out[below]["ok"])
    print(json.dumps(out), flush=True)
    sys.exit(0 if out["ok"] else 1)


if __name__ == "__main__":
    main()
