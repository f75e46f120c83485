"""The one traffic generator: a traffic file of parameters in, a seeded
schedule out.  A new mix is a new file under ``benchmark/traffic/``.

Arrivals and sizes are drawn from ``--seed``: another seed is another
draw of the same process, as another hour of the same users would be.
An arrival process is ``arrivals/<kind>.py`` (``gaps(params, rate,
rng)``, an endless iterator of seconds) and a length distribution is
``lengths/<dist>.py`` (``draw(spec, rng)``), both found by the name in
the traffic file; ``min`` and ``max`` clip a length.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import random
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Planned:
    due: float  # seconds from the start of the stream (open loop only)
    prompt_tokens: int
    output_tokens: int


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def draw_length(spec: dict, rng: random.Random) -> int:
    x = importlib.import_module(f"lengths.{spec['dist']}").draw(spec, rng)
    return int(min(spec.get("max", x), max(spec.get("min", x), round(x))))


def sizes(traffic: dict, n: int, seed: int) -> list[tuple[int, int]]:
    """``n`` (prompt, output) sizes drawn from ``seed``."""
    rng = random.Random(seed)
    return [
        (draw_length(traffic["prompt_tokens"], rng),
         draw_length(traffic["output_tokens"], rng))
        for _ in range(n)
    ]


def open_schedule(traffic: dict, rate: float, horizon_s: float,
                  seed: int) -> list[Planned]:
    """Arrivals of the file's process at ``rate`` requests a second up to
    ``horizon_s``, each with sizes of its own; the gaps and the sizes come
    from separate streams of ``seed``, so a change of rate keeps the sizes."""
    process = importlib.import_module(f"arrivals.{traffic['arrivals']}")
    gaps = process.gaps(traffic, rate, random.Random(seed * 2 + 1))
    rng = random.Random(seed * 2)
    plan, t = [], next(gaps)
    while t < horizon_s:
        plan.append(Planned(t, draw_length(traffic["prompt_tokens"], rng),
                            draw_length(traffic["output_tokens"], rng)))
        t += next(gaps)
    return plan
