"""The one traffic generator: a traffic file of parameters in, a seeded
schedule out.  A new mix is a new file under ``benchmark/traffic/``.

Arrivals and sizes are drawn from ``--seed``: another seed is another
draw of the same process, as another hour of the same users would be.
An arrival process is ``arrivals/<kind>.py`` (``gaps(params, rate,
rng)``, an endless iterator of seconds) and a length distribution is
``lengths/<dist>.py`` (``draw(spec, rng)``), both found by the name in
the traffic file; ``min`` and ``max`` clip a length.

A file with a ``replay`` key (``{"requests": n, "seed": s}``) is a fixed
sample and not a process: the first ``n`` requests of the draw of seed
``s``, their gaps stretched by one factor so that they fill ``n / rate``
seconds, replayed for ever; ``--seed`` decides only at which point of
that period the stream sets in.  Any ``n / rate`` seconds of any seed
hold the same requests at the same distances, begun at another one: the
seed does not change the work.
"""

from __future__ import annotations

import dataclasses
import importlib
import itertools
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


def _stream(traffic: dict, rate: float, seed: int):
    """The draw of ``seed``, endless: the gaps and the sizes come from
    separate streams of it, so a change of rate keeps the sizes."""
    process = importlib.import_module(f"arrivals.{traffic['arrivals']}")
    rng = random.Random(seed * 2)
    t = 0.0
    for gap in process.gaps(traffic, rate, random.Random(seed * 2 + 1)):
        t += gap
        yield Planned(t, draw_length(traffic["prompt_tokens"], rng),
                      draw_length(traffic["output_tokens"], rng))


def open_schedule(traffic: dict, rate: float, horizon_s: float,
                  seed: int) -> list[Planned]:
    """Arrivals of the file's process at ``rate`` requests a second up to
    ``horizon_s``, each with sizes of its own, drawn from ``seed``; under
    ``replay`` the file's one period from a point that ``seed`` picks."""
    replay = traffic.get("replay")
    if replay is None:
        return list(itertools.takewhile(lambda p: p.due < horizon_s,
                                        _stream(traffic, rate, seed)))
    n = replay["requests"]
    period = n / rate
    one = list(itertools.islice(_stream(traffic, rate, replay["seed"]), n))
    stretch = period / one[-1].due
    phase = random.Random(seed).random() * period
    plan: list[Planned] = []
    for k in itertools.count():
        for p in one:
            due = k * period + p.due * stretch - phase
            if due >= horizon_s:
                return plan
            if due >= 0:
                plan.append(dataclasses.replace(p, due=due))
