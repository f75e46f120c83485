"""``median`` and ``sigma`` of the underlying normal."""

import math


def draw(spec: dict, rng) -> float:
    return math.exp(rng.gauss(math.log(spec["median"]), spec["sigma"]))
