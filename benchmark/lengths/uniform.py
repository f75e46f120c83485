"""Uniform over ``min`` .. ``max``."""


def draw(spec: dict, rng) -> float:
    return rng.uniform(spec["min"], spec["max"])
