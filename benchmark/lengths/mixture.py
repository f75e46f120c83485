"""A mixture of length distributions: ``parts``, each a distribution of
its own (``dist`` and its parameters, ``min`` and ``max`` clip it) with a
``weight``; one draw picks a part by weight and then draws from it."""

import importlib


def draw(spec: dict, rng) -> float:
    parts = spec["parts"]
    at = rng.random() * sum(p["weight"] for p in parts)
    for part in parts:
        at -= part["weight"]
        if at < 0:
            break
    x = importlib.import_module(f"lengths.{part['dist']}").draw(part, rng)
    return min(part.get("max", x), max(part.get("min", x), x))
