"""Irregular-interval decayed EWMA (telemetry primitive).

Engine commits land in bursts (wave commits behind XLA compiles, idle
gaps between requests), so a fixed-α EWMA over *observations* would
weight a burst of 50 commits in 10 ms the same as 50 commits spread
over a minute.  The class here weights by **elapsed wall time**
instead: an observation ``dt`` seconds after the previous one replaces
``1 - 2^(-dt / half_life)`` of the running value, so the estimate
always represents "the recent ``half_life``-ish window" regardless of
the arrival pattern.  Unit-tested in tests/test_telemetry.py.
"""

from __future__ import annotations

import time
from typing import Callable, Optional


class DecayedEwma:
    """Time-decayed EWMA of a sampled quantity (e.g. per-dispatch
    speculative acceptance rate).

    The first observation seeds the value exactly; each later
    observation ``x`` at ``dt`` seconds since the previous one folds in
    with weight ``1 - w`` where ``w = 2^(-dt / half_life_s)`` — after
    one half-life of steady observations at ``x``, the value has moved
    half of the way to ``x``.
    """

    def __init__(
        self,
        half_life_s: float = 30.0,
        timer: Callable[[], float] = time.monotonic,
    ):
        if half_life_s <= 0:
            raise ValueError("half_life_s must be > 0")
        self.half_life_s = half_life_s
        self._timer = timer
        self._value: Optional[float] = None
        self._last_t: Optional[float] = None

    @property
    def value(self) -> float:
        return self._value if self._value is not None else 0.0

    @property
    def initialized(self) -> bool:
        return self._value is not None

    def update(self, x: float, now: Optional[float] = None) -> float:
        t = self._timer() if now is None else now
        if self._value is None or self._last_t is None:
            self._value = float(x)
        else:
            dt = max(0.0, t - self._last_t)
            w = 2.0 ** (-dt / self.half_life_s)
            self._value = w * self._value + (1.0 - w) * float(x)
        self._last_t = t
        return self._value
