"""Parse the text of a ``/metrics`` scrape, and take differences."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')

Scrape = dict  # (series name, frozenset of (label, value)) -> float


def parse(text: str) -> Scrape:
    out: Scrape = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line.strip())
        if not m:
            continue
        name, labels, value = m.groups()
        try:
            number = float(value)
        except ValueError:
            continue
        out[(name, frozenset(_LABEL.findall(labels or "")))] = number
    return out


def total(scrape: Scrape, series: str, match: dict | None = None):
    """Sum of ``series`` over every label set that ``match`` admits
    (label -> value or list of values); None when no such line exists."""
    found = None
    for (name, labels), value in scrape.items():
        if name != series:
            continue
        have = dict(labels)
        if all(
            have.get(k) in (v if isinstance(v, list) else [v])
            for k, v in (match or {}).items()
        ):
            found = (found or 0.0) + value
    return found


def delta(before: Scrape, after: Scrape, series: str,
          match: dict | None = None):
    """``series`` at the later scrape minus the earlier; a series that is
    only in the later one started from 0.  None when it is in neither."""
    b = total(after, series, match)
    if b is None:
        return None
    return b - (total(before, series, match) or 0.0)
