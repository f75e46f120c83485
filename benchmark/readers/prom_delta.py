"""Difference of counters between the two scrapes of the window, or the
ratio of two such differences.

params: ``numerator`` and optional ``denominator``: lists of
``{"series": ..., "match": {...}}`` whose differences are added up;
``scale``; ``absent_is_zero`` (a labelled counter that never fired has no
line at all)."""

import prom


def _sum(terms: list[dict], ctx: dict, absent_is_zero: bool):
    out = 0.0
    for term in terms:
        d = prom.delta(ctx["scrape0"], ctx["scrape1"], term["series"],
                       term.get("match"))
        if d is None:
            if not absent_is_zero:
                return None
            d = 0.0
        out += d
    return out


def read(params: dict, ctx: dict):
    if ctx.get("scrape0") is None or ctx.get("scrape1") is None:
        return None
    zero = params.get("absent_is_zero", False)
    value = _sum(params["numerator"], ctx, zero)
    if value is None:
        return None
    if "denominator" in params:
        den = _sum(params["denominator"], ctx, zero)
        if not den:
            return None
        value /= den
    return value * params.get("scale", 1.0)
