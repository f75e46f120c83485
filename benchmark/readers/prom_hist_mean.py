"""Mean of a Prometheus histogram over the window: the difference of its
``_sum`` over the difference of its ``_count`` between the two scrapes.

params: ``histogram`` (base name), ``match`` (labels, optional),
``scale`` (e.g. 1000 for seconds -> ms, 100 for a ratio -> %)."""

import prom


def read(params: dict, ctx: dict):
    if ctx.get("scrape0") is None or ctx.get("scrape1") is None:
        return None
    args = (ctx["scrape0"], ctx["scrape1"])
    match = params.get("match")
    count = prom.delta(*args, params["histogram"] + "_count", match)
    total = prom.delta(*args, params["histogram"] + "_sum", match)
    if not count or total is None:
        return None  # nothing observed in the window: nothing to report
    return total / count * params.get("scale", 1.0)
