"""What the load generator itself saw.

params ``stat``:
- ``e2e``: any name ``e2e.compute`` can parse (``name``), over the sample;
- ``gauge_ratio_peak``: the highest ``numerator / denominator`` of two
  gauges over the once-a-second ``/metrics`` samples of the window;
- ``gauge_mean``: the mean of ``gauge`` over those samples (for a gauge
  that holds the value of the most recent dispatch)."""

import e2e
import prom


def read(params: dict, ctx: dict):
    if params["stat"] == "e2e":
        return e2e.compute(params["name"], ctx["records"], ctx["window_s"])
    if params["stat"] == "gauge_ratio_peak":
        ratios = []
        for scrape in ctx.get("samples") or []:
            num = prom.total(scrape, params["numerator"])
            den = prom.total(scrape, params["denominator"])
            if num is not None and den:
                ratios.append(num / den)
        if not ratios:
            return None
        return max(ratios) * params.get("scale", 1.0)
    if params["stat"] == "gauge_mean":
        values = [
            v for scrape in ctx.get("samples") or []
            if (v := prom.total(scrape, params["gauge"], params.get("match")))
            is not None
        ]
        if not values:
            return None
        return sum(values) / len(values) * params.get("scale", 1.0)
    raise ValueError(f"client reader: unknown stat {params['stat']!r}")
