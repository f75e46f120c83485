"""From the reduced profiler trace of a traced run (``trace_reduce.py``).

params ``stat``:
- ``idle_pct``: 1 - device busy / traced window;
- ``op_share_pct``: device seconds of the operations whose name matches
  the regex ``match`` / device busy seconds."""

import re


def _matching_seconds(trace: dict, pattern: str) -> float:
    return sum(
        s for name, s in trace["op_seconds"].items() if re.search(pattern, name)
    )


def read(params: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("device_planes") or trace["busy_s"] <= 0:
        return None
    stat = params["stat"]
    if stat == "idle_pct":
        return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
    seconds = _matching_seconds(trace, params["match"])
    if seconds <= 0:
        return None  # no operation of that name in this trace
    if stat == "op_share_pct":
        return seconds / trace["busy_s"] * 100.0
    raise ValueError(f"trace reader: unknown stat {stat!r}")
