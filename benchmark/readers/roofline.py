"""A kernel's share of its roofline: the work a call of it has to do (bytes
to move, or operations), over what the device could do in the seconds a
call took.

    share = (counted work a call) / (device seconds a call x peak)

Both sides are taken over the same calls: those of the traced seconds.
Seconds a call come from the reduced profiler trace: the device seconds
of the operations whose name matches ``match`` over their calls.  Work a
call comes from the program's own counters (``bytes`` and ``calls``),
read at the two ends of the capture's real span: ``trace_reduce.py``
places the first and the last device event on the wall clock
(``capture_wall``), ``run.py`` ``observe`` scrapes ``/metrics`` every
quarter of a second around the capture, each scrape with its
``time.time()`` (``fine_samples``), and a counter's value at an end is
the straight line between the scrapes on either side of it.  The peak is
the published one of the run's device, by ``device_kind``
(``peaks.json``: a device that is not there is an error, not a default).

What stays inexact: the counters rise by whole plans, when a plan is
dispatched or when its counts come home, and the trace holds a plan's
calls as they run, up to one fused wave apart.  Both counters jump
together, so only the edge waves' work a call can differ from the
mean's: runs of one code read the state update's share within 1 % of one
another and the grouped matmul's within 2 % (PERF.md section 6, PR 44).

``ssm_update_bytes`` is what the counter counts, from the model's
shapes: the float32 scan state of a row's layer, read and written once.
The kernel's other operands (x, B, C, dt: ~20 KB a row) are left out,
so the share errs low.  A share over 100 % is a fault of the count.

Where the capture cannot be placed (no fine scrape on both sides of an
end, no device event, a trace without its session's start), where the
two clocks cannot be one (the counter counted under half or over twice
the calls the trace holds in those seconds), where the program has no
such counter (a parent before the kernel) or the trace no such
operation, there is nothing to read: None, never a share from other
seconds.
"""

import json
import re
from pathlib import Path

import prom

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"
#: counted calls over traced calls of one span; outside it the span read
#: from the counters is not the span that was traced
SAME_CALLS = (0.5, 2.0)


def ssm_update_bytes(hf_config: dict, rows: int = 1) -> int:
    """State bytes one call of the one-token update moves for ``rows``
    live rows of ONE layer: ``[heads, state, head]`` float32, in and out."""
    return (2 * rows * hf_config["mamba_n_heads"] * hf_config["mamba_d_state"]
            * hf_config["mamba_d_head"] * 4)


def peak(device_kind: str, which: str) -> float:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS}")
    return table[device_kind][which]


def value_at(fine: list, instant: float, series: str):
    """``series`` at ``instant`` on the straight line between the fine
    scrapes on either side of it (``[(wall time, scrape)]`` in order of
    time; a series not there yet started from 0).  None outside them."""
    for (t0, before), (t1, after) in zip(fine, fine[1:]):
        if t0 <= instant <= t1:
            step = prom.delta(before, after, series)
            if step is None:
                return None
            gone = (instant - t0) / (t1 - t0) if t1 > t0 else 0.0
            return prom.total(after, series) - step * (1.0 - gone)
    return None


def rise(fine: list, span: list, series: str):
    """How far ``series`` rose between the two instants of ``span``; None
    where either lies outside the fine scrapes."""
    first, last = (value_at(fine, t, series) for t in span)
    if first is None or last is None:
        return None
    return last - first


def read(params: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("device_planes"):
        return None
    names = [n for n in trace["op_seconds"] if re.search(params["match"], n)]
    calls = sum(trace["op_calls"].get(n, 0) for n in names)
    seconds = sum(trace["op_seconds"][n] for n in names)
    span, fine = trace.get("capture_wall"), ctx.get("fine_samples") or []
    if not calls or seconds <= 0 or not span:
        return None
    work = rise(fine, span, params["bytes"])
    counted = rise(fine, span, params["calls"])
    if not work or not counted or not (
            SAME_CALLS[0] * calls <= counted <= SAME_CALLS[1] * calls):
        return None
    least_s = work / counted / peak(ctx["device_kind"], params["peak"])
    return least_s / (seconds / calls) * 100.0
