"""A kernel's share of its memory roofline: the bytes a call of it has to
move, over what the device could move in the seconds a call took.

    share = (bytes a call) / (device seconds a call x peak bytes a second)

Seconds a call come from the reduced profiler trace: the device seconds
of the operations whose name matches ``match`` over their calls.  Bytes a
call come from the program's own counters (``bytes`` and ``calls``), as
differences across the once-a-second scrapes that bracket the traced
span, so that both sides are averages over calls of the same seconds:
sample ``k`` of a window is scraped ``k + 1`` s after its start and the
profiler client is started ``profile_after_s`` after it (``run.py``), and
the capture itself begins a few seconds after that, so the bracket runs
from ``lead_s`` before the client's start to ``lag_s`` after the span's
recorded end.  The peak is the published one of the run's device, by
``device_kind`` (``peaks.json``: a device that is not there is an error,
not a default).

``ssm_update_bytes`` is what the counter counts, from the model's
shapes: the float32 scan state of a row's layer, read and written once.
The kernel's other operands (x, B, C, dt: ~20 KB a row) are left out,
so the share errs low.  A share over 100 % is a fault of the count.

Where the program has no such counter (a parent before the kernel) or
the trace no such operation, there is nothing to read: None.
"""

import json
import re
from pathlib import Path

import prom

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


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


def _bracket(params: dict, ctx: dict):
    """The scrapes at both ends of the seconds that hold the trace."""
    samples = ctx.get("samples") or []
    span = ctx.get("trace_span")
    if not samples or not span:
        return None
    first = params["profile_after_s"] - params["lead_s"]
    last = params["profile_after_s"] + (span[1] - span[0]) + params["lag_s"]
    lo = min(len(samples) - 1, max(0, int(first) - 1))
    hi = min(len(samples) - 1, max(lo + 1, int(last + 0.999) - 1))
    return (samples[lo], samples[hi]) if hi > lo else None


def read(params: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("device_planes"):
        return None
    names = [n for n in trace["op_seconds"] if re.search(params["match"], n)]
    calls = sum(trace["op_calls"].get(n, 0) for n in names)
    seconds = sum(trace["op_seconds"][n] for n in names)
    ends = _bracket(params, ctx)
    if not calls or seconds <= 0 or ends is None:
        return None
    moved = prom.delta(*ends, params["bytes"])
    counted = prom.delta(*ends, params["calls"])
    if not moved or not counted:
        return None
    per_call = moved / counted
    least_s = per_call / peak(ctx["device_kind"], params["peak"])
    return least_s / (seconds / calls) * 100.0
