"""The reader of a kernel's share of its roofline, guarded in tier 1
(``benchmark/tests`` is outside the tier-1 command): the cases of
``benchmark/tests/test_readers.py`` that need no trace file, copied as
they stand there.  One reading of this reader, 105.3 % where the same
code read 66-94 elsewhere, refused PR 41's gain; since PR 44 both sides
of the division are cut at the capture's own two ends.

Imports no JAX; ``benchmark/`` goes on the path as
``tests/test_benchmark_families.py`` puts it.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
# LAST on the path: ``benchmark/tests`` must not shadow this directory as
# ``tests`` for the test files this worker imports next
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))

import prom  # noqa: E402
from readers import roofline  # noqa: E402

# -- a kernel's share of its roofline: the counters at the capture's ends --

ROOFLINE = {"match": "ssm_decode_update", "peak": "hbm_bytes_per_s",
            "bytes": "state_bytes_total", "calls": "update_calls_total"}
PER_ROW = 8 << 20  # bytes a live row a call
PEAK = roofline.peak("TPU v5 lite", "hbm_bytes_per_s")
KERNEL = "ssm_decode_update custom-call:tpu_custom_call"
WINDOW_AT = 1000.0  # the window's start on the wall clock


def counters(calls: float, work: float) -> dict:
    return {("update_calls_total", frozenset()): calls,
            ("state_bytes_total", frozenset()): work}


def traced(calls: int, seconds: float, span) -> dict:
    return {"device_planes": ["/device:TPU:0"], "busy_s": 1.0,
            "capture_wall": span, "op_seconds": {KERNEL: seconds},
            "op_calls": {KERNEL: calls}}


def parents_reading(samples: list, trace: dict, trace_s: float = 2.0):
    """The arithmetic this reader had until PR 44: the counters' differences
    between the once-a-second scrapes 1 s before the profiler client is
    started (3 s into the window) and 6 s after the seconds it asks for;
    sample ``k`` is scraped ``k + 1`` s into the window."""
    lo, hi = int(3 - 1) - 1, int(3 + trace_s + 6 + 0.999) - 1
    work = prom.delta(samples[lo], samples[hi], ROOFLINE["bytes"])
    calls = prom.delta(samples[lo], samples[hi], ROOFLINE["calls"])
    a_call = trace["op_seconds"][KERNEL] / trace["op_calls"][KERNEL]
    return work / calls / PEAK / a_call * 100.0


def test_rows_that_double_outside_the_capture_do_not_reach_the_share():
    """300 calls a second; 10 live rows a call until 6.5 s into the window
    and 20 from then on; the capture lies in seconds 7-9 and the kernel
    runs at 80 % of the bandwidth.  The nine seconds the parent counted
    (2-11) hold both halves: it read three quarters of the truth."""
    def at(t: float) -> dict:  # the counters t seconds into the window
        work = 300 * PER_ROW * (10 * min(t, 6.5) + 20 * max(0.0, t - 6.5))
        return counters(300 * t, work)

    a_call = 20 * PER_ROW / PEAK / 0.8
    trace = traced(600, 600 * a_call, [WINDOW_AT + 7.0, WINDOW_AT + 9.0])
    ctx = {"trace": trace, "device_kind": "TPU v5 lite",
           "samples": [at(k + 1.0) for k in range(44)],
           "fine_samples": [(WINDOW_AT + 3 + k / 4, at(3 + k / 4))
                            for k in range(28)]}
    assert roofline.read(ROOFLINE, ctx) == pytest.approx(80.0)
    assert parents_reading(ctx["samples"], trace) == pytest.approx(60.0)


def test_counters_that_jump_by_whole_plans_move_the_share_by_little():
    """A wave of 8 steps over 6 layers adds its 48 calls and their bytes
    when it is dispatched, and runs over the 0.2 s that follow; live rows
    swing between 26 and 33 from wave to wave; the capture's ends fall
    inside waves.  The kernel runs at 80 % of the bandwidth in every
    call, and the reading stays within 2 % of that."""
    rows = [26 + (7 * i * i + 3 * i) % 8 for i in range(60)]

    def at(t: float) -> dict:  # the plans dispatched by t
        done = [r for i, r in enumerate(rows) if 0.2 * i <= t]
        return counters(48 * len(done), 48 * PER_ROW * sum(done))

    for first, last in ((7.07, 9.13), (5.31, 7.36), (6.19, 8.2)):
        calls, seconds = 0.0, 0.0
        for i, r in enumerate(rows):  # the part of each wave that ran inside
            inside = max(0.0, min(last, 0.2 * i + 0.2) - max(first, 0.2 * i))
            calls += 48 * inside / 0.2
            seconds += 48 * inside / 0.2 * r * PER_ROW / PEAK / 0.8
        ctx = {"trace": traced(round(calls), seconds * round(calls) / calls,
                               [WINDOW_AT + first, WINDOW_AT + last]),
               "device_kind": "TPU v5 lite",
               "fine_samples": [(WINDOW_AT + 3 + k / 4, at(3 + k / 4))
                                for k in range(28)]}
        assert roofline.read(ROOFLINE, ctx) == pytest.approx(80.0, rel=0.02)


@pytest.mark.parametrize("fault", [
    "no fine scrape", "a trace without its session's start",
    "a capture that begins before the first fine scrape",
    "a capture that ends after the last fine scrape",
    "counters that counted a third of the traced calls",
    "counters that counted three times the traced calls",
    "a program without the counters"])
def test_a_capture_that_cannot_be_placed_gives_no_share(fault):
    def fine(rate=300.0, first=3.0, n=28):
        return [(WINDOW_AT + first + k / 4,
                 counters(rate * (first + k / 4),
                          rate * (first + k / 4) * 20 * PER_ROW))
                for k in range(n)]

    span = [WINDOW_AT + 5.0, WINDOW_AT + 7.0]
    ctx = {"trace": traced(600, 600 * 20 * PER_ROW / PEAK / 0.8, span),
           "device_kind": "TPU v5 lite", "fine_samples": fine()}
    assert roofline.read(ROOFLINE, ctx) == pytest.approx(80.0)
    ctx |= {
        "no fine scrape": {"fine_samples": []},
        "a trace without its session's start": {
            "trace": {**ctx["trace"], "capture_wall": None}},
        "a capture that begins before the first fine scrape": {
            "fine_samples": fine(first=5.25)},
        "a capture that ends after the last fine scrape": {
            "fine_samples": fine(n=15)},
        "counters that counted a third of the traced calls": {
            "fine_samples": fine(rate=100.0)},
        "counters that counted three times the traced calls": {
            "fine_samples": fine(rate=900.0)},
        "a program without the counters": {
            "fine_samples": [(t, {}) for t, _ in fine()]},
    }[fault]
    assert roofline.read(ROOFLINE, ctx) is None
