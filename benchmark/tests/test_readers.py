"""The scrape-difference readers on two saved ``/metrics`` texts, through
the per-layer metric files themselves; the roofline reader on made-up
counters and a made-up trace summary; and what a traced window scrapes."""

import asyncio
import importlib
import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

import prom
import run
from readers import roofline

HERE = Path(__file__).resolve().parent
BEFORE = prom.parse((HERE / "data" / "metrics_before.txt").read_text())
AFTER = prom.parse((HERE / "data" / "metrics_after.txt").read_text())
CTX = {"scrape0": BEFORE, "scrape1": AFTER, "samples": [BEFORE, AFTER],
       "records": [], "window_s": 45.0}


def read(metric: str, ctx=CTX):
    spec = json.loads(
        (HERE.parent / "layer_metrics" / f"{metric}.json").read_text())
    reader = importlib.import_module(f"readers.{spec['reader']}")
    return reader.read(spec["params"], ctx)


def test_parse_keeps_labels_apart():
    assert prom.total(BEFORE, "tgis_tpu_xla_recompile_total") == 3.0
    assert prom.total(BEFORE, "tgis_tpu_xla_recompile_total",
                      {"fn": "ragged_step"}) == 1.0
    assert prom.total(BEFORE, "no_such_series") is None


def test_histogram_mean_is_the_difference_of_sums_over_counts():
    # (45 - 5) s over (30 - 10) requests = 2 s
    assert read("queue_wait_mean_ms") == pytest.approx(2000.0)
    # a histogram the path never fed is left out, not reported as 0
    assert read("decode_plan_ms") is None


def test_step_host_share_is_four_phases_of_six():
    # plan 1 + prepare 2 + dispatch 2 + commit 1 = 6 of 6 + 40 + 4 = 50
    assert read("step_host_pct") == pytest.approx(12.0)


def test_counters_count_a_series_that_appeared_inside_the_window():
    assert read("compiles_in_window") == 1.0
    assert read("preemptions") == 3.0


def test_gauges_sampled_over_the_window():
    assert read("kv_used_peak_pct") == pytest.approx(75.0)
    assert read("decode_fill_pct") == pytest.approx(50.0)  # one sample has it
    assert read("ragged_fill_pct") is None


def test_without_scrapes_the_readers_return_nothing():
    empty = {"scrape0": None, "scrape1": None, "samples": []}
    assert read("queue_wait_mean_ms", empty) is None
    assert read("preemptions", empty) is None
    assert read("kv_used_peak_pct", empty) is None


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


class _Server:
    """Answers every scrape with the instant it was asked."""

    profiler_port = 1

    def __init__(self, began: float):
        self.began = began

    def get_text(self, path):
        return f"asked_at {time.monotonic() - self.began}\n"


def _asked(scrape: dict) -> float:
    return prom.total(scrape, "asked_at")


def _profiler_clients(monkeypatch, tmp_path, files: list):
    """Replace the profiler client by children that do nothing but write,
    one after the other, the bytes of ``files`` as their ``xplane.pb``
    (None: exit with code 1 and write nothing)."""
    spawned = []

    class Child:
        def __init__(self, content):
            self.content = content
            self.returncode = 0 if content is not None else 1

        async def communicate(self):
            await asyncio.sleep(0.2)
            if self.content is not None:
                tmp_path.mkdir(exist_ok=True)  # as the real client does
                (tmp_path / f"{len(spawned)}.xplane.pb").write_bytes(
                    self.content)
            return b"said", None

    async def spawn(*args, **kwargs):
        spawned.append(time.monotonic())
        return Child(files[len(spawned) - 1])

    monkeypatch.setattr(run.asyncio, "create_subprocess_exec", spawn)
    monkeypatch.setattr(run, "PROFILE_AFTER_S", 1.0)
    monkeypatch.setattr(run, "FINE_AFTER_S", 1.0)
    monkeypatch.setattr(run, "CLIENT_UP_S", 0.5)
    return spawned


WHOLE = b"\x0a\x0d/device:TPU:0 and its events"
EMPTY = b"\x0a\x09/host:CPU \x0a\x18/device:CUSTOM:Megascale Trace"


def test_a_traced_window_keeps_its_samples_and_scrapes_finely_only_around_the_capture(
        monkeypatch, tmp_path):
    """``observe`` over a window of 4 s against a server that answers every
    scrape with the instant it was asked, the profiler client replaced by
    a child that writes a file naming a device plane: the once-a-second
    list is what it was (three scrapes, 1, 2 and 3 s in) and the fine list
    lies between the client's start and ``FINE_AFTER_S`` past the seconds
    it asks for."""
    began = time.monotonic()
    _profiler_clients(monkeypatch, tmp_path, [WHOLE])
    session = SimpleNamespace(server=_Server(began), profile_dir=tmp_path,
                              on_cpu=False)

    seen: dict = {}
    asyncio.run(run.observe(session, began, began + 4.0, True, 0.5, seen))
    assert [_asked(s) for s in seen["samples"]] == pytest.approx(
        [1.0, 2.0, 3.0], abs=0.1)
    fine = seen["fine_samples"]
    assert [_asked(s) for _, s in fine] == pytest.approx(
        [1.0, 1.25, 1.5, 1.75, 2.0, 2.25], abs=0.1)
    # each with the wall clock's reading of the instant it was asked
    offset = time.time() - time.monotonic()
    assert [t - offset - began for t, _ in fine] == pytest.approx(
        [_asked(s) for _, s in fine], abs=0.05)
    assert 0 <= _asked(seen["scrape0"]) < 0.1
    assert _asked(seen["scrape1"]) == pytest.approx(4.0, abs=0.1)
    assert [a["wrong"] for a in seen["attempts"]] == [None]

    untraced: dict = {}
    began = time.monotonic()
    session.server = _Server(began)
    asyncio.run(run.observe(session, began, began + 0.3, False, 0.5, untraced))
    assert set(untraced) == {"scrape0", "samples", "scrape1"}
    assert untraced["samples"] == []


def test_a_capture_that_comes_back_empty_is_made_again_inside_the_window(
        monkeypatch, tmp_path):
    """The first client fails, the second writes a file with no device
    plane (the engine stood still), the third a whole one: the fine scrapes
    are the third's own, the once-a-second samples and the scrape at the
    window's end are where they always were."""
    began = time.monotonic()
    spawned = _profiler_clients(monkeypatch, tmp_path, [None, EMPTY, WHOLE])
    session = SimpleNamespace(server=_Server(began), profile_dir=tmp_path,
                              on_cpu=False)
    seen: dict = {}
    asyncio.run(run.observe(session, began, began + 4.0, True, 0.5, seen))
    assert len(spawned) == 3
    said = [a["wrong"] for a in seen["attempts"]]
    assert "collect_profile failed" in said[0] and "no device plane" in said[1]
    assert said[2] is None
    third = spawned[2] - began
    fine = [_asked(s) for _, s in seen["fine_samples"]]
    assert fine[0] == pytest.approx(third, abs=0.1)
    assert fine[-1] == pytest.approx(third + 1.25, abs=0.15)
    assert [_asked(s) for s in seen["samples"]] == pytest.approx(
        [1.0, 2.0, 3.0], abs=0.1)
    assert _asked(seen["scrape1"]) == pytest.approx(4.0, abs=0.1)
    assert list(tmp_path.rglob("*.xplane.pb")) == [tmp_path / "3.xplane.pb"]


def test_a_window_whose_every_capture_is_empty_fails_the_run(
        monkeypatch, tmp_path):
    """No capture is begun that could not end inside the window, and a
    traced run without one says what each attempt met."""
    began = time.monotonic()
    spawned = _profiler_clients(monkeypatch, tmp_path, [EMPTY] * 50)
    session = SimpleNamespace(server=_Server(began), profile_dir=tmp_path,
                              on_cpu=False)
    with pytest.raises(RuntimeError, match="no device plane"):
        asyncio.run(run.observe(session, began, began + 2.5, True, 0.5, {}))
    assert spawned and spawned[-1] - began + 0.5 + 0.5 <= 2.5 + 0.1
