"""The trace reducer: exact numbers on hand-made planes, the real parser
on one small recorded trace, and the trace reader's three statistics."""

import json
from pathlib import Path

import pytest

import trace_reduce
from e2e import Record
from readers import trace as trace_reader

HERE = Path(__file__).resolve().parent
KERNEL = ('%closed_call.501 = bf16[8,256,128]{2,1,0} custom-call(s32[7,16384] '
          '%concatenate.1017), custom_call_target="tpu_custom_call", x={}')
FUSION = "%slice_bitcast_fusion.196.remat2 = bf16[8]{0} fusion(bf16[8] %p), kind=kLoop"
S = 1e9  # the planes are in nanoseconds


def planes():
    ops = [
        ("%while.10 = (s32[]) while(s32[] %x), body=%b", 0 * S, 4 * S),
        (KERNEL, 0.5 * S, 3.0 * S),           # nested in the while
        (KERNEL.replace("501", "502"), 6 * S, 1 * S),
        (FUSION, 7 * S, 1 * S),
    ]
    return [
        ("/host:CPU", [("python3", [("$run", -50 * S, 100 * S)])]),
        ("/device:TPU:0", [("XLA Modules", [("jit_step", 0, 8 * S)]),
                           ("XLA Ops", ops)]),
    ]


def test_names_are_cut_to_instruction_and_kind():
    assert trace_reduce.short_name(KERNEL) == (
        "closed_call custom-call:tpu_custom_call")
    assert trace_reduce.short_name(FUSION) == "slice_bitcast_fusion fusion"
    assert trace_reduce.short_name("PjitFunction(step)") == "PjitFunction(step)"


def test_busy_is_the_union_and_the_window_is_the_devices_own():
    got = trace_reduce.reduce_planes(planes())
    assert got["device_planes"] == ["/device:TPU:0"]
    # ops cover [0,4] + [6,8]: 6 s busy of the 8 s the device plane spans;
    # the host plane's 100 s do not count
    assert got["busy_s"] == pytest.approx(6.0)
    assert got["window_s"] == pytest.approx(8.0)
    assert got["op_seconds"]["closed_call custom-call:tpu_custom_call"] == (
        pytest.approx(4.0))
    assert got["idle_gaps"] == [
        ["before:closed_call custom-call:tpu_custom_call", pytest.approx(2.0)]]
    assert got["device_ops"][0][0] in ("while while",
                                       "closed_call custom-call:tpu_custom_call")


def test_the_readers_statistics():
    summary = trace_reduce.reduce_planes(planes())
    ctx = {"trace": summary}
    assert trace_reader.read({"stat": "idle_pct"}, ctx) == pytest.approx(25.0)
    share = trace_reader.read(
        {"stat": "op_share_pct", "match": "tpu_custom_call"}, ctx)
    assert share == pytest.approx(4.0 / 6.0 * 100)
    with pytest.raises(ValueError):
        trace_reader.read({"stat": "no_such_stat", "match": "."}, ctx)
    assert trace_reader.read(
        {"stat": "op_share_pct", "match": "no_such_kernel"}, ctx) is None
    assert trace_reader.read({"stat": "idle_pct"}, {"trace": None}) is None


def test_a_recorded_trace_goes_through_the_real_parser():
    """``data/cpu_small.xplane.pb``: three runs of one jitted function,
    recorded on the CPU with Python tracing off (8 KB); it has no TPU
    plane, so the host plane stands in for the device here."""
    raw, session_start_ns = trace_reduce.read_planes(
        HERE / "data" / "cpu_small.xplane.pb")
    assert "/host:CPU" in [name for name, _ in raw]
    assert trace_reduce.reduce_planes(raw)["device_planes"] == []
    got = trace_reduce.reduce_planes(raw, r"^/host:CPU$", session_start_ns)
    assert 0 < got["busy_s"] <= got["window_s"] < 1.0
    assert any("PjitFunction" in name for name in got["op_seconds"])
    # the file's Task Environment plane says when its session began, in
    # nanoseconds since the epoch, and the events count from there
    assert session_start_ns == 1790487859575495314
    first, last = got["capture_wall"]
    assert 0 < first - session_start_ns / 1e9 < 0.001
    assert last - first == pytest.approx(got["window_s"], abs=1e-6)


def test_the_capture_is_placed_on_the_wall_clock_or_not_at_all():
    """The device plane's events run from 0 to 8 s after the session's
    start; the host plane's 100 s do not count."""
    begun = 1_790_000_000 * 10**9
    got = trace_reduce.reduce_planes(planes(), session_start_ns=begun)
    assert got["capture_wall"] == pytest.approx([1.79e9, 1.79e9 + 8.0])
    assert trace_reduce.reduce_planes(planes())["capture_wall"] is None
    no_device = trace_reduce.reduce_planes(planes()[:1], session_start_ns=begun)
    assert no_device["capture_wall"] is None


def test_a_file_names_its_device_plane_in_plain_bytes(tmp_path):
    """What the load generator looks at while its window runs: a capture
    of seconds in which the engine stood still names the host's planes
    and the chip's other ones, and no ``/device:TPU:<n>``."""
    whole, empty = tmp_path / "whole.pb", tmp_path / "empty.pb"
    whole.write_bytes(b"\x0a\x15#Chip0 Host Interface\x0a\x0d/device:TPU:0"
                      b"\x12\x07XLA Ops\x0a\x09/host:CPU")
    empty.write_bytes(b"\x0a\x15#Chip0 Host Interface\x0a\x09/host:CPU"
                      b"\x0a\x1f/device:CUSTOM:Megascale Trace")
    assert trace_reduce.names_device_plane(whole)
    assert not trace_reduce.names_device_plane(empty)
    on_cpu = trace_reduce.plane_pattern(True)
    assert trace_reduce.names_device_plane(empty, on_cpu)
    assert trace_reduce.plane_pattern(False) == trace_reduce.DEVICE_PLANE

