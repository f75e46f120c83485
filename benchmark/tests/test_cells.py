"""The committed data files hang together, and the CPU rehearsal cells
still print a result line (each starts the real server on the CPU: about
a minute a cell)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

import traffic

BENCH = Path(__file__).resolve().parent.parent
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def cell_file(name: str) -> dict:
    return json.loads((BENCH / "cells" / f"{name}.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_a_listed_cell_has_its_files_and_they_parse(cell):
    listed = next(w for w in SPEC["workloads"] if w["name"] == cell)
    spec = cell_file(cell)
    assert spec["config"] == listed["config"]
    assert spec["traffic"] == listed["traffic"]
    assert spec["chips"] == listed["chips"]
    assert (BENCH / "configs" / f"{spec['config']}.json").exists()
    # a 2 s capture stays far from the profiler client's 60 s
    assert 0 < spec["trace_s"] <= 2
    assert spec["rate_rps"] > 0 and spec["drain_limit_s"] > 0
    assert spec["warmup"]["quiet_s"] > 0
    for step in spec["warmup"]["script"]:
        assert step["count"] >= 1 and step["wait"] in ("first_token", "done")
        assert step["prompt_tokens"] + step["output_tokens"] <= 4096
    mix = traffic.load_traffic(spec["traffic"])
    plan = traffic.open_schedule(
        mix, spec["rate_rps"], SPEC["run_seconds"], seed=2**31 + 5)
    assert abs(len(plan) - spec["rate_rps"] * SPEC["run_seconds"]) <= (
        4 * (spec["rate_rps"] * SPEC["run_seconds"]) ** 0.5)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_a_listed_layer_metric_has_a_file_that_says_the_same(metric):
    listed = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    spec = json.loads(
        (BENCH / "layer_metrics" / f"{metric}.json").read_text())
    for key in ("name", "unit", "better", "source", "layer"):
        assert spec[key] == listed[key], key
    assert (BENCH / "readers" / f"{spec['reader']}.py").exists()
    assert listed["moves"] in {m["name"] for m in SPEC["end_to_end"]}


@pytest.mark.parametrize("cell", ["rehearsal.open", "rehearsal.closed"])
@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_cell_still_prints_a_line(cell, trace, tmp_path):
    done = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", str(2**31 + 17), "--seconds", "10", "--trace", str(trace),
         "--rehearse-on-cpu", "--log-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=BENCH.parent)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    if trace:
        assert "gen_late_p99_ms" in line["metrics"]
        assert "device_idle_pct" not in line["metrics"]  # no device here
        # the once-a-second samples are what they were (1-9 s into a window
        # of 10), and the fine scrapes lie around the capture and hold it
        said = [json.loads(text) for text in done.stdout.splitlines()
                if text.startswith('{"phase": "trace"')][-1]["capture"]
        assert said["samples"] == 9 and 36 <= said["fine"] <= 40
        first, last = said["fine_after_client_s"]
        begins, ends = said["capture_after_client_s"]
        assert 0 <= first <= begins < ends <= last <= 10.5
        assert [a["wrong"] for a in said["attempts"]] == [None]
    else:
        assert set(line["metrics"]) == {"gap_p99_ms", "setup_s"}
