"""The scrape-difference readers on two saved ``/metrics`` texts, through
the per-layer metric files themselves."""

import importlib
import json
from pathlib import Path

import pytest

import prom

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
