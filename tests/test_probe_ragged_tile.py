"""The chip probe of the attention tile (tools/probe_ragged_tile.py)
stays runnable: PR 35's probe was lost with its scratch directory, and a
committed one that no longer meets the kernel's signature would be lost
the same way.  Here ``probe`` runs the kernel in interpret mode at a
tiny size, which is a rehearsal and no measurement: the script itself
refuses to print a time off the chip."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools import probe_ragged_tile as probe  # noqa: E402


@pytest.mark.parametrize("shape,chunk,stats", [
    pytest.param("2,8,64,0,128", 16, "lanes",
                 id="pages-of-128-lane-padded"),
    pytest.param("2,8,128,200,16", 16, "lanes",
                 id="runs-of-8-pages-under-a-band"),
    pytest.param("8,16,128,0,128", 16, "sublanes",
                 id="128-rows-statistics-on-sublanes"),
    pytest.param("2,8,128,0,16", 1, "lanes", id="a-wave-of-one-token-rows"),
    pytest.param("16,8,128,200,128", 1, "sublanes",
                 id="a-wave-of-128-row-tiles-under-a-band"),
])
def test_probe_times_a_computed_and_a_dead_step_of_each_shape(
        shape, chunk, stats):
    line = probe.probe(shape, items=24, kv_heads=2, chunk=chunk, depth=300,
                       repeats=1)
    g, block_q, *_ = map(int, shape.split(","))
    assert line["platform"] == "cpu"  # every line says where it ran
    assert line["shape"] == shape
    assert line["rows_a_tile"] == g * block_q
    assert line["stats"] == stats
    # two matmuls over a tile of 128 cached tokens at a lane-padded width
    assert line["tile_mflop"] == round(4e-6 * g * block_q * 128 * 128, 1)
    assert 0 < line["items"] <= 24
    for label in ("computed", "dead"):
        assert line[f"{label}_us_item"] >= line[f"{label}_us_item_min"] > 0


def test_probe_measures_nothing_off_the_chip(capsys):
    """The next attention PR is gated on this probe's microseconds
    (PERF.md section 7a): with no TPU it names the platform and exits 1
    before a kernel is built, so no interpret-mode time can pass for
    one."""
    assert probe.main(["--items", "24", "--repeats", "1"]) == 1
    out, err = capsys.readouterr()
    (device,) = map(json.loads, out.splitlines())
    assert device["platform"] == "cpu"
    assert "no TPU" in err
