"""The cell's traffic: a fixed sample, replayed.  The same requests at the
same distances in every window of every seed, begun at another one."""

import itertools
import json
import math
import statistics
from pathlib import Path

import traffic

BENCH = Path(__file__).resolve().parent.parent
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELL = json.loads(
    (BENCH / "cells" / "granite2b.short-answer.json").read_text())
MIX = traffic.load_traffic("short-answer")
N, RATE = MIX["replay"]["requests"], CELL["rate_rps"]
PERIOD_S = N / RATE
BIG_SEED = 2**31 + 11


def inside(plan, start, seconds=PERIOD_S):
    return [p for p in plan if start <= p.due < start + seconds]


def sizes_in_order(requests):
    return [(p.prompt_tokens, p.output_tokens) for p in requests]


def rotations(seq):
    return [seq[i:] + seq[:i] for i in range(len(seq))]


def test_the_file_says_what_it_is():
    who = MIX["who"].lower()
    assert "replay" in who and "fixed sample" in who
    # the rehearsal keeps the drawn process exercised
    assert "replay" not in traffic.load_traffic("rehearsal-open")


def test_one_period_is_one_window():
    assert math.isclose(PERIOD_S, SPEC["run_seconds"])


def test_any_window_of_any_seed_holds_the_period_begun_elsewhere():
    a = traffic.open_schedule(MIX, RATE, 3 * PERIOD_S, seed=BIG_SEED)
    b = traffic.open_schedule(MIX, RATE, 3 * PERIOD_S, seed=12)
    one = sizes_in_order(inside(a, 0))
    assert len(one) == N
    for plan, start in ((a, 7.3), (a, PERIOD_S + 0.1), (b, 0), (b, 31.9)):
        got = sizes_in_order(inside(plan, start))
        assert len(got) == N and got in rotations(one)
    assert sizes_in_order(inside(a, 0)) != sizes_in_order(inside(b, 0))
    assert all(x.due != y.due for x, y in zip(a, b))
    assert all(x.due <= y.due for x, y in zip(a, a[1:]))
    # the same distances: a period later, the same request
    for x, y in zip(a, a[N:]):
        assert math.isclose(y.due - x.due, PERIOD_S, abs_tol=1e-9)
        assert sizes_in_order([x]) == sizes_in_order([y])


def test_the_same_seed_gives_the_same_schedule():
    assert traffic.open_schedule(MIX, RATE, 90, seed=BIG_SEED) == (
        traffic.open_schedule(MIX, RATE, 90, seed=BIG_SEED))


def test_another_rate_is_the_same_sample_at_another_pace():
    slow = traffic.open_schedule(MIX, RATE, PERIOD_S, seed=5)
    fast = traffic.open_schedule(MIX, 2 * RATE, PERIOD_S / 2, seed=5)
    assert sizes_in_order(slow) == sizes_in_order(fast)
    for x, y in zip(slow, fast):
        assert math.isclose(x.due, 2 * y.due, abs_tol=1e-9)


def test_lengths_are_clipped_as_before():
    plan = traffic.open_schedule(MIX, RATE, PERIOD_S, seed=3)
    assert all(32 <= p.prompt_tokens <= 1536 for p in plan)
    assert all(8 <= p.output_tokens <= 64 for p in plan)


def clipped_mean(spec: dict, steps: int = 20000) -> float:
    normal = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
    return statistics.fmean(
        min(spec["max"], max(spec["min"], round(math.exp(
            normal.inv_cdf((i + 0.5) / steps))))) for i in range(steps))


def test_the_replayed_seed_is_the_one_the_rule_gives():
    """The smallest seed whose N prompts and N answers each sum to within
    2 % of N times the clipped distributions' means: a rule on the draw
    alone, no reading of any run in it (PERF.md section 4)."""
    want = {k: N * clipped_mean(MIX[k])
            for k in ("prompt_tokens", "output_tokens")}

    def fits(seed: int) -> bool:
        one = list(itertools.islice(traffic._stream(MIX, RATE, seed), N))
        return all(abs(sum(getattr(p, k) for p in one) / want[k] - 1) <= 0.02
                   for k in want)

    assert next(s for s in itertools.count() if fits(s)) == (
        MIX["replay"]["seed"])
