"""The generator: same seed, same schedule; another seed, another draw."""

import pytest

import traffic

MIXES = ["short-answer", "doc-extract", "rehearsal-open", "rehearsal-closed"]
BIG_SEED = 2**31 + 11  # the driver's seeds do not fit 32 signed bits


def drawn_short_answer() -> dict:
    """``short-answer`` as a process, a draw a seed: the file without its
    ``replay`` key (what the cell itself sends: ``test_replay.py``)."""
    mix = traffic.load_traffic("short-answer")
    return {k: v for k, v in mix.items() if k != "replay"}


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_their_clips(name):
    mix = traffic.load_traffic(name)
    for p, o in traffic.sizes(mix, 500, seed=3):
        assert mix["prompt_tokens"]["min"] <= p <= mix["prompt_tokens"]["max"]
        assert mix["output_tokens"]["min"] <= o <= mix["output_tokens"]["max"]


def test_same_seed_same_schedule_and_another_seed_another_draw():
    mix = drawn_short_answer()
    a = traffic.open_schedule(mix, 1.6, 120, seed=BIG_SEED)
    b = traffic.open_schedule(mix, 1.6, 120, seed=BIG_SEED)
    c = traffic.open_schedule(mix, 1.6, 120, seed=11)
    assert a == b and a != c
    assert sorted(p.prompt_tokens for p in a) != sorted(
        p.prompt_tokens for p in c)  # a draw of its own, not a reordering
    assert 0 < a[0].due and all(x.due <= y.due for x, y in zip(a, a[1:]))
    assert a[-1].due < 120


def test_arrivals_are_poisson_at_the_rate():
    """Counts in 45 s windows: mean = variance = rate x 45."""
    mix = drawn_short_answer()
    plan = traffic.open_schedule(mix, 1.6, 45 * 400, seed=5)
    counts = [0] * 400
    for p in plan:
        counts[int(p.due // 45)] += 1
    mean = sum(counts) / 400
    var = sum((c - mean) ** 2 for c in counts) / 399
    assert 70 <= mean <= 74
    assert 0.8 <= var / mean <= 1.25


def test_a_change_of_rate_keeps_the_sizes():
    mix = drawn_short_answer()
    slow = traffic.open_schedule(mix, 1.0, 60, seed=9)
    fast = traffic.open_schedule(mix, 2.0, 60, seed=9)
    n = min(len(slow), len(fast))
    assert [(p.prompt_tokens, p.output_tokens) for p in slow[:n]] == [
        (p.prompt_tokens, p.output_tokens) for p in fast[:n]]


def test_median_lengths_are_what_the_file_says_and_the_clips_are_reached():
    mix = drawn_short_answer()
    got = traffic.sizes(mix, 4000, seed=0)
    prompts = sorted(p for p, _ in got)
    outs = sorted(o for _, o in got)
    assert 240 <= prompts[2000] <= 272
    assert 23 <= outs[2000] <= 25
    assert prompts[0] == 32 and prompts[-1] == 1536
    assert outs[0] == 8 and outs[-1] == 64


def test_an_unknown_kind_is_a_missing_file():
    mix = dict(drawn_short_answer(), arrivals="no-such")
    with pytest.raises(ImportError):
        traffic.open_schedule(mix, 1.0, 10, seed=1)
