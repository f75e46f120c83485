"""Metric arithmetic on hand-made frame times."""

import pytest

import e2e
from e2e import Record


def record(due, times, *, sample=True, ok=True, sent=None):
    r = Record(due, 10, len(times), in_sample=sample)
    r.sent = due if sent is None else sent
    r.token_times = list(times)
    r.ok = ok
    r.done = times[-1] if times else due
    return r


RECORDS = [
    # due 0, first token at 1.0: TTFT 1.0 s; 4 tokens over 3.0 s: TPOT 1.0 s
    record(0.0, [1.0, 1.0, 3.0, 4.0], sent=0.002),
    # due 10 but sent 0.5 s late: TTFT still from the due time, 2.0 s
    record(10.0, [12.0, 12.5, 13.0], sent=10.5),
    # failed after one token, given up at 21.0: counted by the caller as
    # failed, and in every tail as no better than the worst that finished
    record(20.0, [21.0], ok=False),
    # outside the sample
    record(30.0, [39.0, 40.0], sample=False),
]


def test_percentile_interpolates_between_closest_ranks():
    assert e2e.percentile([1, 2, 3, 4], 50) == 2.5
    assert e2e.percentile([10], 99) == 10
    assert e2e.percentile([0, 10], 90) == pytest.approx(9.0)
    with pytest.raises(ValueError):
        e2e.percentile([], 50)


FINISHED = [r for r in RECORDS if r.ok]


def test_ttft_starts_at_the_due_time():
    assert e2e.ttft_s(FINISHED) == [1.0, 2.0]
    assert e2e.compute("ttft_p50_ms", FINISHED, 45) == pytest.approx(1500.0)
    assert e2e.compute("ttft_p90_ms", FINISHED, 45) == pytest.approx(1900.0)


def test_tpot_is_per_request_and_gaps_are_pooled():
    assert e2e.tpot_s(FINISHED) == [1.0, 0.5]
    assert e2e.compute("tpot_p50_ms", FINISHED, 45) == pytest.approx(750.0)
    assert sorted(e2e.gaps_s(FINISHED)) == [0.0, 0.5, 0.5, 1.0, 2.0]
    assert e2e.compute("gap_p99_ms", FINISHED, 45) == pytest.approx(1960.0)


def test_a_failed_request_counts_as_the_worst_in_every_tail():
    # its own first token came after 1.0 s: no better than the worst (2.0)
    assert e2e.ttft_s(RECORDS) == [1.0, 2.0, 2.0]
    assert e2e.tpot_s(RECORDS) == [1.0, 0.5, 1.0]
    assert sorted(e2e.gaps_s(RECORDS))[-2:] == [2.0, 2.0]
    # given up 30 s after its only token, never having shown a second one
    cut = record(20.0, [21.0], ok=False)
    cut.done = 51.0
    assert e2e.ttft_s(FINISHED + [cut])[-1] == 2.0
    assert e2e.tpot_s(FINISHED + [cut])[-1] == 30.0
    assert e2e.gaps_s(FINISHED + [cut])[-1] == 30.0
    # refused before any token: its wait until then, or the worst
    refused = record(20.0, [], ok=False)
    refused.done = 20.1
    assert e2e.ttft_s(FINISHED + [refused])[-1] == 2.0
    refused.done = 80.0
    assert e2e.ttft_s(FINISHED + [refused])[-1] == 60.0
    assert e2e.tpot_s(FINISHED + [refused])[-1] == 1.0


def test_generator_lateness_and_throughput():
    assert e2e.late_s(RECORDS) == pytest.approx([0.002, 0.5, 0.0])
    # 7 tokens of the two finished sample requests over a 3.5 s window
    assert e2e.compute("out_tok_s", RECORDS, 3.5) == pytest.approx(2.0)


def test_nothing_to_take_a_metric_from_gives_none_and_bad_names_raise():
    assert e2e.compute("ttft_p90_ms", RECORDS[3:], 45) is None
    with pytest.raises(ValueError):
        e2e.compute("goodput_pct", RECORDS, 45)
