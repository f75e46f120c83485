"""End-to-end metric arithmetic over the client's records.

Every latency is on the client's clock and starts at the instant the
request was DUE, so a stall is charged to the requests it delayed.  A
name is parsed, not listed: ``ttft_p90_ms``, ``tpot_p50_ms``,
``gap_p99_ms`` with any percentile, ``out_tok_s``; a later cell may ask
for ``ttft_p95_ms`` without a change here.
"""

from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass
class Record:
    due: float
    prompt_tokens: int
    output_tokens: int
    in_sample: bool = False
    sent: float | None = None
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)  # (text, logprob)
    done: float | None = None
    ok: bool = False
    error: str | None = None


def frames(records: list[Record], t0: float) -> list[dict]:
    """The client's frames of every request, seconds from ``t0``: what a
    run or a sweep keeps when somebody has to look at which gaps make up
    a tail (``--keep-frames``)."""
    return [{"due": r.due - t0, "prompt_tokens": r.prompt_tokens,
             "output_tokens": r.output_tokens, "in_sample": r.in_sample,
             "ok": r.ok, "sent": None if r.sent is None else r.sent - t0,
             "done": None if r.done is None else r.done - t0,
             "token_times": [t - t0 for t in r.token_times]}
            for r in records]


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def finished(records: list[Record]) -> list[Record]:
    return [r for r in records if r.in_sample and r.ok]


def _series(records: list[Record], of_finished, of_failed) -> list[float]:
    """``of_finished(r)`` (a list) over the sample's finished requests; a
    failed one (refused, malformed, cut at the drain limit) adds ONE value:
    what it had shown when it was given up (``of_failed(r)``, or nothing),
    and no less than the worst finished value.  So a tail never gets
    better by losing its slowest requests."""
    values = [v for r in finished(records) for v in of_finished(r)]
    worst = max(values, default=0.0)
    for r in records:
        if r.in_sample and not r.ok:
            values.append(max([worst, *of_failed(r)]))
    return values


def _end(r: Record) -> float:
    return r.done if r.done is not None else r.due


def ttft_s(records: list[Record]) -> list[float]:
    return _series(
        records,
        lambda r: [r.token_times[0] - r.due],
        lambda r: [(r.token_times[0] if r.token_times else _end(r)) - r.due],
    )


def tpot_s(records: list[Record]) -> list[float]:
    return _series(
        records,
        lambda r: ([(r.token_times[-1] - r.token_times[0])
                    / (len(r.token_times) - 1)]
                   if len(r.token_times) > 1 else []),
        lambda r: ([(_end(r) - r.token_times[0])
                    / max(1, len(r.token_times) - 1)]
                   if r.token_times else []),
    )


def gaps_s(records: list[Record]) -> list[float]:
    """Gaps between consecutive token-bearing frames, pooled over the
    sample."""
    return _series(
        records,
        lambda r: [b - a for a, b in zip(r.token_times, r.token_times[1:])],
        lambda r: [_end(r) - r.token_times[-1]] if r.token_times else [],
    )


def late_s(records: list[Record]) -> list[float]:
    return [r.sent - r.due for r in records
            if r.in_sample and r.sent is not None]


_SERIES = {"ttft": ttft_s, "tpot": tpot_s, "gap": gaps_s, "gen_late": late_s}
_NAME = re.compile(r"^(ttft|tpot|gap|gen_late)_p(\d+)_ms$")


def compute(name: str, records: list[Record], window_s: float) -> float | None:
    """The metric ``name`` over the sample, or None when the sample holds
    nothing it can be taken from."""
    if name == "out_tok_s":
        return sum(len(r.token_times) for r in finished(records)) / window_s
    m = _NAME.match(name)
    if not m:
        raise ValueError(f"no arithmetic for end-to-end metric {name!r}")
    values = _SERIES[m.group(1)](records)
    if not values:
        return None
    return percentile(values, float(m.group(2))) * 1000.0
