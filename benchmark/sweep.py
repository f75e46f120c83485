#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one server process, ascending
rates, ``--seconds`` each.  The knee is the highest swept rate at which
the number of requests in flight does not grow across the window (mean of
the last third <= 1.2 x mean of the first third) and nothing failed.  Run
by hand on the chip; the cell file then gets 0.8 x the knee as a number.
A row prints ``offered_rps`` beside ``rate_rps``: the requests that were
due in its window over its length, which a drawn process misses by the
draw (both seeds of PR 33's sweep ran 7-22 % over).

    python3 benchmark/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1.5,2,2.5,3,3.5,4
"""

from __future__ import annotations

import argparse
import asyncio
import json
from pathlib import Path

import run as harness  # also puts this directory on sys.path
import e2e


def in_flight(records, lo: float, hi: float, step: float = 0.25) -> float:
    """Mean number of requests sent and not yet ended over [lo, hi]."""
    counts, t = [], lo
    while t < hi:
        counts.append(sum(
            1 for r in records
            if r.sent is not None and r.sent <= t
            and (r.done is None or r.done > t)
        ))
        t += step
    return sum(counts) / max(1, len(counts))


async def sweep(session, rates: list[float], seconds: float,
                keep_frames: Path | None = None) -> list[dict]:
    session.client = harness.loadgen.Client(session.server.grpc_port)
    rows = []
    for i, rate in enumerate(rates):
        if i:
            session.prebuild(seconds, rate)
        c0 = await asyncio.to_thread(session.server.compiles)
        got = await harness.window(session, seconds, trace=False)
        records, t0 = got["records"], got["start"]
        third = seconds / 3
        first = in_flight(records, t0, t0 + third)
        last = in_flight(records, t0 + 2 * third, t0 + seconds)
        sample = [r for r in records if r.in_sample]
        row = {
            "rate_rps": rate, "offered_rps": len(sample) / seconds,
            "sample": len(sample),
            "failed": sum(1 for r in sample if not r.ok),
            "in_flight_first_third": first, "in_flight_last_third": last,
            "compiles": await asyncio.to_thread(session.server.compiles) - c0,
            "warmup": got["warmup"],
            **{n: e2e.compute(n, records, seconds)
               for n in ("ttft_p90_ms", "tpot_p50_ms", "gap_p99_ms",
                         "out_tok_s", "gen_late_p99_ms")},
        }
        row["sustained"] = row["failed"] == 0 and last <= 1.2 * max(first, 1.0)
        harness.say(phase="sweep", **row)
        if keep_frames:
            keep_frames.mkdir(parents=True, exist_ok=True)
            (keep_frames / f"frames-{i:02d}-{rate}.json").write_text(
                json.dumps(e2e.frames(records, t0)))
        rows.append(row)
        if not row["sustained"]:
            break
    await session.close()
    return rows


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--keep-frames", type=Path, default=None,
                        help="write every row's client frames here")
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    parser.add_argument("--log-dir", type=Path,
                        default=harness.WORK / "logs")
    args = parser.parse_args()
    rates = [float(x) for x in args.rates.split(",")]
    session = harness.Session(
        args.workload, args.seed, on_cpu=args.rehearse_on_cpu, trace=False,
        log_dir=args.log_dir)
    try:
        session.prebuild(args.seconds, rates[0])
        session.ready()
        rows = asyncio.run(sweep(session, rates, args.seconds,
                                 args.keep_frames))
        session.server.drain()
    finally:
        session.server.kill()
    good = [r["rate_rps"] for r in rows if r["sustained"]]
    print(json.dumps({"knee_rps": max(good) if good else None,
                      "rows": rows}), flush=True)


if __name__ == "__main__":
    main()
