#!/usr/bin/env python3
"""Hold a cell's served path to its family's plain reference at prompt
lengths the harness's own two reference requests (48 and 145 tokens) do
not reach: past one attention window, where a model with window layers
has freed pages behind its band.  Run by hand on the chip:

    python3 tools/check_long_context.py --workload <cell> --seed <n> \\
        [--lengths 48,145,4600,9000] [--alone 9000] [--flow-s 20]

It uses ``benchmark/run.py``'s own session (the seeded checkpoint, the
real server as the chip-holding child, the gRPC client, the family's
``score`` in a child on the CPU):

1. ``--alone`` tokens of prompt and 48 of answer are sent to the idle
   server while ``/metrics`` is polled: the most pages either group of
   the cache held while the row decoded (for a model with one group the
   window gauges read 0);
2. the cell's own traffic flows for ``--flow-s`` seconds, then the
   reference prompts are sent into it at once, 17 tokens each (1 from
   prefill and 16 from decode through the cache), and the flow goes on
   until they have ended;
3. the family's reference scores the served tokens; the script prints
   each length's largest |served logprob - reference logprob| and, last,
   one JSON line.

The parent never imports JAX.  Exit code 1 where a difference is over
the family's tolerance.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))

import run as harness  # noqa: E402  (benchmark/run.py)
from run import e2e, loadgen, prom  # noqa: E402

REFERENCE_TOKENS = 17
GAUGES = ("tgis_tpu_kv_pages_used", "tgis_tpu_kv_window_pages_used",
          "tgis_tpu_kv_pages_total", "tgis_tpu_kv_window_pages_total")


async def alone(session, prompt: str, tokens: int) -> dict:
    """One request on an idle server; the gauges' peaks while it decodes
    and what they read at its last token."""
    record = e2e.Record(time.monotonic(), tokens, 48)
    task = asyncio.create_task(session.client.generate(prompt, record))
    seen = []
    while not task.done():
        scrape = prom.parse(await asyncio.to_thread(
            session.server.get_text, "/metrics"))
        if record.token_times:  # decoding: the prompt is behind it
            seen.append({g: prom.total(scrape, g) or 0 for g in GAUGES})
        await asyncio.sleep(0.1)
    await task
    if not record.ok:
        raise RuntimeError(f"the request alone failed: {record.error}")
    return {
        "prompt_tokens": tokens,
        "polls_while_decoding": len(seen),
        **{f"{g}_peak": max((s[g] for s in seen), default=None)
           for g in GAUGES},
        **{f"{g}_last": seen[-1][g] if seen else None for g in GAUGES[:2]},
    }


async def score(session, sequences, lengths, threads: int):
    """The family's reference on the sequences, as ``run.py`` starts it,
    with the host's cores for the long ones."""
    job = harness.WORK / "long_context_job.json"
    job.write_text(json.dumps({
        "family": session.config["family"],
        "model_dir": str(session.model_dir), "config": session.hf_config,
        "sequences": sequences, "prompt_lens": lengths}))
    env = dict(os.environ, **{k: str(threads) for k in (
        "OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS")})
    child = await asyncio.create_subprocess_exec(
        sys.executable, str(harness.HERE / "reference.py"), str(job),
        env=env, stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.PIPE)
    text, err = await child.communicate()
    if child.returncode != 0:
        raise RuntimeError(f"the reference failed: {err.decode()[-800:]}")
    return json.loads(text.decode().strip().splitlines()[-1])


async def check(session, lengths: list[int], alone_tokens: int,
                flow_s: float, threads: int) -> dict:
    session.client = loadgen.Client(session.server.grpc_port)
    rng = random.Random(session.seed + 91)
    out: dict = {}
    if alone_tokens:
        out["alone"] = await alone(
            session, session.maker.make(alone_tokens, rng), alone_tokens)
        harness.say(phase="alone", **out["alone"])
    control = loadgen.Control()
    flowing = asyncio.create_task(session.flow(control))
    await asyncio.sleep(flow_s)
    in_flight = control.in_flight()
    prompts = [session.maker.make(n, rng) for n in lengths]
    records = [e2e.Record(time.monotonic(), n, REFERENCE_TOKENS)
               for n in lengths]
    await asyncio.gather(*(session.client.generate(p, r)
                           for p, r in zip(prompts, records)))
    after = control.in_flight()
    now = time.monotonic()
    control.window, control.limit_at = (now, now), now
    flow = await flowing
    bad = [r.error for r in records if not r.ok]
    if bad:
        raise RuntimeError(f"reference requests failed: {bad[0]}")
    harness.say(phase="served", in_flight_when_sent=in_flight,
                in_flight_when_ended=after, flow_requests=len(flow),
                flow_failed=sum(1 for r in flow
                                if r.error and r.error != "cancelled"))
    tok = session.maker.tok
    sequences, served = [], []
    for prompt, record in zip(prompts, records):
        ids = tok.convert_tokens_to_ids([t for t, _ in record.tokens])
        sequences.append(session.maker.ids(prompt) + ids)
        served.append([lp for _, lp in record.tokens])
    await session.client.close()
    # the server is no longer needed: its cores go to the reference
    session.server.drain()
    session.server.kill()
    scored = await score(session, sequences, lengths, threads)
    tolerance = session.family.LOGPROB_TOLERANCE
    out |= {"in_flight_when_sent": in_flight, "in_flight_when_ended": after,
            "tolerance": tolerance, "lengths": {}}
    for n, got, want in zip(lengths, served, scored):
        diffs = [abs(a - b) for a, b in zip(got, want)]
        out["lengths"][n] = {
            "max_abs_logprob_diff": max(diffs), "first_token_diff": diffs[0],
            "reference_logprob_range": [min(want), max(want)]}
        harness.say(phase="scored", prompt_tokens=n, **out["lengths"][n])
    out["ok"] = all(v["max_abs_logprob_diff"] <= tolerance
                    for v in out["lengths"].values())
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--lengths", default="48,145,4600,9000")
    parser.add_argument("--alone", type=int, default=9000)
    parser.add_argument("--flow-s", type=float, default=20.0)
    parser.add_argument("--threads", type=int, default=12,
                        help="threads of the reference's matmuls")
    parser.add_argument("--log-dir", type=Path,
                        default=harness.WORK / "logs")
    args = parser.parse_args()
    session = harness.Session(args.workload, args.seed, on_cpu=False,
                              trace=False, log_dir=args.log_dir)
    try:
        session.prebuild(args.flow_s + 120)
        session.ready()
        out = asyncio.run(check(
            session, [int(n) for n in args.lengths.split(",")], args.alone,
            args.flow_s, args.threads))
    finally:
        session.server.kill()
    if "jax" in sys.modules:
        raise RuntimeError("the parent imported jax: it could hold the chip")
    print(json.dumps(out), flush=True)
    sys.exit(0 if out["ok"] else 1)


if __name__ == "__main__":
    main()
