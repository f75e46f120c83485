#!/usr/bin/env python3
"""One run of one benchmark cell through the served gRPC path.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent never imports JAX.  It writes (or reuses) the configuration's
seeded checkpoint, starts the real server (``python -m
vllm_tgis_adapter_tpu``) as the one chip-holding child, warms it up (the
cell's script, then the cell's own traffic until nothing has compiled for
``quiet_s``), and measures a window of ``--seconds`` of that same stream.
The last line of standard output is the result.

A cell is ``cells/<name>.json``, a configuration ``configs/<name>.json``,
a traffic mix ``traffic/<name>.json``, a per-layer metric
``layer_metrics/<name>.json`` read by ``readers/<kind>.py``, and a model
family (tensor table, fills, plain reference, tolerance)
``families/<family>.py``, named by its configurations: all found by
name, so a new one is a new file.  Which metrics a cell prints is what
``BENCHMARK.json`` lists for it; a cell it does not list (the rehearsal)
prints every metric there is.  A server that is not on the TPU with
the compiled Pallas kernel fails the run; ``--rehearse-on-cpu`` is the
explicit exception for rehearsing the harness, and says ``cpu`` in the
printed device.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import importlib.util
import json
import os
import random
import shutil
import subprocess
import sys
import time
from pathlib import Path

T_START = time.monotonic()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT))

import checkpoint  # noqa: E402
import e2e  # noqa: E402
import loadgen  # noqa: E402
import prom  # noqa: E402
import trace_reduce  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from server import Server, check_device  # noqa: E402

WORK = HERE / ".work"
#: keys of a configuration file that are not the model's config.json
NOT_HF = {"source", "assumed", "assumed_why", "reduced", "reduced_why",
          "deployment", "dtype", "serve_args", "chips", "why", "family"}
REFERENCE_PROMPTS = (48, 145)  # one inside a page run, one across pages
REFERENCE_TOKENS = 17  # 1 from prefill + 16 from decode through the cache
PROFILE_AFTER_S = 3.0
#: a traced run's scrapes around its capture: how often, and for how long
#: past the seconds the profiler client asks for
FINE_EVERY_S = 0.25
FINE_AFTER_S = 8.0
#: a capture begins about this long after its client is started (3.3-4.2 s
#: seen); one that could not end inside the window is not begun
CLIENT_UP_S = 3.5
SCRIPT_SEED = 1  # the warm-up script's prompts: the same in every run
WARMUP_CAP_S = 600  # a warm-up still compiling after this fails the run
#: logged with every run, judged or not (the last line holds only what
#: ``BENCHMARK.json`` lists)
CLIENT_SEES = ("ttft_p90_ms", "tpot_p50_ms", "gap_p99_ms", "out_tok_s",
               "gen_late_p99_ms")


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def load(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.exists():
        raise SystemExit(f"no {kind[:-1]} {name!r}: {path} is missing")
    return json.loads(path.read_text())


def served_config(config: dict) -> dict:
    """The model's ``config.json`` of a configuration file."""
    return {k: v for k, v in config.items() if k not in NOT_HF}


def load_family(name: str):
    """``families/<name>.py``, wherever on the path a ``families``
    directory holds it."""
    if importlib.util.find_spec(f"families.{name}") is None:
        raise SystemExit(f"no family {name!r}: "
                         f"{HERE / 'families' / f'{name}.py'} is missing")
    return importlib.import_module(f"families.{name}")


def listed(cell_name: str, kind: str) -> dict[str, str]:
    """name -> unit of the ``end_to_end`` or ``per_layer`` metrics that
    ``BENCHMARK.json`` lists for this cell.  A cell it does not list (the
    rehearsal) gets every end-to-end metric and every file under
    ``layer_metrics/``, listed or waiting."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if cell_name in {w["name"] for w in spec["workloads"]}:
        return {m["name"]: m["unit"] for m in spec[kind]
                if cell_name in m.get("workloads", [cell_name])}
    if kind == "end_to_end":
        return {m["name"]: m["unit"] for m in spec[kind]}
    files = [json.loads(p.read_text())
             for p in sorted((HERE / "layer_metrics").glob("*.json"))]
    return {m["name"]: m["unit"] for m in files}


async def start_reference(job: Path, family: str, model_dir: Path,
                          hf_config: dict, sequences: list[list[int]],
                          prompt_lens: list[int]):
    """``reference.py`` on ``job`` as a child with a few threads: the
    family's ``score`` of the sequences, one JSON line on its output."""
    job.write_text(json.dumps({
        "family": family, "model_dir": str(model_dir), "config": hf_config,
        "sequences": sequences, "prompt_lens": prompt_lens}))
    threads = {k: "4" for k in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS",
                                "MKL_NUM_THREADS")}
    return await asyncio.create_subprocess_exec(
        sys.executable, str(HERE / "reference.py"), str(job),
        env=dict(os.environ, **threads),
        stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.PIPE)


def judge(served: list[list[float]], scored: list[list[float]],
          tolerance: float) -> dict:
    """The served logprobs of the reference requests' tokens against the
    reference's.  ``reference_logprob_spread`` is there to be looked at:
    fills that leave the logits flat make every logprob -ln(vocabulary)
    and the comparison passes whatever the server computed."""
    diffs = [abs(a - b) for got, want in zip(served, scored)
             for a, b in zip(got, want)]
    flat = [b for want in scored for b in want]
    return {"ok": max(diffs) <= tolerance,
            "max_abs_logprob_diff": max(diffs),
            "tolerance": tolerance,
            "tokens_scored": len(diffs),
            "reference_logprob_spread": max(flat) - min(flat),
            "first_token_diffs": [abs(g[0] - w[0]) for g, w in
                                  zip(served, scored)]}


class Session:
    """A booted, checked server with its client, prompts and traffic."""

    def __init__(self, cell_name: str, seed: int, *, on_cpu: bool,
                 trace: bool, log_dir: Path):
        self.cell_name, self.seed, self.on_cpu = cell_name, seed, on_cpu
        self.cell = load("cells", cell_name)
        self.config = load("configs", self.cell["config"])
        self.traffic = traffic_mod.load_traffic(self.cell["traffic"])
        self.family = load_family(self.config["family"])
        self.hf_config = served_config(self.config)
        # protoc runs HERE, once, and never in the child at the same time
        import vllm_tgis_adapter_tpu.grpc.pb  # noqa: F401

        t = time.monotonic()
        self.model_dir, reused = checkpoint.ensure_checkpoint(
            WORK, self.cell["config"], self.hf_config, seed,
            self.config["dtype"], self.family.tensors,
        )
        say(phase="checkpoint", reused=reused,
            seconds=round(time.monotonic() - t, 2))
        self.profile_dir = WORK / "profile" if trace else None
        if self.profile_dir:
            shutil.rmtree(self.profile_dir, ignore_errors=True)
        self.server = Server(
            self.model_dir, self.config["serve_args"],
            log_dir / f"server-{cell_name}.log", on_cpu=on_cpu,
            profiler=trace,
        )
        self.client = None
        self.maker = checkpoint.PromptMaker(self.model_dir)

    def ready(self) -> None:
        """Wait for /health and hold the server to its device."""
        healthy = self.server.wait_healthy()
        self.device = self.server.get_json("/debug/state?section=device")[
            "device"]
        check_device(self.device, on_cpu=self.on_cpu,
                     chips=self.cell["chips"])
        say(phase="boot", healthy_after_s=round(healthy, 2),
            weights_load_s=self.device["weights_load_s"],
            kv_pool=self.device["kv_pool"])

    def prompts_for(self, plan, seed: int) -> list[str]:
        return self.maker.make_all([p.prompt_tokens for p in plan], seed)

    def prebuild(self, seconds: float, rate: float | None = None) -> None:
        """Build this run's requests while the server boots: the warm-up's
        script, the reference prompts, and the cell's traffic for as long
        as warm-up, window and drain can take."""
        cell = self.cell
        self.script = []
        for k, step in enumerate(cell["warmup"]["script"]):
            plan = [traffic_mod.Planned(0.0, step["prompt_tokens"],
                                        step["output_tokens"])] * step["count"]
            self.script.append(
                (step, plan, self.prompts_for(plan, SCRIPT_SEED + k)))
        if self.traffic["loop"] == "open":
            horizon = WARMUP_CAP_S + seconds + cell["drain_limit_s"] + 120
            body = traffic_mod.open_schedule(
                self.traffic, rate or cell["rate_rps"], horizon, self.seed)
        else:
            body = [traffic_mod.Planned(0.0, p, o) for p, o in
                    traffic_mod.sizes(self.traffic, self.traffic["requests"],
                                      self.seed)]
        self.stream = (body, self.prompts_for(body, self.seed))
        rng = random.Random(self.seed + 77)
        self.ref_prompts = [self.maker.make(n, rng) for n in REFERENCE_PROMPTS]

    async def run_script(self) -> list:
        """The warm-up's script, before any of the cell's traffic: groups
        of requests of stated sizes, each group sent at once; the next
        group goes when every request of this one has its first token
        (``wait: first_token``) or has ended (``done``).  It exists to meet
        every program the window can need, at the width it can need: a
        ragged step compiles once per flat-token bucket AND per width of
        its work schedule, and the width of a bucket only ever grows.  So
        rows with deep contexts come first and stay, and then one long
        prompt whose chunks walk down every smaller bucket while they
        run.  Returns the requests still running."""
        running = []
        for step, plan, prompts in self.script:
            records = [e2e.Record(time.monotonic(), p.prompt_tokens,
                                  p.output_tokens) for p in plan]
            tasks = [asyncio.create_task(self.client.generate(p, r))
                     for p, r in zip(prompts, records)]
            if step["wait"] == "done":
                await asyncio.gather(*tasks)
            elif step["wait"] == "first_token":
                while not all(r.token_times or r.done for r in records):
                    await asyncio.sleep(0.05)
                running += tasks
            else:
                raise ValueError(f"script step: unknown wait {step['wait']!r}")
            bad = [r.error for r in records if r.error]
            if bad:
                raise RuntimeError(f"warm-up script: {bad[:2]}")
        return running

    async def flow(self, control: loadgen.Control) -> list:
        """The stream, from now until ``control.over()``."""
        plan, prompts = self.stream
        if self.traffic["loop"] == "open":
            return await loadgen.run_open(
                self.client, plan, prompts, time.monotonic(), control)
        return await loadgen.run_closed(
            self.client, [(p.prompt_tokens, p.output_tokens) for p in plan],
            prompts, self.cell["callers"], control)

    async def settle(self, staying: list) -> dict:
        """Set-up, while the cell's traffic flows: the window may start
        once nothing has compiled for ``quiet_s`` (a load from the
        persistent cache counts: it stalls the engine for seconds too) and
        the script's rows have ended."""
        quiet_s, t0 = self.cell["warmup"]["quiet_s"], time.monotonic()
        count, changed, counts = -1, t0, []
        while True:
            await asyncio.sleep(1.0)
            now = time.monotonic()
            c = await asyncio.to_thread(self.server.compiles)
            if c != count:
                count, changed = c, now
                counts.append((round(now - t0, 1), c))
            if now - changed >= quiet_s and all(t.done() for t in staying):
                break
            if now - t0 > WARMUP_CAP_S:
                raise RuntimeError(
                    f"warm-up: still compiling after {WARMUP_CAP_S}s: "
                    f"{counts}")
        await asyncio.gather(*staying)
        out = {"programs": count, "compile_times": counts[-40:],
               "flow_s": round(time.monotonic() - t0, 1)}
        say(phase="warmup", **out)
        return out

    async def reference_requests(self) -> None:
        """The two reference requests, sent as the cell's traffic starts
        (after the script, so they meet no program of their own); then the
        reference itself as a child with a few threads.  Outside the
        sample; the verdict is read after the window."""
        records = [e2e.Record(time.monotonic(), n, REFERENCE_TOKENS)
                   for n in REFERENCE_PROMPTS]
        await asyncio.gather(*(
            self.client.generate(p, r)
            for p, r in zip(self.ref_prompts, records)))
        bad = [r.error for r in records if not r.ok]
        if bad:
            raise RuntimeError(f"reference requests failed: {bad[0]}")
        tok = self.maker.tok
        sequences, self.ref_served = [], []
        for prompt, record in zip(self.ref_prompts, records):
            ids = tok.convert_tokens_to_ids([t for t, _ in record.tokens])
            if any(i is None or i < 0 for i in ids):
                raise RuntimeError("a token text is not in the vocabulary: "
                                   "the reference cannot score it")
            sequences.append(self.maker.ids(prompt) + ids)
            self.ref_served.append([lp for _, lp in record.tokens])
        self.ref_child = await start_reference(
            WORK / "reference_job.json", self.config["family"],
            self.model_dir, self.hf_config, sequences,
            list(REFERENCE_PROMPTS))

    async def reference_verdict(self) -> dict:
        out, err = await self.ref_child.communicate()
        if self.ref_child.returncode != 0:
            return {"ok": False, "error": err.decode()[-500:]}
        scored = json.loads(out.decode().strip().splitlines()[-1])
        return judge(self.ref_served, scored, self.family.LOGPROB_TOLERANCE)

    async def close(self) -> None:
        if self.client:
            await self.client.close()


async def observe(session: Session, start: float, end: float,
                  trace: bool, trace_s: float, out: dict) -> None:
    """Scrape /metrics at both ends of the window; in a traced run also
    sample it once a second and capture a few seconds of profile: a child
    pinned to the CPU asks the server's ``--jax-profiler-port`` for a
    device and host trace with Python tracing off.  From the moment that
    child is started until ``FINE_AFTER_S`` past the seconds it asks for,
    which is where the capture lies (it begins once the child has come up
    and reached the server), a second list holds a scrape every
    ``FINE_EVERY_S`` with its ``time.time()``: the counters' values at the
    capture's own two ends are read from it (``readers/roofline.py``).

    A capture whose client fails, or whose file names no device plane (the
    engine stood still in those seconds: such a file comes back within
    6 s where a whole one takes 20-60), is made again at once, with fine
    scrapes of its own, as long as it can still end inside the window;
    ``attempts`` says what each one met.  The once-a-second samples and
    the scrape at the window's end do not wait for any of that."""
    server = session.server

    async def at(instant: float) -> None:
        await asyncio.sleep(max(0.0, instant - time.monotonic()))

    async def scrape() -> dict:
        return prom.parse(await asyncio.to_thread(server.get_text, "/metrics"))

    async def profile() -> str | None:
        """One capture; what went wrong with it, or None."""
        shutil.rmtree(session.profile_dir, ignore_errors=True)
        begun, began_wall = time.monotonic(), time.time()
        child = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "jax.collect_profile",
            str(server.profiler_port), str(int(trace_s * 1000)),
            "--log_dir", str(session.profile_dir), "--no_perfetto_link",
            "--python_tracer_level=0",
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=asyncio.subprocess.PIPE,
            stderr=asyncio.subprocess.STDOUT)
        text, _ = await child.communicate()
        out["profile_collect_s"] = time.monotonic() - begun
        out["profile_began_wall"] = began_wall
        if child.returncode != 0:
            return f"jax.collect_profile failed: {text.decode()[-600:]}"
        # the profiler client gives request + capture + hand-over 60 s:
        # how far from it this capture stayed (PERF.md section 4)
        files = sorted(session.profile_dir.rglob("*.xplane.pb"))
        if not files:
            return "the profiler client wrote no xplane.pb"
        stat = files[-1].stat()
        out["xplane"] = {"bytes": stat.st_size,
                         "written_after_s": stat.st_mtime - began_wall}
        if not await asyncio.to_thread(
                trace_reduce.names_device_plane, files[-1],
                trace_reduce.plane_pattern(session.on_cpu)):
            return f"no device plane in {stat.st_size} bytes of xplane.pb"
        return None

    async def fine(into: list) -> None:
        tick = time.monotonic()
        until = tick + trace_s + FINE_AFTER_S
        while tick < until:
            await at(tick)
            asked = time.time()
            seen = await scrape()
            into.append(((asked + time.time()) / 2, seen))
            tick += FINE_EVERY_S

    async def capture() -> None:
        await at(start + PROFILE_AFTER_S)
        out["attempts"] = []
        while True:
            began = time.monotonic() - start
            out["fine_samples"] = []
            sampling = asyncio.create_task(fine(out["fine_samples"]))
            wrong = await profile()
            out["attempts"].append({
                "after_s": round(began, 1), "wrong": wrong,
                "took_s": round(out["profile_collect_s"], 1)})
            if wrong is None:
                await sampling
                return
            sampling.cancel()
            if time.monotonic() + CLIENT_UP_S + trace_s > end:
                raise RuntimeError(
                    f"no capture of the window holds a device operation: "
                    f"{out['attempts']}")

    await at(start)
    out["scrape0"] = await scrape()
    out["samples"] = []
    capturing = asyncio.create_task(capture()) if trace else None
    if trace:
        tick = start + 1.0
        while tick < end:
            await at(tick)
            out["samples"].append(await scrape())
            tick += 1.0
    await at(end)
    out["scrape1"] = await scrape()
    if capturing:
        await capturing


def reduce_trace(profile_dir: Path, on_cpu: bool, keep: Path | None) -> dict:
    """In a child pinned to the CPU, after the server has exited."""
    # a rehearsal's pattern exercises the reducer only
    cmd = [sys.executable, str(HERE / "trace_reduce.py"), str(profile_dir),
           trace_reduce.plane_pattern(on_cpu)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=900)
    if keep:
        for path in profile_dir.rglob("*.xplane.pb"):
            keep.mkdir(parents=True, exist_ok=True)
            if path.stat().st_size < 48 << 20:
                shutil.copy(path, keep / path.name)
    shutil.rmtree(profile_dir, ignore_errors=True)
    if done.returncode != 0:
        raise RuntimeError(f"trace reduction failed:\n{done.stderr[-2000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1])


def placed(seen: dict, summary: dict) -> dict:
    """Where the capture and the scrapes around it lay, in seconds after
    the profiler client was started: for the log."""
    began = seen["profile_began_wall"]
    fine = [t - began for t, _ in seen["fine_samples"]]
    return {
        "capture_after_client_s": [
            t - began for t in summary["capture_wall"] or []],
        "fine_after_client_s": fine[:1] + fine[-1:],
        "fine": len(fine), "samples": len(seen["samples"]),
        "attempts": seen["attempts"],
    }


def layer_metrics(cell_name: str, ctx: dict, on_cpu: bool) -> dict:
    """Every per-layer metric that ``BENCHMARK.json`` lists for this cell,
    each through the reader its own file names; one that finds nothing is
    left out.  A CPU rehearsal reports no device metric at all."""
    out = {}
    for name in sorted(listed(cell_name, "per_layer")):
        spec = load("layer_metrics", name)
        if on_cpu and spec["source"] == "device_trace":
            continue
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(spec["params"], ctx)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


async def window(session: Session, seconds: float, trace: bool) -> dict:
    """The script, then the session's stream with the two reference
    requests at its head; once the warm-up has settled, measure
    ``seconds``; returns when the sample has ended."""
    control = loadgen.Control()
    t = time.monotonic()
    staying = await session.run_script()
    say(phase="script", seconds=round(time.monotonic() - t, 1),
        programs=await asyncio.to_thread(session.server.compiles))
    flowing = asyncio.create_task(session.flow(control))
    checking = asyncio.create_task(session.reference_requests())
    settling = asyncio.create_task(session.settle(staying))
    done, _ = await asyncio.wait({flowing, settling},
                                 return_when=asyncio.FIRST_COMPLETED)
    if flowing in done:  # only by an error: it cannot end before a window
        settling.cancel()
        await flowing
        raise RuntimeError("the stream ended during the warm-up")
    start = time.monotonic()
    control.window = (start, start + seconds)
    control.limit_at = start + seconds + session.cell["drain_limit_s"]
    say(phase="window", in_flight=control.in_flight())
    seen: dict = {}
    await observe(session, start, start + seconds, trace,
                  float(session.cell.get("trace_s", 5)), seen)
    records = await flowing
    control.mark_sample()
    await checking
    return {"records": records, "start": start, "seen": seen,
            "warmup": settling.result()}


async def measure(session: Session, seconds: float, trace: bool) -> dict:
    session.client = loadgen.Client(session.server.grpc_port)
    got = await window(session, seconds, trace)
    after = await asyncio.to_thread(
        session.server.get_json, "/debug/state?section=device")
    got["reference"] = await session.reference_verdict()
    await session.close()
    return {**got, "setup_s": got["start"] - T_START,
            "device_after": after["device"]}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-on-cpu", action="store_true",
                        help="serve from the CPU: a rehearsal of the "
                             "harness, never a measurement")
    parser.add_argument("--log-dir", type=Path, default=WORK / "logs")
    parser.add_argument("--keep-trace", type=Path, default=None,
                        help="copy the raw trace here before deleting it")
    parser.add_argument("--keep-frames", type=Path, default=None,
                        help="write the client's frames to this file")
    args = parser.parse_args()
    trace = bool(args.trace)

    session = Session(args.workload, args.seed, on_cpu=args.rehearse_on_cpu,
                      trace=trace, log_dir=args.log_dir)
    server = session.server
    try:
        session.prebuild(args.seconds)
        session.ready()
        got = asyncio.run(measure(session, args.seconds, trace))
        server.drain()
    finally:
        server.kill()

    records, seen = got["records"], got["seen"]
    if args.keep_frames:
        args.keep_frames.parent.mkdir(parents=True, exist_ok=True)
        args.keep_frames.write_text(
            json.dumps(e2e.frames(records, got["start"])))
    sample = [r for r in records if r.in_sample]
    malformed = [r.error for r in sample
                 if r.error and r.error.startswith("ValueError")]
    device = got["device_after"]
    out_device = {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"],
        "memory_peak_bytes": max(
            (m.get("peak_bytes_in_use", 0) for m in device["memory"]),
            default=0),
    }
    result = {
        "correct": bool(sample) and not malformed and got["reference"]["ok"],
        "attempted": len(sample),
        "failed": sum(1 for r in sample if not r.ok),
    }
    metrics = {}
    if not trace:
        for name, unit in listed(args.workload, "end_to_end").items():
            value = (got["setup_s"] if name == "setup_s"
                     else e2e.compute(name, records, args.seconds))
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
    else:
        summary = reduce_trace(session.profile_dir, args.rehearse_on_cpu,
                               args.keep_trace)
        ctx = {
            "records": records,
            "window_s": args.seconds, "scrape0": seen.get("scrape0"),
            "scrape1": seen.get("scrape1"), "samples": seen.get("samples"),
            "fine_samples": seen.get("fine_samples"), "trace": summary,
            "hf_config": session.hf_config,
            "device_kind": device["device_kind"],
        }
        metrics = layer_metrics(args.workload, ctx, args.rehearse_on_cpu)
        if summary["busy_s"] <= 0 and not args.rehearse_on_cpu:
            say(phase="trace_empty", lines=summary["lines"],
                planes=summary["all_planes"], capture=placed(seen, summary))
            raise RuntimeError("the trace holds no device operation")
        if not args.rehearse_on_cpu:
            out_device |= {"busy_s": summary["busy_s"],
                           "window_s": summary["window_s"]}
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
        say(phase="trace", lines=summary["lines"],
            planes=summary["all_planes"],
            profile_collect_s=seen.get("profile_collect_s"),
            xplane=seen.get("xplane"), capture=placed(seen, summary),
            also={n: e2e.compute(n, records, args.seconds)
                  for n in CLIENT_SEES})
    say(phase="detail", reference=got["reference"], warmup=got["warmup"],
        client={n: e2e.compute(n, records, args.seconds)
                for n in CLIENT_SEES},
        sample_errors=sorted({r.error for r in sample if r.error})[:5],
        requests_sent=len(records), setup_s=got["setup_s"],
        compiles_in_window=prom.delta(
            seen["scrape0"], seen["scrape1"], "tgis_tpu_xla_recompile_total"))
    if "jax" in sys.modules:
        raise RuntimeError("the parent imported jax: it could hold the chip")
    # what ``correct`` compared, beside its limit: last on standard error
    # and last in the line
    verdict = got["reference"]
    compared = {"max_abs_logprob_diff": {
        "value": verdict["max_abs_logprob_diff"],
        "limit": verdict["tolerance"]}} if "tolerance" in verdict else {}
    for name, pair in compared.items():
        print(f"compared {name} {pair['value']} limit {pair['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps({**result, "metrics": metrics, "device": out_device,
                      "compared": compared}), flush=True)


if __name__ == "__main__":
    main()
