"""Randomized chaos soak: property-based recovery coverage (nox -s chaos_soak).

``chaos_check`` proves hand-picked recovery scenarios; this harness
proves the *surface*: a SEEDED schedule draws faults (``raise`` /
``oom`` / ``hang``) across the failpoint sites while a mixed
chat/RAG/LoRA workload runs against a supervised engine with the host
KV tier on (some seeds dp=2; some of THOSE run a disaggregated
prefill+decode fleet and always arm the kill-prefill-replica-
mid-handoff fault — docs/SCALING.md "Disaggregated roles"; a fixed
rotation of seeds serves with --kv-quantization int8/fp8, proving
checkpoint/resume and cross-replica migration token-stable under
QUANTIZED KV pages — docs/QUANTIZATION.md).  Asserted here are the
global invariants no single scenario can
(docs/RECOVERY.md "Randomized chaos soak"):

* every submitted request reaches EXACTLY ONE terminal outcome — a
  completed stream or a typed retryable ``EngineRestartError`` — and
  nothing outlives the harness bound (no watchdog-visible hangs);
* every request that completes streams TOKEN-IDENTICAL output to its
  uncrashed baseline (greedy and seeded-sampled alike, resumed from a
  decode checkpoint or not), with zero duplicate/missing DELTA tokens;
* the engine returns to ``serving`` after every injected fault within
  the bound;
* checkpoint/resume adds ZERO new compile shapes over the warmed set
  for its entry points (``gather_kv`` / ``scatter_kv`` ride one fixed
  block shape each — compile-tracker gated).

Each seed is one reproducible schedule: ``python tools/chaos_soak.py
--seed 7`` replays exactly what CI saw.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import random
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# every soak step runs the invariant sanitizer (engine/sanitizer.py):
# a fault schedule that corrupts allocator/arena/tier/pool accounting
# fails AT the corrupting step, not as a downstream token divergence
os.environ.setdefault("TGIS_TPU_SANITIZE", "1")

DEFAULT_SEEDS = 5
DEFAULT_BASE_SEED = 20260804
#: nothing — request, recovery, or drain — may outlive this (the soak's
#: watchdog bound; the in-engine stall watchdog runs far tighter)
HARNESS_BOUND_S = 60.0
#: soft overall budget: exceeded → loud warning, never silent trimming
BUDGET_S = 120.0

REQUESTS_PER_SEED = 8

#: the shared "system prompt" RAG requests reuse (tiers + prefix paths)
RAG_PREFIX = list(range(400, 424))

#: deterministic --kv-quantization rotation per seed (seed % 3): does
#: not perturb the rng draw sequence of pre-existing schedules, and
#: the default 5-seed CI run always covers int8 AND fp8 — every fault,
#: checkpoint/resume and cross-replica migration in those schedules
#: then runs over quantized pages + scale sidecars, with the
#: token-identity invariant held against the SAME-engine baseline
KV_QUANT_ROTATION = ("none", "int8", "fp8")

# (site, action) pool the schedule draws from.  ``hang`` is listed once
# and only used at dp=1 seeds (the watchdog declares the stall and the
# supervisor restarts the replica — detection needs the stalled replica
# to be the one with work, which dp=2 placement makes nondeterministic).
FAULTS = (
    ("core.plan_step", "raise"),
    ("core.commit_step", "raise"),
    ("core.wait_step", "oom"),
    ("scheduler.schedule", "raise"),
    ("runner.dispatch_ragged", "raise"),
    ("runner.dispatch_decode", "raise"),
    # mid-spec-verify death (docs/ATTENTION.md "Speculative decoding"):
    # fires inside the verify dispatch, AFTER the draft proposed but
    # BEFORE any acceptance committed — the checkpoint/resume path must
    # capture only ACCEPTED tokens (in-flight draft tokens die with the
    # dispatch) and resume token-identically.  On non-spec seeds the
    # schedule remaps this to the plain ragged dispatch site.
    ("runner.dispatch_verify", "raise"),
    ("core.wait_step", "hang"),
    # armed in one round, fires during a LATER round's recovery: the
    # death-during-recovery retry, which must adopt the failed
    # attempt's staged checkpoints instead of losing them
    ("supervisor.rebuild", "raise"),
)


def _build_fixtures() -> tuple[str, str]:
    """Tiny llama + one live LoRA adapter, built once per process."""
    from tests.fixture_models import (
        build_tiny_llama,
        build_tiny_lora_adapter,
    )

    model_dir = tempfile.mkdtemp(prefix="soak-model-")
    build_tiny_llama(model_dir)
    adapter_dir = build_tiny_lora_adapter(
        os.path.join(model_dir, "ad-soak"), seed=11, rank=2
    )
    return model_dir, adapter_dir


def _build_engine(
    model_dir: str,
    *,
    dp: int = 1,
    watchdog: bool = False,
    roles: tuple = (),
    spec: bool = False,
    kv_quantization: str = "none",
    enable_prefix_caching: bool = True,
    kvnet_listen: str | None = None,
    kvnet_peers: tuple = (),
    kvnet_node_id: str | None = None,
    kvnet_timeout_s: float = 5.0,
):
    """One production-shaped in-process engine: supervised, front door
    on, host KV tier on, LoRA pool of two."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        FrontdoorConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
        SpeculativeConfig,
    )

    mcfg = ModelConfig.from_pretrained(model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16,
            num_blocks=96,
            cache_dtype=mcfg.dtype,
            enable_prefix_caching=enable_prefix_caching,
            kv_quantization=kv_quantization,
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(32, 64)
        ),
        parallel_config=ParallelConfig(dp_replicas=dp),
        lora_config=LoRAConfig(enabled=True, max_loras=2,
                               max_lora_rank=2),
        dp_replica_roles=tuple(roles),
        kv_host_cache_gb=1.0,
        kvnet_listen=kvnet_listen,
        kvnet_peers=tuple(kvnet_peers),
        kvnet_node_id=kvnet_node_id,
        kvnet_timeout_s=kvnet_timeout_s,
        max_engine_restarts=20,
        engine_restart_window_s=300.0,
        engine_restart_backoff_s=0.01,
        watchdog_deadline_s=1.0 if watchdog else 0.0,
        watchdog_action="restart",
        frontdoor=FrontdoorConfig(enabled=True),
        speculative=(
            SpeculativeConfig(
                draft_model=model_dir,
                num_speculative_tokens=3,
                draft_model_config=mcfg,
            )
            if spec
            else None
        ),
    )
    return AsyncLLMEngine.from_config(config)


def _make_workload(rng: random.Random) -> list[dict]:
    """The seeded mixed workload: chat (unique prompts), RAG (shared
    prefix + unique tail), LoRA-tagged — greedy and seeded-sampled
    mixed in."""
    specs = []
    for i in range(REQUESTS_PER_SEED):
        kind = ("chat", "rag", "lora")[i % 3]
        if kind == "rag":
            prompt = RAG_PREFIX + [
                rng.randrange(3, 300)
                for _ in range(rng.randint(4, 12))
            ]
        else:
            prompt = [
                rng.randrange(3, 300)
                for _ in range(rng.randint(6, 20))
            ]
        sampled = rng.random() < 0.34
        specs.append({
            "kind": kind,
            "prompt": prompt,
            "max_tokens": rng.randint(8, 24),
            "temperature": 0.9 if sampled else 0.0,
            "seed": rng.randrange(1, 2**31) if sampled else None,
        })
    return specs


def _params(spec: dict):
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    return SamplingParams(
        temperature=spec["temperature"],
        seed=spec["seed"],
        max_tokens=spec["max_tokens"],
        ignore_eos=True,
        output_kind=RequestOutputKind.DELTA,
    )


async def _run_request(engine, rid: str, spec: dict, lora_req):
    """One DELTA stream to its terminal outcome.  Returns
    ``("ok", [every streamed token, in order])`` or ``("err", exc)`` —
    exactly one of the two, exactly once."""
    toks: list[int] = []
    try:
        async for out in engine.generate(
            prompt=None,
            sampling_params=_params(spec),
            request_id=rid,
            prompt_token_ids=list(spec["prompt"]),
            lora_request=lora_req if spec["kind"] == "lora" else None,
        ):
            toks.extend(out.outputs[0].token_ids)
        return ("ok", toks)
    except BaseException as e:  # noqa: BLE001 — the outcome IS the result
        return ("err", e)


async def _wait_serving(engine, what: str, bound: float) -> None:
    deadline = time.monotonic() + bound
    while time.monotonic() < deadline:
        if engine.lifecycle == "serving" and all(
            rep.serving for rep in engine._replicas  # noqa: SLF001
        ):
            return
        await asyncio.sleep(0.02)
    raise AssertionError(
        f"seed invariant violated: {what} did not return to serving "
        f"within {bound:.0f}s (lifecycle={engine.lifecycle})"
    )


async def _run_seed(seed: int, model_dir: str, adapter_dir: str) -> dict:
    from vllm_tgis_adapter_tpu import compile_tracker
    from vllm_tgis_adapter_tpu.frontdoor.errors import EngineRestartError
    from vllm_tgis_adapter_tpu.supervisor import failpoints

    rng = random.Random(seed)
    dp = 2 if rng.random() < 0.4 else 1
    # disaggregated-roles seeds: a dp=2 fleet split prefill+decode.
    # Every request then crosses the handoff boundary, and the seed
    # ALWAYS arms the kill-prefill-replica-mid-handoff fault below —
    # role-aware recovery (staged handoffs resume on the decode
    # sibling) is asserted by the same token-identity invariants.
    roles = (
        ("prefill", "decode")
        if dp == 2 and rng.random() < 0.7
        else ()
    )
    # speculative seeds: ~60% of schedules attach the same-weights
    # draft (greedy requests then ride verify spans; seeded-sampled
    # ones stay on plain spans in the SAME dispatches) — composed with
    # dp, roles and every fault in the pool
    spec_on = rng.random() < 0.6
    # quantized-KV seeds: a fixed seed-keyed rotation (not an rng draw,
    # so existing schedules keep their exact fault sequence) serves
    # some schedules with int8/fp8 KV pages — checkpoints, resumes,
    # cross-replica migration and role handoffs then move quantized
    # pages + scale sidecars, and the token-identity invariant (vs the
    # same engine's uncrashed baseline) proves the page scale
    # discipline reproducible across every recompute path
    kvq = KV_QUANT_ROTATION[seed % len(KV_QUANT_ROTATION)]
    engine = _build_engine(
        model_dir, dp=dp, watchdog=(dp == 1), roles=roles, spec=spec_on,
        kv_quantization=kvq,
    )
    hang_released: list[str] = []
    try:
        lora_req = await engine.engine.lora_manager.load_lora_adapter(
            "ad-soak", adapter_dir
        )
        specs = _make_workload(rng)

        # ---- warm phase: the uncrashed BASELINE, and the compile set.
        # Running the identical workload first (a) pins the per-request
        # correct outputs and (b) compiles every shape the chaos phase
        # can reach; the re-send of spec 0 exercises one host-tier
        # promotion so scatter_kv is in the warmed set too.
        baseline: dict[int, list[int]] = {}
        for i, spec in enumerate(specs):
            status, toks = await _run_request(
                engine, f"warm-{seed}-{i}", spec, lora_req
            )
            assert status == "ok", f"warm request {i} failed: {toks!r}"
            baseline[i] = toks
        status, toks = await _run_request(
            engine, f"warm-{seed}-promote", specs[0], lora_req
        )
        assert status == "ok" and toks == baseline[0], (
            "warm re-send diverged — prefix/tier reuse broke determinism"
        )
        warm_shapes = compile_tracker.shapes()

        # ---- chaos phase: same workload, seeded fault schedule
        injected: list[str] = []
        if roles:
            # kill-prefill-replica-mid-handoff: armed BEFORE the
            # workload, so the first handoff drain dies BETWEEN stage
            # and resume — the staged records survive in the
            # fleet-shared tier and role-aware recovery must adopt
            # them onto the decode sibling (docs/SCALING.md)
            failpoints.arm_site("async.handoff", "raise", 1)
            injected.append("async.handoff=raise")
        tasks = {
            i: asyncio.create_task(_run_request(
                engine, f"chaos-{seed}-{i}", spec, lora_req
            ))
            for i, spec in enumerate(specs)
        }
        for _ in range(rng.randint(1, 3)):
            await asyncio.sleep(rng.uniform(0.1, 0.6))
            if all(t.done() for t in tasks.values()):
                break
            site, action = rng.choice(FAULTS)
            if action == "hang" and dp != 1:
                site, action = "core.plan_step", "raise"
            if site == "runner.dispatch_verify" and not spec_on:
                # no draft attached: the verify site never fires —
                # remap to the plain ragged dispatch so the draw still
                # injects a fault
                site = "runner.dispatch_ragged"
            injected.append(f"{site}={action}")
            failpoints.arm_site(site, action, 1)
            if action == "hang":
                # the stall watchdog declares it and the supervisor
                # restarts the replica; the abandoned worker thread is
                # released once recovery is observed
                await _wait_serving(
                    engine, f"hang recovery ({site})", HARNESS_BOUND_S
                )
                failpoints.release(site)
                hang_released.append(site)
            else:
                await _wait_serving(
                    engine, f"recovery after {site}={action}",
                    HARNESS_BOUND_S,
                )

        done, pending = await asyncio.wait(
            tasks.values(), timeout=HARNESS_BOUND_S
        )
        assert not pending, (
            "seed invariant violated: "
            f"{len(pending)} request(s) hung past the "
            f"{HARNESS_BOUND_S:.0f}s harness bound"
        )
        await _wait_serving(engine, "post-chaos engine", HARNESS_BOUND_S)

        ok = retryable = 0
        for i, task in tasks.items():
            status, payload = task.result()
            if status == "ok":
                if payload != baseline[i] and os.environ.get("CHAOS_DEBUG"):
                    rid = f"chaos-{seed}-{i}"
                    for rep_i, e in enumerate(
                        rep.engine for rep in engine._replicas
                    ):
                        for ev in e.recorder.events_for(rid):
                            print("DBG", rep_i, ev)
                assert payload == baseline[i], (
                    f"seed invariant violated: request {i} "
                    f"({specs[i]['kind']}) completed but its streamed "
                    f"tokens diverged from the uncrashed baseline\n"
                    f"  baseline: {baseline[i]}\n  got:      {payload}"
                )
                ok += 1
            else:
                assert isinstance(payload, EngineRestartError), (
                    "seed invariant violated: request "
                    f"{i} terminated with an untyped error: {payload!r}"
                )
                retryable += 1

        # compile discipline: checkpoint/resume rides the fixed-shape
        # per-page programs — across ANY number of checkpoints, pages
        # and resumes, gather/scatter each hold exactly one compiled
        # shape (their first compile may land lazily at the first
        # checkpoint; what must never happen is a SECOND shape), and
        # no other entry point gains a shape the warm phase lacked
        for fn in ("gather_kv", "scatter_kv"):
            fn_shapes = {
                s for s in compile_tracker.shapes() if s[0] == fn
            }
            assert len(fn_shapes) <= 1, (
                "seed invariant violated: checkpoint/resume entry "
                f"point {fn} compiled {len(fn_shapes)} shapes: "
                f"{sorted(fn_shapes)}"
            )
        new_shapes = {
            s for s in compile_tracker.shapes() - warm_shapes
            if s[0] not in ("gather_kv", "scatter_kv")
            and s[0].startswith(("gather", "scatter"))
        }
        assert not new_shapes, (
            "seed invariant violated: unexpected checkpoint/resume "
            f"shapes: {sorted(new_shapes)}"
        )

        restarts = len([
            h for h in (engine.supervisor.restart_history or [])
            if h.get("recovered")
        ])
        resumed = sum(
            h.get("resumed", 0)
            for h in engine.supervisor.restart_history
        )
        if roles:
            # role-aware recovery invariants: the fleet actually handed
            # work off (the warm phase alone guarantees >= 1), the
            # armed mid-handoff kill recovered the PREFILL replica with
            # its role intact, and at least one staged handoff was
            # adopted and resumed rather than lost
            assert engine.handoff_outcomes["completed"] >= 1, (
                "roles seed invariant violated: no handoff completed"
            )
            assert any(
                h.get("recovered") and h.get("replica") == 0
                for h in engine.supervisor.restart_history
            ), (
                "roles seed invariant violated: the prefill replica "
                "was not killed+recovered by the armed handoff fault"
            )
            assert engine._replicas[0].role == "prefill"  # noqa: SLF001
            assert resumed >= 1, (
                "roles seed invariant violated: the mid-handoff kill's "
                "staged records were not adopted and resumed"
            )
        return {
            "seed": seed,
            "dp": dp,
            "roles": list(roles) or None,
            "kv_quantization": kvq,
            "requests": len(specs),
            "ok": ok,
            "retryable": retryable,
            "faults": injected,
            "restarts": restarts,
            "resumed": resumed,
            **({"handoffs": dict(engine.handoff_outcomes)}
               if roles else {}),
        }
    finally:
        # a count=1 fault that never fired must not bleed into the next
        # seed's engine; disarm also frees any still-parked hang thread
        failpoints.disarm()
        try:
            await engine.stop()
        except Exception:  # noqa: BLE001 — teardown is best-effort
            pass


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


async def _cross_host_soak(model_dir: str) -> dict:
    """``--cross-host``: the networked-KV-tier fault family over a real
    loopback TCP fleet (docs/CROSS_HOST.md).

    Two engines in one process — ``A`` prefill-only, ``B`` mixed —
    peered over localhost sockets, exactly the two-process topology's
    wire traffic.  Asserted, against a kvnet-less baseline engine:

    * corrupt-payload: a flipped byte in a remote page blob is a MISS
      (checksum), the span recomputes locally, tokens identical;
    * slow-peer / partition: a peer slower than the timeout (and a
      ``kvnet.get`` failpoint) degrade to the local tiers — the request
      completes token-identically or fails TYPED-retryable, and both
      engines keep serving (a dead remote never stalls the step loop);
    * remote handoff: with the fleet healthy, a request on the
      prefill-only host decodes on the peer, token-identical;
    * machine loss: ``A`` dies mid-decode of a handed-off request —
      ``B`` adopts it, and the union of the tokens streamed before the
      kill with ``B``'s banked tail equals the baseline exactly (zero
      lost outputs).
    """
    from vllm_tgis_adapter_tpu.frontdoor.errors import EngineRestartError
    from vllm_tgis_adapter_tpu.supervisor import failpoints

    prompt = [5 + (i % 40) for i in range(48)]  # 3 full pages at bs=16
    spec = {"kind": "chat", "prompt": prompt, "temperature": 0.0,
            "seed": None, "max_tokens": 12}
    long_spec = {**spec, "max_tokens": 48}

    def _fleet_engine(**kw):  # noqa: ANN003, ANN202
        return _build_engine(
            model_dir,
            # prefix registration demotes prompt pages at prefill
            # commit, making them INDEX-visible without LRU pressure
            enable_prefix_caching=False,
            **kw,
        )

    # ---- uncrashed kvnet-less baseline
    base_engine = _fleet_engine()
    await base_engine.start()
    status, base = await _run_request(base_engine, "xh-base", spec, None)
    assert status == "ok", f"baseline failed: {base!r}"
    status, base_long = await _run_request(
        base_engine, "xh-base-long", long_spec, None
    )
    assert status == "ok", f"long baseline failed: {base_long!r}"
    await base_engine.stop()

    port_a, port_b = _free_port(), _free_port()
    a = _fleet_engine(
        roles=("prefill",),
        kvnet_listen=f"127.0.0.1:{port_a}",
        kvnet_peers=(f"127.0.0.1:{port_b}",), kvnet_node_id="A",
        kvnet_timeout_s=1.0,
    )
    b = _fleet_engine(
        kvnet_listen=f"127.0.0.1:{port_b}",
        kvnet_peers=(f"127.0.0.1:{port_a}",), kvnet_node_id="B",
        kvnet_timeout_s=1.0,
    )
    stats: dict = {}
    consumer = None
    try:
        await a.start()
        await b.start()

        # warm the fleet-shared prefix on B; wait for A's mirror of it
        status, toks = await _run_request(b, "xh-warm", spec, None)
        assert status == "ok" and toks == base, "warm on B diverged"
        for _ in range(200):
            if a.kvnet.peers[0].mirror:
                break
            await asyncio.sleep(0.05)
        assert a.kvnet.peers[0].mirror, (
            "cross-host invariant violated: A never mirrored B's INDEX"
        )

        # ---- fault family: each fault, one request on A (remote
        # prefix fetch from B + remote handoff back to B)
        outcomes: dict[str, str] = {}
        peer = a.kvnet.peers[0]
        for fault in ("corrupt", "slow_peer", "partition", "healthy"):
            if fault == "corrupt":
                peer.corrupt_next = True
            elif fault == "slow_peer":
                peer.delay_s = 2.5  # > kvnet_timeout_s: every RPC times out
            elif fault == "partition":
                failpoints.arm_site("kvnet.get", "raise", 1)
            t0 = time.monotonic()
            status, payload = await asyncio.wait_for(
                _run_request(a, f"xh-{fault}", spec, None),
                timeout=HARNESS_BOUND_S,
            )
            elapsed = time.monotonic() - t0
            if status == "ok":
                assert payload == base, (
                    f"cross-host invariant violated: {fault} request "
                    f"completed but diverged from baseline\n"
                    f"  baseline: {base}\n  got:      {payload}"
                )
                outcomes[fault] = "ok"
            else:
                # a prefill-only host with its one peer unreachable has
                # no decode path — typed-retryable is the ladder floor
                assert fault in ("slow_peer", "partition"), (
                    f"cross-host invariant violated: {fault} request "
                    f"failed ({payload!r}) instead of degrading to the "
                    "local tiers"
                )
                assert isinstance(payload, EngineRestartError), (
                    "cross-host invariant violated: untyped error "
                    f"under {fault}: {payload!r}"
                )
                outcomes[fault] = "retryable"
            assert elapsed < HARNESS_BOUND_S, "fault stalled the loop"
            # corrupt/healthy MUST complete: the remote rung degrades
            # per-page, never per-request
            if fault in ("corrupt", "healthy"):
                assert outcomes[fault] == "ok", (
                    f"{fault} request did not complete"
                )
            peer.delay_s = 0.0
            peer.corrupt_next = False
            failpoints.disarm()
            if fault in ("slow_peer", "partition"):
                # wait for the heartbeat to revive the peer before the
                # next leg (down peers are skipped, not retried inline)
                for _ in range(200):
                    if peer.state == "healthy":
                        break
                    await asyncio.sleep(0.05)
        assert a.kvnet.remote._hits > 0, (  # noqa: SLF001
            "cross-host invariant violated: no remote prefix page was "
            "ever served (the healthy leg should have hit B's mirror)"
        )

        # ---- machine loss: kill A mid-decode of a handed-off request
        got: list[int] = []

        async def _consume() -> None:
            # stream INCREMENTALLY (a real client banks every DELTA as
            # it arrives): tokens A emitted before dying must count —
            # _run_request's end-of-stream return would discard them
            try:
                async for out in a.generate(
                    prompt=None,
                    sampling_params=_params(long_spec),
                    request_id="xh-lost",
                    prompt_token_ids=list(long_spec["prompt"]),
                ):
                    got.extend(out.outputs[0].token_ids)
            except asyncio.CancelledError:
                pass
            except Exception:  # noqa: BLE001 — A dying mid-stream is the point
                pass

        # hold B's replica lock so the cross-host resume blocks right
        # after queue registration — the kill below lands before any
        # decode step, deterministically
        async with b._replicas[0].lock:  # noqa: SLF001
            consumer = asyncio.ensure_future(_consume())
            for _ in range(5000):
                if "xh-lost" in b._queues:  # noqa: SLF001
                    break
                await asyncio.sleep(0.005)
            assert "xh-lost" in b._queues, (  # noqa: SLF001
                "handoff never registered on the survivor"
            )
            await a.kvnet.stop()  # the machine-loss event
            await asyncio.sleep(0.2)
        deadline = time.monotonic() + HARNESS_BOUND_S
        while time.monotonic() < deadline:
            if "xh-lost" in b.kvnet.completed:
                break
            await asyncio.sleep(0.1)
        tail: list[int] = []
        for out in b.kvnet.completed.get("xh-lost", []):
            tail.extend(out.outputs[0].token_ids)
        assert got + tail == base_long, (
            "cross-host invariant violated: streamed+banked tokens "
            "after machine loss diverged from baseline\n"
            f"  baseline ({len(base_long)}): {base_long}\n"
            f"  streamed ({len(got)}) + banked ({len(tail)}): "
            f"{got + tail}"
        )
        stats = {
            "mode": "cross_host",
            "fault_outcomes": outcomes,
            "remote_hits": a.kvnet.remote._hits,  # noqa: SLF001
            "loss_streamed": len(got),
            "loss_banked": len(tail),
            "baseline_tokens": len(base_long),
        }
        return stats
    finally:
        failpoints.disarm()
        if consumer is not None:
            consumer.cancel()
            await asyncio.gather(consumer, return_exceptions=True)
        for eng in (a, b):
            try:
                await eng.stop()
            except Exception:  # noqa: BLE001 — teardown is best-effort
                pass


def _enable_persistent_compile_cache() -> None:
    """A rebuilt engine's recompiles should cost what a TPU restart
    with the persistent compilation cache pays, not a cold build."""
    import jax

    from vllm_tgis_adapter_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seeds", type=int, default=DEFAULT_SEEDS,
                        help="number of seeds (schedules) to run")
    parser.add_argument("--base-seed", type=int,
                        default=DEFAULT_BASE_SEED)
    parser.add_argument("--seed", type=int, default=None,
                        help="run exactly one seed (reproduce a CI run)")
    parser.add_argument("--cross-host", action="store_true",
                        help="run the networked-KV-tier fault family "
                             "(corrupt/slow-peer/partition + "
                             "kill-mid-decode machine loss) over a "
                             "loopback TCP fleet — docs/CROSS_HOST.md")
    args = parser.parse_args(argv)

    _enable_persistent_compile_cache()
    model_dir, adapter_dir = _build_fixtures()

    if args.cross_host:
        try:
            stats = asyncio.run(_cross_host_soak(model_dir))
        except AssertionError as e:
            print(f"chaos_soak: cross-host FAILED: {e}")
            return 1
        print(
            "chaos_soak: cross-host green — faults "
            f"{stats['fault_outcomes']} "
            f"remote_hits={stats['remote_hits']} machine-loss "
            f"streamed+banked={stats['loss_streamed']}+"
            f"{stats['loss_banked']} == "
            f"baseline={stats['baseline_tokens']}"
        )
        return 0

    seeds = (
        [args.seed]
        if args.seed is not None
        else [args.base_seed + i for i in range(args.seeds)]
    )
    t0 = time.monotonic()
    failures = 0
    for seed in seeds:
        try:
            stats = asyncio.run(_run_seed(seed, model_dir, adapter_dir))
        except AssertionError as e:
            failures += 1
            print(f"chaos_soak: seed {seed} FAILED: {e}")
            continue
        roles_note = (
            f" roles={','.join(stats['roles'])} "
            f"handoffs={stats['handoffs']['completed']}"
            if stats.get("roles")
            else ""
        )
        print(
            f"chaos_soak: seed {stats['seed']} ok  dp={stats['dp']} "
            f"kvq={stats['kv_quantization']} "
            f"requests={stats['requests']} "
            f"(ok={stats['ok']} retryable={stats['retryable']}) "
            f"faults=[{', '.join(stats['faults'])}] "
            f"restarts={stats['restarts']} resumed={stats['resumed']}"
            f"{roles_note}"
        )
    elapsed = time.monotonic() - t0
    if elapsed > BUDGET_S:
        print(
            f"chaos_soak: WARNING — {elapsed:.0f}s exceeded the "
            f"{BUDGET_S:.0f}s budget (all {len(seeds)} seed(s) still "
            "ran; nothing was trimmed)"
        )
    if failures:
        print(
            f"chaos_soak: {failures}/{len(seeds)} seed(s) violated an "
            "invariant — reproduce with "
            "`python tools/chaos_soak.py --seed <n>`"
        )
        return 1
    print(
        f"chaos_soak: all {len(seeds)} seed(s) green in {elapsed:.0f}s "
        "(one terminal outcome per request, token-identical resumes, "
        "no harness-bound hangs, zero new checkpoint/resume shapes)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
