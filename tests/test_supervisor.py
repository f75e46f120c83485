"""Self-healing engine: failpoint-driven supervised restart, request
replay, and crash-loop containment (docs/RECOVERY.md) — the chaos gate
(``nox -s chaos_check``).

Layers: failpoint/lifecycle units, then real-engine recovery on the
tiny fixture model — every scenario injects its fault deterministically
through ``supervisor/failpoints.py`` rather than hoping for a real one:
step-loop crash with parked + waiting + mid-decode requests (the
acceptance scenario), XLA-OOM-classified death, watchdog-declared stall
with ``--watchdog-action=restart``, death *during* recovery, and the
crash-loop circuit breaker.
"""

from __future__ import annotations

import asyncio
import re
import time

import pytest

from vllm_tgis_adapter_tpu.supervisor import failpoints


@pytest.fixture(autouse=True)
def _clean_failpoints():
    """Armed failpoints must never leak across tests (a ``hang`` left
    armed would block a worker thread into interpreter shutdown)."""
    yield
    failpoints.disarm()


def _sample(text: str, name: str, labels: tuple[str, ...] = ()) -> float:
    for line in text.splitlines():
        m = re.match(rf"^{re.escape(name)}(\{{[^}}]*\}})? (\S+)$", line)
        if m and all(lbl in (m.group(1) or "") for lbl in labels):
            return float(m.group(2))
    return 0.0


def _scrape() -> str:
    from vllm_tgis_adapter_tpu import metrics

    return metrics.render().decode()


# ------------------------------------------------------------ failpoint units


def test_failpoint_spec_parsing():
    assert failpoints.parse_spec(
        "core.plan_step=raise,core.wait_step=oom:2,"
        "scheduler.schedule=raise:forever"
    ) == [
        ("core.plan_step", "raise", 1),
        ("core.wait_step", "oom", 2),
        ("scheduler.schedule", "raise", failpoints.FOREVER),
    ]
    for bad in (
        "core.plan_step",            # no action
        "core.plan_step=explode",    # unknown action
        "not.a.site=raise",          # unknown site
        "core.plan_step=raise:0",    # count < 1
        "core.plan_step=hang",       # hang at an event-loop site
    ):
        with pytest.raises(ValueError):
            failpoints.parse_spec(bad)
    with pytest.raises(ValueError, match="event loop"):
        failpoints.arm_site("scheduler.schedule", "hang")


def test_failpoint_fire_counts_and_disarm():
    # unarmed: zero-cost no-op
    failpoints.fire("core.plan_step")
    assert not failpoints.is_armed()

    failpoints.arm("core.plan_step=raise:2")
    assert failpoints.is_armed("core.plan_step")
    for _ in range(2):
        with pytest.raises(failpoints.FailpointError):
            failpoints.fire("core.plan_step")
    failpoints.fire("core.plan_step")  # count exhausted: no-op
    assert failpoints.fired("core.plan_step") == 2

    failpoints.arm_site("core.wait_step", "oom")
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        failpoints.fire("core.wait_step")
    failpoints.disarm()
    assert not failpoints.is_armed()
    failpoints.fire("core.wait_step")  # disarmed: no-op


def test_failpoint_hang_rehangs_after_release():
    """A multi-count hang must park on EVERY fire — release() freeing
    one waiter must not let later fires fall through the set event."""
    import threading

    failpoints.arm_site("core.wait_step", "hang", 2)
    done = []

    def worker():
        failpoints.fire("core.wait_step")
        done.append(1)

    t1 = threading.Thread(target=worker)
    t1.start()
    time.sleep(0.1)
    assert not done  # parked
    failpoints.release("core.wait_step")
    t1.join(5)
    assert len(done) == 1
    t2 = threading.Thread(target=worker)
    t2.start()
    time.sleep(0.2)
    assert len(done) == 1  # second fire re-hung, did not fall through
    failpoints.release("core.wait_step")
    t2.join(5)
    assert len(done) == 2


def test_failpoint_oom_classifies_as_device_oom():
    from vllm_tgis_adapter_tpu.frontdoor.errors import (
        DeviceOOMError,
        wrap_engine_error,
    )

    failpoints.arm_site("core.wait_step", "oom")
    with pytest.raises(RuntimeError) as exc_info:
        failpoints.fire("core.wait_step")
    assert isinstance(wrap_engine_error(exc_info.value), DeviceOOMError)


# ------------------------------------------------------------ lifecycle units


def test_engine_lifecycle_fallback_for_boolean_engines():
    from vllm_tgis_adapter_tpu.supervisor.lifecycle import (
        LIFECYCLE_DEAD,
        LIFECYCLE_RECOVERING,
        LIFECYCLE_SERVING,
        engine_is_dead,
        engine_lifecycle,
    )

    class Fake:
        errored = False
        is_running = True

    fake = Fake()
    assert engine_lifecycle(fake) == LIFECYCLE_SERVING
    fake.errored = True
    fake.is_running = False
    assert engine_lifecycle(fake) == LIFECYCLE_DEAD
    assert engine_is_dead(fake)
    # an explicit lifecycle attribute wins over the booleans
    fake.lifecycle = LIFECYCLE_RECOVERING
    assert engine_lifecycle(fake) == LIFECYCLE_RECOVERING
    assert not engine_is_dead(fake)


def test_restart_error_is_retryable_unavailable():
    from vllm_tgis_adapter_tpu.frontdoor.errors import (
        EngineRestartError,
        classify,
        wrap_engine_error,
    )

    err = EngineRestartError("restarting", retry_after_s=2.0)
    # never rewrapped, even though 'RESOURCE' could appear in a message
    assert wrap_engine_error(err) is err
    d = classify(err)
    assert d.grpc_code == "UNAVAILABLE"
    assert d.http_status == 503
    assert d.retry_after_s == 2.0


def test_healthcheck_exit_codes_cover_lifecycle_states():
    pytest.importorskip("grpc")
    import shutil

    if shutil.which("protoc") is None:
        pytest.skip("protoc not available")
    from vllm_tgis_adapter_tpu.grpc.health import DRAINING
    from vllm_tgis_adapter_tpu.grpc.pb.health_pb2 import HealthCheckResponse
    from vllm_tgis_adapter_tpu.healthcheck import exit_code_for

    assert exit_code_for(HealthCheckResponse.SERVING) == 0
    assert exit_code_for(DRAINING) == 2
    assert exit_code_for(HealthCheckResponse.NOT_SERVING) == 3
    assert exit_code_for(HealthCheckResponse.UNKNOWN) == 1


# -------------------------------------------------------------- real engines


def _build_engine(tiny_model_dir, *, max_num_seqs=2, num_blocks=64,
                  max_engine_restarts=3, window_s=300.0, backoff_s=0.02,
                  watchdog_deadline_s=0.0, watchdog_action="snapshot",
                  dump_dir=None, frontdoor=None, frontdoor_enabled=True,
                  dp=1, tier_gb=0.0, decode_resume=True):
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        FrontdoorConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16, num_blocks=num_blocks, cache_dtype=mcfg.dtype
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=max_num_seqs, prefill_buckets=(32, 64)
        ),
        parallel_config=ParallelConfig(dp_replicas=dp),
        lora_config=LoRAConfig(),
        kv_host_cache_gb=tier_gb,
        decode_resume=decode_resume,
        watchdog_deadline_s=watchdog_deadline_s,
        watchdog_action=watchdog_action,
        dump_dir=dump_dir,
        max_engine_restarts=max_engine_restarts,
        engine_restart_window_s=window_s,
        engine_restart_backoff_s=backoff_s,
        frontdoor=frontdoor
        or FrontdoorConfig(enabled=frontdoor_enabled),
    )
    return AsyncLLMEngine.from_config(config)


async def _collect(engine, request_id, *, prompt_ids, max_tokens=8,
                   tenant_id=None):
    """Drive one request to its end; returns ('ok', final) or
    ('err', exception)."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    final = None
    try:
        async for out in engine.generate(
            prompt=None,
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True
            ),
            request_id=request_id,
            prompt_token_ids=list(prompt_ids),
            tenant_id=tenant_id,
        ):
            final = out
        return ("ok", final)
    except BaseException as e:  # noqa: BLE001 — the error IS the result here
        return ("err", e)


async def _wait_for(cond, timeout=20.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


def _output_tokens(engine, request_id) -> int:
    for rep in engine._replicas:
        seq = rep.engine._seqs.get(request_id)
        if seq is not None:
            return seq.num_output_tokens
    return -1


def test_step_crash_replays_preprefill_and_fails_middecode(tiny_model_dir):
    """THE acceptance scenario: a step-loop crash with one mid-decode,
    one scheduler-waiting, and one front-door-parked request yields

    * zero lost pre-prefill requests — the waiting and parked requests
      both complete with token-identical outputs to an uncrashed run,
    * a retryable EngineRestartError (UNAVAILABLE-classified, with a
      Retry-After hint) for the mid-decode request,
    * lifecycle SERVING → (NOT_)SERVING → SERVING via the supervisor's
      listener (what the gRPC health servicer mirrors),
    * engine_restarts_total{cause=step_loop} and
      requests_replayed_total incremented, and a 'restart' event in the
      flight recorder.
    """
    from vllm_tgis_adapter_tpu.frontdoor.errors import (
        EngineRestartError,
        classify,
    )

    # max_num_seqs=1: one running slot, so 'b' must wait in the engine
    # and 'c' must park behind the size-1 admission window
    engine = _build_engine(tiny_model_dir, max_num_seqs=1)
    states = []
    engine.supervisor.add_listener(states.append)
    restarts0 = _sample(
        _scrape(), "tgis_tpu_engine_restarts_total",
        ('cause="step_loop"',),
    )
    replayed0 = _sample(_scrape(), "tgis_tpu_requests_replayed_total")

    prompt_a = list(range(3, 15))
    prompt_b = list(range(5, 17))
    prompt_c = list(range(7, 19))

    async def scenario():
        # baselines on the same (pre-crash) engine: greedy decoding is
        # deterministic, so these are the "correct outputs" replay must
        # reproduce
        ref_b = await _collect(engine, "ref-b", prompt_ids=prompt_b,
                               max_tokens=6)
        ref_c = await _collect(engine, "ref-c", prompt_ids=prompt_c,
                               max_tokens=6)
        assert ref_b[0] == "ok" and ref_c[0] == "ok"

        a_task = asyncio.create_task(
            _collect(engine, "a", prompt_ids=prompt_a, max_tokens=64)
        )
        await _wait_for(lambda: _output_tokens(engine, "a") >= 1,
                        what="request a to emit a token")
        # freeze the step loop mid-decode (worker thread parks inside
        # wait_step) so b/c land deterministically while 'a' holds >= 1
        # emitted token
        failpoints.arm_site("core.wait_step", "hang")
        await asyncio.sleep(0.05)
        b_task = asyncio.create_task(
            _collect(engine, "b", prompt_ids=prompt_b, max_tokens=6)
        )
        c_task = asyncio.create_task(
            _collect(engine, "c", prompt_ids=prompt_c, max_tokens=6)
        )
        await _wait_for(
            lambda: sum(
                len(rep.engine.scheduler.waiting)
                for rep in engine._replicas
            ) >= 1 and engine.frontdoor.parked >= 1,
            what="b engine-waiting and c parked",
        )
        assert _output_tokens(engine, "b") == 0
        # the crash: next planning phase raises, exactly once
        failpoints.arm_site("core.plan_step", "raise", 1)
        failpoints.release("core.wait_step")

        status_a, err_a = await a_task
        status_b, out_b = await b_task
        status_c, out_c = await c_task
        await _wait_for(lambda: engine.lifecycle == "serving",
                        what="recovery to finish")
        # liveness observations must precede stop() (which tears both
        # down by design)
        live = {
            "is_running": engine.is_running,
            "stats_alive": engine._stats_task is not None
            and not engine._stats_task.done(),
        }
        await engine.stop()
        return (status_a, err_a), (status_b, out_b), (status_c, out_c), (
            ref_b[1], ref_c[1],
        ), live

    (status_a, err_a), (status_b, out_b), (status_c, out_c), refs, live = (
        asyncio.run(scenario())
    )

    # mid-decode: retryable UNAVAILABLE with a Retry-After hint
    assert status_a == "err"
    assert isinstance(err_a, EngineRestartError)
    disposition = classify(err_a)
    assert disposition.grpc_code == "UNAVAILABLE"
    assert disposition.retry_after_s is not None

    # pre-prefill: replayed to completion with correct outputs
    assert status_b == "ok" and status_c == "ok"
    assert out_b.outputs[0].token_ids == refs[0].outputs[0].token_ids
    assert out_c.outputs[0].token_ids == refs[1].outputs[0].token_ids
    assert len(out_b.outputs[0].token_ids) == 6

    # lifecycle round trip: SERVING → recovering → SERVING
    assert states[0] == "recovering"
    assert states[-1] == "serving"
    assert not engine.errored
    assert live["is_running"]

    # observability: counters, history, flight recorder
    assert _sample(
        _scrape(), "tgis_tpu_engine_restarts_total",
        ('cause="step_loop"',),
    ) == restarts0 + 1
    assert (
        _sample(_scrape(), "tgis_tpu_requests_replayed_total")
        >= replayed0 + 1
    )
    history = engine.supervisor.restart_history
    assert len(history) == 1 and history[0]["recovered"]
    assert history[0]["replayed"] >= 1 and history[0]["failed"] == 1
    kinds = {e["kind"] for e in engine.engine.recorder.events()}
    assert "restart" in kinds
    # the stats loop survived the death (no one-way latch)
    assert live["stats_alive"]


def test_oom_death_recovers_with_cause_label(tiny_model_dir):
    """An XLA-OOM-shaped death classifies as DeviceOOMError, restarts
    under the 'oom' cause, and the zero-token request replays to full
    completion."""
    engine = _build_engine(tiny_model_dir)
    oom0 = _sample(
        _scrape(), "tgis_tpu_engine_restarts_total", ('cause="oom"',)
    )

    async def scenario():
        # fires on the first wait (the prefill wave): zero tokens
        # emitted yet, so the request is replay-safe
        failpoints.arm_site("core.wait_step", "oom", 1)
        result = await _collect(
            engine, "r", prompt_ids=list(range(3, 12)), max_tokens=5
        )
        await engine.stop()
        return result

    status, final = asyncio.run(scenario())
    assert status == "ok"
    assert len(final.outputs[0].token_ids) == 5
    assert _sample(
        _scrape(), "tgis_tpu_engine_restarts_total", ('cause="oom"',)
    ) == oom0 + 1
    assert engine.supervisor.restart_history[0]["cause"] == "oom"


def test_rebuild_can_free_the_dead_engines_pool(tiny_model_dir):
    """A dispatch that dies with the KV pool among its frame's locals
    (the host-tier gather, as on the v5e in PR 23) must not pin that pool
    through its traceback: on a chip the pool fills HBM, and the rebuild
    allocates its own.  By the time ``_rebuild`` has dropped
    ``runner.caches`` nothing may still reference the old arrays."""
    import weakref

    engine = _build_engine(tiny_model_dir, tier_gb=0.01)
    runner = engine.engine.runner
    refs: list = []

    def dying_gather(slots):
        k_cache, v_cache = runner.caches  # the real frame's locals
        refs[:] = [weakref.ref(k_cache), weakref.ref(v_cache)]
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Error loading program 'jit_gather_kv_page'"
        )

    runner.gather_kv_block = dying_gather
    alive_after_release: list = []
    rebuild = engine.supervisor._rebuild

    def spy(old):
        # what _rebuild does first; plain reference counting must free
        # the arrays here, before the new pool is allocated (no
        # gc.collect: a cycle would hold them across the allocation)
        old.runner.caches = None
        alive_after_release[:] = [ref() is not None for ref in refs]
        return rebuild(old)

    engine.supervisor._rebuild = spy

    async def scenario():
        result = await _collect(
            engine, "r", prompt_ids=list(range(3, 60)), max_tokens=5
        )
        await engine.stop()
        return result

    status, _ = asyncio.run(scenario())
    assert status == "ok"
    assert alive_after_release == [False, False]


def test_watchdog_restart_action_recovers_stuck_dispatch(
    tiny_model_dir, tmp_path
):
    """--watchdog-action=restart: a stuck device dispatch (hang
    failpoint in wait_step) is declared a stall, the diagnostic
    snapshot is written FIRST, and the supervisor then rebuilds the
    engine; the wedged request (zero tokens) replays to completion."""
    engine = _build_engine(
        tiny_model_dir,
        watchdog_deadline_s=0.3,
        watchdog_action="restart",
        dump_dir=str(tmp_path),
    )
    stall0 = _sample(
        _scrape(), "tgis_tpu_engine_restarts_total", ('cause="stall"',)
    )

    async def scenario():
        failpoints.arm_site("core.wait_step", "hang", 1)
        try:
            # the first prefill wave wedges; the watchdog check interval
            # is 1s, so the stall verdict lands within ~2s
            result = await asyncio.wait_for(
                _collect(engine, "stuck", prompt_ids=list(range(3, 12)),
                         max_tokens=4),
                timeout=30,
            )
        finally:
            failpoints.release("core.wait_step")
        await engine.stop()
        return result

    status, final = asyncio.run(scenario())
    assert status == "ok"
    assert len(final.outputs[0].token_ids) == 4
    assert engine.watchdog.stalls == 1
    # snapshot before restart: the dump file exists
    assert engine.watchdog.last_dump_path is not None
    assert list(tmp_path.glob("stall-*.json"))
    assert _sample(
        _scrape(), "tgis_tpu_engine_restarts_total", ('cause="stall"',)
    ) == stall0 + 1
    assert engine.supervisor.restart_history[0]["cause"] == "stall"


def test_death_during_recovery_retries_until_success(tiny_model_dir):
    """A rebuild that itself dies (supervisor.rebuild failpoint) counts
    as another attempt and is retried; the request still completes."""
    engine = _build_engine(tiny_model_dir, max_engine_restarts=4)

    async def scenario():
        failpoints.arm_site("core.plan_step", "raise", 1)
        failpoints.arm_site("supervisor.rebuild", "raise", 1)
        result = await _collect(
            engine, "r", prompt_ids=list(range(3, 12)), max_tokens=4
        )
        await _wait_for(lambda: engine.lifecycle == "serving",
                        what="recovery to finish")
        await engine.stop()
        return result

    status, final = asyncio.run(scenario())
    assert status == "ok"
    assert len(final.outputs[0].token_ids) == 4
    history = engine.supervisor.restart_history
    assert len(history) == 2
    assert history[0]["recovered"] is False
    assert history[1]["recovered"] is True
    assert history[1]["cause"] == "recovery_failure"


def test_crash_loop_trips_circuit_breaker(tiny_model_dir, tmp_path,
                                          monkeypatch):
    """Repeated crashes exceed --max-engine-restarts within the window:
    the breaker escalates to terminal death with the restart history in
    the termination log, and the engine reports lifecycle 'dead'."""
    termination_log = tmp_path / "termination-log"
    termination_log.touch()
    monkeypatch.setenv("TERMINATION_LOG_DIR", str(termination_log))

    from vllm_tgis_adapter_tpu.engine.async_llm import EngineDeadError

    engine = _build_engine(tiny_model_dir, max_engine_restarts=2)

    async def scenario():
        failpoints.arm_site(
            "core.plan_step", "raise", failpoints.FOREVER
        )
        status, err = await _collect(
            engine, "doomed", prompt_ids=list(range(3, 12)), max_tokens=4
        )
        await _wait_for(lambda: engine.dead_event.is_set(),
                        what="terminal death")
        # new work is refused with the terminal error, immediately
        refused = None
        try:
            await _raise_on_err(engine)
        except EngineDeadError as e:
            refused = e
        await engine.stop()
        return status, err, refused

    status, err, refused = asyncio.run(scenario())
    assert status == "err"
    assert isinstance(err, EngineDeadError)
    assert "crash-loop" in str(err)
    assert engine.lifecycle == "dead"
    assert engine.errored
    assert isinstance(refused, EngineDeadError)
    # the breaker allowed exactly max_restarts attempts
    assert len(engine.supervisor.restart_history) == 2
    contents = termination_log.read_text()
    assert "crash-loop" in contents
    assert "restart history" in contents
    assert "cause=step_loop" in contents


async def _raise_on_err(engine):
    async for _ in engine.generate(
        prompt=None,
        sampling_params=None,
        request_id="after-death",
        prompt_token_ids=list(range(3, 8)),
    ):
        pass


def test_recovering_without_frontdoor_refuses_retryable(tiny_model_dir):
    """--disable-frontdoor has nowhere to park arrivals mid-recovery:
    generate() refuses with the retryable EngineRestartError (never the
    terminal dead error), and HTTP /health serves 503 + Retry-After."""
    import sys

    from vllm_tgis_adapter_tpu.frontdoor.errors import EngineRestartError
    from vllm_tgis_adapter_tpu.http import HttpRequest, build_http_server
    from vllm_tgis_adapter_tpu.tgis_utils.args import (
        make_parser,
        postprocess_tgis_args,
    )

    engine = _build_engine(tiny_model_dir, frontdoor_enabled=False)
    assert engine.frontdoor is None

    old_argv = sys.argv
    sys.argv = ["t", "--model", tiny_model_dir, "--max-model-len", "512",
                "--dtype", "float32"]
    try:
        args = postprocess_tgis_args(make_parser().parse_args())
    finally:
        sys.argv = old_argv
    app = build_http_server(args, engine)

    async def scenario():
        await engine.start()
        # hold recovery open inside the rebuild so the RECOVERING state
        # is observable from the outside
        failpoints.arm_site("core.plan_step", "raise", 1)
        failpoints.arm_site("supervisor.rebuild", "hang", 1)
        task = asyncio.create_task(_collect(
            engine, "victim", prompt_ids=list(range(3, 12)), max_tokens=4
        ))
        await _wait_for(lambda: engine.lifecycle == "recovering",
                        what="recovery to start")
        with pytest.raises(EngineRestartError):
            async for _ in engine.generate(
                prompt=None, sampling_params=None,
                request_id="refused",
                prompt_token_ids=list(range(3, 8)),
            ):
                pass
        health = await app.dispatch(HttpRequest("GET", "/health", {}, b""))
        failpoints.release("supervisor.rebuild")
        status, final = await task
        await _wait_for(lambda: engine.lifecycle == "serving",
                        what="recovery to finish")
        healthy = await app.dispatch(HttpRequest("GET", "/health", {}, b""))
        await engine.stop()
        return health, status, final, healthy

    health, status, final, healthy = asyncio.run(scenario())
    assert health.status == 503
    assert health.headers["retry-after"] == "2"
    # the zero-token victim replayed to completion regardless
    assert status == "ok" and len(final.outputs[0].token_ids) == 4
    assert healthy.status == 200


def test_parked_requests_survive_recovery_without_shedding(tiny_model_dir):
    """Recovery PAUSES the front door rather than draining it: parked
    requests are neither failed nor shed, and complete after the
    restart — the 'fleet queue survives one replica's fault' property."""
    engine = _build_engine(tiny_model_dir, max_num_seqs=1)
    shed0 = engine.frontdoor.shed_total if engine.frontdoor else 0

    async def scenario():
        a_task = asyncio.create_task(_collect(
            engine, "a", prompt_ids=list(range(3, 15)), max_tokens=48
        ))
        await _wait_for(lambda: _output_tokens(engine, "a") >= 1,
                        what="request a to emit a token")
        failpoints.arm_site("core.wait_step", "hang")
        await asyncio.sleep(0.05)
        parked = [
            asyncio.create_task(_collect(
                engine, f"p{i}", prompt_ids=list(range(4 + i, 14 + i)),
                max_tokens=4,
            ))
            for i in range(3)
        ]
        await _wait_for(lambda: engine.frontdoor.parked >= 2,
                        what="requests parked")
        failpoints.arm_site("core.plan_step", "raise", 1)
        failpoints.release("core.wait_step")
        await a_task
        results = await asyncio.gather(*parked)
        await engine.stop()
        return results

    results = asyncio.run(scenario())
    assert all(status == "ok" for status, _ in results)
    assert all(len(out.outputs[0].token_ids) == 4 for _, out in results)
    assert engine.frontdoor.shed_total == shed0  # pause, not drain
    assert not engine.frontdoor.paused  # resumed after recovery


def test_debug_state_reports_supervisor_section(tiny_model_dir):
    engine = _build_engine(tiny_model_dir)
    state = engine.debug_state()
    assert state["engine"]["lifecycle"] == "serving"
    sup = state["supervisor"]
    assert sup is not None
    assert sup["restarts"] == 0 and sup["recovering"] is False
    # unsupervised engines report the section as null, not missing
    engine2 = _build_engine(tiny_model_dir, max_engine_restarts=0)
    assert engine2.supervisor is None
    assert engine2.debug_state()["supervisor"] is None


# ------------------------------------------- dp fleet: partial outage


def test_supervisor_restart_budget_is_per_replica(tiny_model_dir):
    """The crash-loop breaker budgets PER REPLICA: independent transient
    faults on different replicas must never pool into an escalation
    that kills healthy replicas (docs/SCALING.md — the pod dies only
    when ONE replica crash-loops or the last replica dies)."""
    engine = _build_engine(tiny_model_dir, max_engine_restarts=2)
    sup = engine.supervisor
    now = time.monotonic()
    assert sup._recent_attempts(0, now) == 0
    sup._attempt_times[0] = [now, now]
    assert sup._recent_attempts(0, now) == 2  # replica 0 exhausted
    assert sup._recent_attempts(1, now) == 0  # replica 1 budget intact
    # stamps age out of the sliding window per replica
    sup._attempt_times[0] = [now - sup.window_s - 1.0]
    assert sup._recent_attempts(0, now) == 0


def test_fleet_serving_hook_reports_true_empty_set(tiny_model_dir):
    """The front door's serving_replicas_fn must report the TRUE
    (possibly empty) serving set — a full outage falls back to the
    capacity prior instead of summing dead replicas' stale EWMAs."""
    from vllm_tgis_adapter_tpu.frontdoor.admission import _ReplicaRate

    engine = _build_engine(tiny_model_dir, dp=2)
    fd = engine.frontdoor
    stale = _ReplicaRate()
    stale.rate = 9999.0
    fd._rep_rates = {0: stale}
    for rep in engine._replicas:
        rep.serving = False
    assert fd._serving_replicas() == frozenset()
    assert fd._throughput() != 9999.0  # prior, not the dead EWMA


def test_dp_replica_death_replays_cross_replica_with_bounded_ttft(
    tiny_model_dir,
):
    """ISSUE 7 chaos acceptance (docs/SCALING.md): replica 0 dies
    mid-load on a dp=2 fleet and recovery is a CAPACITY LOSS, not an
    outage —

    * replica 0's zero-token waiting request replays token-identically
      onto replica 1 BEFORE the rebuild finishes (cross-replica replay),
    * its mid-decode request fails retryable (EngineRestartError),
    * lifecycle stays ``serving``, the front door never pauses, and
      every placement during the recovery window lands on replica 1,
    * replica 1's own traffic keeps flowing: TTFT p99 of probe requests
      during recovery stays within 2x the steady-state baseline,
    * replica 0 re-admits to placement once rebuilt.

    The rebuild is held open with the ``supervisor.rebuild`` hang
    failpoint so the recovery window is deterministic, and the death is
    injected into replica 0's OWN engine (a blocking wait_step that
    raises on release) so the fault targets exactly one replica.
    """
    import threading

    engine = _build_engine(
        tiny_model_dir, dp=2, max_num_seqs=2, num_blocks=128,
        backoff_s=0.0,
    )
    replayed0 = _sample(_scrape(), "tgis_tpu_requests_replayed_total")

    prompt_bg = list(range(3, 15))
    prompt_w = list(range(7, 19))
    prompt_p = list(range(9, 17))
    gate = threading.Event()

    async def probe(tag, i, ttfts):
        status, final = await _collect(
            engine, f"probe-{tag}-{i}", prompt_ids=prompt_p, max_tokens=2
        )
        assert status == "ok"
        m = final.metrics
        ttfts.append(m.first_token_time - m.arrival_time)

    async def scenario():
        # reference output for the to-be-replayed request (greedy is
        # deterministic and replicas share weights, so any replica
        # serves as the oracle)
        ref_w = await _collect(engine, "ref-w", prompt_ids=prompt_w,
                               max_tokens=6)
        assert ref_w[0] == "ok"

        # one long decode per replica; whichever replica takes bg0 is
        # the VICTIM (tenant "vic" pins later traffic to it), the other
        # stays healthy
        bg0_task = asyncio.create_task(_collect(
            engine, "bg0", prompt_ids=prompt_bg, max_tokens=400,
            tenant_id="vic",
        ))
        await _wait_for(lambda: _output_tokens(engine, "bg0") >= 1,
                        what="bg0 decoding")
        victim = engine._owner["bg0"]
        healthy = next(
            rep for rep in engine._replicas if rep is not victim
        )
        # metrics are process-global: earlier tests in this file restart
        # replica 0 too, so snapshot the victim's label series here
        restarts0 = _sample(
            _scrape(), "tgis_tpu_engine_restarts_total",
            ('cause="step_loop"', f'replica="{victim.index}"'),
        )
        bg1_task = asyncio.create_task(_collect(
            engine, "bg1", prompt_ids=prompt_bg, max_tokens=400
        ))
        await _wait_for(lambda: _output_tokens(engine, "bg1") >= 1,
                        what="bg1 decoding")
        assert engine._owner["bg1"] is healthy

        # freeze the victim's step loop: its next blocking result pull
        # parks on the gate, then dies when the gate fires — a replica-
        # targeted equivalent of the core.wait_step hang+raise combo
        orig_wait = victim.engine.wait_step

        parked = threading.Event()

        def blocking_wait(plan, prepared, handle):
            parked.set()
            if not gate.wait(timeout=60):
                return orig_wait(plan, prepared, handle)
            raise failpoints.FailpointError(
                "failpoint core.wait_step: injected replica death"
            )

        victim.engine.wait_step = blocking_wait
        # the loop plans a successor BEFORE it pulls a result, so it may
        # still admit what arrives until it is parked: send w only then
        await _wait_for(parked.is_set, what="the victim's loop to park")
        # w lands on the frozen victim (tenant stickiness) and stays a
        # zero-token waiting request — the replay-safe class
        w_task = asyncio.create_task(_collect(
            engine, "w", prompt_ids=prompt_w, max_tokens=6,
            tenant_id="vic",
        ))
        await _wait_for(
            lambda: "w" in engine._owner
            and len(victim.engine.scheduler.waiting) >= 1,
            what="w waiting on the victim replica",
        )
        assert engine._owner["w"] is victim
        assert _output_tokens(engine, "w") == 0

        # steady-state TTFT baseline: the healthy replica serving its
        # long decode plus one probe at a time — the exact conditions
        # the recovery probes see
        ttft_base: list[float] = []
        for i in range(6):
            await probe("base", i, ttft_base)

        # hold the rebuild open, then fire the death
        failpoints.arm_site("supervisor.rebuild", "hang")
        gate.set()
        await _wait_for(lambda: not victim.serving,
                        what="victim replica quiesced")
        status_bg0, err_bg0 = await bg0_task
        # cross-replica replay happens BEFORE the (hung) rebuild: w
        # completes while the victim is still down
        status_w, out_w = await w_task

        # partial outage invariants, observed mid-recovery
        mid = {
            "lifecycle": engine.lifecycle,
            "is_running": engine.is_running,
            "paused": engine.frontdoor.paused,
            "placed_before": dict(engine.router.placed_by_replica),
        }
        ttft_rec: list[float] = []
        for i in range(6):
            await probe("rec", i, ttft_rec)
        placed_during = {
            k: v - mid["placed_before"].get(k, 0)
            for k, v in engine.router.placed_by_replica.items()
            if v - mid["placed_before"].get(k, 0)
        }

        # let the rebuild finish; the victim re-admits to placement
        failpoints.release("supervisor.rebuild")
        await _wait_for(
            lambda: victim.serving
            and engine.supervisor.restart_history
            and engine.supervisor.restart_history[-1].get("recovered"),
            what="victim replica re-admitted",
        )
        status_bg1, out_bg1 = await bg1_task
        await engine.stop()
        return (status_bg0, err_bg0), (status_w, out_w), ref_w[1], (
            status_bg1, out_bg1
        ), mid, placed_during, ttft_base, ttft_rec, (
            victim.index, healthy.index, restarts0
        )

    (
        (status_bg0, err_bg0), (status_w, out_w), ref_w,
        (status_bg1, out_bg1), mid, placed_during, ttft_base, ttft_rec,
        (victim_idx, healthy_idx, restarts0),
    ) = asyncio.run(scenario())

    # zero requests lost: mid-decode retryable, zero-token replayed
    from vllm_tgis_adapter_tpu.frontdoor.errors import EngineRestartError

    assert status_bg0 == "err" and isinstance(err_bg0, EngineRestartError)
    assert status_w == "ok"
    assert out_w.outputs[0].token_ids == ref_w.outputs[0].token_ids
    # the healthy replica's own traffic was untouched
    assert status_bg1 == "ok" and len(out_bg1.outputs[0].token_ids) == 400

    # capacity loss, not an outage
    assert mid["lifecycle"] == "serving"
    assert mid["is_running"]
    assert not mid["paused"]
    # placement drained away from the victim (w's replay + all probes)
    assert set(placed_during) == {healthy_idx}
    # tenant stickiness FOLLOWED the cross-replica replay: "vic"'s
    # sticky entry re-pinned to the replica its replayed request landed
    # on, not the dead one
    assert engine.router._sticky["vic"] == healthy_idx
    # the restart burned only the victim's budget
    assert set(engine.supervisor._attempt_times) == {victim_idx}

    # healthy-replica TTFT p99 within 2x steady state (+25ms event-loop
    # jitter allowance on the shared CI runner)
    p99_base = sorted(ttft_base)[-1]
    p99_rec = sorted(ttft_rec)[-1]
    assert p99_rec <= 2 * p99_base + 0.025, (
        f"recovery TTFT p99 {p99_rec * 1000:.1f}ms vs baseline "
        f"{p99_base * 1000:.1f}ms"
    )

    # observability: per-replica restart cause + cross-replica replay
    assert _sample(
        _scrape(), "tgis_tpu_engine_restarts_total",
        ('cause="step_loop"', f'replica="{victim_idx}"'),
    ) == restarts0 + 1
    assert (
        _sample(_scrape(), "tgis_tpu_requests_replayed_total")
        >= replayed0 + 1
    )
    history = engine.supervisor.restart_history
    assert history[-1]["recovered"] and history[-1]["replica"] == victim_idx


# ---------------------------------------------- mid-decode checkpoint/resume
#
# ISSUE 10 tentpole (docs/RECOVERY.md): with the host KV tier on, engine
# death no longer costs mid-decode requests — they checkpoint at quiesce
# (frontier-capped page demotion + a DecodeCheckpoint record) and resume
# token-identically on the rebuilt engine or a healthy dp sibling, with
# zero duplicate or missing streamed tokens.  The degradation ladder
# (tier off = the PR-5 tests above, budget exceeded, --no-decode-resume)
# keeps the retryable-failure floor.


def _delta_params(max_tokens=24, *, seed=None, temperature=0.0):
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    return SamplingParams(
        temperature=temperature, seed=seed, max_tokens=max_tokens,
        ignore_eos=True, output_kind=RequestOutputKind.DELTA,
    )


async def _collect_delta(engine, request_id, prompt_ids, params):
    """Drive one DELTA stream to the end; returns EVERY streamed token
    in order (so duplicates and gaps are both visible)."""
    toks: list[int] = []
    async for out in engine.generate(
        prompt=None,
        sampling_params=params,
        request_id=request_id,
        prompt_token_ids=list(prompt_ids),
    ):
        toks.extend(out.outputs[0].token_ids)
    return toks


def test_middecode_checkpoint_resume_local_token_identical(tiny_model_dir):
    """THE local acceptance: a step-loop crash with one greedy and one
    SEEDED-sampled request mid-decode → both checkpoint into the host
    tier and resume on the rebuilt engine, streaming exactly the
    uncrashed token sequence (no duplicates, no gaps — the DELTA frames
    concatenate to the baseline), with the resume observable in the
    counters, the restart history, and the flight recorder."""
    engine = _build_engine(tiny_model_dir, tier_gb=1.0)
    prompt_g = list(range(3, 21))  # 18 tokens: one full 16-token page
    prompt_s = list(range(5, 23))
    n = 48  # long decode: the crash below cannot race the finish
    resumed0 = _sample(
        _scrape(), "tgis_tpu_requests_resumed_total", ('path="local"',)
    )
    ck0 = _sample(
        _scrape(), "tgis_tpu_decode_checkpoints_total",
        ('outcome="resumed"',),
    )

    async def scenario():
        # uncrashed baselines on the same engine (greedy is
        # deterministic; the seeded stream replays per-position draws)
        ref_g = await _collect_delta(
            engine, "ref-g", prompt_g, _delta_params(n)
        )
        ref_s = await _collect_delta(
            engine, "ref-s", prompt_s,
            _delta_params(n, seed=1234, temperature=0.9),
        )
        g_task = asyncio.create_task(_collect_delta(
            engine, "g", prompt_g, _delta_params(n)
        ))
        s_task = asyncio.create_task(_collect_delta(
            engine, "s", prompt_s,
            _delta_params(n, seed=1234, temperature=0.9),
        ))
        # >= 1 COMMITTED (and therefore streamed) token each: the
        # no-duplicate assertion below covers exactly these tokens.
        # Waiting for a deeper window is flaky — wave commits land in
        # bursts while XLA compiles hold the GIL.
        await _wait_for(
            lambda: _output_tokens(engine, "g") >= 1
            and _output_tokens(engine, "s") >= 1,
            what="both requests mid-decode",
        )
        failpoints.arm_site("core.plan_step", "raise", 1)
        toks_g = await g_task
        toks_s = await s_task
        await _wait_for(lambda: engine.lifecycle == "serving",
                        what="recovery to finish")
        new_core = engine._replicas[0].engine
        observed = {
            "promoted": new_core.kv_host_promoted_tokens,
            "kinds": {e["kind"] for e in new_core.recorder.events()},
            "checkpoints_left": len(
                new_core.kv_tier.pending_checkpoints()
            ),
        }
        await engine.stop()
        return ref_g, ref_s, toks_g, toks_s, observed

    ref_g, ref_s, toks_g, toks_s, observed = asyncio.run(scenario())

    # token-identical, zero duplicate/missing streamed tokens
    assert toks_g == ref_g and len(toks_g) == n
    assert toks_s == ref_s and len(toks_s) == n

    # the resume promoted checkpointed pages back from the tier
    assert observed["promoted"] > 0
    assert "resume" in observed["kinds"]
    assert observed["checkpoints_left"] == 0  # consumed, not leaked

    history = engine.supervisor.restart_history
    assert history[-1]["recovered"]
    assert history[-1]["resumed"] == 2
    assert history[-1]["failed"] == 0
    assert _sample(
        _scrape(), "tgis_tpu_requests_resumed_total", ('path="local"',)
    ) == resumed0 + 2
    assert _sample(
        _scrape(), "tgis_tpu_decode_checkpoints_total",
        ('outcome="resumed"',),
    ) == ck0 + 2


def test_middecode_resume_cross_replica_before_rebuild(tiny_model_dir):
    """Cross-replica acceptance: a dp sibling resumes the victim's
    mid-decode request from the SHARED tier BEFORE the victim's rebuild
    completes (held open with a hang failpoint) — the stream finishes
    token-identically while the dead replica is still down."""
    engine = _build_engine(
        tiny_model_dir, dp=2, max_num_seqs=2, tier_gb=1.0
    )
    prompt = list(range(3, 21))
    n = 48
    xr0 = _sample(
        _scrape(), "tgis_tpu_requests_resumed_total",
        ('path="cross_replica"',),
    )

    async def scenario():
        ref = await _collect_delta(
            engine, "ref", prompt, _delta_params(n)
        )
        a_task = asyncio.create_task(_collect_delta(
            engine, "a", prompt, _delta_params(n)
        ))
        await _wait_for(lambda: _output_tokens(engine, "a") >= 1,
                        what="request a mid-decode")
        victim = engine._owner["a"]
        sibling = next(
            r for r in engine._replicas if r is not victim
        )
        # hold the victim's rebuild open, then fault exactly the victim
        failpoints.arm_site("supervisor.rebuild", "hang")

        def boom(*args, **kwargs):
            raise RuntimeError("injected victim fault")

        victim.engine.plan_step = boom  # type: ignore[method-assign]
        toks = await a_task
        # the stream completed while the victim was still rebuilding
        mid = {"victim_serving": victim.serving,
               "lifecycle": engine.lifecycle}
        failpoints.release("supervisor.rebuild")
        await _wait_for(
            lambda: victim.serving
            and engine.supervisor.restart_history
            and engine.supervisor.restart_history[-1].get("recovered"),
            what="victim replica rebuilt",
        )
        observed = {
            "sibling_kinds": {
                e["kind"] for e in sibling.engine.recorder.events()
            },
        }
        await engine.stop()
        return ref, toks, mid, observed

    ref, toks, mid, observed = asyncio.run(scenario())
    assert toks == ref and len(toks) == n
    # resumed while the victim was down: partial outage, not a pause
    assert mid["victim_serving"] is False
    assert mid["lifecycle"] == "serving"
    assert "resume" in observed["sibling_kinds"]
    history = engine.supervisor.restart_history
    assert history[-1]["resumed"] == 1 and history[-1]["failed"] == 0
    assert _sample(
        _scrape(), "tgis_tpu_requests_resumed_total",
        ('path="cross_replica"',),
    ) == xr0 + 1


def _expect_middecode_fallback(tiny_model_dir, engine):
    """Shared ladder driver: one mid-decode request + a step crash must
    yield the PR-5 retryable EngineRestartError and a counted fallback."""
    from vllm_tgis_adapter_tpu.frontdoor.errors import EngineRestartError

    fb0 = _sample(
        _scrape(), "tgis_tpu_decode_checkpoints_total",
        ('outcome="fallback"',),
    )

    async def scenario():
        task = asyncio.create_task(_collect(
            engine, "a", prompt_ids=list(range(3, 21)), max_tokens=64
        ))
        await _wait_for(lambda: _output_tokens(engine, "a") >= 2,
                        what="request a mid-decode")
        failpoints.arm_site("core.plan_step", "raise", 1)
        status, err = await task
        await _wait_for(lambda: engine.lifecycle == "serving",
                        what="recovery to finish")
        await engine.stop()
        return status, err

    status, err = asyncio.run(scenario())
    assert status == "err"
    assert isinstance(err, EngineRestartError)
    assert _sample(
        _scrape(), "tgis_tpu_decode_checkpoints_total",
        ('outcome="fallback"',),
    ) == fb0 + 1


def test_checkpoint_over_tier_budget_falls_back_retryable(tiny_model_dir):
    """Ladder rung: a checkpoint whose written KV cannot fit the tier
    budget keeps today's semantics — retryable failure, counted."""
    engine = _build_engine(tiny_model_dir, tier_gb=1e-6)  # ~1 KiB
    _expect_middecode_fallback(tiny_model_dir, engine)


def test_no_decode_resume_escape_hatch(tiny_model_dir):
    """Ladder rung: --no-decode-resume forces the PR-5 floor even with
    the tier on and healthy."""
    engine = _build_engine(tiny_model_dir, tier_gb=1.0,
                           decode_resume=False)
    _expect_middecode_fallback(tiny_model_dir, engine)


def test_disconnect_mid_resume_drops_checkpoint(tiny_model_dir):
    """Client-disconnect hardening (satellite): a stream that goes away
    while its checkpoint awaits resume is dropped — the staged record
    is discarded, no engine state is created, and the rebuilt engine's
    pool is fully free."""
    engine = _build_engine(tiny_model_dir, tier_gb=1.0)

    async def scenario():
        a_task = asyncio.create_task(_collect_delta(
            engine, "a", list(range(3, 21)), _delta_params(64)
        ))
        await _wait_for(lambda: _output_tokens(engine, "a") >= 4,
                        what="request a mid-decode")
        tier = engine.engine.kv_tier
        # hold the rebuild open so the disconnect lands BETWEEN the
        # checkpoint staging and the resume
        failpoints.arm_site("supervisor.rebuild", "hang")
        failpoints.arm_site("core.plan_step", "raise", 1)
        await _wait_for(lambda: tier.pending_checkpoints(),
                        what="checkpoint staged")
        a_task.cancel()  # the client disconnects
        await asyncio.gather(a_task, return_exceptions=True)
        failpoints.release("supervisor.rebuild")
        await _wait_for(
            lambda: engine.supervisor.restart_history
            and engine.supervisor.restart_history[-1].get("recovered"),
            what="recovery to finish",
        )
        new_core = engine._replicas[0].engine
        observed = {
            "staged": len(tier.pending_checkpoints()),
            "known": "a" in new_core._seqs,
            "free": new_core.scheduler.allocator.num_free,
            "total": new_core.scheduler.allocator.num_blocks,
        }
        await engine.stop()
        return observed

    observed = asyncio.run(scenario())
    assert observed["staged"] == 0  # dropped, not leaked
    assert not observed["known"]  # never resumed into the new engine
    assert observed["free"] == observed["total"]


def test_abort_while_checkpointed_delivers_final_frame(tiny_model_dir):
    """Explicit abort between checkpoint staging and resume: the client
    gets its final aborted frame immediately and the later resume pass
    skips the cancelled record."""
    engine = _build_engine(tiny_model_dir, tier_gb=1.0)

    async def scenario():
        a_task = asyncio.create_task(_collect(
            engine, "a", prompt_ids=list(range(3, 21)), max_tokens=64
        ))
        await _wait_for(lambda: _output_tokens(engine, "a") >= 4,
                        what="request a mid-decode")
        tier = engine.engine.kv_tier
        failpoints.arm_site("supervisor.rebuild", "hang")
        failpoints.arm_site("core.plan_step", "raise", 1)
        await _wait_for(lambda: tier.pending_checkpoints(),
                        what="checkpoint staged")
        await engine.abort("a")
        status, final = await a_task
        failpoints.release("supervisor.rebuild")
        await _wait_for(
            lambda: engine.supervisor.restart_history
            and engine.supervisor.restart_history[-1].get("recovered"),
            what="recovery to finish",
        )
        new_core = engine._replicas[0].engine
        observed = {
            "staged": len(tier.pending_checkpoints()),
            "known": "a" in new_core._seqs,
        }
        await engine.stop()
        return status, final, observed

    status, final, observed = asyncio.run(scenario())
    assert status == "ok"
    assert final.finished
    assert final.outputs[0].finish_reason == "abort"
    assert observed["staged"] == 0
    assert not observed["known"]


def test_failed_recovery_attempt_keeps_checkpoints_for_retry(
    tiny_model_dir,
):
    """Death DURING recovery must not lose the attempt's checkpoints:
    they survive staged in the (surviving) tier, the retry adopts
    them, and the mid-decode request still resumes token-identically."""
    engine = _build_engine(tiny_model_dir, tier_gb=1.0,
                           max_engine_restarts=4)
    prompt = list(range(3, 21))
    n = 48

    async def scenario():
        ref = await _collect_delta(
            engine, "ref", prompt, _delta_params(n)
        )
        a_task = asyncio.create_task(_collect_delta(
            engine, "a", prompt, _delta_params(n)
        ))
        await _wait_for(lambda: _output_tokens(engine, "a") >= 1,
                        what="request a mid-decode")
        # the first rebuild dies; the retry must resume from the
        # checkpoints the failed attempt staged
        failpoints.arm_site("supervisor.rebuild", "raise", 1)
        failpoints.arm_site("core.plan_step", "raise", 1)
        toks = await a_task
        await _wait_for(lambda: engine.lifecycle == "serving",
                        what="recovery to finish")
        staged = len(
            engine._replicas[0].engine.kv_tier.pending_checkpoints()
        )
        await engine.stop()
        return ref, toks, staged

    ref, toks, staged = asyncio.run(scenario())
    assert toks == ref and len(toks) == n
    assert staged == 0  # consumed by the retry, not leaked
    history = engine.supervisor.restart_history
    assert len(history) == 2
    assert history[0]["recovered"] is False
    assert history[1]["recovered"] is True
    assert history[1]["resumed"] == 1


# ------------------------------------------------------- ledger under chaos
#
# ISSUE 16 satellite: the cost ledger must bill exactly ONE closed
# record per request no matter which chaos path the request takes —
# shed at the front door, restart mid-decode with a local resume plus a
# pre-prefill replay, or a cross-replica resume — with the
# restart/resume counts on the record matching what actually happened.


def _ledger_rows(engine, tmp_path):
    """Attach a JSONL sink to the fleet ledger; returns a reader that
    flushes and parses the per-request rows."""
    import json

    from vllm_tgis_adapter_tpu.telemetry import JsonlSink

    path = tmp_path / "ledger.jsonl"
    engine.ledger.sink = JsonlSink(str(path))

    def rows():
        engine.ledger.sink.flush_sync()
        return [json.loads(x) for x in path.read_text().splitlines()]

    return rows


def test_ledger_shed_closes_exactly_one_record(tiny_model_dir, tmp_path):
    """A queue-full shed bills one record with outcome=shed (never
    abort, never a second close when the stream unwinds), while the
    admitted requests bill one finish each."""
    from vllm_tgis_adapter_tpu.engine.config import FrontdoorConfig
    from vllm_tgis_adapter_tpu.frontdoor.errors import AdmissionShedError

    engine = _build_engine(
        tiny_model_dir, max_num_seqs=1,
        frontdoor=FrontdoorConfig(enabled=True, max_waiting_requests=1),
    )
    rows = _ledger_rows(engine, tmp_path)

    async def scenario():
        a_task = asyncio.create_task(_collect(
            engine, "a", prompt_ids=list(range(3, 15)), max_tokens=240,
            tenant_id="acme",
        ))
        # long enough that "a" is still decoding when a 10 ms poll looks
        # (24 tokens are over in ~12 ms of the overlapped loop, and a
        # request that has ended reads -1 here for ever)
        await _wait_for(lambda: _output_tokens(engine, "a") >= 1,
                        what="request a decoding")
        # freeze the step loop: b parks deterministically behind the
        # size-1 waiting bound, so c MUST shed
        failpoints.arm_site("core.wait_step", "hang")
        b_task = asyncio.create_task(_collect(
            engine, "b", prompt_ids=list(range(5, 17)), max_tokens=4,
            tenant_id="acme",
        ))
        await _wait_for(
            lambda: sum(
                len(rep.engine.scheduler.waiting)
                for rep in engine._replicas
            ) >= 1,
            what="b engine-waiting",
        )
        status_c, err_c = await _collect(
            engine, "c", prompt_ids=list(range(7, 19)), max_tokens=4,
            tenant_id="globex",
        )
        failpoints.release("core.wait_step")
        results = await asyncio.gather(a_task, b_task)
        ledger_kinds = {
            e["kind"] for e in engine.engine.recorder.events()
        }
        await engine.stop()
        return (status_c, err_c), results, ledger_kinds

    (status_c, err_c), results, kinds = asyncio.run(scenario())
    assert status_c == "err" and isinstance(err_c, AdmissionShedError)
    assert all(status == "ok" for status, _ in results)

    by_rid = {}
    for row in rows():
        assert row["request_id"] not in by_rid, "double-billed request"
        by_rid[row["request_id"]] = row
    assert set(by_rid) == {"a", "b", "c"}
    assert by_rid["c"]["outcome"] == "shed"
    assert by_rid["c"]["shed_reason"] == "queue_full"
    assert by_rid["c"]["tokens_out"] == 0
    assert by_rid["a"]["outcome"] == "finish"
    assert by_rid["a"]["tokens_out"] == 240
    assert by_rid["b"]["outcome"] == "finish"
    assert engine.ledger.by_outcome["shed"] == 1
    assert engine.ledger.open_count == 0
    # every close emitted a flight-recorder breadcrumb
    assert "ledger" in kinds


def test_ledger_restart_middecode_bills_once(tiny_model_dir, tmp_path):
    """A step crash with one mid-decode and one pre-prefill request:
    the checkpointed request's single record shows resumes=1, the
    replayed request's shows restarts=1 — and neither is billed
    twice despite dying and living again."""
    engine = _build_engine(tiny_model_dir, max_num_seqs=1, tier_gb=1.0)
    rows = _ledger_rows(engine, tmp_path)
    n = 48

    async def scenario():
        a_task = asyncio.create_task(_collect_delta(
            engine, "a", list(range(3, 21)), _delta_params(n)
        ))
        b_task = asyncio.create_task(_collect_delta(
            engine, "b", list(range(5, 17)), _delta_params(4)
        ))
        await _wait_for(lambda: _output_tokens(engine, "a") >= 1,
                        what="request a mid-decode")
        failpoints.arm_site("core.plan_step", "raise", 1)
        toks_a = await a_task
        toks_b = await b_task
        await _wait_for(lambda: engine.lifecycle == "serving",
                        what="recovery to finish")
        await engine.stop()
        return toks_a, toks_b

    toks_a, toks_b = asyncio.run(scenario())
    assert len(toks_a) == n and len(toks_b) == 4

    by_rid = {}
    for row in rows():
        assert row["request_id"] not in by_rid, "double-billed request"
        by_rid[row["request_id"]] = row
    assert set(by_rid) == {"a", "b"}
    # the mid-decode request resumed from its checkpoint, exactly once
    a = by_rid["a"]
    assert a["outcome"] == "finish"
    assert a["resumes"] == 1 and a["restarts"] == 0
    assert a["tokens_out"] == n  # full stream billed across the death
    # the pre-prefill request replayed onto the rebuilt engine
    b = by_rid["b"]
    assert b["outcome"] == "finish"
    assert b["restarts"] == 1 and b["resumes"] == 0
    assert b["tokens_out"] == 4
    assert engine.ledger.closed_total == 2
    assert engine.ledger.open_count == 0


def test_ledger_cross_replica_resume_bills_once(tiny_model_dir, tmp_path):
    """A request resumed onto a dp sibling appears exactly once in the
    ledger — resumes=1, full token total — even though two replicas
    touched it (the acceptance criterion's no-double-billing half)."""
    engine = _build_engine(
        tiny_model_dir, dp=2, max_num_seqs=2, tier_gb=1.0
    )
    rows = _ledger_rows(engine, tmp_path)
    n = 48

    async def scenario():
        a_task = asyncio.create_task(_collect_delta(
            engine, "a", list(range(3, 21)), _delta_params(n)
        ))
        await _wait_for(lambda: _output_tokens(engine, "a") >= 1,
                        what="request a mid-decode")
        victim = engine._owner["a"]
        failpoints.arm_site("supervisor.rebuild", "hang")

        def boom(*args, **kwargs):
            raise RuntimeError("injected victim fault")

        victim.engine.plan_step = boom  # type: ignore[method-assign]
        toks = await a_task
        failpoints.release("supervisor.rebuild")
        await _wait_for(
            lambda: victim.serving
            and engine.supervisor.restart_history
            and engine.supervisor.restart_history[-1].get("recovered"),
            what="victim replica rebuilt",
        )
        await engine.stop()
        return toks

    toks = asyncio.run(scenario())
    assert len(toks) == n

    matching = [r for r in rows() if r["request_id"] == "a"]
    assert len(matching) == 1, "resumed request billed more than once"
    a = matching[0]
    assert a["outcome"] == "finish"
    assert a["resumes"] == 1 and a["handoffs"] == 0
    assert a["tokens_out"] == n
    assert engine.ledger.open_count == 0
