"""The bottleneck doctor (telemetry/doctor.py, docs/OBSERVABILITY.md
"Step anatomy & doctor").

CPU-backed and engine-free: every regime rule fires from synthesized
signal windows (the rule table is a pure function), hysteresis never
flaps on an oscillating signal, cumulative counters are differenced per
replica, episodes emit strict open -> evidence -> close recorder events
and increment the counter metric, and the automatic profiler capture is
episode-bounded, restricted to CAPTURE_REGIMES, single-flight, and
degrades silently when the operator holds the profiler.  The dettest
scenario (tools/dettest/scenarios.py doctor-episode-lifecycle) holds
the same lifecycle grammar under explored interleavings.
"""

from __future__ import annotations

import re

import pytest

from vllm_tgis_adapter_tpu.telemetry.doctor import (
    CLOSE_AFTER,
    COMPILE_INFLIGHT_AGE_S,
    FRAGMENTATION_MIN_OCCUPANCY,
    FRAGMENTATION_THRESHOLD,
    HOST_BOUND_GAP_FRAC,
    MIN_WINDOW_STEPS,
    OPEN_AFTER,
    QUEUE_BOUND_BACKLOG_FACTOR,
    REGIMES,
    SPEC_MIN_ACCEPTANCE,
    TIER_THRASH_PAGES_PER_S,
    Doctor,
    ReplicaSignals,
    _rule_evidence,
)


def _sample(text: str, name: str, labels: tuple[str, ...] = ()) -> float:
    for line in text.splitlines():
        m = re.match(rf"^{re.escape(name)}(\{{[^}}]*\}})? (\S+)$", line)
        if m and all(lbl in (m.group(1) or "") for lbl in labels):
            return float(m.group(2))
    return 0.0


def _scrape() -> str:
    from vllm_tgis_adapter_tpu import metrics

    return metrics.render().decode()


def _quiet(replica: int = 0) -> ReplicaSignals:
    return ReplicaSignals(replica=replica, steps=16)


class _FakeProfiler:
    def __init__(self, status: str = "started"):
        self.status = status
        self.starts = 0
        self.stops = 0

    def start(self):
        self.starts += 1
        return {"status": self.status}

    def stop(self):
        self.stops += 1
        return {"status": "stopped"}


def _doctor(profiler=None):
    events: list[dict] = []
    doctor = Doctor(
        record=lambda replica, **detail: events.append(
            {"replica": replica, **detail}
        ),
        profiler=(lambda: profiler) if profiler is not None else None,
        min_interval=0.0,
    )
    return doctor, events


# ------------------------------------------------------------ rule table


# (regime, firing signals, rates) — each paired below with a near-miss
# that must NOT fire, pinning the threshold comparisons exactly.
FIRING = [
    ("host_bound",
     ReplicaSignals(replica=0, steps=MIN_WINDOW_STEPS,
                    host_gap_frac=HOST_BOUND_GAP_FRAC),
     {}),
    ("compile_storm", _quiet(), {"recompiles_delta": 1}),
    ("compile_storm",
     ReplicaSignals(replica=0, steps=16,
                    compile_inflight_age_s=COMPILE_INFLIGHT_AGE_S),
     {}),
    ("queue_bound",
     ReplicaSignals(replica=0, steps=16,
                    waiting=int(QUEUE_BOUND_BACKLOG_FACTOR * 4),
                    running=4, max_num_seqs=4),
     {}),
    ("tier_thrash", _quiet(),
     {"tier_pages_per_s": TIER_THRASH_PAGES_PER_S,
      "tier_pages_delta": 640}),
    ("allocator_fragmentation",
     ReplicaSignals(replica=0, steps=16,
                    fragmentation=FRAGMENTATION_THRESHOLD,
                    occupancy=FRAGMENTATION_MIN_OCCUPANCY),
     {}),
    ("spec_unprofitable",
     ReplicaSignals(replica=0, steps=16, spec_active=True,
                    spec_acceptance=SPEC_MIN_ACCEPTANCE - 0.01),
     {}),
]

NEAR_MISSES = [
    # a short window is never host_bound, however gappy
    ("host_bound",
     ReplicaSignals(replica=0, steps=MIN_WINDOW_STEPS - 1,
                    host_gap_frac=0.9),
     {}),
    ("host_bound",
     ReplicaSignals(replica=0, steps=MIN_WINDOW_STEPS,
                    host_gap_frac=HOST_BOUND_GAP_FRAC - 0.01),
     {}),
    ("compile_storm", _quiet(), {"recompiles_delta": 0}),
    # backlog alone is not queue_bound: the batch must also be full
    ("queue_bound",
     ReplicaSignals(replica=0, steps=16, waiting=100, running=3,
                    max_num_seqs=4),
     {}),
    ("tier_thrash", _quiet(),
     {"tier_pages_per_s": TIER_THRASH_PAGES_PER_S - 1.0}),
    # an empty pool is only vacuously fragmented
    ("allocator_fragmentation",
     ReplicaSignals(replica=0, steps=16, fragmentation=0.9,
                    occupancy=FRAGMENTATION_MIN_OCCUPANCY - 0.01),
     {}),
    # cold EWMA or inactive spec path never fires
    ("spec_unprofitable",
     ReplicaSignals(replica=0, steps=16, spec_active=True,
                    spec_acceptance=None),
     {}),
    ("spec_unprofitable",
     ReplicaSignals(replica=0, steps=16, spec_active=False,
                    spec_acceptance=0.0),
     {}),
]


@pytest.mark.parametrize(
    ("regime", "sig", "rates"), FIRING,
    ids=[f"fires-{r}-{i}" for i, (r, _, _) in enumerate(FIRING)],
)
def test_rule_fires_with_evidence(regime, sig, rates):
    fired = _rule_evidence(sig, rates)
    assert set(fired) == set(REGIMES)
    assert fired[regime] is not None
    # every OTHER regime stays quiet on this input
    assert all(v is None for k, v in fired.items() if k != regime)


@pytest.mark.parametrize(
    ("regime", "sig", "rates"), NEAR_MISSES,
    ids=[f"quiet-{r}-{i}" for i, (r, _, _) in enumerate(NEAR_MISSES)],
)
def test_rule_near_misses_stay_quiet(regime, sig, rates):
    assert _rule_evidence(sig, rates)[regime] is None


def test_quiet_signals_fire_nothing():
    assert all(
        v is None for v in _rule_evidence(_quiet(), {}).values()
    )


# ------------------------------------------------- hysteresis lifecycle


def _hot(replica: int = 0) -> ReplicaSignals:
    return ReplicaSignals(replica=replica, steps=16, host_gap_frac=0.6)


def test_episode_opens_after_consecutive_firing_evals():
    doctor, events = _doctor()
    t = 0.0
    for i in range(OPEN_AFTER):
        assert not doctor.active  # OPEN_AFTER - i evals still to go
        doctor.evaluate([_hot()], now=(t := t + 1.0))
    (episode,) = doctor.active
    assert episode.regime == "host_bound" and episode.open
    assert doctor.active_regimes() == ["host_bound"]
    assert "host_bound" in doctor.regimes_observed
    assert [e["phase"] for e in events] == ["open", "evidence"]
    assert events[0]["regime"] == "host_bound"
    assert events[1]["host_gap_frac"] == 0.6
    # batch-scoped: doctor events never carry a request_id
    assert all("request_id" not in e for e in events)


def test_oscillating_signal_never_flaps():
    """fire/quiet alternation resets the streak every time: no episode
    ever opens, however long the oscillation runs."""
    doctor, events = _doctor()
    t = 0.0
    for _ in range(10 * OPEN_AFTER):
        doctor.evaluate([_hot()], now=(t := t + 1.0))
        doctor.evaluate([_quiet()], now=(t := t + 1.0))
    assert not doctor.active
    assert not events
    assert doctor.evaluations == 20 * OPEN_AFTER


def test_episode_closes_only_after_sustained_quiet():
    doctor, events = _doctor()
    t = 0.0
    for _ in range(OPEN_AFTER):
        doctor.evaluate([_hot()], now=(t := t + 1.0))
    # a brief quiet dip, then re-fire: still the SAME open episode
    for _ in range(CLOSE_AFTER - 1):
        doctor.evaluate([_quiet()], now=(t := t + 1.0))
    doctor.evaluate([_hot()], now=(t := t + 1.0))
    assert len(doctor.active) == 1
    assert [e["phase"] for e in events] == ["open", "evidence"]
    # sustained quiet closes it
    for _ in range(CLOSE_AFTER):
        doctor.evaluate([_quiet()], now=(t := t + 1.0))
    assert not doctor.active
    assert [e["phase"] for e in events] == ["open", "evidence", "close"]
    assert events[-1]["duration_s"] >= 0
    assert events[-1]["host_gap_frac"] == 0.6  # evidence rides the close
    (closed,) = doctor.episodes
    assert not closed.open
    assert closed.to_dict()["duration_s"] >= 0


def test_counter_differencing_opens_compile_storm():
    """Callers pass cumulative recompile totals; the doctor differences
    per replica, so a steadily-growing counter fires and a flat one
    does not."""
    doctor, events = _doctor()
    t = 0.0
    # baseline eval only seeds the counters: no rates yet, no fire
    doctor.evaluate(
        [ReplicaSignals(replica=0, steps=16, recompiles=5)],
        now=(t := t + 1.0),
    )
    for total in (6, 7):
        doctor.evaluate(
            [ReplicaSignals(replica=0, steps=16, recompiles=total)],
            now=(t := t + 1.0),
        )
    assert doctor.active_regimes() == ["compile_storm"]
    assert events[1]["recompiles_delta"] == 1
    # flat counter: quiet evals eventually close the episode
    for _ in range(CLOSE_AFTER):
        doctor.evaluate(
            [ReplicaSignals(replica=0, steps=16, recompiles=7)],
            now=(t := t + 1.0),
        )
    assert not doctor.active


def test_replicas_tracked_independently():
    doctor, events = _doctor()
    t = 0.0
    for _ in range(OPEN_AFTER):
        doctor.evaluate([_hot(0), _quiet(1)], now=(t := t + 1.0))
    assert [(e.replica, e.regime) for e in doctor.active] == [
        (0, "host_bound")
    ]
    assert all(e["replica"] == 0 for e in events)


def test_episode_ring_is_bounded():
    doctor, _ = _doctor()
    doctor.episodes.extend(
        doctor.episodes.maxlen * 2 * [None]  # type: ignore[list-item]
    )
    assert len(doctor.episodes) == doctor.episodes.maxlen == 64


# ------------------------------------------------------ profiler capture


def _run_episode(doctor, signals, t0=0.0):
    t = t0
    for _ in range(OPEN_AFTER):
        doctor.evaluate(signals, now=(t := t + 1.0))
    for _ in range(CLOSE_AFTER):
        doctor.evaluate([_quiet(s.replica) for s in signals],
                        now=(t := t + 1.0))
    return t


def test_capture_brackets_host_bound_episode():
    profiler = _FakeProfiler()
    doctor, events = _doctor(profiler)
    _run_episode(doctor, [_hot()])
    assert (profiler.starts, profiler.stops) == (1, 1)
    (closed,) = doctor.episodes
    assert closed.captured
    assert events[-1]["phase"] == "close"


def test_non_capture_regime_never_captures():
    profiler = _FakeProfiler()
    doctor, _ = _doctor(profiler)
    queue = ReplicaSignals(replica=0, steps=16, waiting=8, running=4,
                           max_num_seqs=4)
    _run_episode(doctor, [queue])
    assert (profiler.starts, profiler.stops) == (0, 0)
    (closed,) = doctor.episodes
    assert closed.regime == "queue_bound" and not closed.captured


def test_single_flight_capture_across_overlapping_episodes():
    """Two capture-eligible episodes overlap: only the first holds the
    capture, and its close (not the other's) releases it."""
    profiler = _FakeProfiler()
    doctor, _ = _doctor(profiler)
    t = 0.0
    hot2 = [_hot(0), _hot(1)]
    for _ in range(OPEN_AFTER):
        doctor.evaluate(hot2, now=(t := t + 1.0))
    assert len(doctor.active) == 2
    assert profiler.starts == 1
    captured = [e for e in doctor.active if e.captured]
    assert len(captured) == 1
    # close only the non-holding replica first: capture stays out
    holder = captured[0].replica
    other = 1 - holder
    for _ in range(CLOSE_AFTER):
        doctor.evaluate(
            [_hot(holder), _quiet(other)], now=(t := t + 1.0)
        )
    assert profiler.stops == 0
    for _ in range(CLOSE_AFTER):
        doctor.evaluate([_quiet(holder)], now=(t := t + 1.0))
    assert (profiler.starts, profiler.stops) == (1, 1)


def test_operator_held_profiler_degrades_silently():
    """An already-running capture (start() != started) or a raising
    controller degrades to an uncaptured episode — never an error."""
    held = _FakeProfiler(status="already-running")
    doctor, _ = _doctor(held)
    _run_episode(doctor, [_hot()])
    (closed,) = doctor.episodes
    assert not closed.captured
    assert held.stops == 0  # we never took it, we never release it

    class _Broken:
        def start(self):
            raise RuntimeError("profiler disabled")

    doctor2, _ = _doctor(_Broken())
    _run_episode(doctor2, [_hot()])
    (closed2,) = doctor2.episodes
    assert not closed2.captured


# ------------------------------------------------------- metrics + reads


def test_episode_counter_and_gauge():
    before = _sample(
        _scrape(), "tgis_tpu_doctor_episodes_total",
        ('regime="host_bound"', 'replica="0"'),
    )
    doctor, _ = _doctor()
    t = 0.0
    for _ in range(OPEN_AFTER):
        doctor.evaluate([_hot()], now=(t := t + 1.0))
    after = _sample(
        _scrape(), "tgis_tpu_doctor_episodes_total",
        ('regime="host_bound"', 'replica="0"'),
    )
    assert after - before == 1
    assert _sample(_scrape(), "tgis_tpu_doctor_active_regimes") >= 1
    for _ in range(CLOSE_AFTER):
        doctor.evaluate([_quiet()], now=(t := t + 1.0))
    assert _sample(_scrape(), "tgis_tpu_doctor_active_regimes") == 0


def test_debug_state_shape():
    import json

    doctor, _ = _doctor()
    t = _run_episode(doctor, [_hot()])
    for _ in range(OPEN_AFTER):
        doctor.evaluate(
            [ReplicaSignals(replica=0, steps=16, spec_active=True,
                            spec_acceptance=0.1)],
            now=(t := t + 1.0),
        )
    state = doctor.debug_state()
    json.dumps(state)  # wire-ready as-is
    assert state["regimes"] == list(REGIMES)
    (active,) = state["active"]
    assert active["regime"] == "spec_unprofitable"
    assert active["closed_ts"] is None and active["duration_s"] is None
    (recent,) = state["recent"]
    assert recent["regime"] == "host_bound"
    assert recent["duration_s"] is not None
    assert state["evaluations"] == doctor.evaluations
    for key in ("host_bound_gap_frac", "open_after", "close_after",
                "spec_min_acceptance"):
        assert key in state["thresholds"]


def test_maybe_evaluate_throttles_and_never_raises():
    doctor, _ = _doctor()
    calls = []

    def signals_fn():
        calls.append(1)
        return [_quiet()]

    doctor.maybe_evaluate(signals_fn, now=10.0)
    doctor.maybe_evaluate(signals_fn, now=10.1)  # inside min_interval=0
    assert len(calls) == 2  # min_interval=0: both run
    throttled = Doctor(min_interval=5.0)
    throttled.maybe_evaluate(signals_fn, now=10.0)
    throttled.maybe_evaluate(signals_fn, now=12.0)  # throttled away
    assert len(calls) == 3
    throttled.maybe_evaluate(signals_fn, now=16.0)
    assert len(calls) == 4

    def broken():
        raise RuntimeError("signals unavailable")

    doctor.maybe_evaluate(broken, now=20.0)  # swallowed: telemetry
    # a raising record hook is swallowed too
    angry = Doctor(
        record=lambda replica, **detail: (_ for _ in ()).throw(
            RuntimeError("recorder down")
        ),
        min_interval=0.0,
    )
    t = 0.0
    for _ in range(OPEN_AFTER):
        angry.evaluate([_hot()], now=(t := t + 1.0))
    assert angry.active_regimes() == ["host_bound"]


# ------------------------------------------------- end-to-end acceptance
#
# The two validation runs from docs/OBSERVABILITY.md "Validating the
# doctor", driven through a REAL engine on the CPU proxy: each must
# open exactly one correctly-labeled episode whose evidence carries the
# rule's inputs, visible in /debug/doctor, the flight recorder, and an
# exported chrome trace.


def _build_engine(tiny_model_dir, **scheduler_overrides):
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16, num_blocks=64, cache_dtype=mcfg.dtype
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(32, 64),
            **scheduler_overrides,
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )
    return AsyncLLMEngine.from_config(config)


async def _generate(engine, request_id, *, prompt_len=17, max_tokens=4):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    async for _ in engine.generate(
        prompt=None,
        sampling_params=SamplingParams(
            temperature=0.0, max_tokens=max_tokens, ignore_eos=True
        ),
        request_id=request_id,
        prompt_token_ids=list(range(3, 3 + prompt_len)),
    ):
        pass


async def _doctor_http_body(engine, tiny_model_dir):
    """GET /debug/doctor through the real app dispatch."""
    import argparse
    import json

    from vllm_tgis_adapter_tpu.http import HttpRequest, build_http_server

    args = argparse.Namespace(
        served_model_name=None, model=tiny_model_dir, api_key=None,
        root_path=None, profile_dir=None,
    )
    app = build_http_server(args, engine)
    resp = await app.dispatch(HttpRequest("GET", "/debug/doctor", {}, b""))
    assert resp.status == 200
    return json.loads(resp.body)


def _doctor_trace_events(state):
    from vllm_tgis_adapter_tpu.telemetry import timeline

    return timeline.chrome_trace_from_state(state)["traceEvents"]


def _episodes(state, regime):
    doc = state["doctor"]
    return [
        ep for ep in doc["active"] + doc["recent"] if ep["regime"] == regime
    ]


def _feed_host_bound_steps(timeline, steps, monkeypatch):
    """Drive ``steps`` steps through the timeline's real stamp helpers
    on a scripted clock: 1 ms between stamps (a 6 ms step, 2 ms of it
    device-busy) and 14 ms before the next step is entered, so the
    device idles 18 ms (end of one wait to the end of the next
    dispatch) in every 24 ms of step wall: host_gap_frac is 0.75
    whatever the machine's load."""
    import time
    from types import SimpleNamespace

    from vllm_tgis_adapter_tpu.telemetry import steptime

    clock = SimpleNamespace(t=time.perf_counter())

    def tick(seconds=0.001):
        clock.t += seconds
        return clock.t

    with monkeypatch.context() as patched:
        patched.setattr(steptime, "time", SimpleNamespace(
            perf_counter=lambda: clock.t, time=time.time,
        ))
        for step in range(steps):
            prepared = SimpleNamespace()
            t_enter = tick(0.014)
            timeline.stamp_plan(prepared, t_enter=t_enter, t_sched=tick())
            tick()
            timeline.begin_dispatch(prepared)
            tick()
            timeline.end_dispatch(prepared)
            tick()
            timeline.begin_wait(prepared)
            tick()
            timeline.end_wait(prepared)
            tick()
            assert timeline.finish(
                prepared, step=10_000 + step, replica=0, kind="decode",
                tokens=2, fill_ratio=1.0,
            ) is not None


def test_host_bound_run_opens_one_episode(tiny_model_dir, monkeypatch):
    """Acceptance: a real engine whose anatomy window shows the device
    idle on the host 18 ms in every 24 opens exactly one host_bound
    episode, on every surface.  The engine serves one short request
    for real (fewer than MIN_WINDOW_STEPS steps: no rule can fire yet);
    the host-bound window is then fed through its own StepTimeline on
    a scripted clock and the doctor is evaluated at injected times, so
    nothing here reads the machine's load."""
    import asyncio

    before = _sample(
        _scrape(), "tgis_tpu_doctor_episodes_total",
        ('regime="host_bound"', 'replica="0"'),
    )
    engine = _build_engine(tiny_model_dir)

    async def scenario():
        await _generate(engine, "hb-live", max_tokens=4)
        timeline = engine._replicas[0].engine.steptime
        assert 0 < len(timeline) < MIN_WINDOW_STEPS
        # one step more than the window: the first scripted step's gap
        # is measured from the last real step and falls out of it
        _feed_host_bound_steps(timeline, timeline.window + 1, monkeypatch)
        frac = timeline.host_gap_frac()
        now = 1_000.0
        for _ in range(OPEN_AFTER):
            engine.doctor.evaluate(
                engine._doctor_signals(), now=(now := now + 1.0)
            )
        state = engine.debug_state(last_events=4096)
        body = await _doctor_http_body(engine, tiny_model_dir)
        await engine.stop()
        return frac, state, body

    frac, state, body = asyncio.run(scenario())

    assert frac == pytest.approx(18 / 24)
    # the run is genuinely host-bound by the doctor's own rule inputs
    assert frac > HOST_BOUND_GAP_FRAC
    (episode,) = _episodes(state, "host_bound")
    assert episode["replica"] == 0
    assert episode["evidence"]["host_gap_frac"] >= HOST_BOUND_GAP_FRAC
    assert episode["evidence"]["window_steps"] >= MIN_WINDOW_STEPS
    after = _sample(
        _scrape(), "tgis_tpu_doctor_episodes_total",
        ('regime="host_bound"', 'replica="0"'),
    )
    assert after - before == 1.0

    # visible on every surface: recorder, /debug/doctor, chrome trace
    opens = [
        e for e in state["events"]
        if e["kind"] == "doctor"
        and e.get("detail", {}).get("phase") == "open"
        and e.get("detail", {}).get("regime") == "host_bound"
    ]
    assert len(opens) == 1
    assert _episodes({"doctor": body}, "host_bound")
    from vllm_tgis_adapter_tpu.telemetry.timeline import DOCTOR_TID

    assert any(
        e.get("tid") == DOCTOR_TID and "host_bound" in str(e.get("name"))
        for e in _doctor_trace_events(state)
    )


def test_compile_storm_run_opens_one_episode(tiny_model_dir):
    """Acceptance: a fresh-lattice run (this engine's runner has
    compiled nothing yet, so every new prefill bucket / decode shape is
    a cache miss) opens exactly one compile_storm episode whose
    evidence carries the recompile delta, then closes it once the
    lattice stops growing."""
    import asyncio

    before = _sample(
        _scrape(), "tgis_tpu_doctor_episodes_total",
        ('regime="compile_storm"', 'replica="0"'),
    )
    engine = _build_engine(tiny_model_dir)

    async def scenario():
        # one compile per step, one (throttled) doctor eval per compile
        # step: bucket-32 prefill seeds the counter baseline, bucket-64
        # prefill is the first hot eval, and the two decode-wave shapes
        # of a 12-token generation (full wave + tail) finish the
        # OPEN_AFTER streak mid-run
        await _generate(engine, "cs-a", prompt_len=17, max_tokens=1)
        await _generate(engine, "cs-b", prompt_len=40, max_tokens=1)
        await _generate(engine, "cs-c", prompt_len=17, max_tokens=12)
        opened = engine.debug_state(last_events=4096)
        # the lattice is warm now: quiet evals over the same real
        # signal feed close the episode
        for _ in range(CLOSE_AFTER):
            engine.doctor.evaluate(engine._doctor_signals())
        state = engine.debug_state(last_events=4096)
        body = await _doctor_http_body(engine, tiny_model_dir)
        await engine.stop()
        return opened, state, body

    opened, state, body = asyncio.run(scenario())

    (episode,) = _episodes(state, "compile_storm")
    assert episode["replica"] == 0
    assert episode["evidence"]["recompiles_delta"] >= 1
    assert episode["closed_ts"] is not None
    assert _episodes(opened, "compile_storm")  # visible while open, too
    after = _sample(
        _scrape(), "tgis_tpu_doctor_episodes_total",
        ('regime="compile_storm"', 'replica="0"'),
    )
    assert after - before == 1.0

    phases = [
        e["detail"]["phase"] for e in state["events"]
        if e["kind"] == "doctor"
        and e.get("detail", {}).get("regime") == "compile_storm"
    ]
    assert phases[0] == "open" and phases[-1] == "close"
    assert _episodes({"doctor": body}, "compile_storm")
    from vllm_tgis_adapter_tpu.telemetry.timeline import DOCTOR_TID

    assert any(
        e.get("tid") == DOCTOR_TID and "compile_storm" in str(e.get("name"))
        for e in _doctor_trace_events(state)
    )
