"""Step-time anatomy + unified timeline export (docs/OBSERVABILITY.md,
"Step anatomy & doctor").

CPU-backed: the anatomy-sums-to-wall invariant the decomposition is
built around, host_gap semantics (async vs SYNC_DISPATCH device start,
the GAP_CAP clamp, the idle cutoff), ring bounds, the /debug/state
serializer shape, the stall-snapshot StepRecord embed, the chrome-trace
exporter's golden shape (valid JSON, monotonic ts, stable pid/tid), and
the HTTP surfaces (?section= filtering, /debug/doctor,
/debug/timeline) via the real app dispatch.  The GetTimeline RPC twin
is covered in test_grpc_server.py.
"""

from __future__ import annotations

import asyncio
import json
import re
import time
from types import SimpleNamespace

import pytest

from vllm_tgis_adapter_tpu.telemetry.steptime import (
    GAP_CAP_S,
    PHASES,
    StepTimeline,
    _Stamps,
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


# --------------------------------------------------------------- helpers


def _live_step(tl: StepTimeline, *, step: int = 1, sync: bool = False):
    """Drive one step through the real stamp helpers (live clock)."""
    prepared = SimpleNamespace()
    t_enter = time.perf_counter()
    tl.stamp_plan(prepared, t_enter=t_enter, t_sched=time.perf_counter())
    tl.begin_dispatch(prepared)
    tl.end_dispatch(prepared, sync=sync)
    tl.begin_wait(prepared)
    tl.end_wait(prepared)
    return tl.finish(
        prepared, step=step, replica=0, kind="decode", tokens=8,
        fill_ratio=1.0,
    )


def _stamps_at(base: float, *, sync: bool = False,
               wait1_off: float = 0.006) -> _Stamps:
    """Hand-crafted stamps at fixed offsets from ``base`` so the gap
    arithmetic is deterministic.  Offsets: enter +0, sched +1ms,
    prep +2ms, disp0 +3ms, disp1 +4ms, wait0 +5ms, wait1 +6ms."""
    st = _Stamps()
    st.t_enter = base
    st.t_sched = base + 0.001
    st.t_prep = base + 0.002
    st.t_disp0 = base + 0.003
    st.t_disp1 = base + 0.004
    st.t_wait0 = base + 0.005
    st.t_wait1 = base + wait1_off
    st.sync = sync
    return st


def _crafted_step(
    tl: StepTimeline, *, step: int = 1, sync: bool = False,
    base: float | None = None,
):
    """Drive one crafted step through finish() (see _stamps_at)."""
    if base is None:
        base = time.perf_counter() - 0.01  # keep t_end after the stamps
    prepared = SimpleNamespace(_steptime=_stamps_at(base, sync=sync))
    record = tl.finish(
        prepared, step=step, replica=0, kind="ragged", tokens=32,
        fill_ratio=0.5,
    )
    assert record is not None
    return record, base


# ------------------------------------------------------- sum invariant


def test_anatomy_sums_to_step_wall():
    """The load-bearing contract: the six phases telescope, so their
    sum equals wall_s (= host_gap + (t_end - t_enter)) exactly up to
    float association."""
    tl = StepTimeline()
    for step in range(1, 6):
        record = _live_step(tl, step=step)
        assert record is not None
        phases = record.phases()
        assert tuple(phases) == PHASES
        assert abs(sum(phases.values()) - record.wall_s) < 1e-9
        assert all(v >= 0.0 for v in phases.values())


def test_first_step_has_no_host_gap():
    tl = StepTimeline()
    record = _live_step(tl)
    assert record.host_gap_s == 0.0


def test_host_gap_async_measures_lead_in_from_dispatch():
    """Async dispatch: device work starts at enqueue (t_disp1), so the
    gap is t_disp1 - previous device_end."""
    tl = StepTimeline()
    rec1, base1 = _crafted_step(
        tl, step=1, base=time.perf_counter() - 1.0
    )
    assert rec1.host_gap_s == 0.0  # no previous device_end
    # previous device_end = base1 + 6ms; next disp1 = base2 + 4ms
    base2 = base1 + 0.006 + 0.02 - 0.004  # raw gap: exactly 20ms
    rec2, _ = _crafted_step(tl, step=2, base=base2)
    assert abs(rec2.host_gap_s - 0.02) < 1e-9
    assert abs(sum(rec2.phases().values()) - rec2.wall_s) < 1e-9


def test_host_gap_sync_measures_lead_in_from_wait_entry():
    """SYNC_DISPATCH: the blocking wait entry (t_wait0) is when device
    work can start, and it trails the previous device_end by the full
    serialized host phase — the host_bound discriminator."""
    tl = StepTimeline()
    _, base1 = _crafted_step(
        tl, step=1, sync=True, base=time.perf_counter() - 1.0
    )
    base2 = base1 + 0.006 + 0.03 - 0.005  # wait0 lands 30ms after
    rec2, _ = _crafted_step(tl, step=2, sync=True, base=base2)
    assert abs(rec2.host_gap_s - 0.03) < 1e-9


def test_host_gap_blocking_dispatch_uses_dispatch_window():
    """CPU backend with async dispatch disabled:
    the device work runs INSIDE dispatch, so the gap must be measured
    t_disp0 - previous t_disp1 — against the wait stamps it would
    degenerate to ~0 and hide the serialization."""
    tl = StepTimeline(dispatch_blocks=True)
    _, base1 = _crafted_step(
        tl, step=1, base=time.perf_counter() - 1.0
    )
    # previous device_end = t_disp1 = base1 + 4ms; this step's
    # device_start = t_disp0 = base2 + 3ms
    base2 = base1 + 0.004 + 0.04 - 0.003  # raw gap: exactly 40ms
    rec2, _ = _crafted_step(tl, step=2, base=base2)
    assert abs(rec2.host_gap_s - 0.04) < 1e-9
    # under the commit ordering blocking dispatch actually produces —
    # the previous step's (instant) wait retires AFTER this step's
    # dispatch — the wait-stamp reading degenerates to no gap at all
    tl2 = StepTimeline()
    st1 = SimpleNamespace(
        _steptime=_stamps_at(time.perf_counter() - 1.0, wait1_off=0.046)
    )
    tl2.finish(st1, step=1, replica=0, kind="ragged", tokens=1,
               fill_ratio=1.0)
    rec, _ = _crafted_step(
        tl2, step=2,
        base=st1._steptime.t_disp1 + 0.04 - 0.003,
    )
    assert rec.host_gap_s == 0.0


def test_backend_dispatch_blocks_detection():
    import jax

    from vllm_tgis_adapter_tpu.telemetry.steptime import (
        backend_dispatch_blocks,
    )

    assert backend_dispatch_blocks() is False  # suite default: async
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    try:
        assert backend_dispatch_blocks() is True
    finally:
        jax.config.update("jax_cpu_enable_async_dispatch", True)


def test_host_gap_clamped_and_idle_cutoff():
    tl = StepTimeline()
    _, base1 = _crafted_step(
        tl, step=1, base=time.perf_counter() - 5.0
    )
    # a 0.5s gap is a burst edge: clamped to GAP_CAP_S
    rec2, base2 = _crafted_step(tl, step=2, base=base1 + 0.006 + 0.5)
    assert rec2.host_gap_s == GAP_CAP_S
    # a 2s gap is an idle engine: never host-bound, gap zeroed
    rec3, _ = _crafted_step(tl, step=3, base=base2 + 0.006 + 2.0)
    assert rec3.host_gap_s == 0.0
    # overlap (device_start before previous device_end) is not a gap
    tl2 = StepTimeline()
    _, b1 = _crafted_step(tl2, step=1)
    rec, _ = _crafted_step(tl2, step=2, base=b1 + 0.006 - 0.004 - 0.001)
    assert rec.host_gap_s == 0.0


def test_pure_sync_path_backfills_dispatch():
    """step()-style callers stamp only the wait window; finish backfills
    t_disp1 = t_wait0 so dispatch_s collapses into the decomposition
    without breaking the sum."""
    tl = StepTimeline()
    base = time.perf_counter() - 0.01
    st = _Stamps()
    st.t_enter = base
    st.t_sched = base + 0.001
    st.t_prep = base + 0.002
    st.t_wait0 = base + 0.005
    st.t_wait1 = base + 0.006
    st.sync = True
    prepared = SimpleNamespace(_steptime=st)
    record = tl.finish(
        prepared, step=1, replica=0, kind="prefill", tokens=16,
        fill_ratio=1.0,
    )
    assert record is not None
    assert abs(record.dispatch_s - 0.003) < 1e-9  # t_wait0 - t_prep
    assert abs(sum(record.phases().values()) - record.wall_s) < 1e-9


def test_incomplete_stamps_refuse_to_finish():
    tl = StepTimeline()
    assert tl.finish(
        None, step=1, replica=0, kind="decode", tokens=1, fill_ratio=1.0
    ) is None
    assert tl.finish(
        SimpleNamespace(), step=1, replica=0, kind="decode", tokens=1,
        fill_ratio=1.0,
    ) is None
    st = _Stamps()
    st.t_enter = time.perf_counter()  # everything else missing
    prepared = SimpleNamespace(_steptime=st)
    assert tl.finish(
        prepared, step=1, replica=0, kind="decode", tokens=1,
        fill_ratio=1.0,
    ) is None
    assert len(tl) == 0


# ------------------------------------------------------- ring + reads


def test_ring_bounds_and_window_reads():
    tl = StepTimeline(capacity=4, window=2)
    for step in range(10):
        _live_step(tl, step=step)
    assert len(tl) == 4
    assert [r.step for r in tl.last_records(2)] == [8, 9]
    assert tl.last_records(0) == []
    assert [r["step"] for r in tl.records(last_n=3)] == [7, 8, 9]
    assert len(tl.records()) == 4


def test_host_gap_frac_windowing():
    tl = StepTimeline(window=2)
    _, base1 = _crafted_step(
        tl, step=1, base=time.perf_counter() - 20.0
    )
    _crafted_step(tl, step=2, base=base1 + 0.006 + 0.05)  # gappy
    _crafted_step(tl, step=3, base=base1 + 10.0)          # idle: gap 0
    records = tl.last_records(2)
    expected = sum(r.host_gap_s for r in records) / sum(
        r.wall_s for r in records
    )
    assert abs(tl.host_gap_frac() - expected) < 1e-9
    # window=1 sees only the idle step: no gap at all
    assert tl.host_gap_frac(window=1) == 0.0
    assert StepTimeline().host_gap_frac() == 0.0  # empty ring


def test_record_serializer_and_debug_state_shape():
    tl = StepTimeline()
    record = _live_step(tl)
    as_dict = record.to_dict()
    json.dumps(as_dict)  # wire-ready as-is
    assert set(as_dict["phases"]) == set(PHASES)
    for key in ("step", "replica", "kind", "tokens", "fill_ratio",
                "chained", "sync", "ts", "wall_s", "drain_s",
                "compile_fn", "bucket", "running", "compiled"):
        assert key in as_dict
    state = tl.debug_state()
    assert state["steps"] == 1
    assert state["window"] == tl.window
    assert 0.0 <= state["host_gap_frac"] <= 1.0
    assert state["records"] == [as_dict]


def test_anatomy_metrics_observed():
    before = _sample(
        _scrape(), "tgis_tpu_step_anatomy_seconds_count",
        ('phase="device_wait"', 'replica="0"'),
    )
    tl = StepTimeline()
    _live_step(tl)
    after = _sample(
        _scrape(), "tgis_tpu_step_anatomy_seconds_count",
        ('phase="device_wait"', 'replica="0"'),
    )
    assert after - before == 1
    assert "tgis_tpu_host_gap_frac" in _scrape()


# ------------------------------------------------ the stall rule's bucket

LADDER = [16, 32, 64, 128, 256, 512]


@pytest.fixture
def multiple(monkeypatch):
    """Set ``STALL_MULTIPLE``, the one place the rule's constant lives."""
    from vllm_tgis_adapter_tpu.telemetry import steptime

    def set_to(value: float) -> None:
        monkeypatch.setattr(steptime, "STALL_MULTIPLE", value)

    set_to(2.0)
    return set_to


def _fed(tl: StepTimeline, kind: str, device_ms: float, *,
         bucket: int | None = None, waited_ms: float | None = None,
         n: int = 1, steps: int = 1, running: int | None = None,
         compiles: int | None = None) -> None:
    """``n`` dispatches of ``device_ms`` each, back to back on the
    device; the host waits ``waited_ms`` for each result (all of the
    device time unless said).  A mixed step carries two running rows
    unless said; ``compiles`` is compile_tracker's count at its plan."""
    if running is None:
        running = 2 if bucket is not None else 0
    for _ in range(n):
        behind = tl._last_device_end is not None
        start = tl._last_device_end if behind else 50.0
        end = start + device_ms / 1000.0
        waited = device_ms if waited_ms is None else waited_ms
        st = _Stamps()
        st.t_enter, st.t_sched = start - 0.03, start - 0.02
        st.t_prep = st.t_disp0 = start - 0.01
        # enqueued while its predecessor ran, where there is one
        st.t_disp1 = start - 0.005 if behind else start
        st.t_wait0, st.t_wait1 = end - waited / 1000.0, end
        st.compiles = compiles
        record = tl.finish(
            SimpleNamespace(_steptime=st), step=0, replica=0, kind=kind,
            tokens=bucket or steps, fill_ratio=1.0, steps=steps,
            bucket=bucket, running=running,
        )
        assert abs(record.device_s - device_ms / 1000.0) < 1e-9
        assert (record.bucket, record.running) == (bucket, running)


def test_stall_bucket_is_the_last_within_the_multiple_of_a_token_step(
        multiple):
    tl = StepTimeline()
    _fed(tl, "decode-wave", 20.0, n=16)
    for bucket, ms in ((16, 21.0), (32, 22.0), (64, 26.0), (128, 36.0),
                       (256, 55.0), (512, 95.0)):
        _fed(tl, "ragged", ms, bucket=bucket, n=5)
    assert tl.stall_bucket(LADDER) == 128
    # a deeper wave says the same of the token step
    _fed(tl, "decode-wave", 80.0, n=16, steps=4)
    assert tl.stall_bucket(LADDER) == 128
    # the rows' token step grows: so does what a mixed step may cost
    _fed(tl, "decode-wave", 35.0, n=16)
    assert tl.stall_bucket(LADDER) == 256


@pytest.mark.parametrize("value,bucket", [(1.5, 64), (3.0, 256), (1.0, 16)])
def test_stall_bucket_follows_the_constant(multiple, value, bucket):
    """The engine passes no multiple: the one constant in the code
    rules, for every model.  Never under the ladder's first bucket."""
    multiple(value)
    tl = StepTimeline()
    _fed(tl, "decode-wave", 20.0, n=16)
    for b, ms in ((16, 21.0), (32, 22.0), (64, 26.0), (128, 36.0),
                  (256, 55.0), (512, 95.0)):
        _fed(tl, "ragged", ms, bucket=b, n=5)
    assert tl.stall_bucket(LADDER) == bucket


def test_stall_bucket_reads_medians_of_each_bucket(multiple):
    """One stalled step among a bucket's last sixteen moves nothing; a
    bucket that stays slow does, and only its own reading."""
    tl = StepTimeline()
    _fed(tl, "decode-wave", 20.0, n=16)
    _fed(tl, "ragged", 30.0, bucket=64, n=15)
    _fed(tl, "ragged", 900.0, bucket=64)
    _fed(tl, "ragged", 35.0, bucket=128, n=3)
    assert tl.stall_bucket(LADDER) == 512
    _fed(tl, "ragged", 45.0, bucket=128, n=4)
    assert tl.stall_bucket(LADDER) == 64
    # only the last sixteen of a bucket count, and one that was over
    # comes back only well under the limit
    _fed(tl, "ragged", 37.0, bucket=128, n=16)
    assert tl.stall_bucket(LADDER) == 64
    _fed(tl, "ragged", 33.0, bucket=128, n=16)
    assert tl.stall_bucket(LADDER) == 512


def test_stall_bucket_leaves_out_what_the_host_did_not_wait_for(multiple):
    """A result that was there when the host came for it shows the
    host's clock, not the device's work (``WAITED_MIN_S``): neither such
    a mixed step nor such a wave enters a median."""
    tl = StepTimeline()
    _fed(tl, "decode-wave", 20.0, n=16)
    _fed(tl, "ragged", 90.0, bucket=64, waited_ms=0.2, n=16)
    assert not tl._bucket_steps
    assert tl.stall_bucket(LADDER) == 512
    _fed(tl, "ragged", 30.0, bucket=64, n=3)
    _fed(tl, "decode-wave", 60.0, waited_ms=0.2, n=16)  # host-bound waves
    assert tl.stall_bucket(LADDER) == 512
    _fed(tl, "ragged", 50.0, bucket=64, n=4)
    assert tl.stall_bucket(LADDER) == 32


def test_stall_bucket_leaves_out_a_step_that_compiled(multiple):
    """Where dispatch blocks, a step's device window holds its program's
    compile: such a step enters no median, mixed or wave."""
    from vllm_tgis_adapter_tpu import compile_tracker

    tl = StepTimeline()
    now = compile_tracker.total_recompiles()
    _fed(tl, "decode-wave", 4000.0, n=3, compiles=now - 1)
    assert tl.last_records(1)[0].compiled
    assert not tl._token_steps
    _fed(tl, "decode-wave", 20.0, n=3, compiles=now)
    assert not tl.last_records(1)[0].compiled
    _fed(tl, "ragged", 5000.0, bucket=32, n=8, compiles=now - 1)
    assert not tl._bucket_steps
    assert tl.stall_bucket(LADDER) == 512
    _fed(tl, "ragged", 5000.0, bucket=32, n=3, compiles=now)
    assert tl.stall_bucket(LADDER) == 16


def test_stall_bucket_reads_only_steps_that_carried_running_rows(multiple):
    """A cold admission or a fill of the idle engine makes no row wait:
    it says nothing of what a running row pays."""
    tl = StepTimeline()
    _fed(tl, "decode-wave", 20.0, n=16)
    _fed(tl, "ragged", 90.0, bucket=64, running=0, n=16)
    assert not tl._bucket_steps
    assert tl.last_records(1)[0].bucket == 64
    assert tl.stall_bucket(LADDER) == 512
    _fed(tl, "ragged", 90.0, bucket=64, running=1, n=3)
    assert tl.stall_bucket(LADDER) == 32


def test_stall_bucket_takes_an_unmeasured_bucket_as_within(multiple):
    """A bucket's first uses measure it, and fewer than
    ``STALL_MIN_SAMPLES`` of them shut nothing; one measured over the
    limit stops the walk whatever lies above it."""
    tl = StepTimeline()
    _fed(tl, "decode-wave", 20.0, n=4)
    _fed(tl, "ragged", 25.0, bucket=32, n=2)
    assert tl.stall_bucket(LADDER) == 512
    _fed(tl, "ragged", 70.0, bucket=256, n=2)
    assert tl.stall_bucket(LADDER) == 512  # two late results, not three
    _fed(tl, "ragged", 70.0, bucket=256)
    assert tl.stall_bucket(LADDER) == 128  # 64 and 128 never measured
    assert not {64, 128, 512} & set(tl._bucket_steps)


def test_stall_bucket_holds_its_choice_against_a_drifting_ratio(multiple):
    """The ratio moves a few per cent with the rows in flight: a bucket
    found over stays out until it reads ``STALL_HYSTERESIS`` under the
    limit, and one within stays in up to the limit itself."""
    tl = StepTimeline()
    _fed(tl, "decode-wave", 20.0, n=16)
    _fed(tl, "ragged", 30.0, bucket=64, n=5)
    _fed(tl, "ragged", 39.0, bucket=128, n=5)  # 1.95: within
    assert tl.stall_bucket(LADDER) == 512
    _fed(tl, "decode-wave", 19.0, n=16)  # 2.05: over
    assert tl.stall_bucket(LADDER) == 64
    _fed(tl, "decode-wave", 20.0, n=16)  # 1.95 again: still out
    assert tl.stall_bucket(LADDER) == 64
    _fed(tl, "decode-wave", 21.0, n=16)  # 1.86: not 8 % under yet
    assert tl.stall_bucket(LADDER) == 64
    _fed(tl, "decode-wave", 22.0, n=16)  # 1.77: back
    assert tl.stall_bucket(LADDER) == 512
    _fed(tl, "decode-wave", 20.0, n=16)  # 1.95: and it stays
    assert tl.stall_bucket(LADDER) == 512


def test_stall_bucket_gives_a_shut_bucket_one_step_to_be_read_again(
        multiple):
    """No step with running rows takes a bucket over the limit, so no
    step measures it: after ``STALL_REPROBE_S`` without a reading it is
    returned itself, once, and its reading joins the median."""
    from vllm_tgis_adapter_tpu.telemetry import steptime

    tl = StepTimeline()
    _fed(tl, "decode-wave", 20.0, n=16)
    _fed(tl, "ragged", 55.0, bucket=128, n=16)
    _fed(tl, "ragged", 95.0, bucket=256, n=5)
    assert tl.stall_bucket(LADDER) == 64
    tl._bucket_seen[128] -= steptime.STALL_REPROBE_S
    tl._bucket_seen[256] -= steptime.STALL_REPROBE_S
    assert tl.stall_bucket(LADDER) == 128  # one bucket up, not the ladder
    _fed(tl, "ragged", 30.0, bucket=128)  # the step that read it
    assert tl.stall_bucket(LADDER) == 64
    # the load has changed for good: nine such readings move the median
    for _ in range(8):
        tl._bucket_seen[128] -= steptime.STALL_REPROBE_S
        assert tl.stall_bucket(LADDER) == 128
        _fed(tl, "ragged", 30.0, bucket=128)
    assert tl.stall_bucket(LADDER) == 256  # 256's turn to be read again
    _fed(tl, "ragged", 95.0, bucket=256)
    assert tl.stall_bucket(LADDER) == 128


def test_stall_bucket_is_the_whole_ladder_with_nothing_measured(multiple):
    tl = StepTimeline()
    assert tl.stall_bucket(LADDER) == 512
    # mixed steps alone say nothing of a token step
    _fed(tl, "ragged", 400.0, bucket=16, n=16)
    assert tl.stall_bucket(LADDER) == 512
    _fed(tl, "decode-wave", 20.0, n=2)
    assert tl.stall_bucket(LADDER) == 512  # nor do two waves
    _fed(tl, "decode-wave", 20.0)
    assert tl.stall_bucket(LADDER) == 16


# ---------------------------------------------------------- real engine


def _build_engine(tiny_model_dir, **overrides):
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
            max_num_seqs=2, prefill_buckets=(32, 64)
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
        **overrides,
    )
    return AsyncLLMEngine.from_config(config)


async def _generate_one(engine, request_id: str, max_tokens: int = 4):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    final = None
    async for out in engine.generate(
        prompt=None,
        sampling_params=SamplingParams(
            temperature=0.0, max_tokens=max_tokens, ignore_eos=True
        ),
        request_id=request_id,
        prompt_token_ids=list(range(3, 20)),
    ):
        final = out
    return final


def test_engine_populates_step_timeline(tiny_model_dir):
    """A served request leaves finalized StepRecords in the core's
    ring — every one holding the sum invariant — and debug_state()
    carries the step_timeline and doctor sections."""
    engine = _build_engine(tiny_model_dir)

    async def scenario():
        await _generate_one(engine, "steptime-live-1")
        state = engine.debug_state()
        snapshot = engine._stall_snapshot()
        await engine.stop()
        return state, snapshot

    state, snapshot = asyncio.run(scenario())
    json.dumps(state)

    core = engine._replicas[0].engine
    assert len(core.steptime) > 0
    for record in core.steptime.last_records(len(core.steptime)):
        assert abs(sum(record.phases().values()) - record.wall_s) < 1e-9

    (rep_state,) = state["step_timeline"]["replicas"]
    assert rep_state["replica"] == 0
    assert rep_state["steps"] == len(core.steptime)
    assert rep_state["records"]
    kinds = {r["kind"] for r in rep_state["records"]}
    assert kinds <= {"ragged", "solo", "decode-wave"}
    from vllm_tgis_adapter_tpu.telemetry.doctor import REGIMES

    assert state["doctor"]["regimes"] == list(REGIMES)

    # satellite: the watchdog stall snapshot embeds the blamed
    # replica's recent StepRecords for post-mortem anatomy
    blamed = snapshot["stalled_replica"]
    assert blamed["replica"] == 0
    assert blamed["heartbeat_age_s"] >= 0
    assert blamed["step_records"] == core.steptime.records(last_n=64)


# ---------------------------------------------------------- chrome trace


def test_chrome_trace_golden_shape(tiny_model_dir):
    from vllm_tgis_adapter_tpu.telemetry.timeline import (
        DOCTOR_TID,
        EVENTS_TID,
        LEDGER_TID,
        PHASE_TIDS,
        chrome_trace_from_state,
        chrome_trace_json,
    )

    engine = _build_engine(tiny_model_dir)

    async def scenario():
        await _generate_one(engine, "timeline-1")
        state = engine.debug_state()
        await engine.stop()
        return state

    state = asyncio.run(scenario())
    trace = chrome_trace_from_state(state)
    json.dumps(trace)  # valid JSON end to end
    events = trace["traceEvents"]
    assert events

    meta = [e for e in events if e["ph"] == "M"]
    spans = [e for e in events if e["ph"] != "M"]
    assert meta and spans
    # metadata names every fixed track for every replica pid
    named = {(e["pid"], e.get("tid")) for e in meta}
    for pid in trace["otherData"]["replicas"]:
        assert (pid, None) in named
        for tid in (*PHASE_TIDS.values(), EVENTS_TID, DOCTOR_TID,
                    LEDGER_TID):
            assert (pid, tid) in named

    # non-meta events are ts-sorted (Perfetto does not require it, but
    # the exporter promises it so saved traces diff cleanly)
    stamps = [e["ts"] for e in spans]
    assert stamps == sorted(stamps)

    # stable pid/tid mapping: step phases on tracks 1-6 of the
    # replica's process, recorder instants on the fixed events track
    phase_spans = [e for e in spans if e.get("cat") == "step"]
    assert phase_spans
    for span in phase_spans:
        assert span["pid"] == 0
        assert span["tid"] == PHASE_TIDS[span["name"]]
        assert span["dur"] >= 1
    recorder_marks = [e for e in spans if e.get("cat") == "recorder"]
    assert recorder_marks
    assert all(e["tid"] == EVENTS_TID for e in recorder_marks)
    kinds = {e["name"] for e in recorder_marks}
    assert "admit" in kinds and "finish" in kinds

    # the serialized form all three surfaces serve round-trips
    assert json.loads(chrome_trace_json(state, last_steps=2))[
        "traceEvents"
    ]


def test_chrome_trace_ledger_and_doctor_tracks():
    """Offline composition: doctor episodes and --ledger-log records
    land on their fixed tracks with bounded durations."""
    from vllm_tgis_adapter_tpu.telemetry.timeline import (
        DOCTOR_TID,
        LEDGER_TID,
        chrome_trace_from_state,
    )

    state = {
        "step_timeline": {"replicas": []},
        "events": [],
        "doctor": {
            "active": [],
            "recent": [{
                "regime": "host_bound", "replica": 1,
                "opened_ts": 100.0, "closed_ts": 103.5,
                "evidence": {"host_gap_frac": 0.6}, "captured": True,
            }],
        },
    }
    ledger = [
        {"request_id": "r1", "arrival_time": 99.0, "queue_s": 0.5,
         "prefill_s": 0.2, "decode_s": 1.3, "outcome": "finish",
         "tenant": "t", "request_class": "default",
         "tokens_in": 16, "tokens_out": 4},
        {"request_id": "skipped-no-arrival"},
    ]
    trace = chrome_trace_from_state(state, ledger_records=ledger)
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    (doc,) = [e for e in spans if e["cat"] == "doctor"]
    assert doc["name"] == "host_bound"
    assert doc["pid"] == 1 and doc["tid"] == DOCTOR_TID
    assert doc["dur"] == 3_500_000  # 3.5s in chrome-trace microseconds
    assert doc["args"]["captured"] is True and doc["args"]["open"] is False
    (req,) = [e for e in spans if e["cat"] == "ledger"]
    assert req["tid"] == LEDGER_TID and req["name"] == "finish"
    assert req["dur"] == 2_000_000


# --------------------------------------------------------- HTTP surfaces


def _debug_app(engine, tiny_model_dir):
    import argparse

    from vllm_tgis_adapter_tpu.http import build_http_server

    args = argparse.Namespace(
        served_model_name=None, model=tiny_model_dir, api_key=None,
        root_path=None, profile_dir=None,
    )
    return build_http_server(args, engine)


def test_http_section_filter_doctor_and_timeline(tiny_model_dir):
    from vllm_tgis_adapter_tpu.http import HttpRequest

    engine = _build_engine(tiny_model_dir)
    app = _debug_app(engine, tiny_model_dir)

    def _get(path):
        return app.dispatch(HttpRequest("GET", path, {}, b""))

    async def scenario():
        await _generate_one(engine, "http-steptime-1")
        responses = {
            "section": await _get(
                "/debug/state?section=step_timeline,doctor"
            ),
            "bad_section": await _get("/debug/state?section=bogus"),
            "doctor": await _get("/debug/doctor"),
            "timeline": await _get("/debug/timeline?format=chrome"),
            "timeline_default": await _get("/debug/timeline"),
            "bad_format": await _get("/debug/timeline?format=xml"),
            "bad_last": await _get(
                "/debug/timeline?format=chrome&last_steps=zap"
            ),
            "bounded": await _get(
                "/debug/timeline?format=chrome&last_steps=1"
            ),
        }
        await engine.stop()
        return responses

    r = asyncio.run(scenario())

    assert r["section"].status == 200
    section = json.loads(r["section"].body)
    assert set(section) == {"step_timeline", "doctor"}
    assert section["step_timeline"]["replicas"][0]["records"]

    assert r["bad_section"].status == 404
    assert "bogus" in json.loads(r["bad_section"].body)["error"]["message"]

    assert r["doctor"].status == 200
    doctor = json.loads(r["doctor"].body)
    assert doctor["regimes"] and "thresholds" in doctor

    for key in ("timeline", "timeline_default", "bounded"):
        assert r[key].status == 200
        trace = json.loads(r[key].body)
        assert any(e["ph"] == "M" for e in trace["traceEvents"])
    assert r["bad_format"].status == 400
    assert r["bad_last"].status == 400

    # bounded export carries at most 1 step's phase spans per replica
    bounded = json.loads(r["bounded"].body)["traceEvents"]
    steps = {
        e["args"]["step"] for e in bounded
        if e["ph"] == "X" and e.get("cat") == "step"
    }
    assert len(steps) <= 1
