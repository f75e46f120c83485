"""The overlapped step loop: every successor is planned while its
predecessor runs (engine/async_llm.py ``_run_loop``,
``LLMEngine.plan_ahead_step``, ``Scheduler.schedule_ahead``).

Pinned here, for the llama path, the falcon-h1 path (recurrent state:
the batch row is the state's slot) and the DeepSeek path (latent cache,
held experts) at their small test sizes:

* a mixed stream of arrivals and decoding rows gives the tokens and
  logprobs of the strictly serial loop (``enable_chained_decode=False``),
  greedy and seeded, with a row that ends inside a projected step, and
  with the stall rule's bucket pinned under a prompt of three caps;
* an abort between a plan and its commit;
* a batch row reused right after its owner ended gives what a fresh
  server gives;
* every reason to fall back to the serial order is met once and counted;
* the fused depth of a wave follows the host turn over the token step;
* a window of dispatches after the warm-up set compiles nothing new.
"""

from __future__ import annotations

import asyncio
import dataclasses
import types

import pytest

from vllm_tgis_adapter_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    LoRAConfig,
    ModelConfig,
    ParallelConfig,
    SchedulerConfig,
)

FAMILIES = ("llama", "falcon_h1", "deepseek_v2")


@pytest.fixture(scope="module")
def model_dirs(tmp_path_factory, tiny_model_dir):
    """family -> checkpoint directory, each built when first asked for."""
    built = {"llama": tiny_model_dir}

    def get(family: str) -> str:
        if family not in built:
            from tests import fixture_models

            path = str(tmp_path_factory.mktemp(f"tiny-{family}"))
            if family == "falcon_h1":
                built[family] = fixture_models.build_tiny_falcon_h1(path)
            else:
                built[family] = fixture_models.build_tiny_deepseek_v2(
                    path, held=(4, 8))
        return built[family]

    return get


def make_config(model_dir, *, ahead, eos=None, num_blocks=64, max_num_seqs=4,
                **engine_kwargs):
    mcfg = ModelConfig.from_pretrained(model_dir, dtype="float32")
    if eos is not None:
        mcfg = dataclasses.replace(mcfg, eos_token_id=eos)
    return EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=num_blocks,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=max_num_seqs, prefill_buckets=(16, 32, 64),
            num_decode_steps=4, enable_chained_decode=ahead),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
        **engine_kwargs,
    )


def prompt(n: int, seed: int) -> list[int]:
    return [3 + (seed * 7 + i * 13) % 200 for i in range(n)]


def run_stream(config, requests, *, abort=None):
    """Serve ``requests`` (id, prompt ids, sampling kwargs, arrival delay
    in seconds) through the async loop.  Returns id -> (tokens, logprobs
    of the chosen tokens, finish reason) and the engine core.  ``abort``
    names a request to abort once a dispatch was planned ahead."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    async def scenario():
        core = LLMEngine.from_config(config)
        engine = AsyncLLMEngine(core)
        planned_ahead = asyncio.Event()
        inner = core.dispatch_chained_step

        def spy(plan, prepared, prev_handle):
            planned_ahead.set()
            return inner(plan, prepared, prev_handle)

        core.dispatch_chained_step = spy
        results = {}

        async def one(rid, ids, kwargs, delay):
            await asyncio.sleep(delay)
            final = None
            async for out in engine.generate(
                prompt=None,
                sampling_params=SamplingParams(logprobs=1, **kwargs),
                request_id=rid,
                prompt_token_ids=ids,
            ):
                final = out
            done = final.outputs[0]
            chosen = [
                getattr(entry[tok], "logprob", entry[tok])
                for tok, entry in zip(done.token_ids, done.logprobs or [])
            ]
            results[rid] = (list(done.token_ids), chosen, done.finish_reason)

        tasks = [asyncio.create_task(one(*r)) for r in requests]
        if abort is not None:
            await asyncio.wait_for(planned_ahead.wait(), timeout=60)
            await engine.abort(abort)
        await asyncio.wait_for(asyncio.gather(*tasks), timeout=100)
        await engine.stop()
        # every quarantine is lifted once nothing is in flight
        sched = core.scheduler
        assert not sched.allocator._free_epochs and not sched._slot_epochs
        assert sched.allocator.num_free == sched.allocator.num_blocks
        assert len(sched._free_slots) == sched.config.max_num_seqs
        return results, core

    return asyncio.run(scenario())


def ahead_dispatches() -> float:
    from vllm_tgis_adapter_tpu import metrics

    return metrics.step_dispatches_total.labels(
        ahead="true", replica="0")._value.get()


def assert_same(got, want):
    assert got.keys() == want.keys()
    for rid in want:
        assert got[rid][0] == want[rid][0], rid
        assert got[rid][2] == want[rid][2], rid
        assert got[rid][1] == pytest.approx(want[rid][1], abs=1e-5), rid


# ------------------------------------------------- equal to the serial loop


@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
@pytest.mark.parametrize("family", FAMILIES)
def test_mixed_stream_equals_the_serial_loop(model_dirs, family, sampling):
    """Rows decoding while others arrive, one of them ending on an EOS
    the planner cannot foresee (its projected tokens are discarded), one
    ending by length (it gets no successor span): tokens, logprobs and
    finish reasons are the serial loop's."""
    model_dir = model_dirs(family)
    how = (dict(temperature=0.0) if sampling == "greedy"
           else dict(temperature=0.9, seed=11))
    free = dict(ignore_eos=True, **how)
    # which token the row "e" says sixth: it becomes the EOS of both runs
    said, _ = run_stream(
        make_config(model_dir, ahead=False),
        [("e", prompt(9, 1), dict(max_tokens=12, **free), 0.0)])
    eos = said["e"][0][5]
    requests = [
        ("e", prompt(9, 1), dict(max_tokens=12, **how), 0.0),
        ("long", prompt(21, 2), dict(max_tokens=40, **free), 0.0),
        ("late", prompt(33, 3), dict(max_tokens=17, **free), 0.05),
        ("short", prompt(5, 4), dict(max_tokens=3, **free), 0.08),
        ("later", prompt(14, 5), dict(max_tokens=20, **free), 0.12),
    ]
    serial, core = run_stream(
        make_config(model_dir, ahead=False, eos=eos), requests)
    assert not core.ahead_bails
    before = ahead_dispatches()
    ahead, core = run_stream(
        make_config(model_dir, ahead=True, eos=eos), requests)
    assert ahead_dispatches() - before > 10
    assert_same(ahead, serial)
    assert serial["e"][2] == "stop" and len(serial["e"][0]) <= 6
    assert len(serial["long"][0]) == 40


@pytest.mark.parametrize("family", FAMILIES)
def test_mixed_stream_under_a_pinned_stall_bucket(model_dirs, family,
                                                  monkeypatch):
    """The stall rule cuts a prompt of three caps into capped steps over
    the rows that run: the overlapped loop still says what the serial
    loop says, token for token.  The cap is pinned, not read from this
    machine's clock."""
    from vllm_tgis_adapter_tpu import metrics
    from vllm_tgis_adapter_tpu.telemetry.steptime import StepTimeline

    monkeypatch.setattr(StepTimeline, "stall_bucket",
                        lambda self, ladder: 32)
    model_dir = model_dirs(family)
    free = dict(temperature=0.0, ignore_eos=True)
    requests = [
        ("a", prompt(9, 1), dict(max_tokens=48, **free), 0.0),
        ("b", prompt(21, 2), dict(max_tokens=40, **free), 0.0),
        ("long", prompt(96, 3), dict(max_tokens=12, **free), 0.06),
        ("late", prompt(40, 4), dict(max_tokens=9, **free), 0.1),
    ]
    capped = metrics.mixed_steps_total.labels(capped="true")
    before = capped._value.get()
    serial, core = run_stream(make_config(model_dir, ahead=False), requests)
    assert not core.ahead_bails
    assert core.scheduler.stall_bucket == 32
    # 96 tokens beside two rows in a bucket of 32: at least three steps
    assert capped._value.get() - before >= 3
    before, planned = capped._value.get(), ahead_dispatches()
    ahead, core = run_stream(make_config(model_dir, ahead=True), requests)
    assert ahead_dispatches() - planned > 10
    assert capped._value.get() - before >= 3
    assert_same(ahead, serial)
    assert len(serial["long"][0]) == 12


@pytest.mark.parametrize("family", FAMILIES)
def test_abort_between_plan_and_commit(model_dirs, family):
    """An abort that lands while a dispatch planned ahead is in flight:
    the victim ends as aborted, what it held is quarantined until that
    dispatch has retired, and its neighbours say what the serial loop
    says."""
    model_dir = model_dirs(family)
    free = dict(temperature=0.0, ignore_eos=True)
    requests = [
        ("victim", prompt(11, 6), dict(max_tokens=64, **free), 0.0),
        ("a", prompt(19, 7), dict(max_tokens=48, **free), 0.0),
        ("b", prompt(7, 8), dict(max_tokens=30, **free), 0.02),
    ]
    serial, _ = run_stream(make_config(model_dir, ahead=False), requests)
    ahead, _ = run_stream(
        make_config(model_dir, ahead=True), requests, abort="victim")
    assert ahead["victim"][2] == "abort"
    for rid in ("a", "b"):
        assert_same({rid: ahead[rid]}, {rid: serial[rid]})


@pytest.mark.parametrize("family", FAMILIES)
def test_a_row_reused_after_its_owner_ended(model_dirs, family):
    """Two batch rows, three requests: the third waits for a row, and
    gets the one whose owner ended on an unforeseen EOS while a projected
    step still held it.  What it says is what a fresh server says to it
    alone: nothing of the row's last owner is left (for falcon-h1 the row
    is the recurrent state's slot, started from zero at position 0)."""
    model_dir = model_dirs(family)
    free = dict(temperature=0.0, ignore_eos=True)
    said, _ = run_stream(
        make_config(model_dir, ahead=False),
        [("e", prompt(9, 9), dict(max_tokens=12, **free), 0.0)])
    eos = said["e"][0][4]
    requests = [
        ("e", prompt(9, 9), dict(max_tokens=12, temperature=0.0), 0.0),
        ("stays", prompt(17, 10), dict(max_tokens=50, **free), 0.0),
        ("waits", prompt(26, 11), dict(max_tokens=22, **free), 0.0),
    ]
    ahead, _ = run_stream(
        make_config(model_dir, ahead=True, eos=eos, max_num_seqs=2),
        requests)
    alone, _ = run_stream(
        make_config(model_dir, ahead=False, eos=eos, max_num_seqs=2),
        [requests[2]])
    assert ahead["e"][2] == "stop"
    assert_same({"waits": ahead["waits"]}, alone)


def test_a_preempted_row_comes_back_by_recompute_while_planning_ahead(
        model_dirs):
    """falcon-h1, a pool too small for its rows: the youngest is
    preempted (in the serial order: a projection never preempts, it
    bails with ``kv_growth``) and comes back by recompute from position
    0, which a step planned ahead may carry like any admission: tokens
    are those of a roomy pool."""
    from vllm_tgis_adapter_tpu import metrics

    model_dir = model_dirs("falcon_h1")
    free = dict(temperature=0.0, ignore_eos=True)
    requests = [(f"p{i}", prompt(14, 20 + i), dict(max_tokens=24, **free),
                 0.0) for i in range(3)]
    roomy, _ = run_stream(make_config(model_dir, ahead=False), requests)
    preempted = metrics.preemptions_total._value.get()
    before = ahead_dispatches()
    tight, core = run_stream(
        make_config(model_dir, ahead=True, num_blocks=6), requests)
    assert metrics.preemptions_total._value.get() > preempted
    assert core.ahead_bails.get("kv_growth", 0) >= 1
    assert ahead_dispatches() > before
    assert_same(tight, roomy)


def test_a_swapped_row_comes_back_on_a_clean_boundary(model_dirs):
    """llama with swap space, a pool too small: the victim's KV goes to
    the host and its swap-in rebinds the caches, so the loop bails
    (``swap_in``) and restores it between dispatches: tokens are those
    of a roomy pool."""
    from vllm_tgis_adapter_tpu import metrics

    model_dir = model_dirs("llama")
    free = dict(temperature=0.0, ignore_eos=True, repetition_penalty=1.3)
    # a short row ends while others run on: pages come free beside rows
    # in flight, and the swapped head's turn is due
    requests = [(f"s{i}", prompt(9 + i, 30 + i), dict(max_tokens=new, **free),
                 0.0) for i, new in enumerate((30, 12, 40, 40))]
    roomy, _ = run_stream(make_config(model_dir, ahead=False), requests)
    swapped = metrics.kv_swap_in_total.labels(replica="0")._value.get()
    tight, core = run_stream(
        make_config(model_dir, ahead=True, num_blocks=6, swap_space_gib=1.0),
        requests)
    assert metrics.kv_swap_in_total.labels(
        replica="0")._value.get() > swapped
    assert core.ahead_bails.get("swap_in", 0) >= 1
    assert_same(tight, roomy)


# ------------------------------------------------------- the counted bails


def _engine_with_a_step_in_flight(model_dir, **kwargs):
    """A core whose first dispatch (a 16-token prompt's mixed step) is
    enqueued and not yet committed: where the loop plans ahead."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    core = LLMEngine.from_config(
        make_config(model_dir, ahead=True, **kwargs))
    core.overlapped = True
    core.add_request(
        "r", None,
        SamplingParams(temperature=0.0, max_tokens=30, ignore_eos=True),
        prompt_token_ids=prompt(16, 40))
    _, plan, prepared = core.plan_step()
    handle = core.dispatch_step(plan, prepared)
    return core, plan, prepared, handle


def _wait_and_commit(core, plan, prepared, handle):
    core.commit_step(plan, core.wait_step(plan, prepared, handle), prepared)


def _add_waiting(core, rid="w", **params):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    core.add_request(
        rid, None,
        SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True,
                       **params),
        prompt_token_ids=prompt(12, 41))
    return core.scheduler.waiting[-1]


def _fsm_row(core):
    core.scheduler.running[0].fsm = object()


def _spec_row(core):
    core.scheduler.spec_gamma = 2
    core.scheduler.running[0].spec_eligible = True


def _prompt_logprob_head(core):
    _add_waiting(core, prompt_logprobs=1)


def _no_page_left(core):
    # the row's next token starts its second page, and none is free
    core.scheduler.allocator.can_allocate = lambda n: n <= 0


def _swapped_head(core):
    core.scheduler.swap_out_fn = lambda seq: True
    _add_waiting(core).swapped = (None, None, 12, 0)


def _promotion_ready(core):
    core._promotions.append(
        (core.scheduler.running[0],
         types.SimpleNamespace(ready=True, cancelled=False)))


def _prefill_role(core):
    core.replica_role = "prefill"


def _row_the_predecessor_did_not_sample(core):
    seq = _add_waiting(core)
    core.scheduler.waiting.remove(seq)
    core.scheduler.running.append(seq)


def _legacy_planner(core):
    core.scheduler.ragged = False


BAILS = {
    "fsm": _fsm_row,
    "spec_verify": _spec_row,
    "prompt_logprobs": _prompt_logprob_head,
    "kv_growth": _no_page_left,
    "swap_in": _swapped_head,
    "kv_tier": _promotion_ready,
    "handoff": _prefill_role,
    "composition": _row_the_predecessor_did_not_sample,
    "legacy_planner": _legacy_planner,
}


@pytest.mark.parametrize("reason", sorted(BAILS))
def test_a_bail_is_counted_by_its_reason(model_dirs, reason):
    """Each condition under which a projection cannot be right or safe
    makes ``plan_ahead_step`` plan nothing, count the bail under its
    reason and leave pages, rows and epochs as they were; without the
    condition the same call plans the successor."""
    from vllm_tgis_adapter_tpu import metrics

    core, plan, prepared, handle = _engine_with_a_step_in_flight(
        model_dirs("llama"))
    sched = core.scheduler
    counter = metrics.step_ahead_bails_total.labels(
        reason=reason, replica="0")
    counted = counter._value.get()
    BAILS[reason](core)
    free = sched.allocator.num_free
    _, ahead_plan, _ = core.plan_ahead_step(plan)
    assert ahead_plan is None
    assert core.ahead_bails == {reason: 1}
    assert counter._value.get() == counted + 1
    assert sched.allocator.num_free == free
    assert not sched.allocator._free_epochs and not sched._slot_epochs


def test_without_a_reason_the_successor_is_planned(model_dirs):
    """The control of the bail cases: the same engine state with no
    condition set plans a wave whose one row is projected one token on
    and fed from the device."""
    core, plan, prepared, handle = _engine_with_a_step_in_flight(
        model_dirs("llama"))
    _, ahead_plan, ahead_prep = core.plan_ahead_step(plan)
    assert not core.ahead_bails
    assert ahead_plan.inflight == [1]
    assert ahead_prep.feed[0] == 1 and ahead_prep.positions[0] == 16
    core.begin_free_epoch()
    ahead_handle = core.dispatch_chained_step(ahead_plan, ahead_prep, handle)
    _wait_and_commit(core, plan, prepared, handle)
    core.flush_free_epoch()
    _wait_and_commit(core, ahead_plan, ahead_prep, ahead_handle)
    seq = core.scheduler.running[0]
    assert seq.num_output_tokens == 1 + ahead_plan.num_steps


def test_the_staged_runner_is_served_in_the_serial_order(model_dirs):
    """A runner that cannot enqueue-only answers ``SYNC_DISPATCH``: its
    work happens inside the wait, nothing may be enqueued behind it, so
    the loop counts one ``sync_dispatch`` bail, plans nothing ahead and
    keeps the configured depth; tokens are the serial loop's."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.runner import SYNC_DISPATCH

    model_dir = model_dirs("llama")
    free = dict(temperature=0.0, ignore_eos=True)
    requests = [("a", prompt(9, 50), dict(max_tokens=20, **free), 0.0),
                ("b", prompt(13, 51), dict(max_tokens=9, **free), 0.03)]
    serial, _ = run_stream(make_config(model_dir, ahead=False), requests)

    inner = LLMEngine.from_config

    def staged(config, devices=None):
        core = inner(config, devices)
        runner = core.runner
        for kind in ("ragged", "decode"):
            dispatch = getattr(runner, f"dispatch_{kind}")
            wait = getattr(runner, f"wait_{kind}")
            setattr(runner, f"dispatch_{kind}", lambda prep: SYNC_DISPATCH)
            setattr(runner, f"wait_{kind}",
                    lambda prep, handle, d=dispatch, w=wait: w(prep, d(prep)))
        return core

    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(LLMEngine, "from_config", staticmethod(staged))
        before = ahead_dispatches()
        got, core = run_stream(make_config(model_dir, ahead=True), requests)
    finally:
        mp.undo()
    assert core.ahead_bails == {"sync_dispatch": 1}
    assert ahead_dispatches() == before
    assert core.scheduler.wave_depth is None
    assert_same(got, serial)


# ------------------------------------------------------------ the depth rule


def _timeline(turns_ms, step_ms, *, steps=1, kind="decode-wave"):
    """A StepTimeline whose ring holds one record a host turn given (in
    milliseconds), each of a dispatch of ``steps`` token steps of
    ``step_ms`` each."""
    from vllm_tgis_adapter_tpu.telemetry.steptime import (
        StepRecord,
        StepTimeline,
        _Stamps,
    )

    timeline = StepTimeline()
    for i, turn in enumerate(turns_ms):
        st = _Stamps()
        quarter = turn / 4 / 1000.0
        st.t_enter = 0.0
        st.t_sched = quarter
        st.t_prep = 2 * quarter
        st.t_disp0 = st.t_prep
        st.t_disp1 = 3 * quarter
        st.t_wait0 = st.t_disp1
        st.t_wait1 = st.t_disp1 + steps * step_ms / 1000.0
        timeline._ring.append(StepRecord(
            step=i, replica=0, kind=kind, tokens=steps, fill_ratio=1.0,
            stamps=st, t_end=st.t_wait1 + quarter, wall_end=0.0,
            host_gap_s=0.0, steps=steps,
            device_s=steps * step_ms / 1000.0, turn_s=turn / 1000.0))
        if kind == "decode-wave":
            timeline._token_steps.append(step_ms / 1000.0)
    return timeline


def test_depth_is_one_when_the_turn_is_under_a_token_step():
    assert _timeline([9.0] * 16, step_ms=22.0).wave_depth(8) == 1
    # whatever depth the waves measured had
    assert _timeline([9.0] * 16, step_ms=22.0, steps=8).wave_depth(8) == 1


def test_depth_rises_with_the_turn_and_stops_at_the_ceiling():
    depths = [_timeline([turn] * 16, step_ms=10.0).wave_depth(8)
              for turn in (4.0, 10.0, 11.0, 25.0, 39.0, 75.0, 400.0)]
    assert depths == [1, 1, 2, 3, 4, 8, 8]
    assert _timeline([400.0] * 16, step_ms=10.0).wave_depth(3) == 3
    # nothing measured yet: the ceiling stands, as in the serial order
    assert _timeline([], step_ms=10.0).wave_depth(8) == 8


def test_depth_does_not_flap_on_one_outlier():
    """One compile or one stalled turn among sixteen moves neither
    median; a turn that stays long does."""
    steady = [9.0] * 16
    for at in range(16):
        turns = list(steady)
        turns[at] = 4000.0
        assert _timeline(turns, step_ms=22.0).wave_depth(8) == 1
    assert _timeline([9.0] * 7 + [60.0] * 9, step_ms=22.0).wave_depth(8) == 3


def test_the_token_step_is_read_from_the_waves():
    """Mixed steps are longer than a token step: while waves are among
    the recent dispatches the token step is theirs."""
    from vllm_tgis_adapter_tpu.telemetry.steptime import StepTimeline

    waves = _timeline([30.0] * 8, step_ms=20.0, steps=2)
    mixed = _timeline([30.0] * 8, step_ms=90.0, kind="ragged")
    both = StepTimeline()
    both._ring.extend(list(waves._ring) + list(mixed._ring))
    both._token_steps.extend(waves._token_steps)
    assert both.wave_depth(8) == 2
    # mixed steps alone say nothing of a token step: the ceiling stands
    assert mixed.wave_depth(8) == 8


def test_a_wave_the_host_did_not_wait_for_says_nothing_of_the_token_step():
    """While the host's turn is the longer of the two, the distance
    between two results is the turn: such waves leave the token step
    where the last waves the host waited for put it, and the depth
    rises."""
    from vllm_tgis_adapter_tpu.telemetry.steptime import (
        StepTimeline,
        _Stamps,
    )

    timeline = StepTimeline()

    def wave(t0, turn, waited, steps=1):
        """A wave planned ahead whose predecessor's results came at
        ``t0``: the host's turn, then ``waited`` seconds of wait."""
        st = _Stamps()
        st.chained = True
        st.t_enter, st.t_sched, st.t_prep = t0, t0 + 0.001, t0 + 0.002
        st.t_disp0, st.t_disp1 = st.t_prep, t0 - 0.004  # enqueued earlier
        st.t_wait0 = t0 + turn
        st.t_wait1 = st.t_wait0 + waited
        timeline._steptime = st
        carrier = type("P", (), {"_steptime": st})()
        return timeline.finish(carrier, step=0, replica=0,
                               kind="decode-wave", tokens=steps,
                               fill_ratio=1.0, steps=steps)

    t = 100.0
    for _ in range(16):  # device-bound: a 10 ms turn, 12 ms more of wait
        rec = wave(t, 0.010, 0.012)
        t = rec.t_wait1
    assert timeline.wave_depth(8) == 1
    assert list(timeline._token_steps)[-1] == pytest.approx(0.022)
    for _ in range(16):  # host-bound: a 30 ms turn, the wait returns at once
        rec = wave(t, 0.030, 0.0002)
        t = rec.t_wait1
    assert list(timeline._token_steps)[-1] == pytest.approx(0.022)
    assert timeline.wave_depth(8) == 2


def test_the_serial_order_keeps_the_configured_depth(model_dirs):
    """Only the overlapped loop shortens a wave: ``LLMEngine.step`` and
    the serial loop hide no turn, so every fused step saves one."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    core = LLMEngine.from_config(
        make_config(model_dirs("llama"), ahead=True))
    core.add_request(
        "r", None,
        SamplingParams(temperature=0.0, max_tokens=9, ignore_eos=True),
        prompt_token_ids=prompt(9, 60))
    depths = []
    inner = core.runner.dispatch_decode

    def spy(prep):
        depths.append(prep.num_steps)
        return inner(prep)

    core.runner.dispatch_decode = spy
    while core.has_unfinished_requests():
        core.step()
    assert depths == [4, 4]
    assert core.scheduler.wave_depth is None


# ------------------------------------------------------ no program is added


@pytest.mark.parametrize("family", FAMILIES)
def test_a_window_after_the_warm_up_compiles_nothing_new(model_dirs, family):
    """One server, the same mix of arrivals twice: the second pass meets
    dispatches planned ahead of every kind (wave after wave, mixed step
    after wave, wave after mixed step, mixed step after mixed step) and
    compiles no program the first had not: a step planned ahead has no
    twin program."""
    from vllm_tgis_adapter_tpu import compile_tracker
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    config = make_config(model_dirs(family), ahead=True)
    free = dict(temperature=0.0, ignore_eos=True, logprobs=1)

    async def scenario():
        core = LLMEngine.from_config(config)
        engine = AsyncLLMEngine(core)
        kinds = set()
        inner = core.dispatch_chained_step

        def spy(plan, prepared, prev_handle):
            kinds.add((type(spy.prev).__name__, type(plan).__name__))
            spy.prev = plan
            return inner(plan, prepared, prev_handle)

        spy.prev = None
        first = core.dispatch_step

        def serial_spy(plan, prepared):
            spy.prev = plan
            return first(plan, prepared)

        core.dispatch_chained_step = spy
        core.dispatch_step = serial_spy

        async def one(rid, n, new, delay):
            await asyncio.sleep(delay)
            async for _ in engine.generate(
                prompt=None,
                sampling_params=SamplingParams(max_tokens=new, **free),
                request_id=rid, prompt_token_ids=prompt(n, n),
            ):
                pass

        async def stream(tag):
            # arrivals every 20 ms beside rows that decode for far longer
            sizes = ((9, 60), (21, 50), (40, 45), (5, 40), (60, 35),
                     (14, 30), (33, 25), (12, 20))
            await asyncio.gather(*(
                one(f"{tag}{i}", n, new, 0.02 * i)
                for i, (n, new) in enumerate(sizes)))

        await stream("warm")
        compiled = compile_tracker.total_recompiles()
        kinds.clear()
        await stream("window")
        await engine.stop()
        return compiled, compile_tracker.total_recompiles(), kinds

    compiled, after, kinds = asyncio.run(scenario())
    assert after == compiled
    assert {("DecodePlan", "DecodePlan"), ("DecodePlan", "RaggedPlan"),
            ("RaggedPlan", "DecodePlan"), ("RaggedPlan", "RaggedPlan")
            } <= kinds, kinds
    assert compile_tracker.shapes() & {
        ("ragged_chained_decode", s) for _, s in compile_tracker.shapes()
    } == set()
