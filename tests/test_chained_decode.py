"""Decode waves planned ahead (vLLM-style async scheduling).

While one fused decode wave executes on device, its successor is planned
from host projections and dispatched with token feedback read on the
device (runner.last_tokens, the newest sampled token of every batch row;
the general rule, any dispatch behind any, is tests/test_plan_ahead.py).
Pinned here:

* greedy output parity with the synchronous engine;
* rows finishing early (EOS/max_tokens) mid-chain discard the successor
  wave's tokens without corrupting batchmates;
* abort while a chained wave is in flight;
* the free-quarantine epochs that keep stale projected writes off
  re-allocated pages;
* the wave's step count is an input of ONE program, not a compile
  shape: waves of any live length, chained or not, give the tokens
  one-step waves give.
"""

from __future__ import annotations

import asyncio

import pytest


def _config(tiny_model_dir, **sched):
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    return EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=64,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=sched.pop("max_num_seqs", 4),
            prefill_buckets=(32,),
            num_decode_steps=sched.pop("num_decode_steps", 4), **sched),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )


def _sync_baseline(config, requests):
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine = LLMEngine.from_config(config)
    for rid, ids, kwargs in requests:
        engine.add_request(rid, None, SamplingParams(**kwargs),
                           prompt_token_ids=ids)
    outs = {}
    for _ in range(400):
        if not engine.has_unfinished_requests():
            break
        for out in engine.step():
            if out.finished:
                outs[out.request_id] = out
    return {rid: o.outputs[0].token_ids for rid, o in outs.items()}


def _async_run(config, requests, expect_chained=True):
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    async def scenario():
        core = LLMEngine.from_config(config)
        engine = AsyncLLMEngine(core)
        chained_count = [0]
        inner = core.dispatch_chained_step

        def spy(plan, prepared, prev_handle):
            chained_count[0] += 1
            return inner(plan, prepared, prev_handle)

        core.dispatch_chained_step = spy
        results = {}

        async def one(rid, ids, kwargs):
            final = None
            async for out in engine.generate(
                prompt=None,
                sampling_params=SamplingParams(**kwargs),
                request_id=rid,
                prompt_token_ids=ids,
            ):
                final = out
            results[rid] = final.outputs[0].token_ids

        await asyncio.gather(
            *[one(rid, ids, kw) for rid, ids, kw in requests]
        )
        await engine.stop()
        return results, chained_count[0]

    results, chained = asyncio.run(scenario())
    if expect_chained:
        assert chained > 0, "no chained decode wave was dispatched"
    return results


def test_chained_greedy_matches_sync(tiny_model_dir):
    """Long greedy generations (many waves) must be token-identical to
    the synchronous engine, and chained dispatches must actually fire."""
    requests = [
        ("a", list(range(3, 10)),
         dict(temperature=0.0, max_tokens=32, ignore_eos=True)),
        ("b", list(range(11, 20)),
         dict(temperature=0.0, max_tokens=32, ignore_eos=True)),
    ]
    baseline = _sync_baseline(_config(tiny_model_dir), requests)
    chained = _async_run(_config(tiny_model_dir), requests)
    assert chained == baseline


def test_chained_seeded_sampling_matches_sync(tiny_model_dir):
    """Chained waves keep the position-based PRNG streams: a seeded
    sampled request produces the identical tokens as the sync engine."""
    requests = [
        ("s", list(range(3, 10)),
         dict(temperature=0.9, seed=11, max_tokens=24, ignore_eos=True)),
    ]
    baseline = _sync_baseline(_config(tiny_model_dir), requests)
    chained = _async_run(_config(tiny_model_dir), requests)
    assert chained == baseline


def test_chained_mixed_lengths_early_finish(tiny_model_dir):
    """A row hitting max_tokens mid-chain discards its projected wave
    tokens; surviving batchmates stay token-identical to sync."""
    requests = [
        ("short", list(range(3, 10)),
         dict(temperature=0.0, max_tokens=6, ignore_eos=True)),
        ("long", list(range(11, 20)),
         dict(temperature=0.0, max_tokens=40, ignore_eos=True)),
    ]
    baseline = _sync_baseline(_config(tiny_model_dir), requests)
    chained = _async_run(_config(tiny_model_dir), requests)
    assert chained == baseline
    assert len(chained["short"]) == 6
    assert len(chained["long"]) == 40


def test_abort_during_chained_flight(tiny_model_dir):
    """abort() landing while a chained wave is in flight cancels the
    request; its packmate completes identically to sync."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    config = _config(tiny_model_dir)

    async def scenario():
        core = LLMEngine.from_config(config)
        engine = AsyncLLMEngine(core)
        chained_seen = asyncio.Event()
        inner = core.dispatch_chained_step

        def spy(plan, prepared, prev_handle):
            chained_seen.set()
            return inner(plan, prepared, prev_handle)

        core.dispatch_chained_step = spy

        outs = {}

        async def one(rid, max_tokens):
            final = None
            produced = 0
            async for out in engine.generate(
                prompt=None,
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=max_tokens,
                    ignore_eos=True,
                    output_kind=RequestOutputKind.DELTA),
                request_id=rid,
                prompt_token_ids=list(range(3, 10)),
            ):
                final = out
                produced += len(out.outputs[0].token_ids)
            outs[rid] = (final, produced)

        tasks = [
            asyncio.create_task(one("victim", 64)),
            asyncio.create_task(one("survivor", 64)),
        ]
        await asyncio.wait_for(chained_seen.wait(), timeout=30)
        await engine.abort("victim")
        await asyncio.wait_for(asyncio.gather(*tasks), timeout=60)
        await engine.stop()
        # pool fully reclaimable once everything finished (quarantine
        # epochs all flushed)
        alloc = core.scheduler.allocator
        assert not alloc._free_epochs
        assert alloc.num_free == alloc.num_blocks
        return outs

    outs = asyncio.run(scenario())
    assert outs["victim"][0].outputs[0].finish_reason == "abort"
    assert outs["survivor"][0].outputs[0].finish_reason == "length"
    assert outs["survivor"][1] == 64


def test_free_epoch_quarantine_unit():
    """free() during an open epoch buffers; pages release only at the
    matching flush, in FIFO epoch order."""
    from vllm_tgis_adapter_tpu.engine.kv_cache import BlockAllocator

    alloc = BlockAllocator(8, 16)
    a = alloc.allocate(2)
    b = alloc.allocate(2)
    assert alloc.num_free == 4

    alloc.begin_free_epoch()
    alloc.free(a)
    assert alloc.num_free == 4  # quarantined, not reusable
    alloc.begin_free_epoch()
    alloc.free(b)
    assert alloc.num_free == 4

    alloc.flush_free_epoch()  # oldest epoch: releases a
    assert alloc.num_free == 6
    alloc.flush_free_epoch()
    assert alloc.num_free == 8
    # balanced: no epochs left, frees are immediate again
    c = alloc.allocate(1)
    alloc.free(c)
    assert alloc.num_free == 8


def test_engine_death_during_chained_wave_flushes_epochs(tiny_model_dir):
    """A chained dispatch failure is whole-engine death (crash-fast):
    consumers get the error, and the quarantine epochs flush at loop
    teardown so no pages leak."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    config = _config(tiny_model_dir)

    async def scenario():
        core = LLMEngine.from_config(config)
        engine = AsyncLLMEngine(core)

        def boom(plan, prepared, prev_handle):
            raise RuntimeError("injected chained-dispatch failure")

        core.dispatch_chained_step = boom

        with pytest.raises(RuntimeError, match="injected"):
            async for _ in engine.generate(
                prompt=None,
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=32, ignore_eos=True),
                request_id="doomed",
                prompt_token_ids=list(range(3, 10)),
            ):
                pass
        assert engine.errored
        assert not core.scheduler.allocator._free_epochs
        await engine.stop()

    asyncio.run(scenario())


def test_chained_engages_under_saturation(tiny_model_dir):
    """A full batch with a waiting queue BLOCKED on slots must still
    chain (the saturated-server steady state): before round 5 the
    scheduler bailed on ANY waiting work, so a loaded server never got
    on-device token feedback.  Outputs stay token-identical to sync and
    every queued request completes."""
    requests = [
        (f"r{i}", list(range(3 + i, 12 + i)),
         dict(temperature=0.0, max_tokens=24, ignore_eos=True))
        for i in range(5)
    ]
    # max_num_seqs=2 -> 2 running, 3 waiting with no free slot for the
    # whole first cohort; admissions happen only as rows finish
    config = _config(tiny_model_dir, max_num_seqs=2)
    baseline = _sync_baseline(config, requests)
    chained = _async_run(_config(tiny_model_dir, max_num_seqs=2), requests)
    assert chained == baseline
    assert all(len(v) == 24 for v in chained.values())


def test_waiting_head_admissible_predicate(tiny_model_dir):
    """Unit: the chain gate mirrors admission — blocked on slots or
    pages -> not admissible (chain allowed); resources free ->
    admissible (chain bails)."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    config = _config(tiny_model_dir, max_num_seqs=2)
    engine = LLMEngine.from_config(config)
    sched = engine.scheduler
    assert not sched._waiting_head_admissible()  # empty queue

    for rid in ("a", "b", "c"):
        engine.add_request(rid, None,
                           SamplingParams(temperature=0.0, max_tokens=8,
                                          ignore_eos=True),
                           prompt_token_ids=list(range(3, 10)))
    # nothing admitted yet: head is admissible (slots + pages free)
    assert sched._waiting_head_admissible()
    # admit a+b (fills both slots) -> head "c" blocked on slots
    for _ in range(4):
        if len(sched.running) == 2:
            break
        engine.step()
    assert len(sched.running) == 2
    assert sched.waiting and sched.waiting[0].request_id == "c"
    assert not sched._free_slots
    assert not sched._waiting_head_admissible()


def test_admissible_probe_releases_prefix_refcounts(tiny_model_dir):
    """The chain-gate's prefix probe must not pin cached pages: repeated
    probes with prefix caching on leave the allocator's free count
    untouched (match_prefix refcounts its hits; the probe frees them)."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    import dataclasses as _dc

    config = _config(tiny_model_dir, max_num_seqs=2)
    config = _dc.replace(
        config,
        cache_config=_dc.replace(config.cache_config,
                                 enable_prefix_caching=True))
    engine = LLMEngine.from_config(config)
    sched = engine.scheduler

    shared = list(range(3, 35))  # two full pages of shared prefix
    engine.add_request("warm", None,
                       SamplingParams(temperature=0.0, max_tokens=4,
                                      ignore_eos=True),
                       prompt_token_ids=shared)
    for _ in range(40):
        if not engine.has_unfinished_requests():
            break
        engine.step()
    free_before = sched.allocator.num_free

    # same prefix waits in the queue: every probe hits the cache
    engine.add_request("probe-target", None,
                       SamplingParams(temperature=0.0, max_tokens=4,
                                      ignore_eos=True),
                       prompt_token_ids=list(shared))
    for _ in range(25):
        sched._waiting_head_admissible()
    assert sched.allocator.num_free == free_before


# ------------------------------------------------- traced step count


def _wave_compiles(fn: str) -> float:
    """Compiles the tracker has counted for one jitted entry point."""
    from vllm_tgis_adapter_tpu import metrics

    return sum(
        sample.value
        for family in metrics.xla_recompile_total.collect()
        for sample in family.samples
        if sample.name.endswith("_total") and sample.labels["fn"] == fn
    )


def _spy_wave_steps(core):
    """Record the live step count of every plain wave dispatched."""
    seen = []
    inner = core.runner.dispatch_decode

    def spy(prep):
        seen.append(prep.num_steps)
        return inner(prep)

    core.runner.dispatch_decode = spy
    return seen


@pytest.mark.parametrize("steps", [1, 3, 8])
def test_wave_of_n_live_steps_matches_one_step_waves(tiny_model_dir, steps):
    """A fused wave that ends after ``steps`` of its 8 steps (every row
    out of budget) gives the tokens that ``steps`` one-step waves give:
    the reference is the program whose loop can only run once."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    # the prefill samples token 1; one wave of ``steps`` tokens follows
    requests = [
        ("a", list(range(3, 10)),
         dict(temperature=0.0, max_tokens=steps + 1, ignore_eos=True)),
    ]
    baseline = _sync_baseline(
        _config(tiny_model_dir, num_decode_steps=1), requests
    )
    core = LLMEngine.from_config(_config(tiny_model_dir, num_decode_steps=8))
    seen = _spy_wave_steps(core)
    for rid, ids, kwargs in requests:
        core.add_request(rid, None, SamplingParams(**kwargs),
                         prompt_token_ids=ids)
    outs = {}
    for _ in range(50):
        if not core.has_unfinished_requests():
            break
        for out in core.step():
            if out.finished:
                outs[out.request_id] = out.outputs[0].token_ids
    assert seen == [steps]
    assert outs == baseline
    assert len(outs["a"]) == steps + 1


def test_one_wave_program_for_every_step_count(tiny_model_dir):
    """Waves of 1, 3, 8 and 5 live steps through ONE engine compile
    ``ragged_decode`` once: the step count is data."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    core = LLMEngine.from_config(_config(tiny_model_dir, num_decode_steps=8))
    seen = _spy_wave_steps(core)
    before = _wave_compiles("ragged_decode")
    for steps in (1, 3, 8, 5):
        core.add_request(
            f"r{steps}", None,
            SamplingParams(temperature=0.0, max_tokens=steps + 1,
                           ignore_eos=True),
            prompt_token_ids=list(range(3, 10)),
        )
        for _ in range(50):
            if not core.has_unfinished_requests():
                break
            core.step()
    assert seen == [1, 3, 8, 5]
    assert _wave_compiles("ragged_decode") - before == 1


def test_chained_wave_after_short_wave(tiny_model_dir):
    """A wave planned ahead behind a SHORT one (a tight pool halves a
    row's fused run, scheduler._schedule_decode; here the cap is patched
    in for a wave that follows a mixed step) projects each row
    ``prev_k`` tokens on and reads its input token from the row's newest
    sample on the device: tokens equal the sync engine's, and the ONE
    wave program compiles once (there is no chained twin)."""
    from vllm_tgis_adapter_tpu.engine.runner import ModelRunner
    from vllm_tgis_adapter_tpu.engine.scheduler import Scheduler
    from vllm_tgis_adapter_tpu.telemetry.steptime import StepTimeline

    requests = [
        ("a", list(range(3, 10)),
         dict(temperature=0.0, max_tokens=30, ignore_eos=True)),
        ("b", list(range(11, 20)),
         dict(temperature=0.0, max_tokens=30, ignore_eos=True)),
    ]
    baseline = _sync_baseline(_config(tiny_model_dir), requests)

    inner = Scheduler._allowed_steps
    short_prev = []
    plain = _wave_compiles("ragged_decode")
    prepare = ModelRunner.prepare_decode

    def spy(self, plan):
        if plan.inflight:
            short_prev.append((max(plan.inflight), plan.num_steps))
        return prepare(self, plan)

    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(
            Scheduler, "_allowed_steps",
            lambda self, seq: inner(self, seq)
            if self._inflight.get(id(seq), 0) > 1
            else min(inner(self, seq), 3),
        )
        # the depth rule would follow this machine's clock: hold the
        # ceiling so the successor's depth is known
        mp.setattr(StepTimeline, "wave_depth",
                   lambda self, ceiling, window=16: ceiling)
        mp.setattr(ModelRunner, "prepare_decode", spy)
        got = _async_run(_config(tiny_model_dir, num_decode_steps=8),
                         requests)
    finally:
        mp.undo()
    assert got == baseline
    # a plain wave of 3 steps, then its successor at the full 8
    assert (3, 8) in short_prev, short_prev
    assert _wave_compiles("ragged_decode") - plain == 1
    assert _wave_compiles("ragged_chained_decode") == 0
