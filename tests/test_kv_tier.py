"""Tiered KV store (docs/KV_TIERING.md) — the host-RAM hash-addressed
prefix cache behind the device pool (``engine/kv_tier.py``).

Layers: store units (hash addressing, byte-budgeted LRU, corrupt-entry
integrity), the demote→promote round trip on BOTH attention backends
(token-identical to an un-tiered baseline), scheduler parking semantics
(a promoting request must not block other work), compile discipline
(gather/scatter ride one fixed block shape), the ``--no-kv-host-cache``
off-switch, and the cross-restart chaos acceptance: a failpoint-killed
engine rebuilds under supervision and re-serves a warm prefix from the
SURVIVING host tier, token-identically (``nox -s chaos_check``).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from vllm_tgis_adapter_tpu.supervisor import failpoints


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    failpoints.disarm()


# --------------------------------------------------------------- store units


def _tier(budget=1 << 20, block_size=4):
    from vllm_tgis_adapter_tpu.engine.kv_tier import HostKVTier

    return HostKVTier(budget, block_size)


def _page(seed, shape=(2, 2, 4, 8)):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal(shape).astype(np.float32),
        rng.standard_normal(shape).astype(np.float32),
    )


def test_store_hash_addressing_and_chain_peek():
    from vllm_tgis_adapter_tpu.engine.kv_cache import (
        BlockAllocator,
        chain_digests,
    )

    ids = list(range(1, 14))  # 13 tokens, block 4 -> 3 full pages
    digests = chain_digests(ids, 4)
    assert len(digests) == 3
    # identical chain as the allocator's own walk: register pages under
    # the allocator, then verify digests line up via match/peek parity
    alloc = BlockAllocator(8, 4, enable_prefix_caching=True)
    blocks = alloc.allocate(4)
    alloc.register_prefix(ids, blocks)
    assert alloc.peek_prefix(ids) == 12  # 3 pages (capped one short)
    # LoRA seed changes every digest
    assert chain_digests(ids, 4, lora_name="ad")[0] != digests[0]

    tier = _tier()
    tier.submit([(digests[0], *_page(0)), (digests[1], *_page(1))])
    assert tier.peek_pages(digests) == 2
    assert tier.peek_pages(digests[2:]) == 0
    # chain walk stops at the first gap
    tier.submit([(digests[2], *_page(2))])
    assert tier.peek_pages(digests) == 3


def test_store_byte_budget_lru_eviction():
    k, v = _page(0)
    per_entry = k.nbytes + v.nbytes
    tier = _tier(budget=3 * per_entry)
    for i in range(5):
        tier.submit([(bytes([i]) * 8, *_page(i))])
    assert len(tier._entries) == 3
    assert tier.bytes_used == 3 * per_entry
    assert tier.evictions == 2
    # oldest evicted first
    assert tier.peek_pages([bytes([0]) * 8]) == 0
    assert tier.peek_pages([bytes([4]) * 8]) == 1
    # an entry larger than the whole budget is refused, not looped on
    big = np.zeros((2, 2, 4, 8 * 64), np.float32)
    tier.submit([(b"big" * 4, big, big)])
    assert tier.peek_pages([b"big" * 4]) == 0


def test_store_demotion_backpressure_drops_when_backlogged():
    """Gathered device copies live outside the pool budget until the
    transfer drains; past the in-flight byte bound, demotions DROP
    (a future cache miss) instead of accumulating."""
    tier = _tier()
    k, v = _page(0)
    tier.max_inflight_demotion_bytes = k.nbytes + v.nbytes

    async def scenario():
        # saturate the bound with a first in-flight batch, then submit
        # a second — it must drop, not queue
        tier.submit([(b"a" * 8, *_page(1))])
        assert tier._inflight_bytes > 0
        tier.submit([(b"b" * 8, *_page(2))])
        assert tier.demotions_dropped == 1
        for _ in range(100):
            if not tier._tasks:
                break
            await asyncio.sleep(0.01)
        assert tier._inflight_bytes == 0
        assert tier.peek_pages([b"a" * 8]) == 1  # first batch landed
        assert tier.peek_pages([b"b" * 8]) == 0  # dropped one missed

    asyncio.run(scenario())


def test_store_corrupt_entry_dropped_not_served():
    from vllm_tgis_adapter_tpu.engine.kv_tier import PromotionTicket

    tier = _tier()
    d_ok, d_bad = b"ok" * 8, b"bad" * 8
    tier.submit([(d_ok, *_page(0)), (d_bad, *_page(1))])
    # corrupt the second entry in place: truncated K array (short read)
    tier._entries[d_bad].k = tier._entries[d_bad].k[:1]
    ticket = PromotionTicket(
        request_id="r", digests=[d_ok, d_bad], start_tokens=0,
        end_tokens=8,
    )
    tier.start_promotion(ticket, lambda x: x)  # sync path (no loop)
    assert ticket.ready and not ticket.failed
    # only the valid page served; the corrupt one was DROPPED
    assert len(ticket.pages) == 1
    assert ticket.end_tokens == 4
    assert tier.dropped_corrupt == 1
    assert tier.peek_pages([d_bad]) == 0


def test_store_shrunk_to_zero_fails_ticket():
    from vllm_tgis_adapter_tpu.engine.kv_tier import PromotionTicket

    tier = _tier()
    ticket = PromotionTicket(
        request_id="r", digests=[b"gone" * 4], start_tokens=0,
        end_tokens=4,
    )
    tier.start_promotion(ticket, lambda x: x)
    assert ticket.ready and ticket.failed


# ------------------------------------------------------ engine round trips


def _build_engine(tiny_model_dir, *, tier_gb=1.0, num_blocks=6,
                  backend="ragged", prefix_caching=True, max_seqs=4):
    import jax.numpy as jnp  # noqa: F401

    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    return LLMEngine.from_config(EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16, num_blocks=num_blocks, cache_dtype=mcfg.dtype,
            enable_prefix_caching=prefix_caching,
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=max_seqs, prefill_buckets=(32, 64, 128),
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
        kv_host_cache_gb=tier_gb,
        attention_backend=backend,
    ))


def _run(eng, rid, ids, n=6):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    eng.add_request(
        rid, None,
        SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True),
        prompt_token_ids=ids,
    )
    for _ in range(400):
        if not eng.has_unfinished_requests():
            break
        for out in eng.step():
            if out.finished and out.request_id == rid:
                return out.outputs[0].token_ids
    raise AssertionError(f"request {rid} did not finish")


SHARED = list(range(3, 60))  # 57 tokens: 3 full pages + tail
FILLER_1 = list(range(100, 157))
FILLER_2 = list(range(200, 257))


@pytest.mark.parametrize("backend", ["ragged"])
def test_demote_promote_token_identity_vs_untiered(tiny_model_dir, backend):
    """Device pool too small to retain the prefix across churn: the warm
    re-send must be served through the host tier (promotion observed)
    and stay token-identical to an un-tiered engine's output."""
    base = _build_engine(tiny_model_dir, tier_gb=0.0, backend=backend)
    assert base.kv_tier is None
    assert base.scheduler.kv_gate is None  # --no-kv-host-cache contract
    want = _run(base, "a", SHARED)

    eng = _build_engine(tiny_model_dir, tier_gb=1.0, backend=backend)
    assert eng.kv_tier is not None
    got = _run(eng, "a", SHARED)
    # eviction → demotion: nothing copies while the device cache still
    # holds the pages; churning the 9-page pool reclaims them and THAT
    # is when they demote (instead of vanishing)
    _run(eng, "f1", FILLER_1)
    _run(eng, "f2", FILLER_2)
    assert eng.kv_tier.demoted_pages >= 3
    alloc = eng.scheduler.allocator
    hits0, lookups0 = alloc.prefix_hits, alloc.prefix_lookup_tokens
    got2 = _run(eng, "a2", SHARED)
    assert got == got2 == want
    # all three full pages of the warm prompt come back through the host
    # tier: a hit rate of 48 / 57 over a device pool that had lost them
    assert eng.kv_host_promoted_tokens >= 48, "reuse never hit the host tier"
    assert (alloc.prefix_hits - hits0) >= 0.5 * (
        alloc.prefix_lookup_tokens - lookups0
    )
    kinds = [e["kind"] for e in eng.recorder.events()]
    assert "demote_host" in kinds and "promote_host" in kinds


def test_preemption_demotes_into_the_same_store(tiny_model_dir):
    """A preemption victim's computed pages land in the hash-addressed
    store (core._swap_out_seq territory without --swap-space), so its
    resume — and any LATER request sharing the prefix — promotes
    instead of recomputing blind."""
    eng = _build_engine(tiny_model_dir, tier_gb=1.0, num_blocks=10,
                        max_seqs=2)
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    assert eng.scheduler.swap_out_fn is not None  # tier demote hook
    long_a = list(range(3, 60))
    long_b = list(range(70, 127))
    eng.add_request(
        "a", None,
        SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True),
        prompt_token_ids=long_a,
    )
    eng.add_request(
        "b", None,
        SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True),
        prompt_token_ids=long_b,
    )
    for _ in range(600):
        if not eng.has_unfinished_requests():
            break
        eng.step()
    # both finished despite pool pressure; preemption demoted pages
    kinds = [e["kind"] for e in eng.recorder.events()]
    if "preempt" in kinds:
        assert "demote_host" in kinds
    assert eng.kv_tier.demoted_pages > 0


def test_preemption_demotes_only_fully_written_pages(tiny_model_dir):
    """Regression (review finding): the cache-coverage invariant is
    positions [0, num_tokens-1) written — a preemption victim's LAST
    page, which contains the just-sampled token's unwritten slot, must
    NEVER enter the hash-addressed store (a poisoned page would serve
    garbage to every future chain extension through it)."""
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.kv_cache import chain_digests
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    eng = LLMEngine.from_config(EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=32,
                                 cache_dtype=mcfg.dtype,
                                 enable_prefix_caching=True),
        scheduler_config=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(32, 64),
            num_decode_steps=1,  # single-step: num_tokens is steerable
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
        kv_host_cache_gb=1.0,
    ))
    prompt = list(range(3, 19))  # exactly one page
    eng.add_request(
        "v", None,
        SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True),
        prompt_token_ids=prompt,
    )
    seq = eng.scheduler.waiting[0]
    for _ in range(100):
        if seq.num_tokens == 32:  # page 1 full, position 31 UNWRITTEN
            break
        eng.step()
    assert seq.num_tokens == 32
    assert eng.scheduler._preempt_youngest()  # demotes via the tier hook
    digests = chain_digests(list(seq.all_token_ids), 16)
    assert len(digests) == 2
    assert eng.kv_tier.peek_pages(digests[:1]) == 1  # written page tiered
    # the page containing the unwritten just-sampled slot did NOT tier
    assert eng.kv_tier.peek_pages(digests[1:]) == 0


def test_parked_promotion_does_not_block_other_work(tiny_model_dir):
    """While one request parks on a (never-completing) promotion, fresh
    requests keep admitting and finishing — the adapter-pool parking
    contract, on the kv gate."""
    eng = _build_engine(tiny_model_dir, tier_gb=1.0, num_blocks=64)
    sched = eng.scheduler

    from vllm_tgis_adapter_tpu.engine.kv_tier import PromotionTicket
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    eng.add_request(
        "parked", None,
        SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
        prompt_token_ids=SHARED,
    )
    parked = sched.waiting[0]
    # pin an in-flight (never-ready) ticket on the head
    parked.kv_promotion = PromotionTicket(
        request_id="parked", digests=[b"x"], start_tokens=0,
        end_tokens=16,
    )
    eng._promotions.append((parked, parked.kv_promotion))
    got = _run(eng, "fresh", FILLER_1, n=4)
    assert len(got) == 4
    assert parked.num_output_tokens == 0  # still parked, not broken
    # release the park: ticket fails -> request un-parks and completes
    parked.kv_promotion.failed = True
    parked.kv_promotion.ready = True
    out = None
    for _ in range(200):
        if not eng.has_unfinished_requests():
            break
        for o in eng.step():
            if o.finished and o.request_id == "parked":
                out = o
    assert out is not None and len(out.outputs[0].token_ids) == 4


def test_gather_scatter_ride_one_fixed_shape(tiny_model_dir):
    """Compile discipline (ISSUE 9 acceptance): the tier's gather and
    scatter entry points compile ONE block-shaped program each, no
    matter how many pages or prompts flow through."""
    from vllm_tgis_adapter_tpu import compile_tracker

    eng = _build_engine(tiny_model_dir, tier_gb=1.0)
    _run(eng, "a", SHARED)
    _run(eng, "f1", FILLER_1)
    _run(eng, "f2", FILLER_2)
    _run(eng, "a2", SHARED)
    _run(eng, "f3", list(range(300, 345)))  # different page count
    _run(eng, "a3", SHARED)
    assert eng.kv_tier.demoted_pages > 0
    assert eng.kv_host_promoted_tokens > 0
    shapes = [
        key for key in compile_tracker.shapes()
        if key[0] in ("gather_kv", "scatter_kv")
    ]
    gather = [k for k in shapes if k[0] == "gather_kv"]
    scatter = [k for k in shapes if k[0] == "scatter_kv"]
    assert len(gather) <= 1, gather
    assert len(scatter) <= 1, scatter


def test_tier_off_is_pre_tier_engine(tiny_model_dir):
    """--no-kv-host-cache (library default 0.0): no tier object, no
    scheduler gate, no swap hook beyond --swap-space's own — the
    pre-tier engine, byte-identically."""
    eng = _build_engine(tiny_model_dir, tier_gb=0.0)
    assert eng.kv_tier is None
    assert eng.scheduler.kv_gate is None
    assert eng.scheduler.swap_out_fn is None
    assert eng._promotions == []
    # config plumbing: --no-kv-host-cache zeroes the budget
    from vllm_tgis_adapter_tpu.engine.config import EngineConfig
    from vllm_tgis_adapter_tpu.tgis_utils.args import make_parser

    args = make_parser().parse_args(
        ["--model", tiny_model_dir, "--no-kv-host-cache"]
    )
    assert EngineConfig.from_args(args).kv_host_cache_gb == 0.0
    args = make_parser().parse_args(["--model", tiny_model_dir])
    assert EngineConfig.from_args(args).kv_host_cache_gb == 4.0
    # --no-decode-resume: the mid-decode checkpoint/resume escape hatch
    assert EngineConfig.from_args(args).decode_resume is True
    args = make_parser().parse_args(
        ["--model", tiny_model_dir, "--no-decode-resume"]
    )
    assert EngineConfig.from_args(args).decode_resume is False


def test_placement_scores_host_tier_below_device():
    """Router tier weighting (docs/SCALING.md): device residency beats
    host residency; host residency beats nothing."""
    from vllm_tgis_adapter_tpu.frontdoor.placement import (
        PlacementRouter,
        ReplicaSnapshot,
    )

    router = PlacementRouter()
    # host-only coverage still wins a prefix placement over pure load
    idx, policy = router.place([
        ReplicaSnapshot(index=0, load=1, host_prefix_tokens=64),
        ReplicaSnapshot(index=1, load=0, host_prefix_tokens=64),
    ])
    # both replicas share the tier; the LESS loaded one takes it
    assert policy == "prefix" and idx == 1
    # device residency outranks host residency at 4x the weight
    idx, policy = router.place([
        ReplicaSnapshot(index=0, load=0, prefix_tokens=32,
                        host_prefix_tokens=64),
        ReplicaSnapshot(index=1, load=0, prefix_tokens=0,
                        host_prefix_tokens=64),
    ])
    assert policy == "prefix" and idx == 0
    # fleet-uniform host coverage must NOT outrank adapter residency
    # (it carries no replica-discriminating information): the request
    # still routes to its adapter's replica
    idx, policy = router.place([
        ReplicaSnapshot(index=0, load=1, host_prefix_tokens=64,
                        adapter_resident=True),
        ReplicaSnapshot(index=1, load=0, host_prefix_tokens=64),
    ])
    assert policy == "adapter" and idx == 0


# ----------------------------------------------------- chaos acceptance


def _build_async(tiny_model_dir, *, num_blocks=6):
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
            block_size=16, num_blocks=num_blocks, cache_dtype=mcfg.dtype,
            enable_prefix_caching=True,
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(32, 64),
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
        kv_host_cache_gb=1.0,
        max_engine_restarts=3,
        engine_restart_backoff_s=0.02,
        frontdoor=FrontdoorConfig(enabled=True),
    )
    return AsyncLLMEngine.from_config(config)


async def _acollect(engine, request_id, prompt_ids, n=6):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    final = None
    try:
        async for out in engine.generate(
            prompt=None,
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=n, ignore_eos=True
            ),
            request_id=request_id,
            prompt_token_ids=list(prompt_ids),
        ):
            final = out
        return ("ok", final)
    except BaseException as e:  # noqa: BLE001 — the error IS the result
        return ("err", e)


def test_cross_restart_reuse_from_surviving_tier(tiny_model_dir):
    """THE chaos acceptance (ISSUE 9): an engine failpoint-killed while
    the host tier is warm (and a promotion could be mid-flight) rebuilds
    under supervision; the rebuilt replica re-serves the warm prefix
    FROM THE SURVIVING HOST TIER — promotion observed on the NEW engine,
    outputs token-identical to the pre-crash run."""
    baseline = _build_engine(tiny_model_dir, tier_gb=0.0)
    want = _run(baseline, "base", SHARED)

    engine = _build_async(tiny_model_dir)

    async def scenario():
        # 1. warm the tier: serve the shared prefix, then churn it out
        # of the 9-page device pool
        status, final = await _acollect(engine, "warm", SHARED)
        assert status == "ok"
        got = list(final.outputs[0].token_ids)
        assert got == want
        for i, filler in enumerate((FILLER_1, FILLER_2)):
            status, _ = await _acollect(engine, f"filler-{i}", filler)
            assert status == "ok"
        old_tier = engine.engine.kv_tier
        assert old_tier is not None and old_tier.demoted_pages > 0

        # 2. kill the engine: next plan_step raises; the supervisor
        # quiesces, rebuilds, re-arms
        failpoints.arm_site("core.plan_step", "raise", 1)
        kill_task = asyncio.create_task(
            _acollect(engine, "victim", FILLER_1)
        )
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if (
                engine.supervisor is not None
                and any(
                    h.get("recovered")
                    for h in engine.supervisor.restart_history
                )
            ):
                break
            await asyncio.sleep(0.02)
        else:
            raise AssertionError("supervised restart never completed")
        await kill_task  # replayed or failed retryable; either is fine

        # 3. the REBUILT engine carries the SURVIVING tier...
        new_core = engine._replicas[0].engine
        assert new_core.kv_tier is old_tier
        assert new_core.scheduler.kv_gate is not None
        # ...and serves the warm prefix from it, token-identically
        promoted_before = new_core.kv_host_promoted_tokens
        status, final = await _acollect(engine, "rewarm", SHARED)
        assert status == "ok"
        assert list(final.outputs[0].token_ids) == want
        assert new_core.kv_host_promoted_tokens > promoted_before, (
            "rebuilt replica did not hit the host tier"
        )
        kinds = [e["kind"] for e in new_core.recorder.events()]
        assert "promote_host" in kinds
        await engine.stop()

    asyncio.run(scenario())


# --------------------------------------- decode checkpoints (ISSUE 10)


def _ckpt(request_id="r", digests=(), pages=0, **overrides):
    import dataclasses

    from vllm_tgis_adapter_tpu.engine.kv_tier import DecodeCheckpoint

    base = DecodeCheckpoint(
        request_id=request_id, prompt=None,
        prompt_token_ids=[1, 2, 3], output_token_ids=[4, 5],
        params=None, fallback_seed=7, arrival_time=0.0, deadline=None,
        tenant_id=None, lora_name=None, trace_id=None,
        emitted_token_len=2, emitted_text_len=0, stop_scan_pos=0,
        output_logprobs=None, prompt_logprobs=None,
        first_scheduled_time=None, first_token_time=None,
        last_token_time=None, time_in_queue=None,
        digests=list(digests), pages=pages,
    )
    return dataclasses.replace(base, **overrides) if overrides else base


def test_checkpoint_store_stage_validate_pop():
    """Store units for the mid-decode resume records: staging, the
    all-pages-committed validation read (corrupt entries read as
    misses), the trivially-valid zero-page case, and consumption."""
    tier = _tier()
    d0, d1 = b"a" * 8, b"b" * 8
    ckpt = _ckpt(digests=[d0, d1], pages=2)
    tier.stage_checkpoint(ckpt)
    assert tier.pending_checkpoints() == [ckpt]
    assert tier.debug_state()["checkpoints"] == 1

    assert not tier.validate_checkpoint(ckpt)  # nothing committed
    tier.submit([(d0, *_page(0))])
    assert not tier.validate_checkpoint(ckpt)  # short by one page
    tier.submit([(d1, *_page(1))])
    assert tier.validate_checkpoint(ckpt)

    # a checkpoint with no full page written resumes via recompute —
    # trivially valid
    assert tier.validate_checkpoint(_ckpt(request_id="r0"))

    # corrupt entry: validation reads it as a miss (and drops it)
    tier._entries[d1].k = tier._entries[d1].k[:1]
    assert not tier.validate_checkpoint(ckpt)
    assert tier.dropped_corrupt == 1

    assert tier.pop_checkpoint("r") is ckpt
    assert tier.pop_checkpoint("r") is None
    assert tier.pending_checkpoints() == []


def test_abort_mid_promotion_cancels_ticket_and_frees_kv(tiny_model_dir):
    """Client-disconnect hardening (ISSUE 10 satellite): aborting a
    request parked on an IN-FLIGHT promotion cancels its ticket,
    releases the reserved pages and slot, and a late-completing
    assembly must not scatter into the freed pages."""
    eng = _build_engine(tiny_model_dir, tier_gb=1.0)  # 6-page pool
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    # warm the tier with the shared prefix, then keep its pages OUT of
    # the device cache so a re-request must promote
    _run(eng, "warm", SHARED)
    _run(eng, "f1", FILLER_1)
    _run(eng, "f2", FILLER_2)
    # hold the assembly in flight: planning parks the request with a
    # ticket that never completes until we say so
    started = []
    eng.kv_tier.start_promotion = (
        lambda ticket, put_fn: started.append((ticket, put_fn))
    )
    free0 = eng.scheduler.allocator.num_free

    eng.add_request(
        "re", None,
        SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
        prompt_token_ids=SHARED,
    )
    outputs, plan, prepared = eng.plan_step()
    assert plan is None  # parked, nothing else to run
    assert started, "promotion never started"
    seq = next(s for s in eng.scheduler.waiting if s.request_id == "re")
    ticket = seq.kv_promotion
    assert ticket is not None
    assert eng.scheduler.allocator.num_free < free0  # pages reserved

    out = eng.abort_request("re")
    assert out is not None and out.finished
    assert ticket.cancelled
    assert seq.kv_promotion is None
    assert seq.blocks is None
    assert eng.scheduler.allocator.num_free == free0  # pages returned

    # the assembly completes LATE: the drain must skip the cancelled
    # ticket instead of scattering into reassigned pages
    ticket.pages = [(None, None)]
    ticket.ready = True
    eng.plan_step()
    assert eng._promotions == []
    # the engine is still healthy: fresh work runs to completion
    # (real promotion machinery restored first — the filler prefix is
    # host-tiered too and would otherwise park forever on the stub)
    del eng.kv_tier.start_promotion
    got = _run(eng, "after", FILLER_1, n=4)
    assert len(got) == 4
