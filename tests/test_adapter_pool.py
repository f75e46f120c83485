"""Paged LoRA adapter pool (docs/LORA.md): pool units (streaming,
eviction, pinning, prefetch races, pool-full parking), adapter-affinity
scheduling, cross-adapter batch equivalence vs solo baselines on BOTH
attention backends, compile-shape stability across swaps, typed HTTP
adapter errors, and the adapter-swap-during-supervised-restart chaos
scenario (``nox -s chaos_check``).
"""

from __future__ import annotations

import asyncio
import json
import time

import pytest

from vllm_tgis_adapter_tpu.supervisor import failpoints


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    failpoints.disarm()


@pytest.fixture(scope="module")
def lora_dirs(tmp_path_factory):
    """Four distinct real-weight adapters for the tiny llama fixture."""
    from tests.fixture_models import build_tiny_lora_adapter

    root = tmp_path_factory.mktemp("pool-loras")
    return {
        name: build_tiny_lora_adapter(str(root / name), seed=11 + i)
        for i, name in enumerate(("ad-a", "ad-b", "ad-c", "ad-d"))
    }


def _mcfg():
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

    from tests.fixture_models import TINY_LLAMA_CONFIG

    return ModelConfig.from_hf_config("tiny", TINY_LLAMA_CONFIG)


def _make_pool(max_loras=2, max_cpu=8, rank=8):
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.engine.adapter_pool import AdapterPool
    from vllm_tgis_adapter_tpu.engine.lora import LoRAManager

    manager = LoRAManager(
        max_loras=max_loras, max_lora_rank=rank, max_cpu_loras=max_cpu
    )
    pool = AdapterPool(_mcfg(), max_loras, rank, jnp.asarray)
    pool.manager = manager
    manager.attach_pool(pool)
    return manager, pool


# ----------------------------------------------------------------- pool units


def test_pool_streams_and_lru_evicts(lora_dirs):
    manager, pool = _make_pool(max_loras=2)
    for name in ("ad-a", "ad-b", "ad-c"):
        asyncio.run(manager.load_lora_adapter(name, lora_dirs[name]))
    # no event loop → prefetch streams inline
    slot_a = pool.ensure_resident("ad-a")
    slot_b = pool.ensure_resident("ad-b")
    assert slot_a != slot_b and slot_a > 0 and slot_b > 0
    assert pool.num_resident == 2 and pool.swaps_in == 2
    # pool full: the LRU unpinned resident (ad-a) is evicted for ad-c
    pool.ensure_resident("ad-b")  # touch b → a is LRU
    slot_c = pool.ensure_resident("ad-c")
    assert slot_c == slot_a  # a's slot reused
    assert not pool.resident("ad-a") and pool.resident("ad-b")
    assert pool.swaps_out == 1
    # streaming a back in evicts c or keeps b? b is MRU → victim is c...
    # touch c so B becomes LRU, then re-stream a and assert the victim
    pool.ensure_resident("ad-c")
    slot_a2 = pool.ensure_resident("ad-a")
    assert slot_a2 == slot_b  # b (LRU, unpinned) was the victim
    assert pool.resident("ad-c") and not pool.resident("ad-b")


def test_pool_pinned_slots_never_reassigned(lora_dirs):
    manager, pool = _make_pool(max_loras=2)
    for name in ("ad-a", "ad-b", "ad-c"):
        asyncio.run(manager.load_lora_adapter(name, lora_dirs[name]))
    pool.ensure_resident("ad-a")
    pool.ensure_resident("ad-b")
    manager.pin("ad-a")
    manager.pin("ad-b")
    # every slot pinned: the request PARKS (None), nothing is evicted
    assert pool.ensure_resident("ad-c") is None
    assert pool.resident("ad-a") and pool.resident("ad-b")
    # a pin releasing makes exactly that adapter evictable
    manager.unpin("ad-a")
    slot_c = pool.ensure_resident("ad-c")
    assert slot_c is not None
    assert not pool.resident("ad-a") and pool.resident("ad-b")


def test_pool_prefetch_race_is_idempotent(lora_dirs):
    """Two concurrent prefetches of one adapter start ONE stream; the
    gate returns the same slot afterwards (async path)."""
    manager, pool = _make_pool(max_loras=2)
    asyncio.run(manager.load_lora_adapter("ad-a", lora_dirs["ad-a"]))

    async def race():
        assert pool.prefetch("ad-a") is False  # stream task created
        assert pool.prefetch("ad-a") is False  # observed, not duplicated
        assert len(pool._streaming) == 1  # noqa: SLF001 — the race assertion
        while not pool.resident("ad-a"):
            await asyncio.sleep(0.005)
        return pool.ensure_resident("ad-a")

    slot = asyncio.run(race())
    assert slot is not None and pool.swaps_in == 1


def test_host_evict_invalidates_device_residency(lora_dirs):
    manager, pool = _make_pool(max_loras=2, max_cpu=2)
    asyncio.run(manager.load_lora_adapter("ad-a", lora_dirs["ad-a"]))
    asyncio.run(manager.load_lora_adapter("ad-b", lora_dirs["ad-b"]))
    pool.ensure_resident("ad-a")
    assert pool.resident("ad-a")
    # registry at capacity: loading ad-c evicts ad-a from the HOST and
    # must drop its device slot with it
    asyncio.run(manager.load_lora_adapter("ad-c", lora_dirs["ad-c"]))
    assert "ad-a" not in manager.lora_requests
    assert not pool.resident("ad-a")
    assert pool.num_resident == 0 and len(pool._free) == 2  # noqa: SLF001


def test_unknown_adapter_serves_base_slot():
    _, pool = _make_pool()
    assert pool.ensure_resident("never-loaded") == 0


def test_unload_pinned_adapter_is_typed_client_error(lora_dirs):
    from vllm_tgis_adapter_tpu.engine.lora import LoRAError
    from vllm_tgis_adapter_tpu.frontdoor.errors import classify

    manager, _pool = _make_pool()
    asyncio.run(manager.load_lora_adapter("ad-a", lora_dirs["ad-a"]))
    manager.pin("ad-a")
    with pytest.raises(LoRAError) as excinfo:
        manager.unload_lora_adapter("ad-a")
    disposition = classify(excinfo.value)
    assert disposition is not None
    assert disposition.grpc_code == "INVALID_ARGUMENT"
    assert disposition.http_status == 400
    manager.unpin("ad-a")
    manager.unload_lora_adapter("ad-a")
    assert "ad-a" not in manager.lora_requests


def test_corrupt_adapter_config_is_typed(tmp_path):
    """Invalid JSON / corrupt safetensors classify as the typed 4xx,
    not a generic 500 (review finding)."""
    from vllm_tgis_adapter_tpu.engine.lora import (
        LoRAError,
        load_peft_adapter,
    )
    from vllm_tgis_adapter_tpu.frontdoor.errors import classify

    (tmp_path / "adapter_config.json").write_text("{not json")
    with pytest.raises(LoRAError, match="invalid adapter_config.json"):
        load_peft_adapter(str(tmp_path))
    (tmp_path / "adapter_config.json").write_text(json.dumps({
        "peft_type": "LORA", "r": 4, "lora_alpha": 8,
        "target_modules": ["q_proj"],
    }))
    (tmp_path / "adapter_model.safetensors").write_bytes(b"\x00garbage")
    with pytest.raises(LoRAError, match="safetensors") as excinfo:
        load_peft_adapter(str(tmp_path))
    assert classify(excinfo.value).http_status == 400


def test_unknown_target_modules_rejected(tmp_path):
    from vllm_tgis_adapter_tpu.engine.lora import (
        LoRAError,
        load_peft_adapter,
    )

    (tmp_path / "adapter_config.json").write_text(json.dumps({
        "peft_type": "LORA", "r": 4, "lora_alpha": 8,
        "target_modules": ["q_proj", "embed_tokens"],
    }))
    with pytest.raises(LoRAError, match="unknown modules.*embed_tokens"):
        load_peft_adapter(str(tmp_path))


# ------------------------------------------------------------- engine-level


def _engine_config(tiny_model_dir, *, backend="ragged", max_loras=2,
                   max_num_seqs=4, pool=True):
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
        cache_config=CacheConfig(block_size=16, num_blocks=96,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=max_num_seqs, prefill_buckets=(32, 64)),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(enabled=True, max_loras=max_loras,
                               max_lora_rank=8, pool=pool),
        attention_backend=backend,
    )


def _run_requests(engine, reqs, *, max_tokens=6):
    """reqs: [(request_id, lora_name)] — drives the sync engine to
    completion and returns {request_id: token_ids}."""
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    for rid, lora in reqs:
        engine.add_request(rid, "the quick brown fox", SamplingParams(
            temperature=0.0, max_tokens=max_tokens, ignore_eos=True),
            lora_name=lora)
    outs = {}
    for _ in range(10_000):
        if not engine.has_unfinished_requests():
            break
        for o in engine.step():
            outs[o.request_id] = o
    assert not engine.has_unfinished_requests(), "engine wedged"
    return {k: v.outputs[0].token_ids for k, v in outs.items()}


@pytest.mark.parametrize("backend", ["ragged"])
def test_cross_adapter_batch_token_identical_to_solo(
    tiny_model_dir, lora_dirs, backend
):
    """Mixed-adapter batches (MORE adapters than device slots, so the
    pool churns mid-run) must be token-identical to per-adapter solo
    baselines — on both attention backends.  This is the acceptance
    equivalence for the paged pool."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    engine = LLMEngine.from_config(
        _engine_config(tiny_model_dir, backend=backend, max_loras=2)
    )
    pool = engine.runner.adapter_pool
    assert pool is not None
    for name, path in lora_dirs.items():
        asyncio.run(engine.lora_manager.load_lora_adapter(name, path))

    solo = {}
    for name in (None, *lora_dirs):
        key = name or "base"
        solo.update(_run_requests(engine, [(f"solo-{key}", name)]))
    mixed = _run_requests(
        engine,
        [(f"mix-{name or 'base'}", name) for name in (None, *lora_dirs)],
    )
    for name in (None, *lora_dirs):
        key = name or "base"
        assert mixed[f"mix-{key}"] == solo[f"solo-{key}"], key
    # 4 adapters over 2 slots: the pool actually churned
    assert pool.swaps_out > 0
    assert pool.resident_high_water == 2
    # distinct adapters really diverged (the fixtures are live weights)
    assert len({tuple(v) for v in mixed.values()}) == len(mixed)


def test_legacy_no_pool_path_matches_pool(tiny_model_dir, lora_dirs):
    """--no-lora-pool (slow-path fallback) and the pool produce the
    same tokens; the fallback keeps the old sync_lora machinery."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    results = {}
    for pool_on in (True, False):
        engine = LLMEngine.from_config(
            _engine_config(tiny_model_dir, pool=pool_on)
        )
        assert (engine.runner.adapter_pool is not None) == pool_on
        asyncio.run(
            engine.lora_manager.load_lora_adapter("ad-a", lora_dirs["ad-a"])
        )
        results[pool_on] = _run_requests(
            engine, [("r-lora", "ad-a"), ("r-base", None)]
        )
    assert results[True]["r-lora"] == results[False]["r-lora"]
    assert results[True]["r-base"] == results[False]["r-base"]
    assert results[True]["r-lora"] != results[True]["r-base"]


def test_no_new_compile_shapes_on_swap(tiny_model_dir, lora_dirs):
    """The acceptance compile gate: once serving shapes (incl. the one
    jitted slot-scatter program) are warm, adapter swaps add ZERO
    compile shapes — fixed slot stacks mean no retrace, ever."""
    from vllm_tgis_adapter_tpu import compile_tracker
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    engine = LLMEngine.from_config(
        _engine_config(tiny_model_dir, backend="ragged", max_loras=1)
    )
    for name in ("ad-a", "ad-b", "ad-c"):
        asyncio.run(
            engine.lora_manager.load_lora_adapter(name, lora_dirs[name])
        )
    # warm: base + one adapter (compiles serving programs + the
    # lora_slot_update program exactly once)
    _run_requests(engine, [("w-base", None)])
    _run_requests(engine, [("w-a", "ad-a")])
    shapes_before = compile_tracker.num_shapes()
    # three swaps through a ONE-slot pool — maximum churn
    _run_requests(engine, [("s-b", "ad-b")])
    _run_requests(engine, [("s-c", "ad-c")])
    _run_requests(engine, [("s-a", "ad-a")])
    assert engine.runner.adapter_pool.swaps_out >= 3
    assert compile_tracker.num_shapes() == shapes_before


def test_parked_head_does_not_block_resident_work(tiny_model_dir, lora_dirs):
    """Adapter-affinity scheduling: a queue head parked on a (faked,
    never-finishing) adapter stream must not stall admissions — later
    resident-adapter work jumps it, and the head completes once the
    gate opens."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine = LLMEngine.from_config(_engine_config(tiny_model_dir))
    asyncio.run(
        engine.lora_manager.load_lora_adapter("ad-a", lora_dirs["ad-a"])
    )
    blocked = {"ad-a"}
    real_gate = engine._lora_gate

    def gate(seq):
        if seq.lora_name in blocked:
            return False
        return real_gate(seq)

    engine.scheduler.lora_gate = gate
    engine.add_request("head", "alpha beta", SamplingParams(
        temperature=0.0, max_tokens=4, ignore_eos=True), lora_name="ad-a")
    engine.add_request("ready", "gamma delta", SamplingParams(
        temperature=0.0, max_tokens=4, ignore_eos=True))
    outs = {}
    for _ in range(200):
        for o in engine.step():
            outs[o.request_id] = o
        if "ready" in outs:
            break
    assert "ready" in outs and "head" not in outs
    # the head is still parked, first in line
    assert engine.scheduler.waiting[0].request_id == "head"
    blocked.clear()  # stream "completes"
    for _ in range(200):
        if "head" in outs and outs["head"].finished:
            break
        for o in engine.step():
            outs[o.request_id] = o
    assert "head" in outs and outs["head"].finished
    assert len(outs["head"].outputs[0].token_ids) == 4


def test_many_adapters_resident_churn(tiny_model_dir, tmp_path):
    """Scaled-down CPU demo of the acceptance shape: 32 registered
    host-side, 8-slot pool, traffic over 16 adapters → every slot in
    use, nonzero churn, every request completes."""
    from tests.fixture_models import build_tiny_lora_adapter

    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    engine = LLMEngine.from_config(
        _engine_config(tiny_model_dir, backend="ragged", max_loras=8,
                       max_num_seqs=4)
    )
    names = [f"t-{i:02d}" for i in range(32)]
    for i, name in enumerate(names):
        path = build_tiny_lora_adapter(
            str(tmp_path / name), seed=100 + i, rank=2
        )
        asyncio.run(engine.lora_manager.load_lora_adapter(name, path))
    assert len(engine.lora_manager.lora_requests) == 32
    outs = _run_requests(
        engine,
        [(f"r{i}", names[i % 16]) for i in range(24)],
        max_tokens=2,
    )
    assert len(outs) == 24
    pool = engine.runner.adapter_pool
    assert pool.resident_high_water == 8
    assert pool.swaps_out > 0
    assert pool.debug_state()["registered"] == 32


# ------------------------------------------------------------- HTTP surface


def _http_request(method, path, body=None):
    from vllm_tgis_adapter_tpu.http import HttpRequest

    return HttpRequest(
        method, path, {},
        json.dumps(body).encode() if body is not None else b"",
    )


def test_http_adapter_load_errors_are_typed_4xx(
    tiny_model_dir, lora_dirs, tmp_path
):
    """Satellite: adapter load/parse failures are 4xx with actionable
    messages on the HTTP surface — missing config, over-rank, unknown
    targets — and a good load lands in /v1/models and is selectable as
    the completions model."""
    import argparse

    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.http import build_http_server

    engine = AsyncLLMEngine(
        LLMEngine.from_config(_engine_config(tiny_model_dir))
    )
    args = argparse.Namespace(
        model="tiny", served_model_name=None, api_key=None,
        root_path=None, tenant_header="x-tenant-id", profile_dir=None,
    )
    app = build_http_server(args, engine)

    async def scenario():
        # --api-key guards the mutating admin endpoints exactly like
        # the inference endpoints (review finding: no routing around
        # the Bearer check)
        app.state["api_key"] = "sekrit"
        r = await app.dispatch(_http_request(
            "POST", "/v1/load_lora_adapter",
            {"lora_name": "x", "lora_path": "/tmp"},
        ))
        assert r.status == 401
        r = await app.dispatch(_http_request(
            "POST", "/v1/unload_lora_adapter", {"lora_name": "x"},
        ))
        assert r.status == 401
        app.state["api_key"] = None
        # missing adapter_config.json
        r = await app.dispatch(_http_request(
            "POST", "/v1/load_lora_adapter",
            {"lora_name": "bad", "lora_path": str(tmp_path / "nope")},
        ))
        assert r.status == 400
        assert b"adapter_config.json" in r.body
        # over-rank (fixture rank 4 > max_lora_rank 2 config below is
        # not reachable here; craft one over the engine's rank 8)
        big = tmp_path / "big"
        big.mkdir()
        (big / "adapter_config.json").write_text(json.dumps({
            "peft_type": "LORA", "r": 128, "lora_alpha": 8,
            "target_modules": ["q_proj"],
        }))
        r = await app.dispatch(_http_request(
            "POST", "/v1/load_lora_adapter",
            {"lora_name": "big", "lora_path": str(big)},
        ))
        assert r.status == 400 and b"max-lora-rank" in r.body
        # unknown target modules
        weird = tmp_path / "weird"
        weird.mkdir()
        (weird / "adapter_config.json").write_text(json.dumps({
            "peft_type": "LORA", "r": 4, "lora_alpha": 8,
            "target_modules": ["lm_head"],
        }))
        r = await app.dispatch(_http_request(
            "POST", "/v1/load_lora_adapter",
            {"lora_name": "weird", "lora_path": str(weird)},
        ))
        assert r.status == 400 and b"unknown modules" in r.body
        # a good load: 200, listed, and selectable as `model`
        r = await app.dispatch(_http_request(
            "POST", "/v1/load_lora_adapter",
            {"lora_name": "ad-a", "lora_path": lora_dirs["ad-a"]},
        ))
        assert r.status == 200
        r = await app.dispatch(_http_request("GET", "/v1/models"))
        ids = [m["id"] for m in json.loads(r.body)["data"]]
        assert "ad-a" in ids
        r = await app.dispatch(_http_request(
            "POST", "/v1/completions",
            {"model": "ad-a", "prompt": "the quick", "max_tokens": 2,
             "temperature": 0},
        ))
        assert r.status == 200
        # unknown model is still a 404
        r = await app.dispatch(_http_request(
            "POST", "/v1/completions",
            {"model": "no-such", "prompt": "x", "max_tokens": 1},
        ))
        assert r.status == 404
        # unload; unloading again is a typed 400
        r = await app.dispatch(_http_request(
            "POST", "/v1/unload_lora_adapter", {"lora_name": "ad-a"},
        ))
        assert r.status == 200
        r = await app.dispatch(_http_request(
            "POST", "/v1/unload_lora_adapter", {"lora_name": "ad-a"},
        ))
        assert r.status == 400 and b"not loaded" in r.body
        await engine.stop()

    asyncio.run(scenario())


# ------------------------------------------------------------------- chaos


def test_adapter_swap_during_restart_replays_lora_identity(
    tiny_model_dir, lora_dirs
):
    """THE chaos acceptance (ROADMAP item 2 / PR 5's untested hook):
    kill the engine mid-adapter-churn; the zero-token LoRA request must
    replay onto the rebuilt engine CARRYING its adapter identity, the
    cold pool must re-stream exactly that adapter, and the output must
    be token-identical to an uncrashed baseline."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import FrontdoorConfig
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams
    from vllm_tgis_adapter_tpu.frontdoor.errors import EngineRestartError
    import dataclasses

    config = dataclasses.replace(
        _engine_config(tiny_model_dir, max_loras=2, max_num_seqs=2),
        max_engine_restarts=3,
        engine_restart_window_s=300.0,
        engine_restart_backoff_s=0.02,
        frontdoor=FrontdoorConfig(enabled=True),
    )
    engine = AsyncLLMEngine(LLMEngine.from_config(config))
    lora_reqs = {}
    for name in ("ad-a", "ad-b"):
        lora_reqs[name] = asyncio.run(
            engine.engine.lora_manager.load_lora_adapter(
                name, lora_dirs[name]
            )
        )

    async def collect(rid, lora_name, max_tokens=6, prompt_ids=None):
        final = None
        try:
            async for out in engine.generate(
                prompt=None,
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=max_tokens,
                    ignore_eos=True,
                ),
                request_id=rid,
                prompt_token_ids=list(prompt_ids or range(3, 15)),
                lora_request=lora_reqs.get(lora_name),
            ):
                final = out
            return ("ok", final)
        except BaseException as e:  # noqa: BLE001 — the error IS the result
            return ("err", e)

    async def wait_for(cond, what, timeout=20.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            await asyncio.sleep(0.01)
        raise AssertionError(f"timed out waiting for {what}")

    def output_tokens(rid):
        for rep in engine._replicas:
            seq = rep.engine._seqs.get(rid)
            if seq is not None:
                return seq.num_output_tokens
        return -1

    async def scenario():
        # uncrashed baseline for the request that will be replayed
        ref = await collect("ref-b", "ad-b")
        assert ref[0] == "ok"
        old_pool = engine.engine.runner.adapter_pool

        # a long ad-a request reaches mid-decode, then the loop hangs
        a_task = asyncio.create_task(collect("a", "ad-a", max_tokens=64))
        await wait_for(lambda: output_tokens("a") >= 1,
                       "request a to emit a token")
        failpoints.arm_site("core.wait_step", "hang")
        await asyncio.sleep(0.05)
        # the ad-b request lands zero-token (waiting) mid-churn
        b_task = asyncio.create_task(collect("b", "ad-b"))
        await wait_for(
            lambda: sum(len(rep.engine.scheduler.waiting)
                        for rep in engine._replicas) >= 1,
            "b to be engine-waiting",
        )
        assert output_tokens("b") == 0
        failpoints.arm_site("core.plan_step", "raise", 1)
        failpoints.release("core.wait_step")

        status_a, err_a = await a_task
        status_b, out_b = await b_task
        await wait_for(lambda: engine.lifecycle == "serving",
                       "recovery to finish")
        new_pool = engine.engine.runner.adapter_pool
        state = {
            "new_pool_is_new": new_pool is not old_pool,
            "old_released": old_pool.stacks is None,
            "b_resident": new_pool.resident("ad-b"),
            "pins": dict(
                engine.engine.lora_manager._refs  # noqa: SLF001
            ),
        }
        await engine.stop()
        return (status_a, err_a), (status_b, out_b), ref[1], state

    (status_a, err_a), (status_b, out_b), ref_out, state = asyncio.run(
        scenario()
    )
    # mid-decode ad-a request failed retryable; zero-token ad-b request
    # replayed WITH its adapter and is token-identical to the baseline
    assert status_a == "err" and isinstance(err_a, EngineRestartError)
    assert status_b == "ok"
    assert out_b.outputs[0].token_ids == ref_out.outputs[0].token_ids
    # the rebuilt engine got a NEW pool, the dead one's device stacks
    # were released, and ONLY the live request's adapter re-streamed
    assert state["new_pool_is_new"] and state["old_released"]
    assert state["b_resident"]
    # no leaked pins after both requests resolved
    assert state["pins"] == {}
