"""Unified paged HBM arena + heterogeneous-rank gathered matmul + disk
tier (docs/MEMORY.md, ISSUE 14).

Layers: arena accounting units (typed charges, unified cross-type LRU,
pinning, the oversized-adapter liveness fallback), the gathered matmul's
token-identity vs the padded path and its zero-new-compile-shapes swap
contract, disk-tier units (bit-exact roundtrip, corrupt-entry
dropped-not-served — mirroring the host-tier unit — adapter
spill/restore, cross-restart rescan), the engine-level
disk→host→device promotion walk, and THE chaos acceptance: an engine
killed mid-churn with a mixed KV+adapter working set over HBM recovers
with no cross-type page corruption (``nox -s chaos_check``).
"""

from __future__ import annotations

import asyncio
import os
import time

import numpy as np
import pytest

from vllm_tgis_adapter_tpu.supervisor import failpoints


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    failpoints.disarm()


# ------------------------------------------------------------ arena units


class _FakePool:
    def __init__(self, arena, manager):
        self.arena = arena
        self.manager = manager
        self._slots = {}
        self._lru = {}
        self.evicted = []

    def resident_names(self):
        return list(self._slots)

    def last_touch(self, name):
        return self._lru.get(name, 0.0)

    def evict_resident(self, name):
        self._slots.pop(name, None)
        self._lru.pop(name, None)
        self.evicted.append(name)
        self.arena.release_adapter(self, name)

    def make_resident(self, name, pages, ts):
        assert self.arena.charge_adapter(self, name, pages)
        self._slots[name] = len(self._slots) + 1
        self._lru[name] = ts


class _FakeManager:
    def __init__(self):
        self.pins = set()

    def pinned(self, name):
        return name in self.pins


def _arena(num_blocks=32, reserve=4, prefix=True, adapter_budget=0):
    """adapter_budget=0 makes every charge BORROW from the KV pool —
    the page-granular shard-storage shape the cross-type units
    exercise; the engine default (the padded stacks' reservation) is
    covered by the reservation-first + engine-level tests."""
    from vllm_tgis_adapter_tpu.engine.arena import UnifiedArena
    from vllm_tgis_adapter_tpu.engine.kv_cache import BlockAllocator

    alloc = BlockAllocator(num_blocks, 16, enable_prefix_caching=prefix)
    arena = UnifiedArena(
        alloc, kv_page_bytes=1024, min_kv_reserve=reserve,
        adapter_budget_pages=adapter_budget,
    )
    alloc.arena = arena
    manager = _FakeManager()
    pool = _FakePool(arena, manager)
    arena.attach_pool(pool)
    return alloc, arena, pool, manager


def test_arena_charge_release_accounting():
    alloc, arena, pool, _ = _arena()
    pool.make_resident("a1", 8, ts=1.0)
    pool.make_resident("a2", 8, ts=2.0)
    # charges RESERVE page ids: the KV side sees one truthful number
    assert alloc.num_free == 16
    assert arena.adapter_blocks == 16
    # idempotent re-charge
    assert arena.charge_adapter(pool, "a1", 8)
    assert arena.adapter_blocks == 16
    arena.release_adapter(pool, "a1")
    assert alloc.num_free == 24
    assert arena.adapter_blocks == 8
    # release is epoch-proof: an open chained-decode quarantine must
    # not swallow reserved pages (they were never KV-writable)
    alloc.begin_free_epoch()
    arena.release_adapter(pool, "a2")
    assert alloc.num_free == 32
    alloc.flush_all_free_epochs()


def test_arena_kv_pressure_evicts_cold_adapter_pins_survive():
    alloc, arena, pool, manager = _arena()
    pool.make_resident("cold", 8, ts=1.0)
    pool.make_resident("warm", 8, ts=2.0)
    # KV demand beyond free: the COLDEST unpinned adapter funds it
    assert alloc.can_allocate(20)
    assert pool.evicted == ["cold"]
    assert arena.kv_reclaims == 1
    # a pinned adapter is never touched, even when KV starves
    manager.pins.add("warm")
    assert not alloc.can_allocate(30)
    assert pool.evicted == ["cold"]


def test_arena_budget_cap_keeps_kv_reserve():
    alloc, arena, pool, manager = _arena(num_blocks=32, reserve=8)
    # adapters may never push KV below the reserve: 32 - 8 = 24 max
    pool.make_resident("a1", 20, ts=1.0)
    manager.pins.add("a1")
    # the only way to fund a2 would break the reserve: park it
    assert not arena.charge_adapter(pool, "a2", 10)
    assert arena.adapter_blocks == 20
    # ... until the colder sibling is evictable again
    manager.pins.discard("a1")
    assert arena.charge_adapter(pool, "a2", 10)
    assert pool.evicted == ["a1"]
    assert arena.adapter_blocks == 10


def test_arena_reservation_funds_before_borrowing():
    """The no-double-count invariant: charges consume the adapter
    side's OWN boot-time reservation first — the KV pool only lends
    pages for the overflow, and reservation-backed charges are never
    evicted to fund KV demand (they'd free nothing KV can use)."""
    alloc, arena, pool, _ = _arena(adapter_budget=10)
    pool.make_resident("a1", 8, ts=1.0)
    # fully reservation-funded: the KV pool is untouched
    assert alloc.num_free == 32
    assert arena.adapter_reserve_used == 8
    assert arena.borrowed_blocks == 0
    # overflow borrows: 2 from reserve, 4 from the pool
    pool.make_resident("a2", 6, ts=2.0)
    assert arena.adapter_reserve_used == 10
    assert arena.borrowed_blocks == 4
    assert alloc.num_free == 28
    # KV pressure: only the BORROWER (a2) is worth evicting — and a1,
    # though colder, is reservation-backed and must survive
    assert alloc.can_allocate(30)
    assert pool.evicted == ["a2"]
    assert alloc.num_free == 32
    # release returns the reserve too
    arena.release_adapter(pool, "a1")
    assert arena.adapter_reserve_used == 0
    assert arena.adapter_blocks == 0


def test_arena_oversized_adapter_gets_uncharged_residency():
    """Liveness: an adapter bigger than the whole chargeable budget
    must not park its requests forever — it gets UNCHARGED residency
    (pre-arena behavior), visible in the stats."""
    alloc, arena, pool, _ = _arena(num_blocks=8, reserve=4)
    assert arena.charge_adapter(pool, "huge", 100)
    assert arena.adapter_blocks == 0  # uncharged
    assert arena.adapter_charges == 1
    arena.release_adapter(pool, "huge")  # no-op, no underflow
    assert arena.adapter_blocks == 0


def test_arena_unified_lru_cross_type_ordering():
    """The cross-type comparison: whichever cold resident (cached KV
    page vs unpinned adapter) is OLDER funds the demand, and KV
    evictions still demote through the evict hook."""
    alloc, arena, pool, _ = _arena(num_blocks=16, reserve=2)
    demoted = []
    alloc.evict_hook = lambda h, b: demoted.append(b)

    # register + free 8 pages -> cached-free with NOW timestamps
    blocks = alloc.allocate(8)
    alloc.register_prefix(list(range(128)), blocks)
    alloc.free(blocks)
    assert len(alloc._cached_free) == 8

    # an adapter OLDER than every cached page: adapter funds first
    pool.make_resident("ancient", 4, ts=0.0)
    assert len(alloc._free) == 4
    pool._lru["ancient"] = 0.0
    assert arena.charge_adapter(pool, "newcomer", 6)
    assert pool.evicted == ["ancient"]

    # now the cached pages are the older side: they fund (and demote)
    pool._slots["newcomer"] = 9
    pool._lru["newcomer"] = time.monotonic() + 1e6
    assert arena.charge_adapter(pool, "another", 4)
    assert "newcomer" not in pool.evicted
    assert demoted, "cached KV pages funded the charge without demoting"


# ---------------------------------------- heterogeneous-rank gathered path


def test_rank_lattice_units():
    from vllm_tgis_adapter_tpu.engine.lora import (
        adapter_page_cost,
        rank_bucket,
        rank_lattice,
    )

    assert rank_lattice(64) == (4, 8, 16, 32, 64)
    assert rank_lattice(8) == (4, 8)
    assert rank_lattice(2) == (2,)
    assert rank_lattice(48) == (4, 8, 16, 32, 48)
    assert rank_bucket(1, 64) == 4
    assert rank_bucket(4, 64) == 4
    assert rank_bucket(5, 64) == 8
    assert rank_bucket(64, 64) == 64

    class M:
        hidden_size = 64
        head_dim = 16
        num_heads = 4
        num_kv_heads = 2
        intermediate_size = 128
        num_layers = 2

    # true-rank charging: a rank-2 adapter prices far below max-rank
    lo = adapter_page_cost(M, 2, 64, 8192)
    hi = adapter_page_cost(M, 64, 64, 8192)
    assert lo < hi / 4


@pytest.fixture(scope="module")
def het_lora_dirs(tmp_path_factory):
    """Adapters of genuinely DIFFERENT ranks (2, 4, 8) — the
    heterogeneous working set the gathered matmul exists for."""
    from tests.fixture_models import build_tiny_lora_adapter

    root = tmp_path_factory.mktemp("het-loras")
    return {
        name: build_tiny_lora_adapter(
            str(root / name), seed=31 + i, rank=rank
        )
        for i, (name, rank) in enumerate(
            (("het-r2", 2), ("het-r4", 4), ("het-r8", 8))
        )
    }


def _lora_engine(tiny_model_dir, *, gathered=True, max_loras=2,
                 unified_arena=True):
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
        cache_config=CacheConfig(block_size=16, num_blocks=96,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(32, 64)),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(enabled=True, max_loras=max_loras,
                               max_lora_rank=8, gathered=gathered),
        unified_arena=unified_arena,
    ))


def _run_requests(engine, reqs, *, max_tokens=6):
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


def test_gathered_matmul_token_identical_to_padded(
    tiny_model_dir, het_lora_dirs
):
    """THE het-rank equivalence (ISSUE 14 acceptance): mixed-rank
    batches through the gathered path produce exactly the padded
    path's tokens — per-row bucket dispatch changes FLOPs, never
    results."""
    results = {}
    for gathered in (True, False):
        engine = _lora_engine(tiny_model_dir, gathered=gathered)
        stacks = engine.runner.lora_stacks
        assert (stacks.ranks is not None) == gathered
        for name, path in het_lora_dirs.items():
            asyncio.run(engine.lora_manager.load_lora_adapter(name, path))
        results[gathered] = _run_requests(
            engine,
            [(f"r-{n or 'base'}", n) for n in (None, *het_lora_dirs)],
        )
    assert results[True] == results[False]
    # adapters genuinely diverge from base and from each other
    assert len({tuple(v) for v in results[True].values()}) == len(
        results[True]
    )


def test_gathered_swaps_add_zero_compile_shapes(
    tiny_model_dir, het_lora_dirs
):
    """Rank buckets are DATA (the per-slot ranks operand), not compile
    shapes: churning three different-rank adapters through a 1-slot
    pool must add zero new compiled shapes once serving is warm."""
    from vllm_tgis_adapter_tpu import compile_tracker

    engine = _lora_engine(tiny_model_dir, max_loras=1)
    names = list(het_lora_dirs)
    asyncio.run(engine.lora_manager.load_lora_adapter(
        names[0], het_lora_dirs[names[0]]
    ))
    _run_requests(engine, [("warm", names[0])])
    warm = set(compile_tracker.shapes())
    for name in names[1:]:
        asyncio.run(engine.lora_manager.load_lora_adapter(
            name, het_lora_dirs[name]
        ))
        _run_requests(engine, [(f"swap-{name}", name)])
    assert set(compile_tracker.shapes()) == warm
    assert engine.runner.adapter_pool.swaps_out >= 2


def test_arena_charges_follow_pool_churn(tiny_model_dir, het_lora_dirs):
    """Engine-level arena accounting: residency charges true-rank
    pages (consuming the padded stacks' boot-time reservation — the
    KV pool is NOT double-charged) and eviction returns them."""
    engine = _lora_engine(tiny_model_dir, max_loras=1)
    arena = engine.arena
    assert arena is not None
    assert arena.adapter_budget_pages > 0  # the stacks' reservation
    alloc = engine.scheduler.allocator
    base_free = alloc.num_free
    names = list(het_lora_dirs)
    asyncio.run(engine.lora_manager.load_lora_adapter(
        names[0], het_lora_dirs[names[0]]
    ))
    _run_requests(engine, [("a", names[0])])
    assert arena.adapter_blocks > 0
    # true-rank charge fits the padded reservation: zero KV borrow
    # (the ISSUE 8 churn gate's "unchanged" demand hangs on this)
    assert arena.borrowed_blocks == 0
    assert alloc.num_free == base_free
    # churn to the next adapter: old charge released, new one taken
    asyncio.run(engine.lora_manager.load_lora_adapter(
        names[2], het_lora_dirs[names[2]]
    ))
    _run_requests(engine, [("b", names[2])])
    assert arena.adapter_releases >= 1
    state = arena.debug_state()
    assert state["charged_adapters"] == [names[2]]
    # rank-8 charges more of the reservation than rank-2 did
    assert state["adapter_reserve_used"] == state["adapter_blocks"]


def test_no_unified_arena_restores_split_budgets(tiny_model_dir):
    engine = _lora_engine(tiny_model_dir, unified_arena=False)
    assert engine.arena is None
    assert engine.scheduler.allocator.arena is None


# ------------------------------------------------------------- disk tier


def _disk(tmp_path, budget=1 << 20):
    from vllm_tgis_adapter_tpu.engine.kv_tier import DiskKVTier

    return DiskKVTier(budget, directory=str(tmp_path), block_size=4)


def _page(seed, shape=(2, 2, 4, 8)):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal(shape).astype(np.float32),
        rng.standard_normal(shape).astype(np.float32),
    )


def test_disk_store_load_roundtrip_bit_exact(tmp_path):
    disk = _disk(tmp_path)
    k, v = _page(0)
    disk.store_batch([(b"d" * 8, k, v)])
    assert disk.has(b"d" * 8)
    got = disk.load(b"d" * 8)
    assert got is not None
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], v)
    # quantized-page 4-tuples travel verbatim too (scale sidecars)
    ks = np.float32(0.25) * np.ones((2, 2), np.float32)
    disk.store_batch([(b"q" * 8, k, v, ks, ks * 2)])
    got = disk.load(b"q" * 8)
    assert len(got) == 4
    np.testing.assert_array_equal(got[2], ks)


def test_disk_corrupt_entry_dropped_not_served(tmp_path):
    """The disk-tier mirror of the host tier's corrupt-entry unit
    (ISSUE 14 satellite): a payload whose checksum no longer matches
    is UNLINKED and reads as a miss — never served."""
    disk = _disk(tmp_path)
    disk.store_batch([(b"c" * 8, *_page(3))])
    path = disk._page_path(b"c" * 8)
    blob = path.read_bytes()
    # flip one payload byte past the header
    head_len = blob.index(b"\n") + 1
    corrupted = (
        blob[: head_len + 5]
        + bytes([blob[head_len + 5] ^ 0xFF])
        + blob[head_len + 6:]
    )
    path.write_bytes(corrupted)
    assert disk.load(b"c" * 8) is None
    assert disk.dropped_corrupt == 1
    assert not path.exists()
    assert not disk.has(b"c" * 8)


def test_disk_adapter_spill_restore_roundtrip(tmp_path):
    from vllm_tgis_adapter_tpu.engine.lora import LoRAAdapterWeights

    disk = _disk(tmp_path)
    w = LoRAAdapterWeights(
        rank=3, scaling=1.25, target_modules=("q_proj", "v_proj"),
        a={"layers.0.q_proj": np.arange(12, dtype=np.float32).reshape(3, 4)},
        b={"layers.0.q_proj": np.ones((4, 3), np.float32)},
    )
    disk.store_adapter("tenant-7", w, path_hint="/adapters/t7")
    assert disk.has_adapter("tenant-7")
    got, path = disk.load_adapter("tenant-7")
    assert path == "/adapters/t7"
    assert got.rank == 3 and got.scaling == 1.25
    assert got.target_modules == ("q_proj", "v_proj")
    np.testing.assert_array_equal(
        got.a["layers.0.q_proj"], w.a["layers.0.q_proj"]
    )


def test_disk_rescan_adopts_surviving_entries(tmp_path):
    """Cross-restart reuse: a NEW DiskKVTier over an existing directory
    adopts committed entries (sizes from stat, validation lazy)."""
    from vllm_tgis_adapter_tpu.engine.lora import LoRAAdapterWeights

    disk = _disk(tmp_path)
    disk.store_batch([(b"s" * 8, *_page(5))])
    disk.store_adapter("surv", LoRAAdapterWeights(
        rank=1, scaling=1.0, target_modules=("q_proj",),
        a={}, b={},
    ))
    reborn = _disk(tmp_path)
    assert reborn.has(b"s" * 8)
    assert reborn.has_adapter("surv")
    got = reborn.load(b"s" * 8)
    np.testing.assert_array_equal(got[0], _page(5)[0])


def test_disk_byte_budget_lru_unlinks_oldest(tmp_path):
    k, v = _page(0)
    one = len(_disk(tmp_path)._serialize((k, v), {"kind": "kv"}))
    disk = _disk(tmp_path, budget=3 * one + 64)
    for i in range(5):
        disk.store_batch([(bytes([i]) * 8, *_page(i))])
    assert not disk.has(bytes([0]) * 8)
    assert disk.has(bytes([4]) * 8)
    assert disk.evictions >= 2
    assert disk.bytes_used <= disk.budget_bytes


def test_host_eviction_cascades_to_disk_and_promotes_back(tmp_path):
    """The hierarchy walk in store units: host LRU victims spill DOWN
    to disk; a later promotion loads them back UP through the host
    tier (disk → host → device staging)."""
    from vllm_tgis_adapter_tpu.engine.kv_tier import (
        HostKVTier,
        PromotionTicket,
    )

    k, v = _page(0)
    per_entry = k.nbytes + v.nbytes
    tier = HostKVTier(2 * per_entry, 4)
    tier.attach_disk(_disk(tmp_path))
    for i in range(4):
        tier.submit([(bytes([i]) * 8, *_page(i))])
    # two oldest evicted from host RAM... but cascaded to disk
    assert len(tier._entries) == 2
    assert tier.disk.stored_pages == 2
    assert tier.disk.has(bytes([0]) * 8)
    # peeks see the FULL hierarchy
    assert tier.peek_pages([bytes([0]) * 8]) == 1
    # promotion of a disk-only span: staged via the disk load, and the
    # loaded page hops back INTO host RAM
    ticket = PromotionTicket(
        request_id="t", digests=[bytes([0]) * 8],
        start_tokens=0, end_tokens=4,
    )
    tier.start_promotion(ticket, lambda a: a)  # offline: inline
    assert ticket.ready and not ticket.failed
    np.testing.assert_array_equal(ticket.pages[0][0], _page(0)[0])
    assert tier.disk.loaded_pages == 1
    assert bytes([0]) * 8 in tier._entries  # promoted one rung up


def test_metrics_tier_labels():
    """kv_host_tier_bytes / _evictions_total carry the tier label
    (ISSUE 14 satellite) — host and disk are separate series."""
    from vllm_tgis_adapter_tpu import metrics

    metrics.kv_host_tier_bytes.labels(tier="host").set(1.0)
    metrics.kv_host_tier_bytes.labels(tier="disk").set(2.0)
    metrics.kv_host_tier_evictions_total.labels(tier="disk").inc()
    metrics.arena_blocks.labels(type="adapter", replica="0").set(3)


# ------------------------------------------- engine-level disk promotion


SHARED = list(range(3, 60))  # 57 tokens: 3 full pages + tail
FILLER_1 = list(range(100, 157))
FILLER_2 = list(range(200, 257))


def _tiered_engine(tiny_model_dir, disk_dir, *, host_gb, disk_gb=1.0,
                   num_blocks=6):
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
            enable_prefix_caching=True,
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(32, 64, 128),
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
        kv_host_cache_gb=host_gb,
        kv_disk_cache_gb=disk_gb,
        kv_disk_cache_dir=disk_dir,
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


def test_disk_tier_serves_prefix_token_identical(tiny_model_dir, tmp_path):
    """End-to-end hierarchy: host budget too small to RETAIN the warm
    prefix, so it cascades to disk — and the warm re-send still
    promotes token-identically (disk → host → device through the
    existing gate)."""
    base = _tiered_engine(
        tiny_model_dir, str(tmp_path / "none"), host_gb=0.0, disk_gb=0.0
    )
    want = _run(base, "b", SHARED)

    # host budget ~2 pages of this config: fillers evict SHARED's
    # pages out of host RAM onto disk
    from vllm_tgis_adapter_tpu.engine.kv_cache import per_block_bytes

    eng = _tiered_engine(
        tiny_model_dir, str(tmp_path / "d"), host_gb=1.0, num_blocks=6
    )
    pbb = per_block_bytes(eng.config)
    eng.kv_tier.budget_bytes = 2 * pbb
    assert eng.kv_tier.disk is not None

    got = _run(eng, "a", SHARED)
    _run(eng, "f1", FILLER_1)
    _run(eng, "f2", FILLER_2)
    assert eng.kv_tier.disk.stored_pages > 0, "nothing cascaded to disk"
    got2 = _run(eng, "a2", SHARED)
    assert got == got2 == want
    assert eng.kv_tier.disk.loaded_pages > 0, (
        "warm re-send never read the disk tier"
    )
    assert eng.kv_tier.dropped_corrupt == 0
    assert eng.kv_tier.disk.dropped_corrupt == 0


def test_adapter_spill_restore_through_engine(
    tiny_model_dir, tmp_path, het_lora_dirs
):
    """Cold adapters ride the disk rung: a host-registry eviction
    spills the adapter to disk; a LATER request for it parks, restores
    disk→host, streams host→device, and serves the SAME tokens."""
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
    engine = LLMEngine.from_config(EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=96,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(32, 64)),
        parallel_config=ParallelConfig(),
        # host registry of TWO adapters: loading the third evicts one
        lora_config=LoRAConfig(enabled=True, max_loras=1,
                               max_lora_rank=8, max_cpu_loras=2),
        kv_host_cache_gb=1.0,
        kv_disk_cache_gb=1.0,
        kv_disk_cache_dir=str(tmp_path / "ad-disk"),
    ))
    disk = engine.kv_tier.disk
    assert engine.lora_manager.disk_tier is disk
    names = list(het_lora_dirs)
    asyncio.run(engine.lora_manager.load_lora_adapter(
        names[0], het_lora_dirs[names[0]]
    ))
    want = _run_requests(engine, [("first", names[0])])["first"]
    # fill the 2-entry host registry: names[0] spills to disk.  The
    # spill write is a task of the running loop (a worker thread does
    # the write), so the loop has to outlive it, as a server's does:
    # one asyncio.run() per load closed the loop with the write still
    # queued, and closing cancels it
    async def fill_registry():
        manager = engine.lora_manager
        for name in names[1:]:
            await manager.load_lora_adapter(name, het_lora_dirs[name])
        assert names[0] in manager._spilling  # noqa: SLF001 — gate parks on it
        await asyncio.gather(*manager._disk_tasks)  # noqa: SLF001
        assert not manager._spilling  # noqa: SLF001

    asyncio.run(fill_registry())
    assert engine.lora_manager.get_weights(names[0]) is None
    assert disk.has_adapter(names[0])
    # a new request for the spilled adapter: restored, same tokens
    got = _run_requests(engine, [("again", names[0])])["again"]
    assert got == want
    assert disk.loaded_adapters >= 1
    assert engine.lora_manager.get_weights(names[0]) is not None


# ----------------------------------------------------- chaos acceptance


def _build_async(tiny_model_dir, het_lora_dirs, disk_dir):
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
            # 8 pages: three 3-page prefixes + live work can never all
            # stay device-resident, so churn demotes into the tier
            block_size=16, num_blocks=8, cache_dtype=mcfg.dtype,
            enable_prefix_caching=True,
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(32, 64),
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(enabled=True, max_loras=1,
                               max_lora_rank=8),
        kv_host_cache_gb=1.0,
        kv_disk_cache_gb=1.0,
        kv_disk_cache_dir=disk_dir,
        max_engine_restarts=3,
        engine_restart_backoff_s=0.02,
        frontdoor=FrontdoorConfig(enabled=True),
    )
    return AsyncLLMEngine.from_config(config)


async def _acollect(engine, request_id, prompt_ids, n=6, lora=None):
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
            lora_request=lora,
        ):
            final = out
        return ("ok", final)
    except BaseException as e:  # noqa: BLE001 — the error IS the result
        return ("err", e)


def test_arena_chaos_mixed_churn_recovers_no_cross_type_corruption(
    tiny_model_dir, het_lora_dirs, tmp_path
):
    """THE chaos acceptance (ISSUE 14): an engine killed MID-CHURN with
    a mixed KV+adapter working set over HBM (arena charges live, tier
    warm, adapters churning) recovers under supervision with no
    cross-type page corruption — the warm prefix AND the adapter
    request both re-serve token-identically from the surviving tiers
    (every read digest/shape-validated: dropped_corrupt stays 0)."""
    # sync baseline for expected tokens (no tiers, no crash)
    engine0 = _lora_engine(tiny_model_dir, max_loras=1)
    names = list(het_lora_dirs)
    asyncio.run(engine0.lora_manager.load_lora_adapter(
        names[0], het_lora_dirs[names[0]]
    ))
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine0.add_request(
        "b0", None,
        SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True),
        prompt_token_ids=SHARED,
    )
    want_shared = None
    for _ in range(400):
        if not engine0.has_unfinished_requests():
            break
        for o in engine0.step():
            if o.finished:
                want_shared = o.outputs[0].token_ids
    assert want_shared is not None

    engine = _build_async(
        tiny_model_dir, het_lora_dirs, str(tmp_path / "chaos-disk")
    )

    async def scenario():
        lora_reqs = {}
        for name in names:
            lora_reqs[name] = (
                await engine.engine.lora_manager.load_lora_adapter(
                    name, het_lora_dirs[name]
                )
            )
        # 1. build the mixed working set over the 10-page pool: warm
        # prefix + adapter churn (3 ranks over 1 slot, arena charging)
        status, final = await _acollect(engine, "warm", SHARED)
        assert status == "ok"
        assert list(final.outputs[0].token_ids) == want_shared
        for i, (filler, name) in enumerate(
            ((FILLER_1, names[1]), (FILLER_2, names[2]))
        ):
            status, _ = await _acollect(
                engine, f"churn-{i}", filler, lora=lora_reqs[name]
            )
            assert status == "ok"
        core = engine.engine
        old_tier = core.kv_tier
        assert core.arena is not None
        assert core.arena.adapter_charges > 0
        assert old_tier.demoted_pages > 0

        # 2. kill mid-churn: a LoRA request is in flight when the next
        # plan dies
        failpoints.arm_site("core.plan_step", "raise", 1)
        kill = asyncio.create_task(_acollect(
            engine, "victim", FILLER_1, lora=lora_reqs[names[1]]
        ))
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if engine.supervisor is not None and any(
                h.get("recovered")
                for h in engine.supervisor.restart_history
            ):
                break
            await asyncio.sleep(0.02)
        else:
            raise AssertionError("supervised restart never completed")
        await kill

        # 3. the rebuilt engine: surviving tier adopted, fresh arena
        new_core = engine._replicas[0].engine
        assert new_core.kv_tier is old_tier
        assert new_core.arena is not None
        assert new_core.arena is not core.arena or core is new_core

        # 4. NO cross-type corruption: the warm KV prefix re-serves
        # token-identically AND the churned adapter still produces its
        # own (distinct) stream — with zero validation drops anywhere
        status, final = await _acollect(engine, "rewarm", SHARED)
        assert status == "ok"
        assert list(final.outputs[0].token_ids) == want_shared
        status, final_l = await _acollect(
            engine, "re-lora", SHARED, lora=lora_reqs[names[0]]
        )
        assert status == "ok"
        assert list(final_l.outputs[0].token_ids) != want_shared
        assert old_tier.dropped_corrupt == 0
        if old_tier.disk is not None:
            assert old_tier.disk.dropped_corrupt == 0
        assert new_core.arena.debug_state()["adapter_blocks"] >= 0
        await engine.stop()

    asyncio.run(scenario())
