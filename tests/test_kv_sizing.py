"""KV-pool sizing from the HBM budget (kv_cache.resolve_num_blocks).

The reference stack sizes its KV pool from ``gpu_memory_utilization``
(vLLM engine-arg behavior the adapter inherits); these tests pin the TPU
analog: pages derived from per-device free HBM after weights, shrinking
per-device page cost under TP, fail-fast when one sequence cannot fit,
and a static fallback on statless backends.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import pytest

from vllm_tgis_adapter_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    LoRAConfig,
    ModelConfig,
    ParallelConfig,
    SchedulerConfig,
)
from vllm_tgis_adapter_tpu.engine.kv_cache import (
    _FALLBACK_BLOCKS,
    resolve_num_blocks,
)


class FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def make_config(
    *,
    num_kv_heads=8,
    num_layers=4,
    head_dim=64,
    max_model_len=2048,
    block_size=16,
    max_num_seqs=32,
    tp=1,
    util=0.9,
):
    mcfg = ModelConfig(
        model="/tmp/x", model_type="llama", vocab_size=1024,
        hidden_size=num_kv_heads * head_dim * 2, intermediate_size=256,
        num_layers=num_layers, num_heads=num_kv_heads * 2,
        num_kv_heads=num_kv_heads, head_dim=head_dim,
        max_model_len=max_model_len, dtype=jnp.bfloat16,
    )
    return EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=block_size, num_blocks=0,
                                 cache_dtype=jnp.bfloat16),
        scheduler_config=SchedulerConfig(max_num_seqs=max_num_seqs),
        parallel_config=ParallelConfig(tensor_parallel_size=tp),
        lora_config=LoRAConfig(),
        hbm_memory_utilization=util,
    )


def block_bytes(cfg, tp=1):
    m = cfg.model_config
    return (
        2 * m.num_layers * cfg.cache_config.block_size
        * (m.num_kv_heads // tp) * m.head_dim * 2  # bf16
    )


def test_blocks_scale_with_budget():
    cfg = make_config()
    bb = block_bytes(cfg)
    small = resolve_num_blocks(
        cfg, FakeDevice({"bytes_limit": 1000 * bb, "bytes_in_use": 0})
    )
    big = resolve_num_blocks(
        cfg, FakeDevice({"bytes_limit": 2000 * bb, "bytes_in_use": 0})
    )
    assert small == 900  # 1000 * 0.9 utilization
    assert big == 1800


def test_in_use_bytes_subtracted():
    cfg = make_config(util=1.0)
    bb = block_bytes(cfg)
    got = resolve_num_blocks(
        cfg,
        FakeDevice({"bytes_limit": 1000 * bb, "bytes_in_use": 400 * bb}),
    )
    assert got == 600


def test_capped_at_full_batch_occupancy():
    cfg = make_config(max_num_seqs=2, max_model_len=64, block_size=16,
                      util=1.0)
    bb = block_bytes(cfg)
    got = resolve_num_blocks(
        cfg, FakeDevice({"bytes_limit": 10**6 * bb, "bytes_in_use": 0})
    )
    assert got == 2 * (64 // 16)  # pages beyond full occupancy are dead


def test_tp_shrinks_per_device_page_cost():
    # under TP=4 each device holds 1/4 of the kv heads per page, so the
    # same per-device budget fits 4x the pages
    cfg1 = make_config(tp=1, util=1.0)
    cfg4 = make_config(tp=4, util=1.0)
    bb1 = block_bytes(cfg1)
    dev = FakeDevice({"bytes_limit": 200 * bb1, "bytes_in_use": 0})
    assert resolve_num_blocks(cfg4, dev) == 4 * resolve_num_blocks(cfg1, dev)


@pytest.mark.parametrize("scheme", ["int8", "fp8"])
def test_quantized_pages_nearly_double_the_pool(scheme):
    # a quantized page stores one byte an element plus an f32 scale per
    # page per head where bf16 stores two bytes: the same per-device
    # budget holds at least 1.9x the pages, and never quite 2x
    cfg = make_config(util=1.0)
    qcfg = dataclasses.replace(
        cfg,
        cache_config=dataclasses.replace(
            cfg.cache_config, kv_quantization=scheme
        ),
    )
    dev = FakeDevice(
        {"bytes_limit": 1000 * block_bytes(cfg), "bytes_in_use": 0}
    )
    assert resolve_num_blocks(cfg, dev) == 1000
    assert 1900 <= resolve_num_blocks(qcfg, dev) < 2000


def test_too_small_budget_raises():
    cfg = make_config(max_model_len=2048, block_size=16, util=1.0)
    bb = block_bytes(cfg)
    with pytest.raises(RuntimeError, match="KV cache budget too small"):
        resolve_num_blocks(
            cfg, FakeDevice({"bytes_limit": 10 * bb, "bytes_in_use": 0})
        )


def test_statless_backend_falls_back():
    cfg = make_config()
    assert resolve_num_blocks(cfg, FakeDevice(None)) == _FALLBACK_BLOCKS
    assert resolve_num_blocks(cfg, FakeDevice({})) == _FALLBACK_BLOCKS


def test_statless_tpu_is_an_error():
    """The static pool is for CPU tests: a chip without an HBM limit
    must not come up on it."""
    dev = FakeDevice(None)
    dev.platform = "tpu"
    with pytest.raises(RuntimeError, match="static KV pool"):
        resolve_num_blocks(make_config(), dev)


def test_engine_resolves_auto_sizing(tiny_model_dir):
    """num_blocks=0 in the config must be resolved by engine boot."""
    import jax
    from transformers import AutoTokenizer

    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.models.llama import LlamaForCausalLM

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, max_model_len=128,
                                       dtype="float32")
    cfg = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=0,
                                 cache_dtype=jnp.float32),
        scheduler_config=SchedulerConfig(max_num_seqs=4,
                                         prefill_buckets=(32, 128)),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )
    model = LlamaForCausalLM(mcfg)
    params = model.init_params(jax.random.PRNGKey(0))
    tokenizer = AutoTokenizer.from_pretrained(tiny_model_dir)
    bb = (2 * mcfg.num_layers * 16 * mcfg.num_kv_heads * mcfg.head_dim * 4)
    dev = FakeDevice({"bytes_limit": 200 * bb, "bytes_in_use": 20 * bb})
    engine = LLMEngine(cfg, model, params, tokenizer, memory_device=dev)
    expected = min(int(200 * 0.9) - 20, 4 * (128 // 16))
    assert engine.config.cache_config.num_blocks == expected
    assert engine.scheduler.allocator.num_blocks == expected


def test_from_args_requests_auto_sizing(tiny_model_dir):
    from vllm_tgis_adapter_tpu.tgis_utils.args import make_parser

    parser = make_parser()
    args = parser.parse_args(["--model", tiny_model_dir])
    cfg = EngineConfig.from_args(args)
    assert cfg.cache_config.num_blocks == 0  # auto → resolved at boot


# ---------------------------------------------------------- prefix caching


def _alloc(num_blocks=16, block_size=4):
    from vllm_tgis_adapter_tpu.engine.kv_cache import BlockAllocator

    return BlockAllocator(num_blocks, block_size, enable_prefix_caching=True)


def test_prefix_cache_match_and_register():
    a = _alloc()
    ids = list(range(1, 14))  # 13 tokens -> 3 full pages + partial
    blocks = a.allocate(4)
    a.register_prefix(ids, blocks)
    # full prompt re-sent: match caps one token short (needs >=1 to prefill)
    hit, matched = a.match_prefix(ids)
    assert matched == 12 and hit == blocks[:3]
    # shorter shared prefix
    hit2, matched2 = a.match_prefix(ids[:9])
    assert matched2 == 8 and hit2 == blocks[:2]
    # divergent second page
    other = ids[:4] + [99, 98, 97, 96] + ids[8:]
    hit3, matched3 = a.match_prefix(other)
    assert matched3 == 4 and hit3 == blocks[:1]
    # different lora -> no match
    assert a.match_prefix(ids, lora_name="adapterX") == ([], 0)


def test_prefix_cache_refcount_and_reclaim():
    a = _alloc(num_blocks=4, block_size=4)
    ids = list(range(1, 9))  # 2 full pages
    owner = a.allocate(2)
    a.register_prefix(ids, owner)
    hit, matched = a.match_prefix(ids + [42])  # adopts both pages
    assert matched == 8
    # owner releases: pages still referenced by the adopter -> not free
    a.free(owner)
    assert a.num_free == 2  # only the 2 unallocated pages
    # adopter releases: registered pages park in the cached pool
    a.free(hit)
    assert a.num_free == 4
    # they are still matchable...
    hit2, m2 = a.match_prefix(ids + [42])
    assert m2 == 8
    a.free(hit2)
    # ...until allocation pressure reclaims them (LRU) and drops the hash
    taken = a.allocate(4)
    assert len(taken) == 4
    assert a.match_prefix(ids + [42]) == ([], 0)


def test_prefix_cache_engine_end_to_end(tiny_model_dir):
    """Second request with a shared prefix skips prefill for the matched
    pages (prefill_pos > 0 at admission) and produces identical output."""
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

    def build(prefix_caching: bool) -> LLMEngine:
        mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
        return LLMEngine.from_config(EngineConfig(
            model_config=mcfg,
            cache_config=CacheConfig(block_size=16, num_blocks=64,
                                     cache_dtype=mcfg.dtype,
                                     enable_prefix_caching=prefix_caching),
            scheduler_config=SchedulerConfig(
                max_num_seqs=4, prefill_buckets=(32, 64, 128)),
            parallel_config=ParallelConfig(),
            lora_config=LoRAConfig(),
        ))

    shared = list(range(3, 60))  # 57 tokens: 3 full pages of 16 + tail
    sp = dict(temperature=0.0, max_tokens=6, ignore_eos=True)

    def run(eng, rid, ids):
        eng.add_request(rid, None, SamplingParams(**sp),
                        prompt_token_ids=ids)
        for _ in range(60):
            if not eng.has_unfinished_requests():
                break
            for out in eng.step():
                if out.finished:
                    return out.outputs[0].token_ids
        raise AssertionError("did not finish")

    plain = build(False)
    want_a = run(plain, "a", shared)
    want_b = run(plain, "b", shared[:40] + [7, 8, 9])

    cached = build(True)
    got_a = run(cached, "a", shared)
    assert cached.scheduler.allocator.prefix_hits == 0  # cold
    got_b2 = run(cached, "a2", shared)  # full prefix reuse
    assert cached.scheduler.allocator.prefix_hits == 48  # 3 pages
    got_b = run(cached, "b", shared[:40] + [7, 8, 9])  # 2-page reuse
    assert cached.scheduler.allocator.prefix_hits == 48 + 32

    assert got_a == want_a == got_b2
    assert got_b == want_b

    # prompt-logprob requests must NOT adopt cached pages (their table is
    # built from one whole-prompt pass); the full table still comes back
    hits_before = cached.scheduler.allocator.prefix_hits
    cached.add_request(
        "lp", None,
        SamplingParams(temperature=0.0, max_tokens=2, ignore_eos=True,
                       prompt_logprobs=2, logprobs=2),
        prompt_token_ids=shared,
    )
    final = None
    for _ in range(60):
        if not cached.has_unfinished_requests():
            break
        for out in cached.step():
            if out.finished:
                final = out
    assert final is not None
    assert cached.scheduler.allocator.prefix_hits == hits_before
    assert len(final.prompt_logprobs) == len(shared)
    assert final.prompt_logprobs[0] is None
    assert all(e is not None for e in final.prompt_logprobs[1:])


def test_fp8_kv_cache_end_to_end(tiny_model_dir):
    """--kv-cache-dtype float8_e4m3 really stores the KV pool in fp8
    (half the pages' bytes) and generation still runs: K/V quantize on
    the cache write, attention reads cast back to f32 (truthful-flag
    audit, round 4)."""
    import jax.numpy as jnp

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
                                 cache_dtype=jnp.float8_e4m3fn),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(32,)),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )
    engine = LLMEngine.from_config(config)
    assert engine.runner.caches[0].dtype == jnp.float8_e4m3fn
    engine.add_request(
        "f8", None,
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True),
        prompt_token_ids=list(range(3, 12)),
    )
    toks = None
    for _ in range(100):
        if not engine.has_unfinished_requests():
            break
        for out in engine.step():
            if out.finished:
                toks = out.outputs[0].token_ids
    assert toks is not None and len(toks) == 8
