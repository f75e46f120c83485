"""Distributed-layer tests on the 8-device virtual CPU mesh.

The reference has no distributed tests at all (SURVEY.md §4); here TP
sharding is validated numerically: the tp=4 sharded model must produce the
same logits as the unsharded one, through both prefill and paged decode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_tgis_adapter_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    LoRAConfig,
    ModelConfig,
    ParallelConfig,
    SchedulerConfig,
)
from vllm_tgis_adapter_tpu.models.llama import LlamaForCausalLM
from vllm_tgis_adapter_tpu.parallel import (
    build_mesh,
    cache_sharding,
    shard_llama_params,
    validate_tp_divisibility,
)


def tiny_config(**kw) -> ModelConfig:
    defaults = dict(
        model="tiny",
        model_type="llama",
        vocab_size=64,
        hidden_size=32,
        intermediate_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        head_dim=8,
        max_model_len=128,
        dtype=jnp.float32,
    )
    defaults.update(kw)
    return ModelConfig(**defaults)


def test_build_mesh_axes():
    mesh = build_mesh(tensor_parallel_size=4, data_parallel_size=2)
    assert mesh.shape == {"dp": 2, "sp": 1, "tp": 4}
    with pytest.raises(ValueError, match="needs 16 devices"):
        build_mesh(tensor_parallel_size=16)


def test_tp_divisibility_check():
    cfg = tiny_config(num_kv_heads=2)
    with pytest.raises(ValueError, match="num_kv_heads=2"):
        validate_tp_divisibility(cfg, 4)
    validate_tp_divisibility(tiny_config(), 4)


def test_indivisible_vocab_replicates_the_table():
    """granite's vocabulary (49155) is odd: tp must not refuse it, and
    the embedding table then stays whole on every device."""
    from jax.sharding import PartitionSpec as P

    from vllm_tgis_adapter_tpu.parallel import llama_param_specs

    validate_tp_divisibility(tiny_config(vocab_size=63), 4)
    shapes = {
        "embed": jax.ShapeDtypeStruct((63, 32), jnp.float32),
        "lm_head": jax.ShapeDtypeStruct((32, 63), jnp.float32),
        "layers": [],
    }
    specs = llama_param_specs(shapes, tp=4)
    assert specs["embed"] == specs["lm_head"] == P(None, None)
    assert llama_param_specs(shapes, tp=3)["embed"] == P("tp", None)


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_matches_single_device(tp):
    """Sharded prefill + decode ≡ unsharded, bit-for-bit shapes, close values."""
    cfg = tiny_config()
    model = LlamaForCausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    block_size = 4
    num_slots = 16 * block_size
    caches = model.make_kv_caches(num_slots, jnp.float32)

    t, bucket = 5, 8
    token_ids = np.zeros(bucket, np.int32)
    token_ids[:t] = [1, 5, 9, 2, 7]
    positions = np.arange(bucket, dtype=np.int32)
    slot_mapping = np.full(bucket, -1, np.int32)
    slot_mapping[:t] = np.arange(t)  # block 0 + block 1
    logits_idx = np.asarray([t - 1], np.int32)

    def run(params, caches, put):
        logits_p, caches = jax.jit(model.prefill)(
            params,
            caches,
            put(token_ids),
            put(positions),
            put(slot_mapping),
            put(np.asarray(t, np.int32)),
            put(logits_idx),
        )
        # one decode step for the sequence
        block_tables = np.zeros((2, 4), np.int32)
        block_tables[0, :2] = [0, 1]
        logits_d, caches = jax.jit(model.decode, static_argnums=7)(
            params,
            caches,
            put(np.asarray([3, 0], np.int32)),
            put(np.asarray([t, 0], np.int32)),
            put(np.asarray([t, -1], np.int32)),
            put(block_tables),
            put(np.asarray([t + 1, 1], np.int32)),
            block_size,
        )
        return np.asarray(logits_p), np.asarray(logits_d)

    ref_p, ref_d = run(params, caches, jnp.asarray)

    mesh = build_mesh(tensor_parallel_size=tp)
    sharded_params = shard_llama_params(mesh, params)
    sharded_caches = jax.device_put(caches, cache_sharding(mesh))
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    put = lambda x: jax.device_put(jnp.asarray(x), repl)  # noqa: E731
    got_p, got_d = run(sharded_params, sharded_caches, put)

    np.testing.assert_allclose(got_p, ref_p, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_d, ref_d, rtol=2e-5, atol=2e-5)


def test_unimplemented_parallel_modes_fail_fast():
    from vllm_tgis_adapter_tpu.parallel.mesh import mesh_from_parallel_config

    # pp>1 is implemented via engine/pipeline.py; this mesh builder only
    # serves non-pipelined replicas and must say so (ADVICE r3)
    with pytest.raises(NotImplementedError, match="PipelineRunner"):
        mesh_from_parallel_config(ParallelConfig(pipeline_parallel_size=2))
    with pytest.raises(NotImplementedError, match="data-parallel"):
        mesh_from_parallel_config(ParallelConfig(data_parallel_size=2))
    assert mesh_from_parallel_config(ParallelConfig()) is None
    mesh = mesh_from_parallel_config(ParallelConfig(tensor_parallel_size=2))
    assert mesh.shape["tp"] == 2


def test_from_config_shards_on_load(tiny_model_dir):
    """Engine boot with tp=2: every tensor is mesh-sharded as it is read
    (never materialised whole on one device) and generation still works."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, max_model_len=128,
                                       dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=4, num_blocks=64,
                                 cache_dtype=jnp.float32),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(16, 32, 128)),
        parallel_config=ParallelConfig(tensor_parallel_size=2),
        lora_config=LoRAConfig(),
    )
    engine = LLMEngine.from_config(config)
    assert engine.runner.mesh is not None
    wq = engine.runner.params["layers"][0]["wq"]
    assert len(wq.sharding.device_set) == 2  # actually split across tp

    engine.add_request("r1", "hello world", SamplingParams(
        temperature=0.0, max_tokens=4))
    outs = []
    while engine.has_unfinished_requests():
        outs.extend(engine.step())
    assert outs and outs[-1].finished
    assert len(outs[-1].outputs[0].token_ids) == 4


def test_runner_with_tp_mesh():
    """ModelRunner boots with tp>1 and produces tokens (engine-level smoke)."""
    from vllm_tgis_adapter_tpu.engine.runner import ModelRunner
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams
    from vllm_tgis_adapter_tpu.engine.scheduler import PrefillPlan
    from vllm_tgis_adapter_tpu.engine.sequence import Sequence

    mcfg = tiny_config()
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=4, num_blocks=32,
                                 cache_dtype=jnp.float32),
        scheduler_config=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(8, 16)),
        parallel_config=ParallelConfig(tensor_parallel_size=2),
        lora_config=LoRAConfig(),
    )
    model = LlamaForCausalLM(mcfg)
    params = model.init_params(jax.random.PRNGKey(0))
    runner = ModelRunner(config, model, params)
    assert runner.mesh is not None

    seq = Sequence("r1", "hi", [1, 5, 9], SamplingParams(temperature=0.0),
                   fallback_seed=7)
    seq.slot = 0
    from vllm_tgis_adapter_tpu.engine.kv_cache import (
        BlockAllocator,
        SequenceBlocks,
    )

    blocks = SequenceBlocks(BlockAllocator(32, 4))
    blocks.ensure_capacity(3)
    seq.blocks = blocks
    plan = PrefillPlan(seq=seq, token_ids=[1, 5, 9], slots=[0, 1, 2],
                       bucket_len=8)
    sampled, _ = runner.run_prefill(plan)
    assert 0 <= sampled.token_id < mcfg.vocab_size


def test_engine_generation_on_sp_tp_mesh(tiny_model_dir):
    """End-to-end engine generation over a joint sp=2 x tp=2 mesh must
    match the single-device engine token-for-token (VERDICT r2 #4:
    ring attention reachable from config, through the engine's own
    prefill/decode path, not just the bare op)."""
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    def run(parallel_config):
        mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
        config = EngineConfig(
            model_config=mcfg,
            cache_config=CacheConfig(block_size=16, num_blocks=64,
                                     cache_dtype=mcfg.dtype),
            scheduler_config=SchedulerConfig(
                max_num_seqs=4, prefill_buckets=(32, 64)),
            parallel_config=parallel_config,
            lora_config=LoRAConfig(),
        )
        eng = LLMEngine.from_config(config)
        eng.add_request(
            "r", None,
            SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True),
            prompt_token_ids=list(range(3, 40)),
        )
        for _ in range(100):
            if not eng.has_unfinished_requests():
                break
            outs = eng.step()
            for o in outs:
                if o.finished:
                    return o.outputs[0].token_ids
        raise AssertionError("engine did not finish")

    single = run(ParallelConfig())
    sp_tp = run(ParallelConfig(tensor_parallel_size=2,
                               sequence_parallel_size=2))
    assert sp_tp == single
