"""Falcon-H1 on the served path against its plain reference
(models/reference/falcon_h1.py), at a small size with every multiplier
away from 1: hidden 64, 3 layers, 4 / 2 attention heads of 16, 4 mixer
heads of 16, state 16, 2 groups, chunk 8; float32, seeded weights.

Two levels.  The model's two step entry points are driven directly with
hand-built streams (chunks of any length, several sequences in one mixed
step, then decode steps), comparing LOGITS with the reference's full
forward pass.  The engine is driven through its planner and its fused
waves (rows joining and leaving, a slot reused, preemption by recompute),
comparing tokens and logprobs.

``TOLERANCE`` is the float32 limit on a logit (their std is ~0.25 here):
the chunked scan, the one-token update and the reference's token-by-token
recurrence sum the same terms in different orders, and the largest
difference read over the chunkings below is 4.2e-7.  It is tight enough
that holding the scan state in bfloat16 between steps fails it fourteen
times over (1.4e-4; ``test_bfloat16_state_fails_the_tolerance``).
"""

from __future__ import annotations

import dataclasses
import functools

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
from vllm_tgis_adapter_tpu.models.reference import falcon_h1 as reference

TOLERANCE = 1e-5
BLOCK = 16
MAX_BLOCKS = 16  # pages a sequence may hold in the hand-built streams
ROWS = 8  # state slots / sequence descriptors


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory) -> str:
    from tests.fixture_models import build_tiny_falcon_h1

    return build_tiny_falcon_h1(str(tmp_path_factory.mktemp("tiny-falcon")))


@pytest.fixture(scope="module")
def mcfg(model_dir):
    return ModelConfig.from_pretrained(model_dir, dtype="float32")


@pytest.fixture(scope="module")
def params(mcfg, model_dir):
    from vllm_tgis_adapter_tpu.engine.weights import load_model_params

    return load_model_params(mcfg, model_dir)


@pytest.fixture(scope="module")
def ref_logits(mcfg, params):
    """The reference's logits of a whole sequence, computed once each."""
    seen: dict = {}

    def logits(ids):
        key = tuple(int(i) for i in ids)
        for have, value in seen.items():  # causal: a prefix's are a slice
            if have[:len(key)] == key:
                return value[:len(key)]
        seen[key] = np.asarray(reference.forward(mcfg, params, key))
        return seen[key]

    return logits


def tokens(n: int, seed: int) -> list[int]:
    return [int(t) for t in
            np.random.default_rng(seed).integers(3, 500, size=n)]


@functools.cache
def programs(mcfg):
    """The model and its two step entry points, jitted once a module
    (one compile a flat-stream length, as the runner's)."""
    from vllm_tgis_adapter_tpu.models import get_model_class

    model = get_model_class(mcfg.model_type)(mcfg)
    ragged = jax.jit(functools.partial(model.ragged_forward,
                                       block_size=BLOCK))
    decode = jax.jit(
        lambda params, caches, *args, state_slots: model.decode(
            params, caches, *args, BLOCK, state_slots=state_slots))
    return model, ragged, decode


class Driver:
    """The model's step entry points over hand-built streams: sequence
    ``slot`` owns pages ``slot * MAX_BLOCKS ...`` of the pool and state
    slot ``slot``; ``round_state`` is applied to the scan state after
    every step (the bfloat16 control)."""

    def __init__(self, mcfg, params, round_state=None):
        self.model, self._ragged, self._decode = programs(mcfg)
        self.params = params
        k, v = self.model.make_kv_caches(ROWS * MAX_BLOCKS * BLOCK,
                                         mcfg.dtype, block_size=BLOCK)
        self.caches = (k, v, self.model.make_state(ROWS))
        self.pos = {}  # slot -> tokens consumed
        self.round_state = round_state
        self.tables = np.arange(ROWS * MAX_BLOCKS, dtype=np.int32).reshape(
            ROWS, MAX_BLOCKS)

    def _after(self):
        if self.round_state is not None:
            k, v, (ssm, conv) = self.caches
            self.caches = (k, v, (self.round_state(ssm), conv))

    def mixed(self, spans: list[tuple[int, list[int]]], bucket=None):
        """One mixed step: ``spans`` is [(slot, next token ids)] in stream
        order; returns one [len, vocab] logits array a span."""
        total = sum(len(ids) for _, ids in spans)
        t = bucket or max(16, 1 << (total - 1).bit_length())
        token_ids = np.zeros(t, np.int32)
        positions = np.zeros(t, np.int32)
        slot_mapping = np.full(t, -1, np.int32)
        seq_starts = np.full(ROWS + 1, t, np.int32)
        pos_base = np.zeros(ROWS, np.int32)
        state_slots = np.full(ROWS, -1, np.int32)
        tables = np.zeros((ROWS, MAX_BLOCKS), np.int32)
        off = 0
        for i, (slot, ids) in enumerate(spans):
            start = self.pos.get(slot, 0)
            n = len(ids)
            token_ids[off:off + n] = ids
            positions[off:off + n] = start + np.arange(n)
            slot_mapping[off:off + n] = (
                slot * MAX_BLOCKS * BLOCK + start + np.arange(n))
            seq_starts[i], pos_base[i] = off, start
            state_slots[i], tables[i] = slot, self.tables[slot]
            self.pos[slot] = start + n
            off += n
        seq_starts[len(spans)] = off
        logits, self.caches = self._ragged(
            self.params, self.caches, jnp.asarray(token_ids),
            jnp.asarray(positions), jnp.asarray(slot_mapping),
            jnp.asarray(seq_starts), jnp.asarray(pos_base),
            jnp.asarray(off, jnp.int32), jnp.asarray(tables),
            jnp.arange(t, dtype=jnp.int32),
            state_slots=jnp.asarray(state_slots),
        )
        self._after()
        logits, out, off = np.asarray(logits), [], 0
        for _, ids in spans:
            out.append(logits[off:off + len(ids)])
            off += len(ids)
        return out

    def decode(self, rows: dict[int, int]) -> dict[int, np.ndarray]:
        """One decode step: ``rows`` is slot -> input token; rows of the
        batch that are not named are inactive.  slot -> [vocab] logits."""
        token_ids = np.zeros(ROWS, np.int32)
        positions = np.zeros(ROWS, np.int32)
        kv_slots = np.full(ROWS, -1, np.int32)
        context = np.ones(ROWS, np.int32)
        for slot, tok in rows.items():
            pos = self.pos[slot]
            token_ids[slot], positions[slot] = tok, pos
            kv_slots[slot] = slot * MAX_BLOCKS * BLOCK + pos
            context[slot] = pos + 1
            self.pos[slot] = pos + 1
        logits, self.caches = self._decode(
            self.params, self.caches, jnp.asarray(token_ids),
            jnp.asarray(positions), jnp.asarray(kv_slots),
            jnp.asarray(self.tables), jnp.asarray(context),
            state_slots=jnp.arange(ROWS, dtype=jnp.int32),
        )
        self._after()
        logits = np.asarray(logits)
        return {slot: logits[slot] for slot in rows}


# ----------------------------------------------------------------- the model


@pytest.mark.parametrize("length", [1, 5, 8, 16, 37, 59])
def test_whole_prompt_logits_match_reference(mcfg, params, ref_logits,
                                             length):
    ids = BOOK[:length]
    (got,) = Driver(mcfg, params).mixed([(0, ids)])
    np.testing.assert_allclose(got, ref_logits(ids), atol=TOLERANCE, rtol=0)


#: one sequence for the single-sequence cases: each takes a prefix of it
#: (the reference is causal, so its logits are computed once)
BOOK = tokens(99, seed=7)


def chunked_then_decoded(mcfg, params, ref_logits, chunks, steps,
                         round_state=None) -> float:
    """A prompt fed in ``chunks``, then ``steps`` tokens of decode
    (teacher-forced): the largest difference of any row's logits from
    the reference's one full pass."""
    ref_logits(BOOK)
    prompt = BOOK[:sum(chunks)]
    full = BOOK[:len(prompt) + steps]
    want = ref_logits(full)
    drv = Driver(mcfg, params, round_state)
    worst, off = 0.0, 0
    for n in chunks:
        (got,) = drv.mixed([(2, full[off:off + n])])
        worst = max(worst, float(np.abs(got - want[off:off + n]).max()))
        off += n
    for i in range(steps):
        at = len(prompt) + i
        got = drv.decode({2: full[at]})[2]
        worst = max(worst, float(np.abs(got - want[at]).max()))
    return worst


@pytest.mark.parametrize("chunks", [
    (5, 16, 1, 37),  # uneven, crossing the scan's chunks of 8
    (59,), (8, 8, 8), (1, 1, 1, 20), (37, 5, 16, 1),
])
def test_chunked_prefill_then_decode_matches_reference(mcfg, params,
                                                       ref_logits, chunks):
    """The state is handed from chunk to chunk whatever their lengths, and
    from the last chunk to 40 decode steps: every row's logits agree with
    the reference's one full forward pass."""
    assert chunked_then_decoded(
        mcfg, params, ref_logits, chunks, steps=40) < TOLERANCE


def test_bfloat16_state_fails_the_tolerance(mcfg, params, ref_logits):
    """The control: the same comparison with the scan state held in
    bfloat16 between steps does NOT pass, so the tolerance would catch a
    state kept in a lower precision than the configuration states."""
    worst = chunked_then_decoded(
        mcfg, params, ref_logits, (5, 16, 1, 37), steps=40,
        round_state=lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    assert worst > 5 * TOLERANCE, worst


@pytest.mark.parametrize("n_seqs", [2, 5])
def test_several_sequences_in_one_mixed_step(mcfg, params, ref_logits,
                                             n_seqs):
    """Spans of several sequences in one flat stream, of lengths that are
    no multiple of the chunk, some fresh, some continuing, some one
    decode token: each starts from its own slot's state and leaves its
    own there."""
    lengths = [11, 23, 3, 9, 17][:n_seqs]
    seqs = {slot: tokens(n + 12, seed=40 + slot)
            for slot, n in zip((5, 1, 6, 0, 3), lengths)}
    drv = Driver(mcfg, params)
    fed = {slot: 0 for slot in seqs}

    def step(plan):
        spans = [(slot, seqs[slot][fed[slot]:fed[slot] + n])
                 for slot, n in plan]
        for (slot, n), got in zip(plan, drv.mixed(spans)):
            want = ref_logits(seqs[slot])[fed[slot]:fed[slot] + n]
            np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)
            fed[slot] += n

    slots = list(seqs)
    # first chunks of all, of different lengths
    step([(slot, n) for slot, n in zip(slots, lengths)])
    # then one token for the first (a decode row), a chunk for the others
    step([(slots[0], 1)] + [(slot, 9) for slot in slots[1:]])
    # then one token each
    step([(slot, 1) for slot in slots])
    # and the decode wave's step for all of them at once
    got = drv.decode({slot: seqs[slot][fed[slot]] for slot in slots})
    for slot in slots:
        np.testing.assert_allclose(
            got[slot], ref_logits(seqs[slot])[fed[slot]],
            atol=TOLERANCE, rtol=0)


def test_inactive_row_keeps_its_state(mcfg, params, ref_logits):
    """A row that sits out decode steps (finished for this wave, its KV
    slot -1) finds its state as it left it."""
    ids = tokens(30, seed=3)
    other = tokens(30, seed=4)
    drv = Driver(mcfg, params)
    drv.mixed([(1, ids[:20]), (4, other[:20])])
    for at in range(20, 24):  # row 1 sits out
        drv.decode({4: other[at]})
    for at in range(20, 24):
        got = drv.decode({1: ids[at], 4: other[at + 4]})
        np.testing.assert_allclose(got[1], ref_logits(ids)[at],
                                   atol=TOLERANCE, rtol=0)
        np.testing.assert_allclose(got[4], ref_logits(other)[at + 4],
                                   atol=TOLERANCE, rtol=0)


def test_decode_through_the_pallas_kernels_matches_reference(
        mcfg, params, ref_logits, monkeypatch):
    """The wave's step with both kernels as the chip runs them, in
    interpret mode: the attention call over its in-trace schedule and
    ``ssm_decode_update`` over the slab, rows 1 and 4 live, the others
    not in the grid."""
    from vllm_tgis_adapter_tpu.ops import attention as attn_ops
    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    ids, other = BOOK[:30], tokens(30, seed=4)
    drv = Driver(mcfg, params)
    drv.mixed([(1, ids[:20]), (4, other[:20])])
    monkeypatch.setattr(attn_ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ra, "_use_pallas", lambda: True)
    drv._decode = jax.jit(
        lambda params, caches, *args, state_slots: drv.model.decode(
            params, caches, *args, BLOCK, state_slots=state_slots))
    for at in range(20, 26):
        got = drv.decode({1: ids[at], 4: other[at]})
        np.testing.assert_allclose(got[1], ref_logits(ids)[at],
                                   atol=TOLERANCE, rtol=0)
        np.testing.assert_allclose(got[4], ref_logits(other)[at],
                                   atol=TOLERANCE, rtol=0)


# ---------------------------------------------------------------- the engine


@pytest.fixture(scope="module")
def make_engine(mcfg):
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    def build(*, num_blocks=64, max_num_seqs=8, **engine_kwargs):
        config = EngineConfig(
            model_config=mcfg,
            cache_config=CacheConfig(block_size=BLOCK, num_blocks=num_blocks,
                                     cache_dtype=mcfg.dtype),
            scheduler_config=SchedulerConfig(
                max_num_seqs=max_num_seqs,
                prefill_buckets=(16, 32, 64, 128)),
            parallel_config=ParallelConfig(),
            lora_config=LoRAConfig(),
            **engine_kwargs,
        )
        return LLMEngine.from_config(config)

    return build


def greedy(n: int):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True,
                          logprobs=1)


def drain(engine, arrivals=None, max_steps=4000) -> dict:
    """Step to completion; ``arrivals`` maps a step number to requests
    ``(id, prompt ids, new tokens)`` added before that step."""
    arrivals = dict(arrivals or {})
    done, step = {}, 0
    while engine.has_unfinished_requests() or arrivals:
        for rid, ids, n in arrivals.pop(step, []):
            engine.add_request(rid, None, greedy(n), prompt_token_ids=ids)
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out
        step += 1
        assert step < max_steps
    return done


def assert_is_the_references(out, prompt, ref_logits, atol=2e-5):
    """The request's tokens are the reference's greedy tokens and their
    logprobs the reference's, given everything before them."""
    got = list(out.outputs[0].token_ids)
    want = ref_logits(list(prompt) + got)
    logprobs = np.asarray(jax.nn.log_softmax(want, axis=-1))
    for i, (tok, entry) in enumerate(zip(got, out.outputs[0].logprobs)):
        row = len(prompt) - 1 + i
        assert tok == int(np.argmax(want[row])), (i, tok)
        served = entry[tok]
        served = getattr(served, "logprob", served)
        assert abs(served - logprobs[row, tok]) < atol, (i, served)


def test_engine_prefill_and_fused_waves_match_reference(make_engine,
                                                        ref_logits):
    """Through the planner (a 59-token prompt is cut into 32 + 16 + 8 + 2
    + 1) and five fused waves of 8 steps."""
    prompt = tokens(59, seed=11)
    out = drain(make_engine(), {0: [("r", prompt, 40)]})["r"]
    assert len(out.outputs[0].token_ids) == 40
    assert_is_the_references(out, prompt, ref_logits)


def test_rows_join_and_leave_mid_wave(make_engine, ref_logits):
    """One row leaves after 5 tokens (mid-wave), another joins while two
    are decoding: the neighbours' state stands."""
    prompts = {"long": tokens(21, seed=21), "short": tokens(9, seed=22),
               "late": tokens(33, seed=23), "later": tokens(4, seed=24)}
    done = drain(make_engine(), {
        0: [("long", prompts["long"], 37), ("short", prompts["short"], 5)],
        2: [("late", prompts["late"], 19)],
        5: [("later", prompts["later"], 11)],
    })
    assert len(done) == 4
    for rid, out in done.items():
        assert_is_the_references(out, prompts[rid], ref_logits)


def test_reused_slot_gives_what_a_fresh_engine_gives(make_engine,
                                                     ref_logits):
    """With one batch row every request reuses the same state slot; the
    second one finds nothing of the first."""
    first, second = tokens(27, seed=31), tokens(14, seed=32)
    engine = make_engine(max_num_seqs=1)
    drain(engine, {0: [("a", first, 13)]})
    reused = drain(engine, {0: [("b", second, 17)]})["b"]
    fresh = drain(make_engine(max_num_seqs=1), {0: [("b", second, 17)]})["b"]
    assert (list(reused.outputs[0].token_ids)
            == list(fresh.outputs[0].token_ids))
    assert_is_the_references(reused, second, ref_logits)


def test_preemption_by_recompute_gives_the_same_tokens(make_engine,
                                                       ref_logits):
    """A pool too small for three rows preempts the youngest, whose slot
    is freed and whose state is recomputed from position 0 over prompt
    and output: the tokens are those of an unpreempted run."""
    from vllm_tgis_adapter_tpu import metrics

    before = metrics.preemptions_total._value.get()
    prompts = {f"p{i}": tokens(14, seed=50 + i) for i in range(3)}
    done = drain(make_engine(num_blocks=6, max_num_seqs=4), {
        0: [(rid, ids, 24) for rid, ids in prompts.items()]})
    assert metrics.preemptions_total._value.get() > before
    for rid, out in done.items():
        assert len(out.outputs[0].token_ids) == 24
        assert_is_the_references(out, prompts[rid], ref_logits)


def test_state_slab_is_priced_reported_and_counted(make_engine, mcfg):
    """The slab's bytes are what ``make_state`` allocates, the device
    report says so, and a decode wave counts its state traffic."""
    from vllm_tgis_adapter_tpu import metrics
    from vllm_tgis_adapter_tpu.engine.device_report import device_report
    from vllm_tgis_adapter_tpu.engine.kv_cache import state_slab_bytes

    engine = make_engine(max_num_seqs=4)
    ssm, conv = engine.runner.caches[2]
    held = ssm.size * ssm.dtype.itemsize + conv.size * conv.dtype.itemsize
    assert state_slab_bytes(engine.config) == held
    report = device_report(engine)["state_slab"]
    assert report == {"slots": 4, "slots_used": 0, "bytes": held}

    bytes0 = metrics.ssm_state_bytes_total._value.get()
    scanned0 = metrics.ssm_scan_tokens_total._value.get()
    engine.add_request("m", None, greedy(9), prompt_token_ids=tokens(20, 1))
    engine.step()
    assert device_report(engine)["state_slab"]["slots_used"] == 1
    drain(engine)
    assert engine.scheduler.free_slot_count == 4
    mx = mcfg.mixer
    row_step = 2 * mcfg.num_layers * mx.num_heads * mx.d_state * 16 * 4
    assert mx.head_dim == 16 and mx.scan_state_bytes * 2 * 3 == row_step
    # 20 prompt tokens through the scan (16 + 4); 8 decode steps of one
    # row through the update (the first token comes from the prefill)
    assert metrics.ssm_scan_tokens_total._value.get() - scanned0 == 20
    assert metrics.ssm_state_bytes_total._value.get() - bytes0 == 8 * row_step


# ----------------------------------------------------------------- refusals


REFUSED = {
    "prefix caching": dict(
        cache_config=CacheConfig(enable_prefix_caching=True)),
    "swap space": dict(swap_space_gib=1.0),
    "kvnet listen": dict(kvnet_listen="127.0.0.1:7000"),
    "kvnet peers": dict(kvnet_peers=("127.0.0.1:7001",)),
    "tp": dict(parallel_config=ParallelConfig(tensor_parallel_size=2)),
    "pp": dict(parallel_config=ParallelConfig(pipeline_parallel_size=2)),
    "sp": dict(parallel_config=ParallelConfig(sequence_parallel_size=2)),
    "dp": dict(parallel_config=ParallelConfig(data_parallel_size=2)),
    "dp replicas": dict(parallel_config=ParallelConfig(dp_replicas=2)),
}


def engine_config(mcfg, **kwargs) -> EngineConfig:
    base = dict(
        model_config=mcfg, cache_config=CacheConfig(),
        scheduler_config=SchedulerConfig(), parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
    )
    return EngineConfig(**{**base, **kwargs})


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_boot_refuses_what_carries_no_state(mcfg, what):
    with pytest.raises(ValueError, match="recurrent state") as err:
        engine_config(mcfg, **REFUSED[what])
    assert "does not compose with model_type 'falcon_h1'" in str(err.value)


def test_boot_refuses_speculative_decoding(mcfg):
    from vllm_tgis_adapter_tpu.engine.config import SpeculativeConfig

    spec = SpeculativeConfig(draft_model="/nowhere",
                             num_speculative_tokens=3,
                             draft_model_config=mcfg)
    with pytest.raises(ValueError, match="--speculative-model does not"):
        engine_config(mcfg, speculative=spec)


def test_boot_refuses_the_bucketed_backend(mcfg):
    with pytest.raises(ValueError, match="bucketed was retired"):
        engine_config(mcfg, attention_backend="bucketed")


def test_llama_lineage_is_not_refused(tiny_model_dir):
    """The same flags boot for a model whose only state is KV pages."""
    llama = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    assert not llama.has_recurrent_state
    engine_config(llama, **REFUSED["prefix caching"])
    engine_config(llama, **REFUSED["swap space"])


@pytest.mark.parametrize("key,value", [
    ("mamba_norm_before_gate", True), ("mamba_proj_bias", True),
    ("attention_bias", True), ("mlp_bias", True), ("projectors_bias", True),
    ("rope_scaling", {"rope_type": "linear", "factor": 2.0}),
    ("mamba_use_mlp", False), ("mamba_conv_bias", False),
])
def test_config_refuses_what_the_model_does_not_compute(key, value):
    from tests.fixture_models import TINY_FALCON_H1_CONFIG

    hf = dict(TINY_FALCON_H1_CONFIG, **{key: value})
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config("tiny", hf, dtype="float32")


def test_prompt_logprobs_request_is_refused(make_engine):
    """They ride the legacy solo-prefill program, which carries no
    state: refused with a sentence, not served wrong."""
    params = dataclasses.replace(greedy(4), prompt_logprobs=1)
    with pytest.raises(ValueError, match="prompt logprobs are not served"):
        make_engine().add_request("lp", None, params,
                                  prompt_token_ids=tokens(9, 2))


def test_host_tier_turns_itself_off(make_engine):
    """As for rolling-window eviction: a warning, no tier, and with it no
    decode resume."""
    engine = make_engine(kv_host_cache_gb=0.01)
    assert engine.kv_tier is None


def test_lora_on_the_mixer_is_refused(tmp_path):
    """An adapter that targets the mixer's projections is refused at
    load: the model applies deltas to attention and MLP alone."""
    import json

    from vllm_tgis_adapter_tpu.engine.lora import LoRAError, load_peft_adapter

    (tmp_path / "adapter_config.json").write_text(json.dumps({
        "peft_type": "LORA", "r": 4, "lora_alpha": 8,
        "target_modules": ["q_proj", "in_proj", "out_proj"],
    }))
    with pytest.raises(LoRAError, match="in_proj"):
        load_peft_adapter(str(tmp_path))


def test_state_model_refuses_a_mesh(mcfg, params):
    """tp > 1 is refused at boot; a mesh handed to the runner directly
    (dp slices, tests) is refused there."""
    from jax.sharding import Mesh

    from vllm_tgis_adapter_tpu.engine.runner import ModelRunner
    from vllm_tgis_adapter_tpu.models import get_model_class

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="has shard rules yet"):
        ModelRunner(
            engine_config(mcfg, cache_config=CacheConfig(num_blocks=8)),
            get_model_class("falcon_h1")(mcfg), dict(params), mesh=mesh)
