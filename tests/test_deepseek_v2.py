"""DeepSeek-V2 on the served path against its plain reference
(models/reference/deepseek_v2.py), at a small size: hidden 64, one dense
and three expert layers, 4 heads (nope 16 / rope 8 / v 16), q rank 24,
latent 32, 16 experts of width 32 in 4 groups (2 groups and 3 experts a
token, weights 4 x score), 2 shared experts, YaRN factor 4 at original
length 64; float32, seeded weights.

The reference is the NON-absorbed form on the checkpoint's own tensors;
the server runs the absorbed form on its loader's pytree over a paged
pool of latent rows.  Two levels, as tests/test_falcon_h1.py: the
model's two step entry points over hand-built streams, on LOGITS; the
engine through its planner and fused waves, on tokens and logprobs.
Most cases run a server that holds ONE group of four experts (experts
4-7) against the reference's same share.

``TOLERANCE`` is the float32 limit on a logit (their std is ~1 here);
the largest difference read over the cases below is 6e-6.  Holding the
latent pool in bfloat16, or scoring the router in bfloat16, fails it
(the two controls at the end of the model's section).
"""

from __future__ import annotations

import dataclasses
import functools
import json

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
from vllm_tgis_adapter_tpu.models.reference import deepseek_v2 as reference

TOLERANCE = 3e-5
BLOCK = 16
MAX_BLOCKS = 16  # pages a sequence may hold in the hand-built streams
ROWS = 8  # sequence descriptors
GROUP = (4, 8)  # the one group of experts most cases hold


@pytest.fixture(scope="module")
def whole_dir(tmp_path_factory) -> str:
    from tests.fixture_models import build_tiny_deepseek_v2

    return build_tiny_deepseek_v2(str(tmp_path_factory.mktemp("tiny-ds")))


@pytest.fixture(scope="module")
def share_dir(tmp_path_factory) -> str:
    """The same checkpoint, of whose experts only group 1 is there."""
    from tests.fixture_models import build_tiny_deepseek_v2

    return build_tiny_deepseek_v2(
        str(tmp_path_factory.mktemp("tiny-ds-share")), held=GROUP)


@pytest.fixture(scope="module")
def hf(whole_dir) -> dict:
    with open(f"{whole_dir}/config.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tensors(whole_dir) -> dict:
    from vllm_tgis_adapter_tpu.engine.weights import load_checkpoint_tensors

    return load_checkpoint_tensors(whole_dir)


def served(model_dir):
    from vllm_tgis_adapter_tpu.engine.weights import load_model_params

    mcfg = ModelConfig.from_pretrained(model_dir, dtype="float32")
    return mcfg, load_model_params(mcfg, model_dir)


@pytest.fixture(scope="module")
def whole(whole_dir):
    return served(whole_dir)


@pytest.fixture(scope="module")
def share(share_dir):
    return served(share_dir)


@pytest.fixture(scope="module")
def ref_logits(hf, tensors):
    """The reference's logits of a whole sequence for a held share,
    computed once each."""
    seen: dict = {}

    def logits(ids, held=GROUP):
        key = tuple(int(i) for i in ids)
        for (have, share), value in seen.items():  # causal: a prefix's
            if share == held and have[:len(key)] == key:
                return value[:len(key)]
        seen[key, held] = np.asarray(
            reference.forward(hf, tensors, key, held=held))
        return seen[key, held]

    return logits


def tokens(n: int, seed: int) -> list[int]:
    return [int(t) for t in
            np.random.default_rng(seed).integers(3, 500, size=n)]


BOOK = tokens(120, seed=7)


@functools.cache
def programs(mcfg):
    """The model and its two step entry points, jitted once a
    configuration."""
    from vllm_tgis_adapter_tpu.models import get_model_class

    model = get_model_class(mcfg.model_type)(mcfg)
    ragged = jax.jit(functools.partial(model.ragged_forward,
                                       block_size=BLOCK))
    decode = jax.jit(lambda params, caches, *args: model.decode(
        params, caches, *args, BLOCK))
    return model, ragged, decode


class Driver:
    """The model's step entry points over hand-built streams: sequence
    ``slot`` owns pages ``slot * MAX_BLOCKS ...`` of the pool;
    ``round_pool`` is applied to the pool after every step (the
    lower-precision control)."""

    def __init__(self, mcfg, params, round_pool=None):
        self.model, self._ragged, self._decode = programs(mcfg)
        self.mcfg, self.params = mcfg, params
        self.caches = self.model.make_kv_caches(
            ROWS * MAX_BLOCKS * BLOCK, mcfg.dtype, block_size=BLOCK)
        self.pos = {}  # slot -> tokens consumed
        self.round_pool = round_pool
        self.tables = np.arange(ROWS * MAX_BLOCKS, dtype=np.int32).reshape(
            ROWS, MAX_BLOCKS)

    def _after(self):
        if self.round_pool is not None:
            pool, counts = self.caches
            self.caches = (self.round_pool(pool), counts)

    def stream(self, spans, bucket=None):
        """The operands of one mixed step over ``spans`` = [(slot, next
        token ids)] in stream order."""
        total = sum(len(ids) for _, ids in spans)
        t = bucket or max(16, 1 << (total - 1).bit_length())
        token_ids = np.zeros(t, np.int32)
        positions = np.zeros(t, np.int32)
        slot_mapping = np.full(t, -1, np.int32)
        seq_starts = np.full(ROWS + 1, t, np.int32)
        pos_base = np.zeros(ROWS, np.int32)
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
            tables[i] = self.tables[slot]
            self.pos[slot] = start + n
            off += n
        seq_starts[len(spans)] = off
        return (token_ids, positions, slot_mapping, seq_starts, pos_base,
                np.asarray(off, np.int32), tables,
                np.arange(t, dtype=np.int32))

    def mixed(self, spans, bucket=None, **kwargs):
        """One mixed step; returns one [len, vocab] logits array a span."""
        operands = self.stream(spans, bucket)
        logits, self.caches = self._ragged(
            self.params, self.caches, *map(jnp.asarray, operands), **kwargs)
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
        )
        self._after()
        logits = np.asarray(logits)
        return {slot: logits[slot] for slot in rows}

    @property
    def counts(self) -> list[int]:
        return [int(c) for c in self.caches[1]]


# ----------------------------------------------------------------- the model


@pytest.mark.parametrize("length", [1, 5, 8, 16, 37, 59])
def test_whole_prompt_logits_match_reference(whole, ref_logits, length):
    """Every expert held: the uncut model, absorbed against non-absorbed."""
    ids = BOOK[:length]
    drv = Driver(*whole)
    (got,) = drv.mixed([(0, ids)])
    np.testing.assert_allclose(got, ref_logits(ids, held=None),
                               atol=TOLERANCE, rtol=0)
    # no pair is dropped: 3 experts a token in each of 3 expert layers
    assert drv.counts[0] == 9 * length


def test_the_shares_add_up(hf, tensors):
    """One expert layer's routed sum over each of the four groups,
    with the shared experts counted once, is the uncut layer's."""
    v = jax.random.normal(jax.random.PRNGKey(3), (40, 64), jnp.float32)

    def w(name):
        return jnp.asarray(tensors[f"model.layers.2.mlp.{name}.weight"],
                           jnp.float32)

    with jax.default_matmul_precision("highest"):
        uncut = reference.experts_layer(hf, w, v)
        parts = [reference.experts_layer(hf, w, v, held=(g, g + 4),
                                         shared=False)
                 for g in range(0, 16, 4)]
        shared = reference.experts_layer(hf, w, v, held=(0, 0))
    assert all(float(jnp.max(jnp.abs(p))) > 0.1 for p in parts)
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=2e-5, rtol=0)


@pytest.mark.parametrize("group", [0, 1, 2, 3])
def test_a_served_share_is_the_references_share(whole, hf, tensors, group):
    """The server's expert layer, told it holds one group, gives the
    reference's partial result of that group (shared experts with it)."""
    mcfg, params = whole
    held = (4 * group, 4 * group + 4)
    share_cfg = dataclasses.replace(mcfg, experts=dataclasses.replace(
        mcfg.experts, first_held=held[0], num_held=4))
    model = programs(share_cfg)[0]
    layer = dict(params["layers"][2])
    for key in ("experts_gate", "experts_up", "experts_down"):
        layer[key] = layer[key][held[0]:held[1]]
    v = jax.random.normal(jax.random.PRNGKey(5), (24, 64), jnp.float32)
    valid = jnp.arange(24) < 20  # four padding rows, routed nowhere
    got, counts = model._experts(layer, v, valid)

    def w(name):
        return jnp.asarray(tensors[f"model.layers.2.mlp.{name}.weight"],
                           jnp.float32)

    with jax.default_matmul_precision("highest"):
        want = reference.experts_layer(hf, w, v, held=held)
        chosen, _ = reference.route(hf, w("gate"), v[:20])
    np.testing.assert_allclose(got[:20], want[:20], atol=2e-5, rtol=0)
    local = int(jnp.sum((chosen >= held[0]) & (chosen < held[1])))
    assert int(counts[0]) == local
    assert int(counts[1]) == len(
        {int(e) for e in np.asarray(chosen).ravel()
         if held[0] <= e < held[1]})


def chunked_then_decoded(served_model, ref_logits, chunks, steps, **driver):
    """A prompt fed in ``chunks``, then ``steps`` tokens of decode: the
    largest |logit - reference| over every position."""
    ids = BOOK[:sum(chunks) + steps]
    want = ref_logits(ids)
    drv = Driver(*served_model, **driver)
    worst, at = 0.0, 0
    for n in chunks:
        (got,) = drv.mixed([(3, ids[at:at + n])])
        worst = max(worst, float(np.max(np.abs(got - want[at:at + n]))))
        at += n
    for k in range(steps):
        got = drv.decode({3: ids[at + k]})[3]
        worst = max(worst, float(np.max(np.abs(got - want[at + k]))))
    return worst


@pytest.mark.parametrize("chunks", [
    (59,), (5, 16, 1, 37), (1, 1, 57), (32, 16, 8, 2, 1), (16, 16, 16, 11),
])
def test_chunked_prefill_then_decode_matches_reference(share, ref_logits,
                                                       chunks):
    """One group held: the latent rows a chunk wrote are what the next
    chunk and 40 decode steps attend to."""
    assert chunked_then_decoded(share, ref_logits, chunks, 40) < TOLERANCE


def test_a_bfloat16_latent_pool_fails_the_tolerance(share, ref_logits):
    def rounded(pool):
        return pool.astype(jnp.bfloat16).astype(pool.dtype)

    worst = chunked_then_decoded(share, ref_logits, (5, 16, 1, 37), 40,
                                 round_pool=rounded)
    assert worst > 10 * TOLERANCE


def test_bfloat16_router_scores_fail_the_tolerance(share, ref_logits,
                                                   monkeypatch):
    from vllm_tgis_adapter_tpu.ops import moe

    route = moe.group_limited_route
    monkeypatch.setattr(
        moe, "group_limited_route",
        lambda logits, **kw: route(
            logits.astype(jnp.bfloat16).astype(jnp.float32), **kw))
    programs.cache_clear()
    try:
        worst = chunked_then_decoded(share, ref_logits, (59,), 8)
    finally:
        programs.cache_clear()
    assert worst > 10 * TOLERANCE


@pytest.mark.parametrize("n_seqs", [2, 5])
def test_several_sequences_in_one_mixed_step(share, ref_logits, n_seqs):
    """Prompts of different lengths side by side, then a mixed step in
    which some rows decode one token while another's chunk runs."""
    prompts = [tokens(n, seed=40 + n) for n in (23, 9, 31, 4, 17)[:n_seqs]]
    drv = Driver(*share)
    first = drv.mixed([(s, p[:-2]) for s, p in enumerate(prompts)])
    for got, p in zip(first, prompts):
        np.testing.assert_allclose(got, ref_logits(p)[:len(p) - 2],
                                   atol=TOLERANCE, rtol=0)
    second = drv.mixed([
        (s, p[-2:-1] if s % 2 else p[-2:]) for s, p in enumerate(prompts)])
    for s, (got, p) in enumerate(zip(second, prompts)):
        want = ref_logits(p)
        want = want[-2:-1] if s % 2 else want[-2:]
        np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)


def test_absorbed_attention_is_the_non_absorbed(whole, hf, tensors):
    """One layer's attention branch alone: ``q_nope W_k`` over the
    cached latent and ``o_lat W_v`` against keys and values expanded a
    head from the latent."""
    mcfg, params = whole
    model = programs(mcfg)[0]
    u = jax.random.normal(jax.random.PRNGKey(9), (33, 64), jnp.float32)
    layer = params["layers"][1]
    q_row, row = model._latent_qkv(layer, u, model._rope(jnp.arange(33)))
    mla = mcfg.mla
    scores = jnp.einsum("thd,sd->hts", q_row, row) * mla.softmax_scale
    scores = jnp.where(jnp.tril(jnp.ones((33, 33), bool))[None], scores,
                       -jnp.inf)
    o_lat = jnp.einsum("hts,sc->thc", jax.nn.softmax(scores, -1),
                       row[:, :mla.kv_lora_rank])
    got = model._attention_out(layer, o_lat)
    with jax.default_matmul_precision("highest"):
        want = reference.attention(
            hf, lambda n: jnp.asarray(
                tensors[f"model.layers.1.self_attn.{n}.weight"]), u)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def pallas(monkeypatch):
    """Both attention modules take their Pallas branch (interpret mode
    off the TPU); the program has no option for it."""
    from vllm_tgis_adapter_tpu.ops import attention as attn_ops

    monkeypatch.setattr(attn_ops, "_use_pallas", lambda: True)


def test_mixed_step_through_the_pallas_kernel(share, ref_logits,
                                              monkeypatch):
    """A chunk, then a mixed step of a second chunk beside another
    sequence's prompt and a one-token span, through the kernel over a
    host-built schedule at the model's query block."""
    from vllm_tgis_adapter_tpu.ops.ragged_attention import (
        build_work_schedule,
    )

    pallas(monkeypatch)
    mcfg, params = share
    ids, other, third = BOOK[:45], tokens(30, seed=4), tokens(12, seed=5)
    drv = Driver(mcfg, params)
    drv._ragged = jax.jit(functools.partial(drv.model.ragged_forward,
                                            block_size=BLOCK))

    def step(spans):
        t = 64
        starts = {slot: drv.pos.get(slot, 0) for slot, _ in spans}
        rows, off = [], 0
        for slot, span in spans:
            rows.append((off, len(span), starts[slot]))
            off += len(span)
        tables = np.stack([drv.tables[slot] for slot, _ in spans])
        work, n_items = build_work_schedule(
            rows, tables, block_size=BLOCK,
            block_q=drv.model.ragged_block_q, t_pad=t)
        return drv.mixed(spans, bucket=t, work=jnp.asarray(work),
                         n_items=jnp.asarray(n_items, jnp.int32))

    (got,) = step([(2, ids[:20])])
    np.testing.assert_allclose(got, ref_logits(ids)[:20], atol=TOLERANCE,
                               rtol=0)
    step([(5, third[:11])])
    a, b, c = step([(2, ids[20:45]), (4, other), (5, third[11:])])
    np.testing.assert_allclose(a, ref_logits(ids)[20:45], atol=TOLERANCE,
                               rtol=0)
    np.testing.assert_allclose(b, ref_logits(other), atol=TOLERANCE, rtol=0)
    np.testing.assert_allclose(c, ref_logits(third)[11:], atol=TOLERANCE,
                               rtol=0)


def test_decode_through_the_pallas_kernel(share, ref_logits, monkeypatch):
    """The wave's step with the kernel over its in-trace schedule at a
    query block of one token: rows 1 and 4 live, the others dead items."""
    mcfg, params = share
    ids, other = BOOK[:30], tokens(30, seed=4)
    drv = Driver(mcfg, params)
    drv.mixed([(1, ids[:20]), (4, other[:20])])
    pallas(monkeypatch)
    drv._decode = jax.jit(lambda params, caches, *args: drv.model.decode(
        params, caches, *args, BLOCK))
    for at in range(20, 26):
        got = drv.decode({1: ids[at], 4: other[at]})
        np.testing.assert_allclose(got[1], ref_logits(ids)[at],
                                   atol=TOLERANCE, rtol=0)
        np.testing.assert_allclose(got[4], ref_logits(other)[at],
                                   atol=TOLERANCE, rtol=0)


def test_the_kernel_over_runs_of_pages_is_the_xla_path(monkeypatch):
    """The kernel alone, in interpret mode, against the ``jax.numpy``
    path on contexts of several runs of 128 tokens (the online softmax
    carries over a sequence's items): the wave's in-trace schedule at a
    query block of one token, and a host-built one at four."""
    from vllm_tgis_adapter_tpu.ops import attention as attn_ops
    from vllm_tgis_adapter_tpu.ops import mla_attention as mla
    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    heads, width, values, seqs, max_blocks = 8, 256, 128, 8, 64
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.standard_normal(
        (2, seqs * max_blocks * BLOCK, width)), jnp.float32)
    tables = np.arange(seqs * max_blocks, dtype=np.int32).reshape(
        seqs, max_blocks)

    def both(q, positions, seq_starts, pos_base, total, layer, **schedule):
        args = (q, pool, jnp.asarray(positions), jnp.asarray(seq_starts),
                jnp.asarray(pos_base), jnp.asarray(total, jnp.int32),
                jnp.asarray(tables), BLOCK, 0.1)
        want = mla.mla_ragged_attention(*args, layer=layer,
                                        value_dim=values)
        monkeypatch.setattr(attn_ops, "_use_pallas", lambda: True)
        got = mla.mla_ragged_attention(*args, layer=layer, value_dim=values,
                                       **schedule)
        monkeypatch.undo()
        return got, want

    # the wave: rows at contexts of 3, 1, 6 and 2 runs, four rows dead
    pos_base = np.zeros(seqs, np.int32)
    pos_base[:4] = [299, 16, 699, 128]
    live = np.arange(seqs) < 4
    work, n_items = ra.decode_work_schedule(
        jnp.asarray(pos_base), jnp.asarray(live), jnp.asarray(tables),
        block_size=BLOCK, block_q=mla.DECODE_BLOCK_Q)
    assert int(n_items) == 3 + 1 + 6 + 2 + 4  # and a dead item a dead row
    q = jnp.asarray(rng.standard_normal((seqs, heads, width)), jnp.float32)
    got, want = both(q, pos_base, np.arange(seqs + 1), pos_base, seqs, 1,
                     work=work, n_items=n_items,
                     block_q=mla.DECODE_BLOCK_Q)
    np.testing.assert_allclose(got[:4], want[:4], atol=1e-5, rtol=0)

    # a mixed step: a chunk deep in its sequence, a one-token span, a chunk
    spans = [(0, 40, 260), (40, 1, 16), (41, 23, 677)]
    t = 64
    positions = np.zeros(t, np.int32)
    seq_starts = np.full(seqs + 1, t, np.int32)
    pos_base = np.zeros(seqs, np.int32)
    for i, (start, n, pos0) in enumerate(spans):
        positions[start:start + n] = pos0 + np.arange(n)
        seq_starts[i], pos_base[i] = start, pos0
    seq_starts[len(spans)] = t
    work, n_items = ra.build_work_schedule(
        spans, tables, block_size=BLOCK, block_q=mla.MIXED_BLOCK_Q, t_pad=t)
    q = jnp.asarray(rng.standard_normal((t, heads, width)), jnp.float32)
    got, want = both(q, positions, seq_starts, pos_base, t, 0,
                     work=jnp.asarray(work),
                     n_items=jnp.asarray(n_items, jnp.int32),
                     block_q=mla.MIXED_BLOCK_Q)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_bfloat16_flips_few_expert_sets_and_stays_near(share, hf, tensors,
                                                      monkeypatch, capsys):
    """The server's model in bfloat16 (weights and activations) against
    the float32 reference on one prompt, one group held: how many (token,
    layer) expert sets differ, and the logits' distance."""
    from vllm_tgis_adapter_tpu.models import get_model_class
    from vllm_tgis_adapter_tpu.ops import moe

    mcfg, params = share
    low_cfg = dataclasses.replace(mcfg, dtype=jnp.bfloat16)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    ids = BOOK[:59]
    served_sets = []
    route = moe.group_limited_route

    def recording(logits, **kw):
        experts, weights = route(logits, **kw)
        served_sets.append(np.sort(np.asarray(experts), -1))
        return experts, weights

    monkeypatch.setattr(moe, "group_limited_route", recording)
    drv = Driver(low_cfg, low)
    drv._ragged = functools.partial(  # not jitted: the recorder sees values
        get_model_class("deepseek_v2")(low_cfg).ragged_forward,
        block_size=BLOCK)
    (got,) = drv.mixed([(0, ids)], bucket=59)
    monkeypatch.undo()

    want_sets = []
    ref_route = reference.route

    def ref_recording(hf_, gate, v):
        experts, weights = ref_route(hf_, gate, v)
        want_sets.append(np.sort(np.asarray(experts), -1))
        return experts, weights

    monkeypatch.setattr(reference, "route", ref_recording)
    want = np.asarray(reference.forward(hf, tensors, ids, held=GROUP))
    flipped = sum(int((a[:59] != b).any(-1).sum())
                  for a, b in zip(served_sets, want_sets))
    worst = float(np.max(np.abs(np.asarray(got, np.float32) - want)))
    with capsys.disabled():
        print(f"\nbfloat16 on the CPU: {flipped} of {3 * 59} (token, layer) "
              f"expert sets differ; largest logit difference {worst:.4f}")
    assert len(served_sets) == len(want_sets) == 3
    assert flipped <= 18  # a tenth: rounding decides a few, not most
    assert TOLERANCE < worst < 1.0


@pytest.mark.parametrize("sizes", [
    (0, 24, 0, 0), (5, 0, 19, 0), (3, 4, 5, 6), (0, 0, 0, 0),
])
def test_grouped_matmul_is_a_loop_over_experts(sizes):
    """An expert that gets no token, one that gets all, and none at all;
    rows past the last real pair are the caller's to mask."""
    from vllm_tgis_adapter_tpu.ops.moe import grouped_matmul

    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 16, 8)), jnp.float32)
    got = grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32))
    at = 0
    for e, n in enumerate(sizes):
        np.testing.assert_allclose(got[at:at + n], lhs[at:at + n] @ rhs[e],
                                   atol=1e-5, rtol=0)
        at += n


def test_a_routers_tie_breaks_to_the_lower_index():
    """Equal scores within a group and equal groups: the lower index."""
    from vllm_tgis_adapter_tpu.ops.moe import group_limited_route

    flat = jnp.zeros((2, 16), jnp.float32)
    experts, weights = group_limited_route(
        flat, n_group=4, topk_group=2, top_k=3, scaling=4.0)
    assert np.asarray(experts).tolist() == [[0, 1, 2], [0, 1, 2]]
    np.testing.assert_allclose(weights, 4.0 / 16, rtol=1e-6)
    # group 2 wins on its one large expert; within it experts 9 and 10 tie
    tied = flat.at[0, 8].set(3.0).at[0, 9].set(1.0).at[0, 10].set(1.0)
    experts, _ = group_limited_route(
        tied, n_group=4, topk_group=1, top_k=3, scaling=1.0)
    assert np.asarray(experts)[0].tolist() == [8, 9, 10]


def test_routing_is_the_references(hf, tensors):
    from vllm_tgis_adapter_tpu.ops.moe import group_limited_route

    v = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)
    gate = jnp.asarray(tensors["model.layers.1.mlp.gate.weight"])
    with jax.default_matmul_precision("highest"):
        want_e, want_w = reference.route(hf, gate, v)
        got_e, got_w = group_limited_route(
            v @ gate.T, n_group=4, topk_group=2, top_k=3, scaling=4.0)
    assert np.array_equal(got_e, want_e)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-6)


def test_yarn_tables_are_the_references(whole, hf):
    from vllm_tgis_adapter_tpu.models.llama import rotary_cos_sin

    mcfg, _ = whole
    assert mcfg.rope_inv_freq_divisors is not None
    cos, sin = rotary_cos_sin(jnp.arange(300), 8, mcfg.rope_theta,
                              mcfg.rope_inv_freq_divisors, mcfg.rope_mscale)
    want_cos, want_sin = reference.rope_tables(hf, 300)
    np.testing.assert_allclose(cos, want_cos, atol=2e-5)
    np.testing.assert_allclose(sin, want_sin, atol=2e-5)
    assert mcfg.mla.softmax_scale == pytest.approx(
        reference.softmax_scale(hf))


# ---------------------------------------------------------------- the engine


@pytest.fixture(scope="module")
def make_engine(share):
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    def build(*, num_blocks=64, max_num_seqs=8, **engine_kwargs):
        mcfg = share[0]
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


def assert_is_the_references(out, prompt, ref_logits, atol=3e-5):
    """The request's tokens are the reference's greedy tokens and their
    logprobs the reference's, given everything before them."""
    got = list(out.outputs[0].token_ids)
    want = ref_logits(list(prompt) + got)
    logprobs = np.asarray(jax.nn.log_softmax(want, axis=-1))
    for i, (tok, entry) in enumerate(zip(got, out.outputs[0].logprobs)):
        row = len(prompt) - 1 + i
        assert tok == int(np.argmax(want[row])), (i, tok)
        got_lp = entry[tok]
        got_lp = getattr(got_lp, "logprob", got_lp)
        assert abs(got_lp - logprobs[row, tok]) < atol, (i, got_lp)


def test_engine_prefill_and_fused_waves_match_reference(make_engine,
                                                        ref_logits):
    """Through the planner (a 59-token prompt is cut into 32 + 16 + 8 + 2
    + 1) and five fused waves of 8 steps, with one group held."""
    from vllm_tgis_adapter_tpu import metrics

    before = {
        name: getattr(metrics, name)._value.get()
        for name in ("mla_attn_flops_total", "mla_attn_calls_total",
                     "moe_routed_pairs_total", "moe_local_pairs_total",
                     "moe_expert_bytes_total", "moe_expert_calls_total")}
    prompt = tokens(59, seed=11)
    engine = make_engine()
    out = drain(engine, {0: [("r", prompt, 40)]})["r"]
    assert len(out.outputs[0].token_ids) == 40
    assert_is_the_references(out, prompt, ref_logits)
    grew = {name: getattr(metrics, name)._value.get() - was
            for name, was in before.items()}
    # 98 tokens went through (the last sampled one never does): every
    # (row, cached token) pair under the mask, 4 heads, 4 layers
    pairs = 98 * 99 // 2
    assert grew["mla_attn_flops_total"] == pairs * 4 * 2 * (40 + 32) * 4
    assert grew["moe_routed_pairs_total"] == 98 * 3 * 3
    assert 0 < grew["moe_local_pairs_total"] < grew["moe_routed_pairs_total"]
    assert grew["moe_expert_calls_total"] % 9 == 0
    assert grew["mla_attn_calls_total"] * 9 == (
        grew["moe_expert_calls_total"] * 4)
    assert grew["moe_expert_bytes_total"] > 0


def test_rows_join_and_leave_mid_wave(make_engine, ref_logits):
    """One row leaves after 5 tokens (mid-wave), another joins while two
    are decoding."""
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


def test_preemption_by_recompute_gives_the_same_tokens(make_engine,
                                                       ref_logits):
    """A pool too small for three rows preempts the youngest, whose
    latent rows are recomputed from position 0 over prompt and output."""
    from vllm_tgis_adapter_tpu import metrics

    before = metrics.preemptions_total._value.get()
    prompts = {f"p{i}": tokens(14, seed=50 + i) for i in range(3)}
    done = drain(make_engine(num_blocks=6, max_num_seqs=4), {
        0: [(rid, ids, 24) for rid, ids in prompts.items()]})
    assert metrics.preemptions_total._value.get() > before
    for rid, out in done.items():
        assert len(out.outputs[0].token_ids) == 24
        assert_is_the_references(out, prompts[rid], ref_logits)


def test_latent_pool_is_priced_and_reported(make_engine, share):
    """A page's bytes are the pool's own: layers x block x row lanes x
    itemsize, which is what ``make_kv_caches`` allocates; the device
    report says the row's layout and the held experts."""
    from vllm_tgis_adapter_tpu.engine.device_report import device_report
    from vllm_tgis_adapter_tpu.engine.kv_cache import per_block_bytes

    mcfg = share[0]
    engine = make_engine(num_blocks=12)
    pool = engine.runner.caches[0]
    assert pool.shape == (4, 12 * BLOCK, mcfg.mla.row_width)
    assert per_block_bytes(engine.config) == pool.nbytes // 12
    assert mcfg.mla.row_width == 256 and mcfg.mla.rope_offset == 128
    report = device_report(engine)
    assert report["latent_pool"] == {
        "row": "c_kv[0:32] | k_pe[128:136] | zeros to 256 lanes",
        "row_values": 40, "row_lanes": 256,
        "bytes_per_token": 4 * 256 * 4, "tokens": 12 * BLOCK,
    }
    assert report["held_experts"] == {
        "first": 4, "last": 7, "held": 4, "routed": 16,
        "expert_layers": 3}


def test_which_experts_are_held_is_read_from_the_checkpoint(share, whole,
                                                            share_dir,
                                                            tmp_path):
    from tests.fixture_models import build_tiny_deepseek_v2
    from vllm_tgis_adapter_tpu.engine.weights import (
        deepseek_v2_tensor_names,
    )

    assert share[0].experts.held == GROUP
    assert share[0].experts.num_routed == 16
    assert whole[0].experts.held == (0, 16)
    assert share[1]["layers"][1]["router"].shape == (64, 16)
    assert share[1]["layers"][1]["experts_gate"].shape == (4, 64, 32)
    names = deepseek_v2_tensor_names(share[0])
    assert "model.layers.1.mlp.experts.4.up_proj.weight" in names
    assert "model.layers.1.mlp.experts.3.up_proj.weight" not in names
    # config.json counts 3 experts but the checkpoint holds 4
    bad = build_tiny_deepseek_v2(str(tmp_path / "bad"), held=GROUP)
    with open(f"{bad}/config.json") as f:
        cfg = json.load(f)
    with open(f"{bad}/config.json", "w") as f:
        json.dump(dict(cfg, n_routed_experts=3), f)
    with pytest.raises(ValueError, match="contiguous"):
        ModelConfig.from_pretrained(bad, dtype="float32")


# ------------------------------------------------------------- the refusals

REFUSED = {
    "--kv-quantization": dict(
        cache=dict(kv_quantization="int8")),
    "--enable-prefix-caching": dict(cache=dict(enable_prefix_caching=True)),
    "--swap-space": dict(engine=dict(swap_space_gib=1.0)),
    "--kvnet-listen": dict(engine=dict(kvnet_listen="127.0.0.1:0",
                                       kv_host_cache_gb=1.0)),
    "--enable-lora": dict(lora=dict(enabled=True, max_loras=2)),
    "--tensor-parallel-size": dict(parallel=dict(tensor_parallel_size=2)),
    "--pipeline-parallel-size": dict(
        parallel=dict(pipeline_parallel_size=2)),
    "--sequence-parallel-size": dict(
        parallel=dict(sequence_parallel_size=2)),
    "--dp-replicas": dict(parallel=dict(dp_replicas=2)),
}


def engine_config(mcfg, cache=None, engine=None, lora=None, parallel=None):
    return EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=BLOCK, num_blocks=64,
                                 cache_dtype=mcfg.dtype, **(cache or {})),
        scheduler_config=SchedulerConfig(max_num_seqs=8,
                                         prefill_buckets=(16, 32, 64)),
        parallel_config=ParallelConfig(**(parallel or {})),
        lora_config=LoRAConfig(**(lora or {})),
        **(engine or {}),
    )


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_boot_refuses_what_knows_no_latent_pool(share, what):
    with pytest.raises(ValueError) as err:
        engine_config(share[0], **REFUSED[what])
    assert "latent" in str(err.value) or what in str(err.value)
    assert "deepseek_v2" in str(err.value)


def test_boot_refuses_speculative_decoding(share):
    mcfg = share[0]
    from vllm_tgis_adapter_tpu.engine.config import SpeculativeConfig

    spec = SpeculativeConfig(draft_model="/nowhere",
                             num_speculative_tokens=3,
                             draft_model_config=mcfg)
    with pytest.raises(ValueError, match="--speculative-model"):
        engine_config(mcfg, engine=dict(speculative=spec))


def test_boot_refuses_the_bucketed_backend(share):
    with pytest.raises(ValueError, match="bucketed"):
        engine_config(share[0], engine=dict(attention_backend="bucketed"))


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("scoring_func", "sigmoid"),
    ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
    ("moe_layer_freq", 2), ("q_lora_rank", None),
])
def test_config_refuses_what_the_model_does_not_compute(hf, key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config("x", dict(hf, **{key: value}),
                                   dtype="float32")


def test_prompt_logprobs_request_is_refused(make_engine):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    engine = make_engine()
    with pytest.raises(ValueError, match="latent"):
        engine.add_request(
            "p", None, SamplingParams(max_tokens=2, prompt_logprobs=1),
            prompt_token_ids=tokens(9, seed=1))


def test_host_tier_turns_itself_off(make_engine):
    engine = make_engine(kv_host_cache_gb=1.0)
    assert engine.kv_tier is None


def test_latent_model_refuses_a_mesh(share):
    from jax.sharding import Mesh

    from vllm_tgis_adapter_tpu.engine.runner import ModelRunner
    from vllm_tgis_adapter_tpu.models import get_model_class

    mcfg, params = share
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="latent cache"):
        ModelRunner(engine_config(mcfg),
                    get_model_class(mcfg.model_type)(mcfg), dict(params),
                    mesh=mesh)


def test_legacy_solo_path_is_refused(share):
    model = programs(share[0])[0]
    with pytest.raises(NotImplementedError, match="ragged path only"):
        model.prefill()
    with pytest.raises(NotImplementedError, match="ragged path only"):
        model.prefill_chunk()
