"""Command A+ (``cohere2_moe``) on the served path against its plain
reference (models/reference/cohere2_moe.py), at a small size: hidden 64,
one period of three window layers and one full one, 8 query heads over
2 KV heads of 16, window 8, pages of 4 tokens, 8 sigmoid-routed experts
of width 32 (2 a token) of which most cases hold experts 2-3, 2 shared
experts averaged, a tied head; float32, seeded weights.

The reference is the whole-sequence forward pass under each layer's own
mask on the checkpoint's own tensors; the server runs its loader's
pytree over a cache in two groups (a pool and a block table for the
window layers, another for the full layer), whose window group keeps a
row's pages only as far back as its band.  Prompts are 3-5 windows long,
so pages have been freed, and some handed out again, before the tokens
that are compared.  On LOGITS throughout: the model's two step entry
points over hand-built streams, and the engine through its planner, its
chunked prefill and its fused waves on tokens and logprobs.

``TOLERANCE`` is the float32 limit on a logit (their std is ~8 here:
the tied head multiplies by an embedding of std 1); the largest
difference read over the cases below is 2e-5.
"""

from __future__ import annotations

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
from vllm_tgis_adapter_tpu.models.reference import cohere2_moe as reference
from vllm_tgis_adapter_tpu.ops import ragged_attention as ragged

TOLERANCE = 1e-4
BLOCK = 4
WINDOW = 8
HELD = (2, 4)  # the experts most cases hold


@pytest.fixture(scope="module")
def hf() -> dict:
    from tests.fixture_models import TINY_COHERE2_MOE_CONFIG

    return dict(TINY_COHERE2_MOE_CONFIG)


@pytest.fixture(scope="module")
def tensors(hf) -> dict:
    from tests.fixture_models import cohere2_moe_tensors

    return {k: jnp.asarray(v) for k, v in cohere2_moe_tensors(hf).items()}


def served(model_dir):
    from vllm_tgis_adapter_tpu.engine.weights import load_model_params
    from vllm_tgis_adapter_tpu.models import get_model_class

    mcfg = ModelConfig.from_pretrained(model_dir, dtype="float32")
    model = get_model_class(mcfg.model_type)(mcfg)
    return mcfg, model, load_model_params(mcfg, model_dir)


@pytest.fixture(scope="module")
def share(tmp_path_factory):
    """A server's share: experts 2-3 of the checkpoint's 8."""
    from tests.fixture_models import build_tiny_cohere2_moe

    return served(build_tiny_cohere2_moe(
        str(tmp_path_factory.mktemp("tiny-cmd")), held=HELD))


@pytest.fixture(scope="module")
def ref_logits(hf, tensors):
    """The reference's logits of a sequence, for the held share."""
    cache: dict = {}

    def logits(ids, held=HELD):
        key = (tuple(ids), held)
        if key not in cache:
            cache[key] = np.asarray(
                reference.forward(hf, tensors, list(ids), held=held))
        return cache[key]

    return logits


def tokens(n: int, seed: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, 500, n).tolist()


def schedule_items(spans, *, block_size: int, block_q: int,
                   window: int = 0) -> int:
    """The count of live items a schedule holds for ``spans``, in closed
    form: for each (query block, sequence) pair the pages of
    ``band_pages`` cut into runs."""
    run = ragged.pages_per_item(block_size)
    total = 0
    for start, n_rows, pos0 in spans:
        for qb in range(start // block_q, (start + n_rows - 1) // block_q + 1):
            row_lo = max(start, qb * block_q)
            row_hi = min(start + n_rows - 1, (qb + 1) * block_q - 1)
            first, last = ragged.band_pages(
                pos0 + row_lo - start, pos0 + row_hi - start, block_size,
                window)
            total += -(-(last - first + 1) // run)
    return total


# ------------------------------------------------- the model's entry points


_PROGRAMS: dict = {}


def programs(model):
    """The model's two step entry points, jitted once a model: every
    span is padded to one stream length, as the runner pads to a
    bucket."""
    from vllm_tgis_adapter_tpu.ops import attention as attn_ops

    key = (id(model), attn_ops._use_pallas())  # a trace takes one branch
    if key not in _PROGRAMS:
        _PROGRAMS[key] = (
            jax.jit(model.ragged_forward, static_argnames=("block_size",)),
            jax.jit(model.decode, static_argnums=(7,)),
        )
    return _PROGRAMS[key]


class Driver:
    """Hand-built streams over a cache in two groups: one sequence, its
    full group's pages and its window group's handed out from two free
    lists in an order of their own; the window group's pages behind the
    band are freed before each span, as the scheduler frees them."""

    ROWS = 4
    MAX_BLOCKS = 16
    STREAM = 40  # every span is padded to this many rows

    def __init__(self, served_model, *, pages: int = 24,
                 window_pages: int = 12, poison: bool = False,
                 stream: int = STREAM):
        self.STREAM = stream
        self.mcfg, self.model, self.params = served_model
        self.caches = self.model.make_kv_caches(
            pages * BLOCK, jnp.float32, window_slots=window_pages * BLOCK)
        self.free = list(range(pages - 1, -1, -1))
        self.window_free = list(range(window_pages))
        self.table: list[int] = []
        self.window_table: list[int] = []
        self.poison = poison
        self.freed = 0

    def _grow(self, upto: int, first_row: int) -> None:
        lo = first_row - WINDOW + 1
        for page in range(min(max(lo, 0) // BLOCK, len(self.window_table))):
            if self.window_table[page] >= 0:
                freed = self.window_table[page]
                self.window_table[page] = -1
                self.window_free.insert(0, freed)
                self.freed += 1
                if self.poison:
                    self._fill_with_nan(freed)
        while len(self.table) * BLOCK < upto:
            self.table.append(self.free.pop())
            self.window_table.append(self.window_free.pop())

    def _fill_with_nan(self, page: int) -> None:
        """What a freed page may come to hold: anything."""
        k_full, v_full, k_win, v_win, counts = self.caches
        at = slice(page * BLOCK, (page + 1) * BLOCK)
        self.caches = (k_full, v_full, k_win.at[:, :, at].set(jnp.nan),
                       v_win.at[:, :, at].set(jnp.nan), counts)

    def _tables(self):
        from vllm_tgis_adapter_tpu.engine.runner import _live_table

        full = np.zeros((self.ROWS, self.MAX_BLOCKS), np.int32)
        window = np.zeros_like(full)
        full[0, :len(self.table)] = self.table
        window[0, :len(self.window_table)] = _live_table(self.window_table)
        return full, window

    def _slots(self, table, positions):
        return np.asarray([table[p // BLOCK] * BLOCK + p % BLOCK
                           for p in positions], np.int32)

    def span(self, ids: list[int], start: int):
        """One mixed step holding the span ``ids`` from position
        ``start``; logits of every row.  Where the kernel runs (the
        ``pallas`` fixture), under one schedule a layer kind."""
        from vllm_tgis_adapter_tpu.ops import attention as attn_ops

        t, pad = len(ids), self.STREAM - len(ids)
        self._grow(start + t, start)
        pos = np.arange(start, start + t, dtype=np.int32)
        full, window = self._tables()
        starts = np.full(self.ROWS + 1, self.STREAM, np.int32)
        starts[0], starts[1] = 0, t
        base = np.zeros(self.ROWS, np.int32)
        base[0] = start
        extra = {}
        if attn_ops._use_pallas():
            block_q = min(self.model.ragged_block_q, self.STREAM)
            t_pad = -(-self.STREAM // block_q) * block_q
            spans = [(0, t, start)]
            work, n = ragged.build_work_schedule(
                spans, full, block_size=BLOCK, block_q=block_q, t_pad=t_pad,
                w_bucket=8)
            w_work, w_n = ragged.build_work_schedule(
                spans, window, block_size=BLOCK, block_q=block_q,
                t_pad=t_pad, window=WINDOW, w_bucket=8)
            extra = dict(work=jnp.asarray(work), n_items=jnp.asarray(n),
                         window_work=jnp.asarray(w_work),
                         window_n_items=jnp.asarray(w_n))
            self.items = (n, w_n)

        def padded(x, fill):
            return jnp.asarray(np.pad(x, (0, pad), constant_values=fill))

        logits, self.caches = programs(self.model)[0](
            self.params, self.caches, padded(np.asarray(ids, np.int32), 0),
            padded(pos, 0), padded(self._slots(self.table, pos), -1),
            jnp.asarray(starts), jnp.asarray(base),
            jnp.asarray(t, jnp.int32), jnp.asarray(full),
            jnp.arange(self.STREAM), block_size=BLOCK,
            window_slots=padded(self._slots(self.window_table, pos), -1),
            window_tables=jnp.asarray(window), **extra)
        return np.asarray(logits)[:t]

    def step(self, token: int, position: int):
        """One step of the fused wave for the one row."""
        self._grow(position + 1, position)
        full, window = self._tables()
        pos = np.zeros(self.ROWS, np.int32)
        pos[0] = position
        slots = np.full(self.ROWS, -1, np.int32)
        wslots = slots.copy()
        slots[0] = self._slots(self.table, [position])[0]
        wslots[0] = self._slots(self.window_table, [position])[0]
        ids = np.zeros(self.ROWS, np.int32)
        ids[0] = token
        logits, self.caches = programs(self.model)[1](
            self.params, self.caches, jnp.asarray(ids), jnp.asarray(pos),
            jnp.asarray(slots), jnp.asarray(full), jnp.asarray(pos + 1),
            BLOCK, window_slots=jnp.asarray(wslots),
            window_tables=jnp.asarray(window))
        return np.asarray(logits)[0]


def chunked_then_decoded(served_model, ref_logits, chunks, steps, **driver):
    """Prefill in ``chunks``, then ``steps`` decode steps; the largest
    difference from the reference over every row, and the driver."""
    ids = tokens(sum(chunks) + steps, seed=sum(chunks))
    want = ref_logits(ids)
    drv = Driver(served_model, **driver)
    worst, at = 0.0, 0
    for n in chunks:
        got = drv.span(ids[at:at + n], at)
        worst = max(worst, float(np.max(np.abs(got - want[at:at + n]))))
        at += n
    for _ in range(steps):
        got = drv.step(ids[at], at)
        worst = max(worst, float(np.max(np.abs(got - want[at]))))
        at += 1
    return worst, drv


# (a) prefill, chunked prefill and decode through the two-group cache:
# 3-5 windows long, so the compared rows sit behind freed pages
@pytest.mark.parametrize("chunks,steps", [
    ((40,), 0), ((24,), 12), ((16, 8, 5, 3), 9), ((7, 13, 11), 6),
    ((1, 1, 30), 8),
])
def test_two_group_cache_matches_reference(share, ref_logits, chunks, steps):
    worst, drv = chunked_then_decoded(share, ref_logits, chunks, steps)
    assert worst < TOLERANCE, worst
    # every page wholly behind the last span's band has been freed, and
    # the window group holds its band and that span, not the history
    last = sum(chunks) + steps - 1 if steps else sum(chunks[:-1])
    assert drv.freed == max(last - WINDOW + 1, 0) // BLOCK
    live = sum(p >= 0 for p in drv.window_table)
    assert live == len(drv.window_table) - drv.freed
    assert live <= (WINDOW + (1 if steps else chunks[-1])) // BLOCK + 2


# (b) a freed page holds anything at all afterwards: NaN changes nothing
@pytest.mark.parametrize("chunks,steps", [((16, 8, 5, 3), 9), ((24,), 12)])
def test_a_freed_page_filled_with_nan_changes_nothing(share, ref_logits,
                                                      chunks, steps):
    worst, drv = chunked_then_decoded(share, ref_logits, chunks, steps,
                                      poison=True)
    assert drv.freed > 3
    assert worst < TOLERANCE, worst


def test_a_page_freed_inside_the_band_is_seen(share, ref_logits,
                                              monkeypatch):
    """The control of (b): were eviction one page too eager, the NaN
    would be read."""
    import sys

    monkeypatch.setattr(sys.modules[__name__], "WINDOW", WINDOW - BLOCK)
    worst, _ = chunked_then_decoded(share, ref_logits, (16, 8, 5, 3), 9,
                                    poison=True)
    assert not worst < TOLERANCE


# (c) the window schedule against the full schedule + mask
@pytest.fixture
def pallas(monkeypatch):
    from vllm_tgis_adapter_tpu.ops import attention as attn_ops

    # the model and ops/moe.py ask attn_ops; the ragged kernel's module
    # holds the two names itself
    for module in (attn_ops, ragged):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_pallas_interpret", lambda: True)


@pytest.mark.parametrize("chunks", [(40,), (16, 24)])
def test_window_layers_through_the_kernel_match_reference(share, ref_logits,
                                                          chunks, pallas):
    """Through the kernel (interpreted), each layer kind under its own
    schedule: the reference's logits, and as many items as the closed
    form."""
    ids = tokens(sum(chunks), seed=3)
    want = ref_logits(ids)
    drv = Driver(share, window_pages=16)
    at = 0
    for n in chunks:
        got = drv.span(ids[at:at + n], at)
        assert float(np.max(np.abs(got - want[at:at + n]))) < TOLERANCE
        block_q = min(drv.model.ragged_block_q, drv.STREAM)
        # the stream's padding has a query block of its own
        dead = -(-drv.STREAM // block_q) - (-(-n // block_q))
        for window, count in zip((0, WINDOW), drv.items):
            assert count - dead == schedule_items(
                [(0, n, at)], block_size=BLOCK, block_q=block_q,
                window=window)
        at += n


def test_the_kernel_over_freed_pages_filled_with_nan(share, ref_logits,
                                                     pallas):
    """(b) through the kernel (interpreted): the window layers' schedule
    never names a freed page, so NaN in one reaches no tile, with the
    chunks' tiles of 256 rows (4 query heads a KV head x 64) keeping
    their statistics on sublanes and the wave's of 16 on lanes."""
    mcfg, model, _ = share
    assert ragged.tile_stats(
        mcfg.q_per_kv, ragged.query_block(model.ragged_block_q, 64)
    ) == "sublanes"
    assert ragged.tile_stats(
        mcfg.q_per_kv, ragged.decode_block_q(Driver.ROWS)) == "lanes"
    worst, drv = chunked_then_decoded(
        share, ref_logits, (16, 8, 5, 3), 9, poison=True, window_pages=16,
        stream=64)
    assert drv.freed > 3
    assert worst < TOLERANCE, worst


def kernel_case(window: int, *, schedule_window: int, pos0: int = 520,
                rows: int = 24):
    """One sequence of ``pos0 + rows`` cached tokens in pages of 16 (a
    run of 8 pages is 128 tokens), its last ``rows`` the queries: the
    kernel's output under ``window``'s mask over the schedule built for
    ``schedule_window``, and the schedule's item count."""
    rng = np.random.default_rng(7)
    bs, heads, kv, dh = 16, 4, 2, 128
    n_pages = -(-(pos0 + rows) // bs)
    table = rng.permutation(n_pages + 3)[:n_pages].astype(np.int32)[None]
    k = jnp.asarray(rng.standard_normal((kv, (n_pages + 3) * bs, dh)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal(k.shape), jnp.float32)
    q = jnp.asarray(rng.standard_normal((rows, heads, dh)), jnp.float32)
    work, n = ragged.build_work_schedule(
        [(0, rows, pos0)], table, block_size=bs, block_q=8, t_pad=rows,
        window=schedule_window)
    out = ragged.ragged_paged_attention(
        q, k, v, jnp.arange(pos0, pos0 + rows), jnp.asarray([0, rows]),
        jnp.asarray([pos0]), jnp.asarray(rows), jnp.asarray(table), bs,
        dh ** -0.5, work=jnp.asarray(work), n_items=jnp.asarray(n),
        window=window, block_q=8)
    return np.asarray(out), n, (q, k, v, table)


def test_window_schedule_is_the_full_schedule_and_mask(pallas):
    """The kernel over the window layers' schedule gives what it gives
    over the causal schedule under the same mask, from fewer items (the
    closed form's count), and both are the XLA path's."""
    window = 200
    banded, n_banded, (q, k, v, table) = kernel_case(
        window, schedule_window=window)
    causal, n_causal, _ = kernel_case(window, schedule_window=0)
    assert float(np.max(np.abs(banded - causal))) < 1e-5
    for w, n in ((window, n_banded), (0, n_causal)):
        assert n == schedule_items(
            [(0, 24, 520)], block_size=16, block_q=8, window=w)
    # 3 query blocks: 5 runs each of the whole past, 2 of the band
    assert n_banded == 6 and n_causal == 15
    xla = ragged.ragged_attention_xla(
        q, k, v, jnp.arange(520, 544), jnp.asarray([0, 24]),
        jnp.asarray(24), jnp.asarray(table), 16, 128 ** -0.5,
        window=window)
    assert float(np.max(np.abs(banded - np.asarray(xla)))) < 1e-5


def test_a_schedule_that_drops_a_run_in_the_band_is_seen(pallas):
    """The control: a schedule built for a band half as wide leaves out
    a run of pages the mask still lets rows read, and the output
    moves."""
    window = 200
    whole, n_whole, _ = kernel_case(window, schedule_window=window)
    short, n_short, _ = kernel_case(window, schedule_window=window // 2)
    assert n_short < n_whole
    assert float(np.max(np.abs(short - whole))) > 1e-2


def test_decode_schedule_follows_the_band():
    """The in-trace schedule of the fused wave: a row's items start at
    its band's first page, and their count is the closed form."""
    tables = jnp.asarray(np.arange(4 * 64).reshape(4, 64), jnp.int32)
    pos = jnp.asarray([5, 200, 131, 17], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    for window in (0, 48, 130):
        work, n = ragged.decode_work_schedule(
            pos, active, tables, block_size=BLOCK, window=window)
        work, n = np.asarray(work), int(n)
        want = sum(
            schedule_items([(0, 1, p)], block_size=BLOCK, block_q=1,
                                  window=window)
            for p, a in zip([5, 200, 131, 17], active) if a)
        assert n == want, (window, n, want)
        for item in work[:, :n].T:
            row, pages, first = item[1], item[2], item[3]
            lo, hi = ragged.band_pages(int(pos[row]), int(pos[row]), BLOCK,
                                       window)
            assert lo <= first and first + pages - 1 <= hi
            # the page ids are the table's, from the run's first page on
            ids = item[ragged.WORK_FIELDS:ragged.WORK_FIELDS + pages]
            assert ids.tolist() == tables[row, first:first + pages].tolist()


def test_decode_through_the_pallas_kernel(share, ref_logits, pallas):
    worst, _ = chunked_then_decoded(share, ref_logits, (21,), 3,
                                    window_pages=16)
    assert worst < TOLERANCE, worst


# (d) the guide's share test
def test_the_shares_add_up(hf, tensors):
    """The routed sums of all 4 shares of two experts each, the shared
    experts' mean and attention counted once, add up to the uncut
    layer's output."""
    ids = jnp.asarray(tokens(19, seed=5))
    with jax.default_matmul_precision("highest"):
        x = tensors["model.embed_tokens.weight"][ids]
        for i in (0, 3):  # a window layer and the full one
            whole = reference.block(hf, tensors, i, x)
            parts = x + sum(
                reference.block(hf, tensors, i, x, (lo, lo + 2),
                                attention_on=False, shared=False) - x
                for lo in range(0, 8, 2))
            parts = parts + (
                reference.block(hf, tensors, i, x, (0, 0)) - x)
            assert float(jnp.max(jnp.abs(whole - parts))) < 1e-5


def test_one_wide_swiglu_over_four_is_the_shared_mean(share, hf, tensors):
    """The server's shared experts are ONE SwiGLU of their widths
    together whose output is divided by their number; the reference runs
    them one by one and averages."""
    mcfg, model, params = share
    u = jnp.asarray(np.random.default_rng(1).standard_normal((9, 64)),
                    jnp.float32)
    layer = params["layers"][1]
    assert layer["w_gate"].shape == (64, 2 * 32)

    def get(name):
        return tensors[f"model.layers.1.mlp.{name}.weight"]

    with jax.default_matmul_precision("highest"):
        want = reference.experts_layer(hf, get, u, held=(0, 0))
        got, _ = model._experts(layer, u, jnp.ones(9, bool))
        routed = reference.experts_layer(hf, get, u, held=HELD, shared=False)
    assert float(jnp.max(jnp.abs(got - routed - want))) < 1e-5


# (e) the router
def test_sigmoid_route_is_a_plain_top_k():
    from vllm_tgis_adapter_tpu.ops.moe import sigmoid_route

    rng = np.random.default_rng(2)
    logits = rng.standard_normal((33, 16)).astype(np.float32) * 3
    logits[4, 3] = logits[4, 9]  # a tie breaks to the lower index
    experts, weights = sigmoid_route(jnp.asarray(logits), top_k=4)
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :4]
    assert np.asarray(experts).tolist() == order.tolist()
    chosen = np.take_along_axis(scores, order, axis=-1)
    want = chosen / chosen.sum(-1, keepdims=True)
    assert float(np.max(np.abs(np.asarray(weights) - want))) < 1e-6
    assert weights.dtype == jnp.float32
    # bfloat16 logits are scored in float32
    _, w16 = sigmoid_route(jnp.asarray(logits, jnp.bfloat16), top_k=4)
    assert w16.dtype == jnp.float32


def test_routing_is_the_references(hf, tensors):
    from vllm_tgis_adapter_tpu.ops.moe import sigmoid_route

    u = jnp.asarray(np.random.default_rng(3).standard_normal((25, 64)),
                    jnp.float32)
    gate = tensors["model.layers.2.mlp.gate.weight"]
    with jax.default_matmul_precision("highest"):
        want_e, want_w = reference.route(hf, gate, u)
        got_e, got_w = sigmoid_route(u @ gate.T, top_k=2)
    assert got_e.tolist() == want_e.tolist()
    assert float(jnp.max(jnp.abs(got_w - want_w))) < 1e-6


# (f) per-layer kinds as data: the llama lineage's two patterns
@pytest.mark.parametrize("window,full_first,want", [
    (0, 0, (0, 0, 0)), (8, 0, (8, 8, 8)), (8, 2, (0, 0, 8)),
])
def test_llama_lineage_patterns_are_two_fillings(window, full_first, want):
    import dataclasses

    mcfg = ModelConfig(
        model="x", model_type="qwen2", vocab_size=8, hidden_size=8,
        intermediate_size=8, num_layers=3, num_heads=2, num_kv_heads=2,
        head_dim=4, max_model_len=64, sliding_window=window,
        max_window_layers=full_first)
    assert tuple(mcfg.window_of_layer(i) for i in range(3)) == want
    assert all(mcfg.layer_rotates(i) for i in range(3))
    assert not mcfg.has_window_group  # one table, as ever
    assert mcfg.group_window == max(want)
    # the fields stay live: a test's dataclasses.replace is seen
    assert dataclasses.replace(mcfg, sliding_window=0).window_layers == ()


def test_config_reads_layer_types(share, hf):
    mcfg = share[0]
    assert mcfg.layer_windows == (8, 8, 8, 0)
    assert mcfg.layer_rotary == (True, True, True, False)
    assert mcfg.has_window_group and mcfg.group_window == 8
    assert mcfg.window_layers == (0, 1, 2) and mcfg.full_layers == (3,)
    assert mcfg.experts.held == HELD and mcfg.experts.num_routed == 8
    assert mcfg.norm_type == "layernorm" and mcfg.tie_word_embeddings
    # without layer_types: layer_switch's pattern
    no_types = {k: v for k, v in hf.items() if k != "layer_types"}
    assert ModelConfig.from_hf_config(
        "x", no_types, dtype="float32").layer_windows == (8, 8, 8, 0)


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("use_qk_norm", True),
    ("use_parallel_block", False), ("first_k_dense_replace", 1),
    ("expert_selection_fn", "softmax"), ("norm_topk_prob", False),
    ("shared_expert_combination_strategy", "sum"),
    ("position_embedding_type", "rope_neox"), ("rotary_pct", 0.5),
    ("tie_word_embeddings", False), ("sliding_window", None),
    ("layer_types", ["sliding_attention", "chunked"] * 2),
])
def test_config_refuses_what_the_model_does_not_compute(hf, key, value):
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config("x", dict(hf, **{key: value}),
                                   dtype="float32")


def test_loader_asks_for_the_held_experts_tensors(share, tmp_path):
    from safetensors import safe_open

    from vllm_tgis_adapter_tpu.engine.weights import (
        cohere2_moe_tensor_names,
    )

    mcfg = share[0]
    with safe_open(f"{mcfg.model}/model.safetensors", framework="np") as f:
        assert sorted(f.keys()) == sorted(cohere2_moe_tensor_names(mcfg))


# ---------------------------------------------------------------- the engine


@pytest.fixture(scope="module")
def make_engine(share):
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

    def build(*, num_blocks=64, num_window_blocks=0, max_num_seqs=4,
              max_num_batched_tokens=16, **engine_kwargs):
        mcfg = share[0]
        config = EngineConfig(
            model_config=mcfg,
            cache_config=CacheConfig(
                block_size=BLOCK, num_blocks=num_blocks,
                num_window_blocks=num_window_blocks,
                cache_dtype=mcfg.dtype),
            scheduler_config=SchedulerConfig(
                max_num_seqs=max_num_seqs,
                max_num_batched_tokens=max_num_batched_tokens,
                prefill_buckets=(16,)),
            parallel_config=ParallelConfig(),
            lora_config=LoRAConfig(),
            **engine_kwargs,
        )
        return LLMEngine.from_config(config)

    return build


@pytest.fixture(scope="module")
def engine(make_engine):
    """One engine for the cases that need no pool of their own: a window
    pool that could not hold two of their rows' histories."""
    return make_engine(num_blocks=96, num_window_blocks=28)


def greedy(n: int):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True,
                          logprobs=1)


def drain(engine, arrivals=None, max_steps=4000, watch=None) -> dict:
    arrivals = dict(arrivals or {})
    done, step = {}, 0
    while engine.has_unfinished_requests() or arrivals:
        for rid, ids, n in arrivals.pop(step, []):
            engine.add_request(rid, None, greedy(n), prompt_token_ids=ids)
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out
        if watch is not None:
            watch(engine)
        step += 1
        assert step < max_steps
    return done


def assert_is_the_references(out, prompt, ref_logits, atol=TOLERANCE):
    got = list(out.outputs[0].token_ids)
    want = ref_logits(list(prompt) + got)
    logprobs = np.asarray(jax.nn.log_softmax(want, axis=-1))
    for i, (tok, entry) in enumerate(zip(got, out.outputs[0].logprobs)):
        row = len(prompt) - 1 + i
        assert tok == int(np.argmax(want[row])), (i, tok)
        got_lp = entry[tok]
        got_lp = getattr(got_lp, "logprob", got_lp)
        assert abs(got_lp - logprobs[row, tok]) < atol, (i, got_lp)


def test_engine_chunked_prefill_and_waves_match_reference(engine,
                                                          ref_logits):
    """Through the planner: a 45-token prompt in chunks of 16 and fused
    waves, the window group rolling behind it; the counters count both
    kinds' pairs and the pages freed."""
    from vllm_tgis_adapter_tpu import metrics

    names = ("window_attn_flops_total", "window_attn_calls_total",
             "full_attn_flops_total", "full_attn_calls_total",
             "kv_window_pages_freed_total", "moe_local_pairs_total")
    before = {n: getattr(metrics, n)._value.get() for n in names}
    prompt = tokens(45, seed=11)
    held = []

    def watch(eng):
        for seq in eng.scheduler.running:
            table = seq.blocks.window.blocks
            held.append(len(table) - table.count(-1))

    out = drain(engine, {0: [("r", prompt, 30)]}, watch=watch)["r"]
    assert len(out.outputs[0].token_ids) == 30
    assert_is_the_references(out, prompt, ref_logits)
    grew = {n: getattr(metrics, n)._value.get() - before[n] for n in names}
    # 74 tokens went through (the last sampled one never does): the
    # causal pairs on one full layer, the banded pairs on three window
    # layers, 8 heads of 16
    rows = 74
    causal = rows * (rows + 1) // 2
    banded = causal - (rows - WINDOW) * (rows - WINDOW + 1) // 2
    assert grew["full_attn_flops_total"] == causal * 8 * 2 * 2 * 16
    assert grew["window_attn_flops_total"] == 3 * banded * 8 * 2 * 2 * 16
    assert grew["window_attn_calls_total"] == (
        3 * grew["full_attn_calls_total"])
    assert grew["kv_window_pages_freed_total"] >= (rows - WINDOW) // BLOCK - 1
    assert grew["moe_local_pairs_total"] > 0
    # a decoding row holds its band, a run's worth of wave steps and a
    # page, not its history
    assert max(held) <= (WINDOW + 16) // BLOCK + 1 < 74 // BLOCK


def test_rows_join_and_leave_with_a_small_window_pool(engine, ref_logits):
    """Four rows of 3-5 windows through a window pool that could not
    hold two of their histories: pages freed behind one row's band are
    handed to another while both run."""
    prompts = {"a": tokens(33, seed=21), "b": tokens(40, seed=22),
               "c": tokens(9, seed=23), "d": tokens(27, seed=24)}
    done = drain(engine, {
        0: [("a", prompts["a"], 21), ("b", prompts["b"], 13)],
        2: [("c", prompts["c"], 30)],
        5: [("d", prompts["d"], 11)],
    })
    assert len(done) == 4
    for rid, out in done.items():
        assert_is_the_references(out, prompts[rid], ref_logits)
    sched = engine.scheduler
    assert sched.window_allocator.num_free == 28
    assert sched.allocator.num_free == 96


def test_running_rows_have_a_schedule_of_their_own_in_a_mixed_step(
        make_engine, ref_logits, pallas):
    """Through the kernel (interpreted), as on the chip: a mixed step's
    running rows lead the stream as one-token spans, the two host-built
    schedules hold the prompt chunks alone, and the program builds the
    rows' own as the wave does.  Rows of 3-5 windows join while others
    decode; every token is the reference's."""
    engine = make_engine(num_blocks=96, num_window_blocks=40)
    prepare, seen = engine.runner.prepare_ragged, []

    def spy(plan):
        prep = prepare(plan)
        spans = [(len(i.token_ids), i.start_pos) for i in plan.items]
        seen.append((spans, prep))
        return prep

    engine.runner.prepare_ragged = spy
    prompts = {"a": tokens(33, seed=31), "b": tokens(40, seed=32),
               "c": tokens(27, seed=33)}
    done = drain(engine, {
        0: [("a", prompts["a"], 14)],
        4: [("b", prompts["b"], 9)],
        6: [("c", prompts["c"], 7)],
    })
    for rid, out in done.items():
        assert_is_the_references(out, prompts[rid], ref_logits)
    mixed = [(spans, prep) for spans, prep in seen if prep.unit_rows]
    assert mixed and max(prep.unit_rows for _, prep in mixed) >= 2
    block_q = engine.runner.model.ragged_block_q
    for spans, prep in mixed:
        units = spans[:prep.unit_rows]
        assert all(n == 1 for n, _ in units)
        # what follows the rows is a chunk
        assert all(n > 1 for n, _ in spans[prep.unit_rows:][:1])
        for window, count, chunk_items in zip(
                (0, WINDOW), prep.unit_items,
                (prep.work_items, prep.window_work_items)):
            assert count == schedule_items(
                [(s, 1, pos) for s, (_, pos) in enumerate(units)],
                block_size=BLOCK, block_q=1, window=window)
            # the chunks' schedule names no running row
            bq = min(block_q, prep.bucket)
            at, chunks = prep.unit_rows, []
            for n, pos in spans[prep.unit_rows:]:
                chunks.append((at, n, pos))
                at += n
            live = schedule_items(chunks, block_size=BLOCK, block_q=bq,
                                  window=window)
            dead = sum(
                1 for qb in range(-(-prep.bucket // bq))
                if not any(a < (qb + 1) * bq and a + n > qb * bq
                           for a, n, _ in chunks))
            assert chunk_items == live + dead


def test_preemption_by_recompute_gives_the_same_tokens(make_engine,
                                                       ref_logits):
    """A full group too small for three rows preempts the youngest,
    whose pages of both groups are released and recomputed."""
    from vllm_tgis_adapter_tpu import metrics

    before = metrics.preemptions_total._value.get()
    prompts = {f"p{i}": tokens(14, seed=50 + i) for i in range(3)}
    done = drain(make_engine(num_blocks=18, num_window_blocks=40), {
        0: [(rid, ids, 24) for rid, ids in prompts.items()]})
    assert metrics.preemptions_total._value.get() > before
    for rid, out in done.items():
        assert len(out.outputs[0].token_ids) == 24
        assert_is_the_references(out, prompts[rid], ref_logits)


def test_the_pools_are_priced_and_split_by_the_rule(make_engine, share):
    from vllm_tgis_adapter_tpu.engine.kv_cache import (
        per_block_bytes,
        window_band_blocks,
        window_block_bytes,
        window_blocks_for,
    )

    mcfg = share[0]
    engine = make_engine(num_blocks=64)
    k_full, _, k_win, _, _ = engine.runner.caches
    assert k_full.shape[:3] == (1, 2, 64 * BLOCK)
    # max_model_len 256 in pages of 4: 64 pages a sequence in the full
    # group; the band: (8 + 16) / 4 + a run of 32 pages + 1
    band = window_band_blocks(engine.config)
    assert band == min((WINDOW + 16) // BLOCK + 32 + 1, 64)
    assert engine.config.cache_config.num_window_blocks == max(
        band, -(-64 * band // 64))
    assert k_win.shape[:3] == (3, 2, band * BLOCK)
    assert per_block_bytes(engine.config) == 2 * k_full.nbytes // 64
    assert window_block_bytes(engine.config) == 2 * k_win.nbytes // band
    assert window_blocks_for(engine.config, 640) == 10 * band


REFUSED = {
    "--kv-quantization": dict(cache=dict(kv_quantization="int8")),
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
def test_boot_refuses_what_knows_one_table(share, what):
    with pytest.raises(ValueError) as err:
        engine_config(share[0], **REFUSED[what])
    assert "two groups" in str(err.value) and what in str(err.value)
    assert "cohere2_moe" in str(err.value)


def test_boot_refuses_speculative_decoding(share):
    from vllm_tgis_adapter_tpu.engine.config import SpeculativeConfig

    mcfg = share[0]
    spec = SpeculativeConfig(draft_model="/nowhere",
                             num_speculative_tokens=3,
                             draft_model_config=mcfg)
    with pytest.raises(ValueError, match="--speculative-model"):
        engine_config(mcfg, engine=dict(speculative=spec))


def test_prompt_logprobs_request_is_refused(make_engine):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    with pytest.raises(ValueError, match="one block table"):
        make_engine().add_request(
            "p", None, SamplingParams(max_tokens=2, prompt_logprobs=1),
            prompt_token_ids=[5, 6, 7])


def test_host_tier_turns_itself_off(make_engine):
    assert make_engine(kv_host_cache_gb=1.0).kv_tier is None


def test_page_size_and_chunk_budget_follow_the_shapes(share, tmp_path):
    """No flag: a model with a window group gets pages of one run of
    the kernel's work item, the llama lineage keeps 16, a page size that
    is named stands; and since a mixed step's running rows have a
    schedule of their own, the chunk budget no longer shrinks with the
    rows (64 rows at 16 k tokens left no room for a chunk)."""
    import dataclasses

    from fixture_models import build_tiny_cohere2_moe, build_tiny_llama
    from vllm_tgis_adapter_tpu.models.cohere2_moe import (
        Cohere2MoeForCausalLM,
    )
    from vllm_tgis_adapter_tpu.tgis_utils.args import make_parser

    mcfg = share[0]
    assert mcfg.default_block_size == ragged.KV_TILE == 128
    parser = make_parser()
    cmd = str(build_tiny_cohere2_moe(tmp_path / "cmd"))
    llama = str(build_tiny_llama(tmp_path / "llama"))
    for argv, want in (
        (["--model", cmd], 128), (["--model", cmd, "--block-size", "32"], 32),
        (["--model", llama], 16),
    ):
        config = EngineConfig.from_args(parser.parse_args(argv))
        assert config.cache_config.block_size == want
    wide = Cohere2MoeForCausalLM(dataclasses.replace(
        mcfg, num_heads=128, num_kv_heads=8, max_model_len=16384))
    assert wide.ragged_block_q == 64
    assert [wide.max_step_prefill_tokens(rows, 128)
            for rows in (8, 32, 64)] == [2048, 2048, 2048]
    assert ragged.max_step_prefill_tokens(16384, 32, 128, 64) == 2048
    with pytest.raises(ValueError, match="would not fit"):
        ragged.max_step_prefill_tokens(16384, 64, 128, 64)


def test_config_file_of_the_share_counts_the_held(share):
    cfg = json.loads(open(f"{share[0].model}/config.json").read())
    assert cfg["num_experts"] == 2
