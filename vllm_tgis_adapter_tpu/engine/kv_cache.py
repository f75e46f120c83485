"""Paged KV-cache block management (host-side bookkeeping).

TPU-native counterpart of vLLM's block manager: the device holds one flat
slot-indexed cache per K/V (see ops/attention.py for the layout); this
module owns which pages belong to which sequence.  Allocation is on-demand
per decode step; when the pool runs dry the scheduler preempts the
youngest sequence and re-prefills it later (engine/scheduler.py).

Device memory sizing happens at engine boot: the page count is derived
from the HBM budget left after weights (engine/core.py).
"""

from __future__ import annotations

import time
from collections import deque
from typing import TYPE_CHECKING, Optional

from vllm_tgis_adapter_tpu.logging import init_logger

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.config import EngineConfig

logger = init_logger(__name__)

# Static pool size used when the backend exposes no memory stats (CPU/test
# backends): enough pages that the CI-sized models never preempt, small
# enough not to blow up host RAM in the 8-virtual-device suite.
_FALLBACK_BLOCKS = 2048


def _lora_stack_bytes(config: "EngineConfig") -> int:
    """Device bytes of the padded LoRA stacks (engine/lora.py
    ``build_lora_stacks``): f32 ``[L, S, d_in, r]`` + ``[L, S, r, d_out]``
    per target, S = max_loras + 1."""
    if not config.lora_config.enabled:
        return 0
    from vllm_tgis_adapter_tpu.engine.lora import LORA_TARGETS, _target_dims

    m = config.model_config
    s = config.lora_config.max_loras + 1
    r = config.lora_config.max_lora_rank
    elems = 0
    for target in LORA_TARGETS:
        din, dout = _target_dims(m, target)
        elems += m.num_layers * s * (din * r + r * dout)
    return elems * 4


def state_slab_bytes(config: "EngineConfig") -> int:
    """Device bytes of the recurrent-state slab (docs/STATE.md): one slot
    a batch row, ``max_num_seqs`` of them, each the float32 scan state
    and the convolution's tail of every layer
    (``MixerConfig.state_bytes_per_row``; models/falcon_h1.py
    ``make_state`` allocates exactly this).  0 for a model whose only
    per-sequence state is KV pages."""
    import jax.numpy as jnp

    m = config.model_config
    if not m.has_recurrent_state:
        return 0
    return config.scheduler_config.max_num_seqs * m.mixer.state_bytes_per_row(
        m.num_layers, jnp.dtype(m.dtype).itemsize
    )


def per_block_bytes(config: "EngineConfig") -> int:
    """Per-device bytes ONE page costs (both caches, target + draft).

    Quantization-aware (docs/QUANTIZATION.md): with ``--kv-quantization``
    the K/V payload shrinks to the storage dtype's itemsize (1 byte for
    int8/fp8) and the per-page-per-head f32 scale sidecar
    (ops/kv_quant.py) is added — ~2x pages per HBM budget at the usual
    ``block_size * head_dim`` tile sizes (held at >= 1.9x by
    tests/test_kv_sizing.py).
    """
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import kv_quant

    ccfg = config.cache_config
    tp = config.parallel_config.tensor_parallel_size or 1
    qdtype = kv_quant.storage_dtype(ccfg.kv_quantization)
    itemsize = jnp.dtype(
        ccfg.cache_dtype if qdtype is None else qdtype
    ).itemsize

    def one_model(m) -> int:  # noqa: ANN001
        if m.has_latent_cache:
            # one latent row a token a layer, not per-head keys and
            # values (models/deepseek_v2.py make_kv_caches;
            # docs/LATENT_ATTENTION.md)
            return (m.num_layers * ccfg.block_size * m.mla.row_width
                    * itemsize)
        kv_heads_per_dev = max(1, m.num_kv_heads // tp)
        # with window and full layers in two groups, the FULL group's
        # page: its layers alone (the other's is window_block_bytes)
        layers = len(m.full_layers) if m.has_window_group else m.num_layers
        payload = (
            2 * layers * ccfg.block_size
            * kv_heads_per_dev * kv_quant.cache_head_dim(m.head_dim)
            * itemsize
        )
        if qdtype is not None:
            payload += kv_quant.scale_bytes_per_page(
                m.num_layers, kv_heads_per_dev
            )
        return payload

    block_bytes = one_model(config.model_config)
    if config.speculative is not None:
        # the draft model keeps a parallel paged cache with the same slot
        # geometry (engine/speculative.py) — its pages share the budget
        block_bytes += one_model(config.speculative.draft_model_config)
    return block_bytes


def window_block_bytes(config: "EngineConfig") -> int:
    """Per-device bytes ONE page of the window group costs: keys and
    values of the window layers alone.  0 for a model with one group."""
    import jax.numpy as jnp

    from vllm_tgis_adapter_tpu.ops import kv_quant

    m, ccfg = config.model_config, config.cache_config
    if not m.has_window_group:
        return 0
    return (2 * len(m.window_layers) * ccfg.block_size * m.num_kv_heads
            * kv_quant.cache_head_dim(m.head_dim)
            * jnp.dtype(ccfg.cache_dtype).itemsize)


def window_band_blocks(config: "EngineConfig") -> int:
    """Pages of the window group a sequence at ``max_model_len`` holds
    at most: its band, the prompt tokens one step may add to it
    (``max_num_batched_tokens``: they are written before the pages
    behind them are freed), and a run of pages and one more for the
    band's ragged ends — or the whole sequence where that is shorter."""
    from vllm_tgis_adapter_tpu.ops.ragged_attention import pages_per_item

    m, bs = config.model_config, config.cache_config.block_size
    band = (
        -(-(m.group_window
            + config.scheduler_config.max_num_batched_tokens) // bs)
        + pages_per_item(bs) + 1
    )
    return min(band, -(-m.max_model_len // bs))


def window_blocks_for(config: "EngineConfig", num_blocks: int) -> int:
    """The window group's pages beside ``num_blocks`` of the full
    group.  The rule: both groups hold the SAME number of sequences at
    ``max_model_len``, of which the full group keeps every page
    (``max_model_len / block_size``) and the window group its band
    (``window_band_blocks``); so the window group gets ``band /
    (max_model_len / block_size)`` pages for each page of the full
    group, and never less than one band."""
    full_seq = -(-config.model_config.max_model_len
                 // config.cache_config.block_size)
    band = window_band_blocks(config)
    return max(band, -(-num_blocks * band // full_seq))


def device_memory_stats(device) -> dict:
    """``device.memory_stats()``, or ``{}`` on backends that keep none
    (the CPU backend returns None)."""
    try:
        return device.memory_stats() or {}
    except Exception:  # pragma: no cover - backend-dependent API
        return {}


def resolve_num_blocks(
    config: "EngineConfig", device=None
) -> int:
    """Size the KV page pool from the device's free-HBM budget.

    The reference stack sizes its pool from ``gpu_memory_utilization``
    (vLLM behavior the adapter inherits via its engine args); the TPU
    analog measures per-device free HBM AFTER the weights are resident
    (PJRT ``memory_stats``), applies ``hbm_memory_utilization`` to the
    device's total, and divides by the per-device bytes of one page
    (:func:`per_block_bytes` — quantization-aware, scale sidecar
    included).

    Under TP the cache is head-sharded, so each device holds
    ``num_kv_heads / tp`` heads of every page — the per-device page cost
    shrinks with the mesh and the pool grows accordingly.

    Backends without memory stats (CPU tests) fall back to a static
    pool; on a TPU backend that is an error.
    """
    import jax

    mcfg = config.model_config
    ccfg = config.cache_config

    block_bytes = per_block_bytes(config)
    blocks_per_seq = -(-mcfg.max_model_len // ccfg.block_size)
    if mcfg.has_window_group:
        # a page of the full group comes with its share of the window
        # group's pages (window_blocks_for): priced together, so the
        # budget is split by that one rule
        block_bytes += -(-window_block_bytes(config)
                         * window_band_blocks(config) // blocks_per_seq)
    # beyond full occupancy (every batch row at max_model_len) extra pages
    # can never be touched
    full_occupancy = config.scheduler_config.max_num_seqs * blocks_per_seq

    if device is None:
        device = jax.local_devices()[0]
    stats = device_memory_stats(device)
    limit = stats.get("bytes_limit")
    in_use = stats.get("bytes_in_use", 0)
    if not limit:
        if getattr(device, "platform", None) == "tpu":
            # a chip that reports no HBM limit is a broken runtime, not
            # a reason to serve from a pool sized for CPU tests
            raise RuntimeError(
                f"{device} reports no memory_stats() bytes_limit: "
                "refusing to serve from the static KV pool on a TPU "
                "backend"
            )
        num_blocks = min(full_occupancy, _FALLBACK_BLOCKS)
        logger.info(
            "backend exposes no memory stats; static KV pool of %d pages "
            "(%d tokens)", num_blocks, num_blocks * ccfg.block_size,
        )
        return num_blocks

    budget = int(limit * config.hbm_memory_utilization) - int(in_use)
    lora_bytes = _lora_stack_bytes(config)
    if lora_bytes:
        # the runner materialises the stacked adapter tensors on the first
        # hot-load (runner.sync_lora), AFTER the pool is sized — reserve
        # their footprint now or the first load OOMs
        budget -= lora_bytes
        logger.info(
            "reserving %.2f GB for LoRA adapter stacks", lora_bytes / 1e9
        )
    state_bytes = state_slab_bytes(config)
    if state_bytes:
        # the runner allocates the state slab AFTER the pool is sized
        # (engine/runner.py): price it before the pages are counted
        budget -= state_bytes
        logger.info(
            "reserving %.2f GB for the recurrent-state slab (%d slots)",
            state_bytes / 1e9, config.scheduler_config.max_num_seqs,
        )
    num_blocks = budget // block_bytes
    if num_blocks < blocks_per_seq:
        raise RuntimeError(
            f"KV cache budget too small: {budget / 1e9:.2f} GB free under "
            f"hbm_memory_utilization={config.hbm_memory_utilization} fits "
            f"{max(num_blocks, 0)} pages but one max-length sequence needs "
            f"{blocks_per_seq}; lower --max-model-len or raise "
            f"--hbm-memory-utilization"
        )
    num_blocks = min(num_blocks, full_occupancy)
    logger.info(
        "KV pool: %d pages x %d tokens (%.2f GB/device of %.2f GB HBM, "
        "%.2f GB in use after weights)",
        num_blocks, ccfg.block_size, num_blocks * block_bytes / 1e9,
        limit / 1e9, in_use / 1e9,
    )
    return num_blocks


def chain_digests(
    token_ids: list[int],
    block_size: int,
    lora_name: Optional[str] = None,
    max_pages: Optional[int] = None,
) -> list[bytes]:
    """The token-chain digests ``match_prefix`` walks, one per full page
    — shared with the host KV tier (engine/kv_tier.py) so the device
    cache and the host store can never disagree about what a key means.
    ``max_pages`` defaults to every FULL page; promotion callers pass
    ``(len - 1) // block_size`` to honor match_prefix's one-token-short
    cap."""
    if max_pages is None:
        max_pages = len(token_ids) // block_size
    h = BlockAllocator._chain_seed(lora_name)
    out: list[bytes] = []
    for p in range(max_pages):
        h = BlockAllocator._chain_step(
            h, tuple(token_ids[p * block_size: (p + 1) * block_size])
        )
        out.append(h)
    return out


class BlockAllocator:
    """Refcounted allocator over a fixed pool of KV pages, with optional
    content-addressed prefix caching.

    Prefix caching (the engine's analog of vLLM's automatic prefix
    caching): a page whose tokens are a full page-aligned slice of a
    prompt is registered under the rolling hash of the prompt up to and
    including that page.  A later prompt that shares the prefix adopts
    those pages read-only (refcount++) and starts prefill AFTER them —
    the chunked-prefill path (models/llama.py prefill_chunk) already
    attends through the paged cache from any start position, so reuse
    needs no new device code.  Freed-but-registered pages park in an LRU
    side pool and are reclaimed only when the free list runs dry.

    Safety: registered pages are never written again — prefill writes
    start at the first unmatched token, decode writes start after the
    prompt — and sharing keys include the LoRA adapter (same tokens under
    different adapters produce different K/V).
    """

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool = False):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self._free: list[int] = list(range(num_blocks - 1, -1, -1))
        self._refcount: dict[int, int] = {}
        # unified paged arena (engine/arena.py): when set, a shortfall
        # consults it so cold unpinned adapters can fund KV demand
        # before the scheduler resorts to preemption — and vice versa
        self.arena = None
        # content-addressing state (empty unless prefix caching is on).
        # Chain keys are sha256 digests over the full token chain (seed ‖
        # page₀ ‖ … ‖ pageₚ): prompts are attacker-controlled, so the
        # chain must be collision-resistant — Python's hash() is not.
        self._hash_to_block: dict[bytes, int] = {}
        self._block_hash: dict[int, bytes] = {}
        self._cached_free: dict[int, None] = {}  # LRU order: oldest first
        # park timestamp per cached-free page — the arena's unified LRU
        # compares these against adapter last-touch times to decide
        # which cold resident funds a shortfall
        self._cached_at: dict[int, float] = {}
        self.prefix_hits = 0  # tokens served from cache (stats/metrics)
        # cumulative prompt tokens of fresh admissions that consulted the
        # prefix cache — the denominator of kv_prefix_hit_rate{tier}
        # (prefix_hits / lookup tokens); fed by the scheduler at
        # admission and by the host-tier promotion apply (engine/core.py)
        self.prefix_lookup_tokens = 0
        # eviction → demotion hook (engine/kv_tier.py, set by the engine
        # core when the host tier is on): called with (chain_digest,
        # block) just BEFORE a registered page is reclaimed and its hash
        # dropped — the one moment device content is about to vanish.
        # The hook runs under the engine lock (allocate() is only called
        # from planning/admission), so it may enqueue device gathers.
        self.evict_hook = None
        # free epochs (chained-decode quarantine, engine/async_llm.py):
        # while a chained wave is in flight its predecessor's stale K/V
        # writes may still land on pages freed by finished/aborted rows,
        # so those frees buffer in the newest epoch and only release when
        # the wave that could touch them has retired
        self._free_epochs: deque[list[list[int]]] = deque()

    @property
    def num_free(self) -> int:
        return len(self._free) + len(self._cached_free)

    def can_allocate(self, n: int) -> bool:
        if n > self.num_free and self.arena is not None:
            # unified arena: cold unpinned adapters may fund the
            # shortfall before the caller concludes "preempt/refuse"
            self.arena.fund_kv(n)
        return self.num_free >= n

    def oldest_cached_ts(self):
        """Park time of the coldest cached-free page (None when none) —
        the KV side's entry in the arena's unified LRU comparison."""
        for block in self._cached_free:
            return self._cached_at.get(block, 0.0)
        return None

    def allocate(self, n: int) -> list[int]:
        if n > self.num_free and self.arena is not None:
            self.arena.fund_kv(n)
        if n > self.num_free:
            raise RuntimeError(
                f"KV cache exhausted: need {n} pages, {self.num_free} free"
            )
        taken: list[int] = []
        while len(taken) < n and self._free:
            taken.append(self._free.pop())
        while len(taken) < n:
            # reclaim the least-recently-parked cached page
            block = next(iter(self._cached_free))
            del self._cached_free[block]
            self._cached_at.pop(block, None)
            if self.evict_hook is not None:
                h = self._block_hash.get(block)
                if h is not None:
                    # demote instead of vanishing: the host tier copies
                    # the page before its content is overwritten
                    self.evict_hook(h, block)
            self._drop_hash(block)
            taken.append(block)
        for block in taken:
            self._refcount[block] = 1
        return taken

    def free(self, blocks: list[int]) -> None:
        if self._free_epochs:
            # quarantined: released at flush_free_epoch once the in-flight
            # chained wave (the last program that may write them) retires
            self._free_epochs[-1].append(list(blocks))
            return
        self._free_now(blocks)

    def _free_now(self, blocks: list[int]) -> None:
        for block in reversed(blocks):
            left = self._refcount.get(block, 1) - 1
            if left > 0:
                self._refcount[block] = left
                continue
            self._refcount.pop(block, None)
            if block in self._block_hash:
                # keep registered content resident until pages are needed
                self._cached_free.pop(block, None)
                self._cached_free[block] = None  # move to MRU end
                self._cached_at[block] = time.monotonic()
            else:
                self._free.append(block)

    def free_reserved(self, blocks: list[int]) -> None:
        """Release pages the arena reserved for adapter charges,
        BYPASSING any open free epoch: reserved pages were never
        addressable by KV programs, so the chained-decode stale-write
        quarantine cannot apply to them — and quarantining them would
        make an adapter eviction unable to fund the very KV demand
        that triggered it."""
        self._free_now(blocks)

    # ------------------------------------------------- chained-free epochs

    def begin_free_epoch(self) -> None:
        """Open a quarantine epoch: subsequent free() calls buffer until
        the matching flush.  Epochs nest as a FIFO — one per in-flight
        chained decode wave."""
        self._free_epochs.append([])

    def flush_free_epoch(self) -> None:
        """Release the OLDEST epoch's buffered frees (its potential stale
        writers have retired)."""
        if not self._free_epochs:
            return
        for blocks in self._free_epochs.popleft():
            self._free_now(blocks)

    def flush_all_free_epochs(self) -> None:
        """Chain ended with no wave in flight: release everything."""
        while self._free_epochs:
            self.flush_free_epoch()

    def blocks_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)

    # ------------------------------------------------------- prefix caching

    @staticmethod
    def _chain_seed(lora_name: Optional[str]) -> bytes:
        import hashlib

        return hashlib.sha256(
            b"kv-prefix\x00" + (lora_name or "").encode()
        ).digest()

    @staticmethod
    def _chain_step(parent: bytes, page: tuple) -> bytes:
        import hashlib

        h = hashlib.sha256(parent)
        h.update(repr(page).encode())
        return h.digest()

    def _drop_hash(self, block: int) -> None:
        h = self._block_hash.pop(block, None)
        if h is not None and self._hash_to_block.get(h) == block:
            del self._hash_to_block[h]

    def match_prefix(
        self, token_ids: list[int], lora_name: Optional[str] = None
    ) -> tuple[list[int], int]:
        """Adopt the longest chain of cached pages covering the prompt.

        Returns (blocks, matched_tokens).  Matching is capped one token
        short of the prompt so at least the final position always runs
        through prefill (its logits seed the first sampled token).
        Adopted pages are refcounted and must be released via free().
        """
        if not self.enable_prefix_caching:
            return [], 0
        max_pages = (len(token_ids) - 1) // self.block_size
        h = self._chain_seed(lora_name)
        blocks: list[int] = []
        for p in range(max_pages):
            page = tuple(
                token_ids[p * self.block_size: (p + 1) * self.block_size]
            )
            h = self._chain_step(h, page)
            block = self._hash_to_block.get(h)
            if block is None:
                break
            self._refcount[block] = self._refcount.get(block, 0) + 1
            self._cached_free.pop(block, None)  # now live again
            self._cached_at.pop(block, None)
            blocks.append(block)
        return blocks, len(blocks) * self.block_size

    def peek_prefix(
        self, token_ids: list[int], lora_name: Optional[str] = None
    ) -> int:
        """Length (in tokens) of the cached prefix ``match_prefix`` would
        adopt — WITHOUT adopting it.  Pure hash-walk: no refcounts, no
        ``_cached_free`` LRU reordering, safe inside an open free epoch.
        The probe the chained-decode admissibility check uses
        (scheduler._waiting_head_admissible): a blocked head probed every
        chained wave must not promote its prefix pages to MRU or pin
        refcounts it cannot release symmetrically."""
        if not self.enable_prefix_caching:
            return 0
        max_pages = (len(token_ids) - 1) // self.block_size
        h = self._chain_seed(lora_name)
        matched = 0
        for p in range(max_pages):
            page = tuple(
                token_ids[p * self.block_size: (p + 1) * self.block_size]
            )
            h = self._chain_step(h, page)
            if h not in self._hash_to_block:
                break
            matched += 1
        return matched * self.block_size

    def register_prefix(
        self,
        token_ids: list[int],
        blocks: list[int],
        lora_name: Optional[str] = None,
    ) -> None:
        """Publish a prompt's full pages for reuse (first writer wins)."""
        if not self.enable_prefix_caching:
            return
        h = self._chain_seed(lora_name)
        for p in range(len(token_ids) // self.block_size):
            page = tuple(
                token_ids[p * self.block_size: (p + 1) * self.block_size]
            )
            h = self._chain_step(h, page)
            if h not in self._hash_to_block:
                block = blocks[p]
                if block not in self._block_hash:
                    self._hash_to_block[h] = block
                    self._block_hash[block] = h


class SequenceBlocks:
    """Per-sequence page list + slot computation.

    A model whose window layers and full layers keep their pages in two
    groups (``ModelConfig.has_window_group``) gives a sequence a table
    in each: this object's own ``blocks`` is the full group's, and
    ``window`` is a second ``SequenceBlocks`` over the window group's
    allocator, position-aligned with it.  ``evict_below`` then frees
    the window group's pages behind the band and leaves the full
    group's alone.  A model with one kind of layer has ``window`` None
    and one table, as ever."""

    def __init__(self, allocator: BlockAllocator,
                 window_allocator: Optional[BlockAllocator] = None):
        self._allocator = allocator
        self.blocks: list[int] = []
        self.num_tokens = 0
        self._evicted_upto = 0  # rolling-window cursor (evict_below)
        self.window: Optional[SequenceBlocks] = (
            None if window_allocator is None
            else SequenceBlocks(window_allocator)
        )

    def adopt(self, blocks: list[int]) -> None:
        """Prepend already-refcounted pages (prefix-cache hits)."""
        self.blocks.extend(blocks)

    def ensure_capacity(self, num_tokens: int, *,
                        window_group: bool = True) -> None:
        """Grow the page list to hold ``num_tokens`` total tokens, in
        both groups where there are two.  ``window_group=False`` grows
        the full group alone: a prompt is admitted with every page of
        its full group, and its window group grows chunk by chunk
        (``window.ensure_capacity``) as the band moves over it."""
        needed = self._allocator.blocks_needed(num_tokens) - len(self.blocks)
        if needed > 0:
            self.blocks.extend(self._allocator.allocate(needed))
        if self.window is not None and window_group:
            self.window.ensure_capacity(num_tokens)

    def slot_for(self, position: int) -> int:
        """Flat cache slot for the token at ``position``."""
        block = self.blocks[position // self._allocator.block_size]
        return block * self._allocator.block_size + (
            position % self._allocator.block_size
        )

    def slots_for_range(self, start: int, end: int) -> list[int]:
        return [self.slot_for(p) for p in range(start, end)]

    def evict_below(self, position: int) -> int:
        """Rolling-window eviction: free every page that lies ENTIRELY
        below ``position`` (window layers never read below the band
        again) — of the window group where the sequence has one, else
        of its one table.  Freed entries become -1 — the list keeps its
        position-aligned indexing, device-side lookups clamp negative
        ids and the band mask discards whatever those pages now hold.
        A cursor makes each call O(pages newly freed), not O(history).
        Returns the number of pages freed."""
        if self.window is not None:
            return self.window.evict_below(position)
        bs = self._allocator.block_size
        last_dead = min(position // bs, len(self.blocks))
        if last_dead <= self._evicted_upto:
            return 0
        dead = self.blocks[self._evicted_upto:last_dead]
        self.blocks[self._evicted_upto:last_dead] = [-1] * len(dead)
        self._evicted_upto = last_dead
        if dead:
            self._allocator.free(dead)
        return len(dead)

    def release(self) -> None:
        live = [b for b in self.blocks if b >= 0]
        if live:
            self._allocator.free(live)
        self.blocks = []
        self.num_tokens = 0
        self._evicted_upto = 0
        if self.window is not None:
            self.window.release()
