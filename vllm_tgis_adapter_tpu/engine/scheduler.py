"""Continuous-batching scheduler.

TPU-native counterpart of the scheduling capability the reference adapter
consumes through ``engine.generate`` / ``engine.abort`` (SURVEY.md §2.3).
Design for XLA's compile-once model (SURVEY.md §7 "hard parts"):

* the serving planner is RAGGED (``_schedule_ragged``): every device step
  is one flat mixed token stream — a decode span (or speculative verify
  span) per running row plus prefill chunks sliced to exactly fill one
  power-of-two flat-length bucket; pure-decode steps fuse K steps at ONE
  batch width (max_num_seqs);
* the legacy solo-prefill/fused-decode alternation survives only for
  pp>1 / sp>1 engines and prompt-logprob heads (docs/ATTENTION.md);
* each running sequence owns a fixed batch row (``slot``) so device-side
  per-row state (seen-token matrix, PRNG seeds) never shuffles;
* when the KV page pool runs dry the youngest running sequence is
  preempted (pages freed, re-admitted later via recompute-prefill over
  prompt+generated tokens) — same recovery semantics as the reference
  stack's recompute preemption.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

from vllm_tgis_adapter_tpu import metrics
from vllm_tgis_adapter_tpu.engine.config import CacheConfig, SchedulerConfig
from vllm_tgis_adapter_tpu.engine.kv_cache import BlockAllocator, SequenceBlocks
from vllm_tgis_adapter_tpu.engine.sequence import Sequence, SequenceStatus
from vllm_tgis_adapter_tpu.logging import init_logger
from vllm_tgis_adapter_tpu.supervisor import failpoints

logger = init_logger(__name__)


@dataclasses.dataclass
class PrefillPlan:
    seq: Sequence
    bucket_len: int  # padded chunk length (compile bucket)
    token_ids: list[int]  # tokens of THIS chunk (whole prompt if unchunked)
    slots: list[int]  # flat KV slot per chunk token
    # chunked prefill (token-budgeted admission): tokens already in the KV
    # cache before this chunk, and whether this chunk completes the prompt
    # (only final chunks sample a token and move the sequence to decode)
    start_pos: int = 0
    is_final: bool = True


@dataclasses.dataclass
class RaggedItem:
    """One sequence's contiguous span of a ragged mixed batch."""

    seq: Sequence
    token_ids: list[int]  # tokens entering the flat stream this step
    slots: list[int]  # flat KV slot per token
    start_pos: int  # global position of the span's first token
    is_final: bool  # samples a token this step (decode items always do)
    is_decode: bool  # decode span for a running row (incl. verify spans)
    # speculative verify span (docs/ATTENTION.md "Speculative decoding"):
    # > 0 means this running row's span reserves ``spec_width`` stream
    # rows — its last sampled token plus spec_width-1 draft-token
    # placeholders the runner scatters in AFTER the draft proposes.
    # Acceptance emits up to ``spec_width`` tokens for the row.
    spec_width: int = 0
    # plan-ahead (schedule_ahead): tokens of this row that were still in
    # flight when the span was planned.  ``start_pos`` and ``slots``
    # already count them; ``token_ids[0]`` is a placeholder, the device
    # reads the row's input token from its predecessor's sample
    inflight: int = 0


@dataclasses.dataclass
class RaggedPlan:
    """One unified ragged dispatch: decode rows for every running
    sequence plus as many prefill tokens (whole prompts or chunks,
    sliced to fit) as the flat token bucket holds — the ragged
    backend's replacement for the solo/packed/chunked prefill plans
    and the single-step decode alternation (ops/ragged_attention.py).
    Spans are contiguous in ``items`` order; the only padding is the
    tail of ``token_bucket``."""

    items: list[RaggedItem]  # stream order: decode rows, then prefill
    token_bucket: int  # single flat-length compile bucket
    total_tokens: int  # real tokens across all spans
    # the stall rule (Scheduler.stall_bucket), not the backlog and not
    # the chunk budget, cut this step's prompt share
    capped: bool = False


@dataclasses.dataclass
class DecodePlan:
    seqs: list[Sequence]  # active rows, in slot order
    batch_bucket: int  # padded batch width
    # multi-step decode: the device runs ``num_steps`` fused steps; row i
    # is live for its first ``steps_per_seq[i]`` of them (bounded by its
    # max_tokens remainder and the model-length headroom), masked after
    num_steps: int = 1
    steps_per_seq: list[int] = dataclasses.field(default_factory=list)
    # plan-ahead (schedule_ahead): per row, the tokens still in flight
    # when the wave was planned (see RaggedItem.inflight); empty = none
    inflight: list[int] = dataclasses.field(default_factory=list)


#: The stall rule yields to backlog: a step's prompt budget is never
#: less than one part in this many of the prompt tokens waiting, so an
#: overloaded server plans the full steps it would plan without the rule
BACKLOG_SHARE = 4


class _AheadBail(Exception):
    """Raised inside planning when a projection cannot be right or
    safe; ``schedule_ahead`` turns it into a counted bail."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class Scheduler:
    def __init__(
        self,
        scheduler_config: SchedulerConfig,
        cache_config: CacheConfig,
        num_blocks: int,
        max_model_len: int = 1 << 30,
        num_window_blocks: int = 0,
    ):
        self.config = scheduler_config
        self.block_size = cache_config.block_size
        self.max_model_len = max_model_len
        self.allocator = BlockAllocator(
            num_blocks,
            cache_config.block_size,
            enable_prefix_caching=getattr(
                cache_config, "enable_prefix_caching", False
            ),
        )
        # a model whose window layers and full layers keep their pages
        # in two groups (ModelConfig.has_window_group): ``allocator`` is
        # then the full group's pool, this the window group's, whose
        # pages behind a row's band are freed as the row advances
        # (_roll_window; docs/ATTENTION.md).  None = one group
        self.window_allocator: Optional[BlockAllocator] = (
            BlockAllocator(num_window_blocks, cache_config.block_size)
            if num_window_blocks > 0 else None
        )
        self.waiting: deque[Sequence] = deque()
        self.running: list[Sequence] = []
        # sequences the scheduler itself finished (rejected prompts); the
        # engine core drains this each step to emit their final outputs
        self.newly_finished: list[Sequence] = []
        self._free_slots = list(range(scheduler_config.max_num_seqs - 1, -1, -1))
        # batch rows freed while a planned-ahead dispatch may still touch
        # them (a row is also its slot of the recurrent-state slab and of
        # the seen matrix): one list per open free epoch, FIFO like the
        # allocator's page epochs (begin_free_epoch / flush_free_epoch)
        self._slot_epochs: deque[list[int]] = deque()
        # plan-ahead projection (schedule_ahead): id(seq) -> tokens of the
        # row still in flight.  Empty outside schedule_ahead, so every
        # planner below reads a row's committed state unless told
        # otherwise — the serial order is the projection with nothing
        # in flight
        self._inflight: dict[int, int] = {}
        self._ahead = False
        # why the last schedule_ahead refused (None = it planned, or
        # there was nothing to plan)
        self.ahead_bail: Optional[str] = None
        # fused steps a wave may hold (engine/core.py sets it from the
        # measured host turn and token step while the loop overlaps
        # them); None = the configured ceiling num_decode_steps
        self.wave_depth: Optional[int] = None
        # largest flat-token bucket a mixed step may take while rows are
        # running (engine/core.py sets it from the measured step times,
        # telemetry/steptime.py stall_bucket): every running row waits
        # through the step for its one token.  None = the whole ladder
        self.stall_bucket: Optional[int] = None
        # prefill token budget per device step: prompts longer than this
        # are admitted in chunks, with decode steps interleaved between
        # chunks so long prompts cannot starve running sequences
        self.chunk_budget = min(
            scheduler_config.max_num_batched_tokens,
            max(scheduler_config.prefill_buckets),
        )
        self._last_was_prefill = False
        # ragged data path (the serving default, engine/core.py):
        # schedule() plans token-budgeted RaggedPlans instead of the
        # legacy solo-prefill/fused-decode alternation (which survives
        # only for pp>1 / sp>1 engines and prompt-logprob heads).  The
        # flat-length buckets are a power-of-two ladder — the ONLY
        # compile lattice the mixed path has — sized so the widest
        # bucket holds a full decode batch plus the chunk budget.
        self.ragged = False
        # speculative verify spans (docs/ATTENTION.md): > 0 means every
        # spec-eligible running row plans a (spec_gamma+1)-token verify
        # span instead of a one-token decode span.  Set via
        # set_spec_gamma by the engine when a draft model is attached —
        # it widens the flat-bucket ceiling so a full spec decode batch
        # still fits one dispatch.
        self.spec_gamma = 0
        self._rebuild_ragged_buckets()
        # rolling-window KV eviction (sliding-window models): pages that
        # fall entirely below every layer's attention band free as decode
        # advances, bounding a generation's KV footprint by
        # ~window+block_size instead of its full history.  Set by the
        # engine only when EVERY layer is banded (max_window_layers == 0),
        # prefix caching is off (registered pages must stay intact) and
        # speculation is off (the draft cache shares slot geometry).
        self.rolling_window = 0
        # --swap-space hooks (engine/core.py): swap_out_fn(seq) copies a
        # preemption victim's KV to host (sets seq.swapped, returns bool);
        # swap_drop_fn(seq) releases a held host copy when the sequence
        # falls back to recompute admission.  None = recompute-only.
        self.swap_out_fn = None
        self.swap_drop_fn = None
        # flight recorder (flight_recorder.py), shared with the engine
        # core so scheduler-originated events (preemption) land in the
        # same per-request timeline; None when running standalone (tests)
        self.recorder = None
        # queue-TTL plumbing (frontdoor): scan only once a deadline-
        # bearing request has ever been added; shed_hook (set by the
        # async layer) keeps the front door's lifetime shed count in
        # step with scheduler-side sheds
        self._saw_deadlines = False
        self.shed_hook = None
        # adapter-residency gate (engine/adapter_pool.py, set by the
        # engine core in pool mode): gate(seq) -> bool.  True resolves
        # seq.lora_slot and admits; False means the adapter is still
        # streaming host→device — the request PARKS in `waiting` and
        # planning prefers resident-adapter work instead of blocking
        # the batch on the transfer.  None (legacy / no LoRA) admits
        # everything.
        self.lora_gate = None
        # host-KV-tier gate (engine/kv_tier.py, set by the engine core
        # when --kv-host-cache-gb > 0): gate(seq, start) -> bool.  False
        # means the request's prompt prefix is being promoted host →
        # device — it PARKS in `waiting` (adapter-pool style) and
        # planning serves other work until the restored pages land.
        # ``start=False`` is a pure probe (no promotion started) for
        # chained-decode admissibility checks.
        self.kv_gate = None
        # prefill/decode disaggregation role (engine/core.py
        # set_replica_role, docs/SCALING.md): informational for
        # planning/estimation — a 'prefill' scheduler's running set is
        # empty by construction (handed-off sequences leave at commit),
        # and a 'decode' scheduler's waiting set is mostly parked
        # promotions whose prompt spans restore rather than recompute.
        self.role = "mixed"

    def _rebuild_ragged_buckets(self) -> None:
        """Flat-length compile ladder: pow2 from 16 up to a ceiling that
        holds a full decode batch (every running row's span — one token,
        or spec_gamma+1 for a verify span) plus the chunk budget."""
        span = 1 + self.spec_gamma
        ceiling = 1
        while ceiling < self.chunk_budget + self.config.max_num_seqs * span:
            ceiling *= 2
        self.ragged_buckets = []
        b = 16
        while b < ceiling:
            self.ragged_buckets.append(b)
            b *= 2
        self.ragged_buckets.append(ceiling)

    def set_spec_gamma(self, gamma: int) -> None:
        """Enable speculative verify-span planning (engine core, at
        draft attach / supervised re-attach).  Recomputes the flat
        bucket ladder so ``max_num_seqs`` verify spans fit one plan."""
        self.spec_gamma = max(0, gamma)
        self._rebuild_ragged_buckets()

    # ------------------------------------------------------------ bookkeeping

    @property
    def num_unfinished(self) -> int:
        return len(self.waiting) + len(self.running)

    def add(self, seq: Sequence) -> None:
        seq.status = SequenceStatus.WAITING
        if seq.deadline is not None:
            # arms the per-step TTL scan (_shed_expired); stays set —
            # deployments that never use deadlines never pay the scan
            self._saw_deadlines = True
        self.waiting.append(seq)

    def waiting_token_backlog(self) -> int:
        """Tokens the waiting queue still owes the device (prompt
        remainder + requested output budget) — the front door's
        queue-drain-estimate input (frontdoor/admission.py)."""
        total = 0
        for seq in self.waiting:
            remaining_prompt = max(
                0, len(seq.all_token_ids) - seq.prefill_pos
            )
            ticket = getattr(seq, "kv_promotion", None)
            if ticket is not None:
                # parked host-tier promotion (incl. every resumed
                # handoff on a decode-role replica): the covered span
                # will be RESTORED, not recomputed — pricing it as
                # prefill work would inflate the front door's drain
                # estimate and fire deadline sheds spuriously
                remaining_prompt = max(
                    0,
                    min(
                        remaining_prompt,
                        len(seq.all_token_ids) - ticket.end_tokens,
                    ),
                )
            total += remaining_prompt + (seq.params.max_tokens or 0)
        return total

    def abort(self, request_id: str) -> Optional[Sequence]:
        for i, seq in enumerate(self.waiting):
            if seq.request_id == request_id:
                del self.waiting[i]
                seq.status = SequenceStatus.FINISHED_ABORTED
                # mid-chunked-prefill sequences wait with pages+slot held
                self.finish(seq)
                self._drop_swap(seq)
                return seq
        for seq in self.running:
            if seq.request_id == request_id:
                seq.status = SequenceStatus.FINISHED_ABORTED
                self.finish(seq)
                return seq
        return None

    def _drop_swap(self, seq: Sequence) -> None:
        if seq.swapped is not None:
            if self.swap_drop_fn is not None:
                self.swap_drop_fn(seq)
            seq.swapped = None

    def register_prefix(self, seq: Sequence) -> None:
        """Publish a completed prefill's full prompt pages for reuse.

        Called by the engine core AFTER the prefill dispatch executed —
        registering at plan time would let another request adopt pages
        whose K/V had not been written yet had the owner been aborted.
        """
        if seq.blocks is not None:
            self.allocator.register_prefix(
                seq.prompt_token_ids, seq.blocks.blocks, seq.lora_name
            )

    @property
    def free_slot_count(self) -> int:
        """Batch rows no sequence holds (a row is also the sequence's
        slot of the recurrent-state slab, docs/STATE.md)."""
        return len(self._free_slots)

    # ------------------------------------------------- groups of pages

    def _pools(self) -> list[BlockAllocator]:
        """The pool of every group of layers: one, or the full group's
        and the window group's."""
        if self.window_allocator is None:
            return [self.allocator]
        return [self.allocator, self.window_allocator]

    def _new_blocks(self) -> SequenceBlocks:
        """A sequence's tables: one a group."""
        return SequenceBlocks(self.allocator, self.window_allocator)

    def _band_pages(self, num_tokens: int) -> int:
        """Pages of the window group a row of ``num_tokens`` tokens comes
        to hold: its band (``rolling_window``) and one step's chunk over
        it, or all of it where it is shorter."""
        return self.allocator.blocks_needed(
            min(num_tokens, self.rolling_window + self.chunk_budget)
        ) + 1

    def _window_growth(self, seq: Sequence, upto: int) -> int:
        """Pages the window group must add before ``seq`` holds tokens
        up to ``upto``; for a row not admitted yet, the band it will
        come to hold (admission counts both groups).  0 with one
        group."""
        if self.window_allocator is None:
            return 0
        if seq.blocks is None:
            return self._band_pages(len(seq.all_token_ids))
        return max(
            0,
            self.allocator.blocks_needed(upto)
            - len(seq.blocks.window.blocks),
        )

    # ------------------------------------------------------ free epochs

    def begin_free_epoch(self) -> None:
        """Open a quarantine epoch for pages (allocator) and batch rows:
        what is freed from now on is released when the dispatch that was
        just planned ahead has retired (flush_free_epoch)."""
        for pool in self._pools():
            pool.begin_free_epoch()
        self._slot_epochs.append([])

    def flush_free_epoch(self) -> None:
        """Release the OLDEST epoch's pages and rows."""
        for pool in self._pools():
            pool.flush_free_epoch()
        if self._slot_epochs:
            self._free_slots.extend(self._slot_epochs.popleft())

    def flush_all_free_epochs(self) -> None:
        for pool in self._pools():
            pool.flush_all_free_epochs()
        while self._slot_epochs:
            self._free_slots.extend(self._slot_epochs.popleft())

    def finish(self, seq: Sequence) -> None:
        """Release a sequence's device resources (idempotent)."""
        ticket = getattr(seq, "kv_promotion", None)
        if ticket is not None:
            # abort/preemption mid-promotion: the apply must never
            # scatter into pages this release is about to free
            ticket.cancel()
            seq.kv_promotion = None
        if seq in self.running:
            self.running.remove(seq)
        if seq.slot >= 0:
            # inside an open free epoch the row is quarantined with its
            # pages: a dispatch planned before this release may still
            # write the slot's state
            (
                self._slot_epochs[-1] if self._slot_epochs
                else self._free_slots
            ).append(seq.slot)
            seq.slot = -1
        if seq.blocks is not None:
            seq.blocks.release()
            seq.blocks = None
        seq.prefill_pos = 0  # preemption-resume re-runs the whole prefill
        seq.draft_pos = 0  # the draft's pages were released with ours

    # -------------------------------------------------------------- planning

    def _prefill_bucket(self, n: int) -> Optional[int]:
        for b in self.config.prefill_buckets:
            if n <= b:
                return b
        return None

    # adapter-gate scan bound: a parked head looks this far down the
    # waiting queue for resident-adapter work (bounded — the queue is
    # client-sized)
    LORA_SCAN = 16

    def _lora_ready(self, seq: Sequence) -> bool:
        return self.lora_gate is None or self.lora_gate(seq)

    def _tier_ready(self, seq: Sequence) -> bool:
        """May ``seq`` admit now, or should it park for a host-tier
        prefix promotion?  Calling this may START a promotion (pages
        allocated, transfer scheduled) — planning paths only."""
        return self.kv_gate is None or self.kv_gate(seq)

    def _tier_ready_peek(self, seq: Sequence) -> bool:
        """Pure probe: False only while a promotion is in flight.  Never
        starts one — safe from chained-decode admissibility checks."""
        return self.kv_gate is None or self.kv_gate(seq, start=False)

    def _lora_standin(self) -> Optional[Sequence]:
        """First fresh, adapter-ready waiting candidate behind a parked
        head (bounded scan; no queue mutation) — the ONE predicate both
        head promotion and chained-decode admissibility use, so the two
        can never disagree about whether resident work exists."""
        for i, seq in enumerate(self.waiting):
            if i == 0:
                continue
            if i > self.LORA_SCAN:
                return None
            if (
                seq.swapped is not None
                or seq.blocks is not None
                or seq.prefill_pos != 0
            ):
                continue
            # peek-only tier probe: a standin scan must not fan out
            # promotion starts down the queue (fresh candidates without
            # a ticket always pass it)
            if self._lora_ready(seq) and self._tier_ready_peek(seq):
                return seq
        return None

    def _promote_lora_ready(self) -> Optional[Sequence]:
        """The waiting HEAD is parked on adapter streaming: move the
        first fresh, adapter-ready candidate to the queue head so it
        (and the head-only chunk/swap invariants) serve resident work
        while the stream completes.  The parked former head keeps the
        next position and resumes the moment its adapter lands."""
        seq = self._lora_standin()
        if seq is not None:
            self.waiting.remove(seq)
            self.waiting.appendleft(seq)
        return seq

    def schedule(
        self, prefill_only: bool = False
    ) -> Optional[PrefillPlan | RaggedPlan | DecodePlan]:
        """Pick the next device step.

        The ragged planner (``self.ragged``, the serving default) plans
        token-budgeted mixed dispatches; the legacy solo-prefill /
        fused-decode alternation below survives only for pp>1 / sp>1
        engines (no ragged plumbing through the staged runner / sp ring
        yet) and for prompt-logprob heads, which need full-bucket logits
        rows.  Prefill normally has priority, but right after a prefill
        chunk a decode step runs first if any rows are runnable —
        chunked admission of a long prompt interleaves with decode
        instead of starving it.

        ``prefill_only`` (async overlap, engine/async_llm.py): another
        dispatch is still in flight, so only plans independent of its
        commit — admissions — may be produced.  The prefill/decode
        interleave is preserved: right after a prefill, returning None
        makes the loop drain the in-flight dispatch and run the decode,
        so heavy admission still cannot starve running sequences.
        """
        failpoints.fire("scheduler.schedule")
        self._shed_expired()
        if self.ragged:
            return self._schedule_ragged(prefill_only)
        if self._last_was_prefill and self.running:
            if prefill_only:
                return None
            self._last_was_prefill = False
            plan = self._schedule_decode()
            if plan is not None:
                return plan
        plan = self._try_schedule_prefill()
        if plan is not None:
            self._last_was_prefill = True
            return plan
        self._last_was_prefill = False
        if prefill_only:
            return None
        return self._schedule_decode()

    def _shed_expired(self) -> None:
        """Queue-TTL shed (frontdoor): drop waiting requests whose
        deadline passed before they reached prefill.

        Only pure pre-prefill entries qualify — no KV pages written, no
        output tokens, no held resources (mid-chunk prefills, swapped
        and preempted sequences have sunk device work worth finishing).
        The shed emits through ``newly_finished`` like any other
        scheduler-rejected request, so the client still receives a
        final (empty, aborted) output frame.
        """
        if not self._saw_deadlines or not self.waiting:
            return
        now = time.time()
        expired = [
            s for s in self.waiting
            if s.deadline is not None
            and now >= s.deadline
            and s.prefill_pos == 0
            and s.num_output_tokens == 0
            and s.blocks is None
            and s.swapped is None
        ]
        for seq in expired:
            self.waiting.remove(seq)
            seq.status = SequenceStatus.FINISHED_ABORTED
            self.finish(seq)  # no-op resource-wise; keeps invariants
            self.newly_finished.append(seq)
            queued_s = max(0.0, now - seq.metrics.arrival_time)
            logger.warning(
                "shedding request %s: queued %.1fs, deadline passed "
                "before prefill", seq.request_id, queued_s,
            )
            if self.recorder is not None:
                self.recorder.record(
                    "shed", seq.request_id, trace_id=seq.trace_id,
                    reason="ttl", queued_s=round(queued_s, 3),
                )
            metrics.frontdoor_sheds_total.labels(reason="ttl").inc()
            if self.shed_hook is not None:
                self.shed_hook()

    def _adoptable(self, seq: Sequence) -> bool:
        # prompt-logprob requests never adopt cached prefix pages: the
        # adopted span's logits are skipped, so its table rows could
        # never be computed.  (Chunked admission is fine — each chunk
        # computes and appends its own rows, runner.prepare_prefill.)
        return seq.params.prompt_logprobs is None

    def _try_schedule_prefill(self) -> Optional[PrefillPlan]:
        if not self.waiting:
            return None
        seq = self.waiting[0]
        if seq.swapped is not None and self.swap_out_fn is not None:
            # a swapped head is re-admitted by try_swap_in (plan_step
            # drains it on every clean dispatch boundary with the same
            # slot+page requirements); recompute-admitting it here —
            # e.g. during async prefill_only planning — would forfeit
            # the saved KV
            return None
        if seq.kv_promotion is not None or (
            seq.blocks is None
            and seq.prefill_pos == 0
            and not (self._lora_ready(seq) and self._tier_ready(seq))
        ):
            # head parked on adapter streaming or a host-tier prefix
            # promotion (mid-chunk heads hold a pin and are always
            # resident): serve ready work around it instead of stalling
            # admissions on the transfer.  The kv_promotion check comes
            # FIRST: a promoting head already holds its target pages, so
            # falling through to first-chunk admission would clobber
            # them with a fresh SequenceBlocks.
            seq = self._promote_lora_ready()
            if seq is None:
                return None
            if not self._tier_ready(seq):
                # the standin's own prefix turned out to be host-tier
                # resident: it parks (now at the head, ticket attached)
                # and the next planning pass scans for ready work again
                return None
        first_chunk = seq.prefill_pos == 0
        if first_chunk and not self._free_slots:
            return None
        token_ids = seq.all_token_ids  # includes output on preemption-resume
        total = len(token_ids)
        if first_chunk:
            # adopt cached prefix pages BEFORE sizing the chunk: matched
            # tokens skip prefill entirely (the first chunk then starts at
            # start_pos = matched and attends to the shared pages through
            # the paged cache, exactly like a later chunk).  prompt-logprob
            # requests never adopt (their skipped span's table rows could
            # never be computed — see _adoptable)
            seq.blocks = self._new_blocks()
            if self._adoptable(seq):
                hit_blocks, matched = self.allocator.match_prefix(
                    token_ids, seq.lora_name
                )
                if matched:
                    seq.blocks.adopt(hit_blocks)
                    seq.prefill_pos = matched
        remaining = total - seq.prefill_pos
        chunk = min(remaining, self.chunk_budget)
        bucket = self._prefill_bucket(chunk)

        def roll_back_admission() -> None:
            if seq.blocks is not None:
                seq.blocks.release()
                seq.blocks = None
            seq.prefill_pos = 0

        if bucket is None:
            # cannot happen if server-side validation enforced max_model_len
            roll_back_admission()
            self.waiting.popleft()
            seq.status = SequenceStatus.FINISHED_LENGTH
            self.newly_finished.append(seq)
            logger.warning("request %s exceeds the largest prefill bucket",
                           seq.request_id)
            return None
        end = seq.prefill_pos + chunk
        if first_chunk:
            needed = (
                self.allocator.blocks_needed(total)
                - len(seq.blocks.blocks)
            )
            if not self.allocator.can_allocate(needed):
                # never preempt running work to admit new work — wait for
                # pages to free up as running sequences finish
                if not self.running:
                    roll_back_admission()
                    self.waiting.popleft()
                    seq.status = SequenceStatus.FINISHED_LENGTH
                    self.newly_finished.append(seq)
                    logger.warning(
                        "request %s needs %d KV pages but the pool only "
                        "has %d",
                        seq.request_id, needed, self.allocator.num_blocks,
                    )
                    return None
                roll_back_admission()
                return None
            seq.blocks.ensure_capacity(total)
            seq.slot = self._free_slots.pop()
            # count cache hits only once admission actually succeeded
            # (a rolled-back admission re-matches on its next attempt)
            self.allocator.prefix_hits += seq.prefill_pos
            self.allocator.prefix_lookup_tokens += total
            if seq.prefill_pos:
                metrics.kv_prefix_tokens_reused_total.labels(
                    tier="device"
                ).inc(seq.prefill_pos)

        plan = PrefillPlan(
            seq=seq,
            bucket_len=bucket,
            token_ids=token_ids[seq.prefill_pos:end],
            slots=seq.blocks.slots_for_range(seq.prefill_pos, end),
            start_pos=seq.prefill_pos,
            is_final=end == total,
        )
        seq.prefill_pos = end
        if plan.is_final:
            self.waiting.popleft()
            seq.status = SequenceStatus.RUNNING
            self.running.append(seq)
        # non-final: the sequence stays at the queue head (FCFS) with its
        # pages and slot held; the next prefill step continues it
        return plan

    def _allowed_steps(self, seq: Sequence) -> int:
        """Device steps row ``seq`` may run this dispatch (≥1)."""
        if seq.fsm is not None:
            # constrained rows take one step per dispatch: the host must
            # advance the FSM and rebuild the token mask between tokens
            return 1
        a = self._inflight.get(id(seq), 0)
        k = self.config.num_decode_steps
        if self.wave_depth is not None:
            k = min(k, self.wave_depth)
        if seq.params.max_tokens is not None:
            k = min(k, seq.params.max_tokens - seq.num_output_tokens - a)
        k = min(k, self.max_model_len - seq.num_tokens - a)
        return max(1, k)

    def _ends_in_flight(self, seq: Sequence) -> bool:
        """Plan-ahead: will ``seq`` certainly end by length inside the
        dispatch in flight?  Such a row gets no successor span (an end by
        EOS or a stop string cannot be known: that row runs on and its
        tokens are discarded at commit)."""
        a = self._inflight.get(id(seq), 0)
        if not a:
            return False
        mt = seq.params.max_tokens
        return (
            mt is not None and mt - seq.num_output_tokens - a < 1
        ) or self.max_model_len - seq.num_tokens - a < 1

    def _live_rows(self) -> list[Sequence]:
        """Running rows the next dispatch decodes: all of them, less
        those a planned-ahead dispatch knows will have ended."""
        if not self._inflight:
            return self.running
        return [s for s in self.running if not self._ends_in_flight(s)]

    def _still_running(self, live: list[Sequence]) -> list[Sequence]:
        """``live`` less what a preemption of this planning pass took
        out of ``running``, in batch-row order."""
        running = {id(s) for s in self.running}
        return sorted(
            (s for s in live if id(s) in running), key=lambda s: s.slot
        )

    def _check_growth(self, rows: list[Sequence], tokens) -> None:  # noqa: ANN001
        """Plan-ahead never preempts on a projection: refuse up front
        when the pages the rows' growth needs are not free."""
        if not self._ahead:
            return
        needed = sum(
            max(
                0,
                self.allocator.blocks_needed(tokens(seq))
                - len(seq.blocks.blocks),
            )
            for seq in rows
        )
        # a running row's tables are of one length in every group
        if needed and not all(
            pool.can_allocate(needed) for pool in self._pools()
        ):
            raise _AheadBail("kv_growth")

    def _schedule_decode(self) -> Optional[DecodePlan]:
        live = self._live_rows()
        if not live:
            return None
        inflight = self._inflight
        # rolling-window eviction runs BEFORE capacity/preemption: the
        # pages it reclaims must be visible to this pass's ensure_capacity,
        # or a tight pool preempts work that eviction could have fed
        self._roll_window(live)
        self._check_growth(
            live,
            lambda s: s.num_tokens - 1 + inflight.get(id(s), 0)
            + self._allowed_steps(s),
        )
        # grow each sequence's page list for every token this dispatch may
        # write (positions num_tokens-1 … num_tokens-2+allowed); preempt
        # youngest sequences if the pool runs dry.  Iterate over a snapshot
        # but re-check membership: a preemption earlier in this loop may
        # have evicted a later element (blocks == None).
        planned: dict[int, int] = {}
        for seq in sorted(live, key=lambda s: s.metrics.arrival_time):
            if seq not in self.running:
                continue  # preempted earlier in this same pass
            k = self._allowed_steps(seq)
            while True:
                try:
                    seq.blocks.ensure_capacity(
                        seq.num_tokens - 1 + inflight.get(id(seq), 0) + k
                    )
                    break
                except RuntimeError:
                    if k > 1:
                        # pool is tight: shrink this row's fused-step run
                        # before resorting to preemption
                        k = k // 2
                        continue
                    if not self._preempt_youngest(exclude=seq):
                        from vllm_tgis_adapter_tpu.frontdoor.errors import (
                            KVPoolExhaustedError,
                        )

                        raise KVPoolExhaustedError(
                            "KV cache too small for a single sequence"
                        ) from None
            planned[id(seq)] = k
        seqs = self._still_running(live)
        if not seqs:
            return None
        steps_per_seq = [planned[id(s)] for s in seqs]
        return DecodePlan(
            seqs=seqs,
            inflight=(
                [inflight.get(id(s), 0) for s in seqs] if inflight else []
            ),
            # ONE decode width (max_num_seqs) — the per-width bucket
            # ladder retired with the bucketed backend; dead rows are
            # masked on device (slot -1), exactly like bucket padding
            # was, and the occupancy gauge keeps reporting real/width
            batch_bucket=self.config.max_num_seqs,
            # only as many steps as some row can consume run: an
            # all-FSM-constrained batch (every row at 1 step) would
            # otherwise pay num_decode_steps of dead decode+sample work.
            # The device reads the same bound from the rows' limits
            # (runner._build_decode_fn): a loop count, not a program.
            num_steps=max(steps_per_seq),
            steps_per_seq=steps_per_seq,
        )

    # ------------------------------------------------------- ragged planning

    def _ragged_bucket(self, n: int) -> int:
        for b in self.ragged_buckets:
            if n <= b:
                return b
        return self.ragged_buckets[-1]

    def _spec_extra(self, seq: Sequence) -> int:
        """Draft-token rows a verify span may append for ``seq`` this
        dispatch (0 = plain one-token decode span): bounded by the
        configured γ, the row's max_tokens remainder (the span emits up
        to extra+1 tokens) and the model-length headroom (positions at
        or past max_model_len have no page to write)."""
        if self.spec_gamma <= 0 or not seq.spec_eligible:
            return 0
        extra = self.spec_gamma
        if seq.params.max_tokens is not None:
            extra = min(
                extra, seq.params.max_tokens - seq.num_output_tokens - 1
            )
        extra = min(extra, self.max_model_len - seq.num_tokens)
        return max(0, extra)

    def _stall_budget(self, base: int, budget: int) -> tuple[int, bool]:
        """Prompt tokens a step that carries ``base`` tokens of running
        rows may hold, and whether the stall rule set that number: what
        fits the bucket ``stall_bucket`` allows (never under the bucket
        the rows themselves need), but no less than one part in
        ``BACKLOG_SHARE`` of the prompt tokens waiting (queued and
        continuing; prompt-logprob heads and swapped rows are other
        paths') and no more than ``budget``."""
        room = max(self.stall_bucket, self._ragged_bucket(base + 1)) - base
        if room >= budget:
            return budget, False
        backlog = 0
        for seq in self.waiting:
            if (
                seq.params.prompt_logprobs is not None
                or seq.swapped is not None
            ):
                continue
            backlog += max(1, len(seq.all_token_ids) - seq.prefill_pos)
            if backlog >= BACKLOG_SHARE * budget:
                return budget, False
        share = -(-backlog // BACKLOG_SHARE)
        return max(room, share), room >= share

    def _schedule_ragged(
        self, prefill_only: bool = False
    ) -> Optional[RaggedPlan | PrefillPlan | DecodePlan]:
        """Plan one unified ragged step (the serving default).

        Every running row contributes a decode span — ONE token, or,
        when a draft model is attached and the row is spec-eligible, a
        (γ+1)-token speculative VERIFY span ``[last_token, γ draft
        placeholders]`` (docs/ATTENTION.md "Speculative decoding"); the
        rest of the flat token bucket fills with prefill work —
        continuing chunks first, then new admissions, the LAST one
        sliced so the bucket is exactly full whenever backlog exists
        (fill ratio 1, no per-prompt bucket padding).  Pure-decode steps
        (no admissible prefill) fall through to ``_schedule_decode`` —
        the fused K-step wave runs the same ragged kernel via the
        runner's ragged decode program, so chaining keeps working —
        UNLESS a verify span is planned: speculation emits up to γ+1
        tokens per row per dispatch, so the verify plan rides instead.

        Prompt-logprob requests need full-bucket logits rows, which the
        ragged step's per-sequence sample gather does not produce; a
        head bearing one is served by the legacy solo-prefill path
        (rare, debug-oriented — documented in docs/ATTENTION.md).

        ``prefill_only`` (a dispatch is in flight): decode spans depend
        on the pending commit, so only a cold-start admission-only plan
        (no running rows) may be produced.
        """
        head = self.waiting[0] if self.waiting else None
        if head is not None and head.params.prompt_logprobs is not None:
            if self._ahead:
                # the solo-prefill path alternates on committed state
                raise _AheadBail("prompt_logprobs")
            # legacy fallback: solo prefill for the lp head, with the
            # usual prefill/decode anti-starvation alternation
            if self._last_was_prefill and self.running:
                if prefill_only:
                    return None
                self._last_was_prefill = False
                plan = self._schedule_decode()
                if plan is not None:
                    return plan
            plan = self._try_schedule_prefill()
            if plan is not None:
                self._last_was_prefill = True
                return plan
            self._last_was_prefill = False
            if prefill_only:
                return None
            return self._schedule_decode()
        if prefill_only and self.running:
            return None
        if not self.waiting and self.spec_gamma <= 0:
            # nothing to admit and nothing to verify: the fused wave,
            # without walking the rows here first
            return None if prefill_only else self._schedule_decode()

        # mandatory decode spans: one token per running row (γ+1 for a
        # spec-eligible verify span), youngest preempted when the pool
        # runs dry (same policy as _schedule_decode; a tight pool
        # shrinks the verify span before resorting to preemption)
        decode_seqs: list[Sequence] = []
        spec_extra: dict[int, int] = {}
        inflight = self._inflight
        live = self._live_rows()
        if live:
            self._roll_window(live)
            self._check_growth(
                live, lambda s: s.num_tokens + inflight.get(id(s), 0)
            )
            for seq in sorted(live, key=lambda s: s.metrics.arrival_time):
                if seq not in self.running:
                    continue  # preempted earlier in this pass
                extra = self._spec_extra(seq)
                while True:
                    try:
                        seq.blocks.ensure_capacity(
                            seq.num_tokens + inflight.get(id(seq), 0)
                            + extra
                        )
                        break
                    except RuntimeError:
                        if extra > 0:
                            extra //= 2
                            continue
                        if not self._preempt_youngest(exclude=seq):
                            from vllm_tgis_adapter_tpu.frontdoor.errors import (
                                KVPoolExhaustedError,
                            )

                            raise KVPoolExhaustedError(
                                "KV cache too small for a single sequence"
                            ) from None
                spec_extra[id(seq)] = extra
            decode_seqs = self._still_running(live)
        base = sum(1 + spec_extra.get(id(s), 0) for s in decode_seqs)
        has_spec = any(
            spec_extra.get(id(s), 0) > 0 for s in decode_seqs
        )

        # phase 1 (no state mutation): how many prefill tokens COULD
        # ride this dispatch — continuing chunks and new prompts, in
        # queue order, later entries jumping blocked ones
        budget = min(self.chunk_budget, self.ragged_buckets[-1] - base)
        # running rows wait through this step for their one token: it
        # holds no more prompt tokens than keep it near a token step
        ruled = False
        if decode_seqs and self.stall_bucket is not None:
            budget, ruled = self._stall_budget(base, budget)
        tokens_left = budget
        cands: list[tuple[Sequence, int]] = []
        cut = False  # a prompt got fewer tokens than it has left
        slots_left = len(self._free_slots)
        # pages of the window group still free for this step's chunks
        window_left = (
            self.window_allocator.num_free
            if self.window_allocator is not None else 0
        )
        window_blocked: list[Sequence] = []
        for seq in list(self.waiting):
            if tokens_left <= 0:
                break
            if (
                seq.params.prompt_logprobs is not None
                or seq.swapped is not None
            ):
                continue  # legacy path / swap-in path own these
            if not self._lora_ready(seq):
                # adapter still streaming: the row parks and the bucket
                # fills with resident-adapter work — batch composition
                # prefers residency so churn cannot thrash the pool
                continue
            if not self._tier_ready(seq):
                # host-tier promotion in flight (or just started): the
                # row parks and the bucket fills with resident work —
                # the SAME parking shape the adapter gate uses, on the
                # ragged planner too
                continue
            first = seq.prefill_pos == 0 and seq.blocks is None
            matched = 0
            if first:
                if slots_left <= 0:
                    continue
                if self._adoptable(seq):
                    matched = self.allocator.peek_prefix(
                        seq.all_token_ids, seq.lora_name
                    )
                if self._ahead and not self.allocator.can_allocate(
                    self.allocator.blocks_needed(len(seq.all_token_ids))
                    - self.allocator.blocks_needed(matched)
                ):
                    # inside an open free epoch an admission that adopts
                    # cached pages and is then rolled back would pin them
                    # until the epoch flushes (its decref is quarantined,
                    # _waiting_head_admissible): look before adopting
                    continue
            remaining = len(seq.all_token_ids) - max(
                seq.prefill_pos, matched
            )
            if remaining <= 0:
                remaining = 1  # defensive: the last row always runs
            take = min(remaining, tokens_left)
            grow = self._window_growth(seq, seq.prefill_pos + take)
            if grow > window_left:
                # the window group cannot hold this chunk (or, for a
                # new row, its band) yet: pages free behind other rows'
                # bands and as rows end
                window_blocked.append(seq)
                continue
            window_left -= grow
            cands.append((seq, take))
            tokens_left -= take
            if take < remaining:
                cut = True
            if first:
                slots_left -= 1

        if not cands:
            if (
                window_blocked and not decode_seqs and not self._ahead
                and not self._inflight
            ):
                # nothing runs and every prompt waits for pages of the
                # window group that only other waiting prompts hold: the
                # youngest of them gives its pages back and starts over
                if self._preempt_youngest(exclude=window_blocked[0]):
                    return self._schedule_ragged(prefill_only)
            if prefill_only or not decode_seqs:
                return None
            if not has_spec:
                # pure decode, nothing to verify: the fused K-step wave
                # (ragged kernel inside)
                return self._schedule_decode()
            # pure decode with verify spans: the spec plan rides alone —
            # γ+1 potential tokens per row per dispatch beat the fused
            # wave's one-per-step on the latency the dispatch saves

        desired = base + sum(take for _, take in cands)
        # floor bucket + slice-to-fit: whenever backlog covers a bucket
        # the dispatch is exactly full; a thin backlog pads only the
        # smallest bucket's tail
        bucket = self.ragged_buckets[0]
        for b in self.ragged_buckets:
            if b <= desired:
                bucket = b
        bucket = max(bucket, self._ragged_bucket(base + 1))
        space = bucket - base

        # phase 2: allocate + emit, truncating to the bucket.  Verify
        # spans carry placeholder 0s after the last sampled token — the
        # runner scatters the draft's proposals into those stream rows
        # on device (prepare_ragged / _ragged_verify_fn)
        items: list[RaggedItem] = []
        for seq in decode_seqs:
            extra = spec_extra.get(id(seq), 0)
            ahead = inflight.get(id(seq), 0)
            pos0 = seq.num_tokens - 1 + ahead
            items.append(
                RaggedItem(
                    seq=seq,
                    token_ids=[seq.all_token_ids[-1]] + [0] * extra,
                    slots=seq.blocks.slots_for_range(
                        pos0, pos0 + 1 + extra
                    ),
                    start_pos=pos0,
                    is_final=True,
                    is_decode=True,
                    spec_width=(1 + extra) if extra > 0 else 0,
                    inflight=ahead,
                )
            )
        total = base
        for seq, take in cands:
            if space <= 0:
                break
            token_ids = seq.all_token_ids
            n_total = len(token_ids)
            first = seq.prefill_pos == 0 and seq.blocks is None
            if first:
                if not self._free_slots:
                    continue
                seq.blocks = self._new_blocks()
                if self._adoptable(seq):
                    hit_blocks, matched = self.allocator.match_prefix(
                        token_ids, seq.lora_name
                    )
                    if matched:
                        seq.blocks.adopt(hit_blocks)
                        seq.prefill_pos = matched
                needed = (
                    self.allocator.blocks_needed(n_total)
                    - len(seq.blocks.blocks)
                )
                if not self.allocator.can_allocate(needed):
                    # never preempt to admit; if NOTHING can run at all
                    # the prompt can never fit — reject like the legacy
                    # path so the engine does not spin forever
                    if not self.running and not items:
                        seq.blocks.release()
                        seq.blocks = None
                        seq.prefill_pos = 0
                        self.waiting.remove(seq)
                        seq.status = SequenceStatus.FINISHED_LENGTH
                        self.newly_finished.append(seq)
                        logger.warning(
                            "request %s needs %d KV pages but the pool "
                            "only has %d",
                            seq.request_id, needed,
                            self.allocator.num_blocks,
                        )
                        continue
                    seq.blocks.release()
                    seq.blocks = None
                    seq.prefill_pos = 0
                    continue
                seq.blocks.ensure_capacity(n_total, window_group=False)
                seq.slot = self._free_slots.pop()
                self.allocator.prefix_hits += seq.prefill_pos
                self.allocator.prefix_lookup_tokens += n_total
                if seq.prefill_pos:
                    metrics.kv_prefix_tokens_reused_total.labels(
                        tier="device"
                    ).inc(seq.prefill_pos)
            if n_total - seq.prefill_pos <= 0:
                # mirrors phase 1's remaining<=0 guard: a waiting row
                # whose prompt is somehow fully prefilled re-runs its
                # last position so it samples, finishes, and leaves the
                # queue instead of wedging as a perpetual candidate
                seq.prefill_pos = n_total - 1
            chunk = min(take, space, n_total - seq.prefill_pos)
            if chunk <= 0:
                continue
            end = seq.prefill_pos + chunk
            if seq.blocks.window is not None:
                # the window group grows chunk by chunk behind a band
                # that moves with it (phase 1 saw the pages free)
                self._roll_window([seq], upto=seq.prefill_pos + 1)
                seq.blocks.window.ensure_capacity(end)
            items.append(
                RaggedItem(
                    seq=seq,
                    token_ids=list(token_ids[seq.prefill_pos:end]),
                    slots=seq.blocks.slots_for_range(seq.prefill_pos, end),
                    start_pos=seq.prefill_pos,
                    is_final=end == n_total,
                    is_decode=False,
                )
            )
            seq.prefill_pos = end
            space -= chunk
            total += chunk
            if end == n_total:
                self.waiting.remove(seq)
                seq.status = SequenceStatus.RUNNING
                self.running.append(seq)
            # non-final: stays in waiting with pages+slot held; the next
            # ragged step continues it (any queue position, unlike the
            # legacy head-only chunk invariant)
        if total == base and not decode_seqs:
            return None
        if total == base and not has_spec:
            # every candidate was blocked: fall back to the fused wave
            return self._schedule_decode()
        return RaggedPlan(
            items=items,
            token_bucket=self._ragged_bucket(total),
            total_tokens=total,
            capped=ruled and cut,
        )

    def _roll_window(self, seqs: list[Sequence],
                     upto: Optional[int] = None) -> None:
        """Free KV pages entirely below the attention band (see
        ``rolling_window``) — of the window group where a row has two
        groups, whatever its full group keeps.  The next row a sequence
        runs sits at position ``upto - 1`` (its ``num_tokens``: the last
        sampled token; a prompt chunk passes its first row), and no wave
        — in flight or planned — reads positions under ``upto -
        window``; band masks discard whatever a reallocated page later
        holds, and the free epochs keep a freed page from being handed
        out while a dispatch that reads it is still in flight."""
        if not self.rolling_window:
            return
        freed = 0
        for seq in seqs:
            lo = (upto or seq.num_tokens) - self.rolling_window
            if lo > 0 and seq.blocks is not None:
                freed += seq.blocks.evict_below(lo)
        if freed and self.window_allocator is not None:
            metrics.kv_window_pages_freed_total.inc(freed)

    def schedule_ahead(
        self, inflight: dict[int, int]
    ) -> Optional[RaggedPlan | DecodePlan]:
        """Plan the NEXT dispatch while its predecessor still executes
        (the rule of the overlapped step loop, engine/async_llm.py).

        ``inflight`` maps ``id(seq)`` to the tokens the predecessor
        samples for that row: every running row is ASSUMED to consume
        its whole budget there, so its position, its page growth, its
        sampling counters and its remaining budget are projected from
        committed state plus those tokens, and its input token is read
        on the device from the predecessor's sample (RaggedItem.inflight
        / DecodePlan.inflight).  Admissions and prompt chunks come from
        the host as in the serial order.  A row that will certainly end
        by length inside the predecessor gets no span; one that ends by
        EOS or a stop string runs on and has its tokens discarded at
        commit (the fused-decode over-run path), its pages and its batch
        row quarantined until this dispatch has retired (free epochs).

        Returns None with ``ahead_bail`` set wherever the projection
        could be wrong or unsafe — the caller then commits the
        predecessor and plans in the serial order: an FSM-constrained
        row (the host rebuilds its mask between tokens), a verify span
        of speculative decoding (its length is the acceptance), a
        prompt-logprob head (the solo-prefill path), a swap-in that is
        due (it rebinds the caches on a clean boundary), a running row
        the predecessor did not sample (resumed or restored behind its
        back), page growth that would need preemption, the legacy
        planner.  None with ``ahead_bail`` None means nothing to plan.
        """
        failpoints.fire("scheduler.schedule")
        self.ahead_bail = self._ahead_refusal(inflight)
        if self.ahead_bail is not None:
            return None
        self._shed_expired()
        self._inflight, self._ahead = inflight, True
        try:
            return self._schedule_ragged()
        except _AheadBail as bail:
            self.ahead_bail = bail.reason
            return None
        finally:
            self._inflight, self._ahead = {}, False

    def _ahead_refusal(self, inflight: dict[int, int]) -> Optional[str]:
        """The reasons against planning ahead that need no planning."""
        if not self.ragged:
            return "legacy_planner"
        for seq in self.running:
            if id(seq) not in inflight:
                return "composition"
            if seq.fsm is not None:
                return "fsm"
            if self.spec_gamma > 0 and seq.spec_eligible:
                return "spec_verify"
        head = self.waiting[0] if self.waiting else None
        if (
            head is not None
            and head.swapped is not None
            and self.swap_out_fn is not None
            and self._waiting_head_admissible()
        ):
            return "swap_in"
        return None

    def _waiting_head_admissible(self) -> bool:
        """Could the waiting head make progress if plan_step ran now?

        Used by ``schedule_ahead`` for a swapped head (is its swap-in
        due?) and by tests of the admission rules.  Mirrors
        the resource checks of ``_try_schedule_prefill`` /
        ``try_swap_in``.  The prefix probe is ``peek_prefix`` — a pure
        hash-walk (ADVICE r5): the old match_prefix+free round-trip
        promoted a blocked head's cached pages to the LRU's MRU end on
        EVERY chained-wave attempt (skewing eviction order), and inside
        an open free epoch its decref would quarantine while the incref
        applied immediately, temporarily pinning cached pages."""
        if not self.waiting:
            return False
        seq = self.waiting[0]
        if not self._lora_ready(seq) or not self._tier_ready_peek(seq):
            # a head parked on adapter streaming or a host-tier prefix
            # promotion cannot progress; the first ready candidate in
            # scan range stands in (it is what schedule() would promote)
            # — none ready means chaining is free throughput.  The tier
            # probe is peek-only: admissibility checks must not START
            # promotions (they run between chained waves, possibly
            # inside an open free epoch).
            seq = self._lora_standin()
            if seq is None:
                return False
        total = len(seq.all_token_ids)
        if seq.swapped is not None:
            return bool(self._free_slots) and self.allocator.can_allocate(
                self.allocator.blocks_needed(total)
            )
        if seq.prefill_pos > 0:
            return True  # mid-chunk prefill always continues
        if not self._free_slots:
            return False
        matched = 0
        if self._adoptable(seq):
            matched = self.allocator.peek_prefix(
                seq.all_token_ids, seq.lora_name
            )
        needed = self.allocator.blocks_needed(total) - (
            self.allocator.blocks_needed(matched) if matched else 0
        )
        if (
            self.window_allocator is not None
            and not self.window_allocator.can_allocate(
                self._band_pages(total))
        ):
            return False
        return self.allocator.can_allocate(max(0, needed))

    def try_swap_in(self) -> Optional[Sequence]:
        """Re-admit the queue head from its host KV copy (no recompute).

        Allocates a batch slot + pages for the full token history and
        moves the sequence straight to RUNNING; the engine then scatters
        the host copy into the fresh pages (runner.restore_kv) before the
        next dispatch.  Returns None when the head is not swapped or
        resources are short — a swapped head is re-admitted EXCLUSIVELY
        here (prefill admission skips it), retried on every clean
        dispatch boundary until the slot + pages free up; its host copy
        is held until then (or dropped on abort)."""
        if not self.waiting:
            return None
        seq = self.waiting[0]
        if seq.swapped is None or not self._free_slots:
            return None
        total = len(seq.all_token_ids)
        needed = self.allocator.blocks_needed(total)
        if not self.allocator.can_allocate(needed):
            return None
        seq.blocks = self._new_blocks()
        seq.blocks.ensure_capacity(total)
        seq.slot = self._free_slots.pop()
        self.waiting.popleft()
        seq.status = SequenceStatus.RUNNING
        self.running.append(seq)
        return seq

    # ------------------------------------------------------------ preemption

    def _preempt_youngest(self, exclude: Optional[Sequence] = None) -> bool:
        # mid-chunked-prefill sequences sit in `waiting` but hold their
        # full page allocation — they must be reclaimable too, or decode
        # page pressure escalates to engine death instead of preemption
        candidates = [s for s in self.running if s is not exclude] + [
            s for s in self.waiting
            if s.blocks is not None and s is not exclude
        ]
        if not candidates:
            return False
        victim = max(candidates, key=lambda s: s.metrics.arrival_time)
        logger.info("preempting request %s (KV pool exhausted)",
                    victim.request_id)
        victim.metrics.events.append(("preempted", time.time_ns()))
        if self.recorder is not None:
            self.recorder.record(
                "preempt", victim.request_id,
                trace_id=victim.trace_id,
                was_running=victim in self.running,
                pages_held=(
                    len(victim.blocks.blocks)
                    if victim.blocks is not None else 0
                ),
            )
        metrics.preemptions_total.inc()
        was_running = victim in self.running
        if was_running and self.swap_out_fn is not None:
            # decode-phase victim: copy its computed KV to host BEFORE the
            # pages free; re-admission then restores instead of
            # recomputing (mid-prefill victims always recompute — their
            # cache coverage is partial and cheap to redo)
            self.swap_out_fn(victim)
        self.finish(victim)  # releases pages+slot, resets prefill_pos
        victim.status = SequenceStatus.PREEMPTED
        if was_running:
            self.waiting.appendleft(victim)
        # mid-prefill victims are already queued; they re-run from chunk 0
        return True
