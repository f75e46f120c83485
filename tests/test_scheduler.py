"""Scheduler unit tests: admission, buckets, preemption bookkeeping."""

from __future__ import annotations

import pytest


def make_scheduler(num_blocks=8, max_num_seqs=4, block_size=4,
                   num_decode_steps=8, **cfg_kwargs):
    from vllm_tgis_adapter_tpu.engine.config import CacheConfig, SchedulerConfig
    from vllm_tgis_adapter_tpu.engine.scheduler import Scheduler

    return Scheduler(
        SchedulerConfig(max_num_seqs=max_num_seqs, prefill_buckets=(8, 16, 32),
                        num_decode_steps=num_decode_steps, **cfg_kwargs),
        CacheConfig(block_size=block_size, num_blocks=num_blocks),
        num_blocks,
    )


def make_seq(request_id, prompt_len, arrival=0.0, max_tokens=64):
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams
    from vllm_tgis_adapter_tpu.engine.sequence import Sequence

    return Sequence(
        request_id,
        "x" * prompt_len,
        list(range(prompt_len)),
        SamplingParams(max_tokens=max_tokens),
        arrival_time=arrival,
    )


def test_prefill_then_decode_cycle():
    from vllm_tgis_adapter_tpu.engine.scheduler import DecodePlan, PrefillPlan

    sched = make_scheduler()
    seq = make_seq("a", 5)
    sched.add(seq)
    plan = sched.schedule()
    assert isinstance(plan, PrefillPlan)
    assert plan.bucket_len == 8
    assert plan.token_ids == seq.prompt_token_ids
    assert len(plan.slots) == 5
    seq.output_token_ids.append(1)

    plan2 = sched.schedule()
    assert isinstance(plan2, DecodePlan)
    assert plan2.seqs == [seq]
    # ONE decode width: the per-width bucket ladder is retired
    assert plan2.batch_bucket == 4


def test_prefill_waits_for_free_pages():
    sched = make_scheduler(num_blocks=4, block_size=4)  # 16 slots total
    a = make_seq("a", 10, arrival=0.0)  # needs 3 blocks
    sched.add(a)
    sched.schedule()
    b = make_seq("b", 10, arrival=1.0)  # needs 3 blocks; only 1 free
    sched.add(b)
    plan = sched.schedule()
    # b cannot be admitted; decode for a proceeds instead
    from vllm_tgis_adapter_tpu.engine.scheduler import DecodePlan

    assert isinstance(plan, DecodePlan)
    assert plan.seqs == [a]
    assert len(sched.waiting) == 1


def test_decode_preempts_youngest_when_pool_dry():
    """Growing an older sequence preempts the youngest, which recomputes."""
    from vllm_tgis_adapter_tpu.engine.scheduler import DecodePlan
    from vllm_tgis_adapter_tpu.engine.sequence import SequenceStatus

    # num_decode_steps=1 so the interleaved decode between the two prefills
    # does not pre-grow a's page list
    sched = make_scheduler(num_blocks=4, block_size=4, num_decode_steps=1)
    a = make_seq("a", 7, arrival=0.0)  # 2 blocks
    sched.add(a)
    sched.schedule()
    b = make_seq("b", 7, arrival=1.0)  # 2 blocks → pool now full
    sched.add(b)
    sched.schedule()  # interleave: decode for a runs after a's prefill
    sched.schedule()  # now b's prefill is admitted
    assert sched.allocator.num_free == 0

    # a grows past its block boundary: 8 tokens fit, the 9th needs a page
    a.output_token_ids.extend([0, 1])  # num_tokens 9 → needs 3rd block
    plan = sched.schedule()
    assert isinstance(plan, DecodePlan)
    assert plan.seqs == [a]
    assert b.status == SequenceStatus.PREEMPTED
    assert b in sched.waiting
    assert b.blocks is None  # pages released


def test_preemption_mid_pass_does_not_crash():
    """Regression: a sequence preempted earlier in the same decode pass must
    be skipped, not dereferenced (blocks is None)."""
    from vllm_tgis_adapter_tpu.engine.scheduler import DecodePlan

    sched = make_scheduler(num_blocks=4, block_size=4)
    a = make_seq("a", 7, arrival=0.0)
    sched.add(a)
    sched.schedule()
    b = make_seq("b", 7, arrival=1.0)
    sched.add(b)
    sched.schedule()
    # both now need a 3rd block simultaneously
    a.output_token_ids.extend([0, 1])
    b.output_token_ids.extend([0, 1])
    plan = sched.schedule()
    assert isinstance(plan, DecodePlan)
    assert plan.seqs == [a]


def test_abort_waiting_and_running():
    from vllm_tgis_adapter_tpu.engine.sequence import SequenceStatus

    sched = make_scheduler()
    a = make_seq("a", 4)
    b = make_seq("b", 4)
    sched.add(a)
    sched.add(b)
    sched.schedule()  # admits a
    assert sched.abort("b").status == SequenceStatus.FINISHED_ABORTED
    assert sched.abort("a").status == SequenceStatus.FINISHED_ABORTED
    assert sched.abort("nope") is None
    assert sched.num_unfinished == 0
    assert sched.allocator.num_free == sched.allocator.num_blocks


def test_oversized_prompt_rejected():
    from vllm_tgis_adapter_tpu.engine.sequence import SequenceStatus

    sched = make_scheduler()
    seq = make_seq("big", 64)  # exceeds largest bucket (32)
    sched.add(seq)
    assert sched.schedule() is None
    assert seq.status == SequenceStatus.FINISHED_LENGTH
    assert sched.newly_finished == [seq]


def test_ragged_buckets_widen_for_spec_gamma():
    """The flat-length ladder must hold a full decode batch of verify
    spans: set_spec_gamma recomputes the ceiling to cover
    max_num_seqs * (gamma + 1) + chunk_budget."""
    sched = make_scheduler(max_num_seqs=12)
    base_ceiling = sched.ragged_buckets[-1]
    assert base_ceiling >= sched.chunk_budget + 12
    sched.set_spec_gamma(4)
    assert sched.spec_gamma == 4
    assert sched.ragged_buckets[-1] >= sched.chunk_budget + 12 * 5
    assert sched.ragged_buckets[-1] >= base_ceiling
    # pow2 ladder from 16
    for a, b in zip(sched.ragged_buckets, sched.ragged_buckets[1:]):
        assert b == 2 * a
    sched.set_spec_gamma(0)
    assert sched.ragged_buckets[-1] == base_ceiling


def test_chunked_prefill_interleaves_with_decode():
    """A prompt above max_num_batched_tokens is admitted in chunks and
    decode steps run between chunks (VERDICT r2 #3: no decode starvation
    while a long prompt prefils)."""
    from vllm_tgis_adapter_tpu.engine.scheduler import DecodePlan, PrefillPlan

    sched = make_scheduler(num_blocks=32, block_size=4,
                           max_num_batched_tokens=8)
    short = make_seq("short", 5, arrival=0.0)
    sched.add(short)
    assert isinstance(sched.schedule(), PrefillPlan)

    long = make_seq("long", 20, arrival=1.0)  # 3 chunks of <=8
    sched.add(long)

    kinds = []
    chunk_plans = []
    for _ in range(8):
        plan = sched.schedule()
        if plan is None:
            break
        kinds.append(type(plan).__name__)
        if isinstance(plan, PrefillPlan):
            chunk_plans.append(plan)
        if isinstance(plan, DecodePlan):
            # emulate the engine: each scheduled decode produces a token
            for s in plan.seqs:
                s.output_token_ids.append(1)
        if long.status.name == "RUNNING" and len(chunk_plans) >= 3:
            break

    # the long prompt was split into 3 chunks: 8 + 8 + 4 tokens
    assert [len(p.token_ids) for p in chunk_plans] == [8, 8, 4]
    assert [p.start_pos for p in chunk_plans] == [0, 8, 16]
    assert [p.is_final for p in chunk_plans] == [False, False, True]
    # decode ran between the chunks — the short request kept generating
    first_chunk = kinds.index("PrefillPlan")
    assert "DecodePlan" in kinds[first_chunk:]
    assert short.num_output_tokens > 0
    # slots: each chunk wrote its own token range
    assert chunk_plans[1].slots == long.blocks.slots_for_range(8, 16)


def test_chunked_prefill_abort_releases_pages():
    """Aborting a request mid-chunked-prefill frees its pages and slot."""
    sched = make_scheduler(num_blocks=32, block_size=4,
                           max_num_batched_tokens=8)
    long = make_seq("long", 20, arrival=0.0)
    sched.add(long)
    plan = sched.schedule()
    assert plan is not None and not plan.is_final
    free_before = sched.allocator.num_free
    assert long.blocks is not None
    sched.abort("long")
    assert long.blocks is None
    assert sched.allocator.num_free > free_before
    assert sched.schedule() is None


def test_mid_chunk_prefill_sequence_is_preemptible():
    """Decode page pressure must reclaim a mid-chunked-prefill sequence's
    pages (it holds its full allocation while still in `waiting`), not
    raise the engine-killing 'KV cache too small' error."""
    from vllm_tgis_adapter_tpu.engine.scheduler import DecodePlan
    from vllm_tgis_adapter_tpu.engine.sequence import SequenceStatus

    # pool: 5 pages of 4 slots.  A (7 tokens) takes 2; B (12 tokens,
    # chunked by 8) takes 3 up front → pool dry.
    sched = make_scheduler(num_blocks=5, block_size=4, num_decode_steps=1,
                           max_num_batched_tokens=8)
    a = make_seq("a", 7, arrival=0.0)
    sched.add(a)
    sched.schedule()  # prefill a
    b = make_seq("b", 12, arrival=1.0)
    sched.add(b)
    sched.schedule()  # interleave: decode a
    plan = sched.schedule()  # first chunk of b (8 of 12 tokens)
    assert plan is not None and not plan.is_final
    assert sched.allocator.num_free == 0

    # a crosses a page boundary: needs a 3rd page; b (mid-prefill, in
    # waiting) must be the preemption victim
    a.output_token_ids.extend([0, 1])  # num_tokens 9
    plan = sched.schedule()
    assert isinstance(plan, DecodePlan) and plan.seqs == [a]
    assert b.status == SequenceStatus.PREEMPTED
    assert b.blocks is None and b.prefill_pos == 0
    assert b in sched.waiting  # never left the queue; re-runs from chunk 0


def test_ragged_fully_prefilled_waiting_row_reruns_and_finishes():
    """Defensive path of _schedule_ragged: a waiting row whose prompt is
    somehow already fully prefilled (impossible today — guarded against
    future prefix-adoption/replay changes) must re-run its last position
    and leave the queue, not wedge as a perpetual zero-chunk candidate."""
    from vllm_tgis_adapter_tpu.engine.kv_cache import SequenceBlocks
    from vllm_tgis_adapter_tpu.engine.scheduler import RaggedPlan
    from vllm_tgis_adapter_tpu.engine.sequence import SequenceStatus

    sched = make_scheduler(num_blocks=16)
    sched.ragged = True
    seq = make_seq("a", 6)
    sched.add(seq)
    # hand-build the supposedly impossible state: pages + slot held,
    # prefill_pos past the end, still parked in waiting
    seq.blocks = SequenceBlocks(sched.allocator)
    seq.blocks.ensure_capacity(len(seq.all_token_ids))
    seq.slot = sched._free_slots.pop()
    seq.prefill_pos = len(seq.all_token_ids)

    plan = sched.schedule()
    assert isinstance(plan, RaggedPlan)
    [item] = plan.items
    assert item.seq is seq
    assert item.start_pos == len(seq.all_token_ids) - 1
    assert item.token_ids == [seq.all_token_ids[-1]]
    assert item.is_final and not item.is_decode
    assert seq.status == SequenceStatus.RUNNING
    assert seq in sched.running and seq not in sched.waiting


# ------------------------------------------------- speculative verify spans


def _spec_seq(rid, prompt_len, max_tokens=64, arrival=0.0):
    seq = make_seq(rid, prompt_len, arrival=arrival, max_tokens=max_tokens)
    seq.spec_eligible = True
    return seq


def _admit_running(sched, seq):
    """Drive one sequence through ragged admission to RUNNING."""
    sched.add(seq)
    plan = sched.schedule()
    from vllm_tgis_adapter_tpu.engine.scheduler import RaggedPlan

    assert isinstance(plan, RaggedPlan)
    seq.output_token_ids.append(1)
    return plan


def test_ragged_verify_span_planning():
    """A spec-eligible running row plans a (γ+1)-token verify span —
    last sampled token + γ placeholder rows with real KV slots — while
    an ineligible row in the SAME plan keeps a plain one-token span."""
    from vllm_tgis_adapter_tpu.engine.scheduler import RaggedPlan

    sched = make_scheduler(num_blocks=32)
    sched.ragged = True
    sched.set_spec_gamma(3)
    a = _spec_seq("a", 5)
    b = make_seq("b", 5, arrival=1.0)  # not spec-eligible
    _admit_running(sched, a)
    _admit_running(sched, b)

    plan = sched.schedule()
    assert isinstance(plan, RaggedPlan)
    by_rid = {it.seq.request_id: it for it in plan.items}
    va = by_rid["a"]
    assert va.spec_width == 4
    assert len(va.token_ids) == 4 and len(va.slots) == 4
    assert va.token_ids[0] == a.all_token_ids[-1]
    assert va.start_pos == a.num_tokens - 1
    assert all(s >= 0 for s in va.slots)  # pages reserved for the span
    vb = by_rid["b"]
    assert vb.spec_width == 0 and len(vb.token_ids) == 1
    # the verify span counts γ+1 rows against the flat bucket
    assert plan.total_tokens == 4 + 1


def test_ragged_verify_span_budget_caps():
    """max_tokens remainder and model-length headroom cap the span: a
    row one token from its budget plans a PLAIN span (no draft rows to
    accept), and a near-model-len row truncates."""
    from vllm_tgis_adapter_tpu.engine.scheduler import RaggedPlan

    sched = make_scheduler(num_blocks=32, max_num_seqs=4)
    sched.ragged = True
    sched.set_spec_gamma(4)
    # budget: max_tokens=2 → after 1 output token, only 1 more may
    # emit — extra is 0, so the row plans NO verify span and the pure-
    # decode step falls through to the fused wave as without spec
    from vllm_tgis_adapter_tpu.engine.scheduler import DecodePlan

    a = _spec_seq("a", 5, max_tokens=2)
    _admit_running(sched, a)
    plan = sched.schedule()
    assert isinstance(plan, DecodePlan)

    sched2 = make_scheduler(num_blocks=32, max_num_seqs=4)
    sched2.ragged = True
    sched2.set_spec_gamma(4)
    sched2.max_model_len = 8  # prompt 5 + 1 output → 2 tokens headroom
    b = _spec_seq("b", 5)
    _admit_running(sched2, b)
    plan2 = sched2.schedule()
    assert isinstance(plan2, RaggedPlan)
    assert plan2.items[0].spec_width == 3  # 1 + min(gamma, headroom 2)


def test_ragged_verify_spans_mix_with_fresh_prefill():
    """One plan carries fresh-prefill spans AND verify spans AND plain
    decode spans — the mixed-bucket composition the ISSUE names."""
    from vllm_tgis_adapter_tpu.engine.scheduler import RaggedPlan

    sched = make_scheduler(num_blocks=64, max_num_seqs=4)
    sched.ragged = True
    sched.set_spec_gamma(2)
    a = _spec_seq("a", 4)
    b = make_seq("b", 4, arrival=1.0)
    _admit_running(sched, a)
    _admit_running(sched, b)
    fresh = make_seq("c", 6, arrival=2.0)
    sched.add(fresh)
    plan = sched.schedule()
    assert isinstance(plan, RaggedPlan)
    kinds = {
        it.seq.request_id: (it.is_decode, it.spec_width) for it in plan.items
    }
    assert kinds["a"] == (True, 3)
    assert kinds["b"] == (True, 0)
    assert kinds["c"] == (False, 0)


def test_ragged_verify_pure_decode_plans_ragged_not_fused():
    """Pure-decode steps with a spec-eligible row plan a verify
    RaggedPlan instead of falling to the fused wave; with no eligible
    row the fused wave still runs."""
    from vllm_tgis_adapter_tpu.engine.scheduler import (
        DecodePlan,
        RaggedPlan,
    )

    sched = make_scheduler(num_blocks=32)
    sched.ragged = True
    sched.set_spec_gamma(3)
    a = _spec_seq("a", 5)
    _admit_running(sched, a)
    plan = sched.schedule()
    assert isinstance(plan, RaggedPlan)
    assert plan.items[0].spec_width == 4

    sched2 = make_scheduler(num_blocks=32)
    sched2.ragged = True
    sched2.set_spec_gamma(3)
    b = make_seq("b", 5)  # ineligible
    _admit_running(sched2, b)
    plan2 = sched2.schedule()
    assert isinstance(plan2, DecodePlan)


def test_ragged_verify_span_shrinks_under_page_pressure():
    """A tight KV pool halves the verify span before preempting — the
    row degrades to a plain decode span instead of evicting siblings."""
    from vllm_tgis_adapter_tpu.engine.scheduler import RaggedPlan

    # block_size=4, 6 pages: two 6-token rows hold 2 pages each; γ=8
    # wants 4 pages per row (14 token slots) but only the OLDER row can
    # grow — the younger halves its span until it fits its own pages
    sched = make_scheduler(num_blocks=6, max_num_seqs=2)
    sched.ragged = True
    sched.set_spec_gamma(8)
    a = _spec_seq("a", 5)
    b = _spec_seq("b", 5, arrival=1.0)
    _admit_running(sched, a)
    _admit_running(sched, b)
    plan = sched.schedule()
    assert isinstance(plan, RaggedPlan)
    # both rows still present (no preemption), spans shrunk to fit
    assert {it.seq.request_id for it in plan.items} == {"a", "b"}
    widths = {it.seq.request_id: it.spec_width for it in plan.items}
    assert widths["a"] == 9  # full span: 1 + γ
    assert 0 < widths["b"] < 9  # shrunk, not preempted
    assert len(sched.running) == 2


def test_ragged_verify_span_capacity_reservation():
    """A verify span's KV slots are reserved through ensure_capacity at
    plan time: positions [num_tokens-1, num_tokens-1+extra] all carry
    real (non-negative, distinct) slots."""
    from vllm_tgis_adapter_tpu.engine.scheduler import RaggedPlan

    sched = make_scheduler(num_blocks=32)
    sched.ragged = True
    sched.set_spec_gamma(3)
    a = _spec_seq("a", 5)
    _admit_running(sched, a)
    plan = sched.schedule()
    assert isinstance(plan, RaggedPlan)
    it = plan.items[0]
    assert it.spec_width == 4
    assert len(set(it.slots)) == 4
    assert min(it.slots) >= 0
    # the pages backing the span belong to the sequence
    covered = (a.num_tokens - 1) + it.spec_width - 1
    assert len(a.blocks.blocks) * sched.block_size > covered


def test_spec_gamma_ignored_without_eligible_rows():
    """spec_gamma set but no eligible row: planning is byte-identical
    to a non-spec scheduler (plain decode spans, fused-wave fallthrough
    intact)."""
    from vllm_tgis_adapter_tpu.engine.scheduler import DecodePlan

    sched = make_scheduler(num_blocks=32)
    sched.ragged = True
    sched.set_spec_gamma(4)
    b = make_seq("b", 5)  # spec_eligible False
    _admit_running(sched, b)
    plan = sched.schedule()
    assert isinstance(plan, DecodePlan)
    assert plan.batch_bucket == sched.config.max_num_seqs


# ------------------------------------------------------ the stall rule


def _stall_scheduler(rows, *, chunk=1024, gamma=0, max_num_seqs=64):
    """A ragged scheduler with ``rows`` rows running (each one token
    past its prompt), planned without the stall rule so far."""
    from vllm_tgis_adapter_tpu.engine.config import CacheConfig, SchedulerConfig
    from vllm_tgis_adapter_tpu.engine.scheduler import Scheduler

    sched = Scheduler(
        SchedulerConfig(max_num_seqs=max_num_seqs,
                        prefill_buckets=(16, chunk), num_decode_steps=1),
        CacheConfig(block_size=16, num_blocks=2048),
        2048,
    )
    sched.ragged = True
    if gamma:
        sched.set_spec_gamma(gamma)
    for i in range(rows):
        seq = make_seq(f"row{i}", 5, arrival=float(i), max_tokens=10_000)
        seq.spec_eligible = bool(gamma)
        sched.add(seq)
        while sched.waiting:
            for item in sched.schedule().items:
                if item.is_final:
                    item.seq.output_token_ids.append(1)
    return sched


def _prefill_to_the_end(sched, prompts):
    """Plan until every prompt of ``prompts`` runs, giving every
    running row its token after each plan.  Returns, per plan,
    ``(token_bucket, base, waiting tokens before it, capped,
    {request_id: chunk tokens})`` and checks that each prompt is
    prefilled completely, in order, with one final chunk."""
    from vllm_tgis_adapter_tpu.engine.scheduler import RaggedPlan

    seen = {p.request_id: [] for p in prompts}
    finals = {p.request_id: 0 for p in prompts}
    plans = []
    for _ in range(10_000):
        if not sched.waiting:
            break
        waiting = sum(
            len(s.all_token_ids) - s.prefill_pos for s in sched.waiting
        )
        plan = sched.schedule()
        assert isinstance(plan, RaggedPlan)
        base = sum(len(i.token_ids) for i in plan.items if i.is_decode)
        chunks = {}
        for item in plan.items:
            if item.is_decode:
                continue
            rid = item.seq.request_id
            assert item.start_pos == len(seen[rid])
            seen[rid] += item.token_ids
            finals[rid] += item.is_final
            chunks[rid] = list(item.token_ids)
        plans.append((plan.token_bucket, base, waiting, plan.capped, chunks))
        for item in plan.items:
            if item.is_final:
                item.seq.output_token_ids.append(1)
    assert not sched.waiting
    for p in prompts:
        assert seen[p.request_id] == p.prompt_token_ids
        assert finals[p.request_id] == 1
    return plans


@pytest.mark.parametrize(
    ("rows", "cap", "prompt_lens"),
    [
        (3, 64, [300]),
        (3, 16, [300]),
        (35, 128, [300]),
        (35, 128, [1024]),  # a lone prompt of more than four budgets
        (23, 64, [90, 700, 40]),
        (40, 16, [500]),  # the rows alone need a larger bucket than the cap
        (3, None, [300]),
        (0, 32, [300, 40]),
    ],
    ids=["few-rows", "smallest-bucket", "many-rows", "over-four-budgets",
         "three-prompts", "cap-under-the-rows", "no-cap", "no-running-row"],
)
def test_stall_rule_bounds_a_mixed_step_and_loses_no_token(
        rows, cap, prompt_lens):
    """With rows running and ``stall_bucket`` set, a plan's flat bucket
    stays within ``max(stall_bucket, bucket of base + 1)`` unless more
    than four such budgets of prompt tokens wait, and then within the
    bucket a quarter of them needs; whatever the cap, every prompt is
    prefilled completely and in order."""
    sched = _stall_scheduler(rows)
    sched.stall_bucket = cap
    prompts = [make_seq(f"p{i}", n, arrival=100.0 + i, max_tokens=10_000)
               for i, n in enumerate(prompt_lens)]
    for p in prompts:
        sched.add(p)
    plans = _prefill_to_the_end(sched, prompts)
    if cap is None or not rows:
        assert not any(capped for *_, capped, _ in plans)
        return
    for bucket, base, waiting, capped, _ in plans:
        allowed = max(cap, sched._ragged_bucket(base + 1))
        if waiting <= 4 * (allowed - base):
            assert bucket <= allowed
        else:
            assert bucket <= sched._ragged_bucket(base - (-waiting // 4))
            assert not capped
    assert any(capped for *_, capped, _ in plans)


def test_stall_rule_off_or_no_running_row_plans_as_before():
    """``stall_bucket=None`` gives the floor-bucket walk the planner
    always made (written out here), and a cap changes nothing for a
    step with no running row."""
    sched = _stall_scheduler(3)
    prompt = make_seq("p", 300, arrival=100.0, max_tokens=10_000)
    sched.add(prompt)
    plans = _prefill_to_the_end(sched, [prompt])
    assert [(b, len(c["p"])) for b, _, _, _, c in plans] == [
        (256, 253), (32, 29), (16, 13), (16, 5),
    ]

    walks = []
    for cap in (None, 16):
        cold = _stall_scheduler(0, chunk=256)
        cold.stall_bucket = cap
        prompts = [make_seq("a", 700, arrival=1.0, max_tokens=10_000),
                   make_seq("b", 90, arrival=2.0, max_tokens=10_000)]
        # both arrive at an idle engine: the first plan has no running row
        for p in prompts:
            cold.add(p)
        plan = cold.schedule()
        walks.append((plan.token_bucket, plan.capped,
                      [(i.seq.request_id, i.token_ids) for i in plan.items]))
    assert walks[0] == walks[1]
    assert walks[0][0] == 256


def test_stall_rule_yields_to_a_backlog():
    """Sixteen caps' worth of prompt tokens waiting: the step is the one
    the planner makes without the rule, at its top bucket."""
    planned = []
    for cap in (None, 64):
        sched = _stall_scheduler(3, chunk=256)
        sched.stall_bucket = cap
        for i in range(4):
            sched.add(make_seq(f"p{i}", 16 * 64 // 4, arrival=100.0 + i,
                               max_tokens=10_000))
        plan = sched.schedule()
        planned.append((plan.token_bucket, plan.total_tokens, plan.capped,
                        [(i.seq.request_id, i.token_ids)
                         for i in plan.items]))
    assert planned[0] == planned[1]
    assert planned[0][0] == 256 and not planned[0][2]


def test_stall_rule_keeps_a_full_verify_batch_in_one_plan():
    """64 rows of spec_gamma 3 are 256 tokens of verify spans: the
    smallest cap still leaves them one plan, with a prompt chunk beside
    them in the bucket they need."""
    sched = _stall_scheduler(64, gamma=3)
    sched.stall_bucket = sched.ragged_buckets[0]
    prompt = make_seq("p", 400, arrival=100.0, max_tokens=10_000)
    # the batch is full: the prompt waits for a row, the spans ride alone
    sched.add(prompt)
    plan = sched.schedule()
    spans = [i for i in plan.items if i.is_decode]
    assert len(spans) == 64 and all(i.spec_width == 4 for i in spans)
    assert plan.token_bucket == 256 and plan.total_tokens == 256
