"""Telemetry signal layer (docs/OBSERVABILITY.md): decayed EWMAs, SLO
class resolution / sliding-window burn rates, the request cost ledger,
MFU math, and the end-to-end token-conservation property — every token
the engine delivers is billed to exactly one (tenant, class) cell.
"""

from __future__ import annotations

import asyncio
import json

import pytest

from vllm_tgis_adapter_tpu.telemetry import (
    CostLedger,
    DecayedEwma,
    JsonlSink,
    SloEngine,
    resolve_request_class,
)
from vllm_tgis_adapter_tpu.telemetry.slo import parse_slo_config


class _FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


# ------------------------------------------------------------------ ewma


def test_decayed_ewma_half_life_math():
    """After exactly one half-life of steady observations at x, the
    value has moved half of the way from the seed to x."""
    ewma = DecayedEwma(half_life_s=10.0)
    assert not ewma.initialized
    assert ewma.value == 0.0

    ewma.update(1.0, now=0.0)  # seed exactly
    assert ewma.initialized
    assert ewma.value == 1.0

    # one half-life later at 0.0: w = 2^-1 = 0.5 → value 0.5
    ewma.update(0.0, now=10.0)
    assert ewma.value == pytest.approx(0.5)
    # another half-life at 0.0 → 0.25
    ewma.update(0.0, now=20.0)
    assert ewma.value == pytest.approx(0.25)

    # dt = 0 (same-instant sample): w = 1, the old value stands
    ewma.update(100.0, now=20.0)
    assert ewma.value == pytest.approx(0.25)


def test_decayed_ewma_weights_time_not_observations():
    """A burst of N samples in epsilon time moves the value no further
    than one sample would — the property a fixed-alpha EWMA lacks."""
    burst = DecayedEwma(half_life_s=10.0)
    burst.update(0.0, now=0.0)
    for i in range(50):
        burst.update(1.0, now=1e-9 * (i + 1))

    single = DecayedEwma(half_life_s=10.0)
    single.update(0.0, now=0.0)
    single.update(1.0, now=50e-9)

    assert burst.value == pytest.approx(single.value, abs=1e-6)
    assert burst.value < 0.001  # barely moved


# ------------------------------------------------------- class resolution


def test_resolve_request_class():
    # explicit header wins, case-insensitively, over any token shape
    assert resolve_request_class({"x-request-class": "rag"}, 4, 4) == "rag"
    assert resolve_request_class({"X-Request-Class": "BATCH"}, 4, 4) == (
        "batch"
    )
    # invalid header value falls through to the heuristic
    assert resolve_request_class({"x-request-class": "vip"}, 4, 4) == "chat"
    # prompt-heavy shape (long context, short answer) → rag
    assert resolve_request_class(None, 1024, 32) == "rag"
    # long prompt with a long answer is NOT rag
    assert resolve_request_class(None, 1024, 400) == "chat"
    # very long decode → batch
    assert resolve_request_class(None, 16, 600) == "batch"
    # everything else → chat
    assert resolve_request_class(None, 16, 16) == "chat"
    assert resolve_request_class({}, 16, None) == "chat"


def test_parse_slo_config():
    defaults = parse_slo_config(None)
    assert defaults["chat"]["ttft_p99_s"] == 10.0
    assert set(defaults) == {"chat", "rag", "batch"}

    # inline JSON overrides only the declared fields
    tightened = parse_slo_config('{"chat": {"ttft_p99_s": 0.5}}')
    assert tightened["chat"]["ttft_p99_s"] == 0.5
    assert tightened["chat"]["itl_p99_s"] == defaults["chat"]["itl_p99_s"]
    assert tightened["rag"] == defaults["rag"]

    # unknown classes are ignored, not installed
    assert "vip" not in parse_slo_config('{"vip": {"ttft_p99_s": 1}}')

    # malformed input degrades to defaults — a bad operator config
    # must not take serving down
    assert parse_slo_config("{not json") == defaults
    assert parse_slo_config("/nonexistent/slo.json") == defaults


def test_parse_slo_config_from_file(tmp_path):
    p = tmp_path / "slo.json"
    p.write_text('{"batch": {"availability": 0.9}}')
    cfg = parse_slo_config(str(p))
    assert cfg["batch"]["availability"] == 0.9


# -------------------------------------------------------------- slo engine


def test_slo_attainment_and_burn():
    clock = _FakeClock()
    slo = SloEngine(timer=clock)

    # no traffic is not an SLO violation
    assert slo.attainment("chat", "ttft") == 1.0
    assert slo.burn_rate("chat") == 0.0

    # chat ttft_p99_s default is 10.0: 98 good + 2 bad → 98% attainment
    for _ in range(98):
        slo.observe_ttft("chat", 1.0)
    for _ in range(2):
        slo.observe_ttft("chat", 30.0)
    assert slo.attainment("chat", "ttft") == pytest.approx(0.98)
    # burn = (1 - 0.98) / 0.01 budget = 2x
    assert slo.burn_rate("chat", "5m") == pytest.approx(2.0)

    # unknown class never raises on the hot path
    slo.observe_ttft("vip", 1.0)
    assert slo.attainment("vip", "ttft") == 1.0


def test_slo_windows_slide():
    clock = _FakeClock()
    slo = SloEngine(timer=clock)
    slo.observe_ttft("chat", 99.0)  # one breach
    assert slo.attainment("chat", "ttft", "5m") < 1.0
    assert slo.attainment("chat", "ttft", "1h") < 1.0

    # 6 minutes later the 5m window has forgotten it; the 1h has not
    clock.advance(360.0)
    assert slo.attainment("chat", "ttft", "5m") == 1.0
    assert slo.attainment("chat", "ttft", "1h") < 1.0

    clock.advance(3600.0)
    assert slo.attainment("chat", "ttft", "1h") == 1.0


def test_slo_availability_excludes_aborts():
    clock = _FakeClock()
    slo = SloEngine(timer=clock)
    slo.observe_outcome("chat", "finish")
    slo.observe_outcome("chat", "abort")  # client hangup: excluded
    assert slo.attainment("chat", "availability") == 1.0

    slo.observe_outcome("chat", "shed")
    slo.observe_outcome("chat", "failed")
    # 1 good / 3 counted; budget = 1 - 0.999
    assert slo.attainment("chat", "availability") == pytest.approx(1 / 3)
    assert slo.burn_rate("chat") == pytest.approx((2 / 3) / 0.001)


def test_slo_declared_objectives_change_goodness():
    clock = _FakeClock()
    slo = SloEngine(
        parse_slo_config('{"chat": {"ttft_p99_s": 0.05}}'), timer=clock
    )
    slo.observe_ttft("chat", 1.0)  # fine by default, breach at 50 ms
    assert slo.attainment("chat", "ttft") == 0.0
    assert slo.burn_rate("chat") == pytest.approx(100.0)


def test_slo_debug_and_stats_surfaces():
    slo = SloEngine()
    slo.observe_ttft("chat", 999.0)
    frag = slo.stats_fragment()
    assert frag.startswith("slo burn(5m)")
    assert "chat" in frag
    state = slo.debug_state()
    chat = state["classes"]["chat"]
    assert chat["objectives"]["ttft_p99_s"] == 10.0
    assert chat["windows"]["5m"]["burn_rate"] == pytest.approx(100.0)
    assert chat["windows"]["5m"]["ttft"]["samples"] == 1
    assert state["observed_total"] == 1


# ----------------------------------------------------------------- ledger


def _metrics(arrival=100.0, scheduled=101.0, first=103.0, last=109.0):
    class M:
        arrival_time = arrival
        first_scheduled_time = scheduled
        first_token_time = first
        last_token_time = last
        time_in_queue = None

    return M()


def test_ledger_lifecycle_and_phase_split():
    ledger = CostLedger()
    rec = ledger.open("r1", tenant="acme", request_class="rag",
                      tokens_in=7, lora_name="ad")
    assert rec is not None
    assert ledger.open_count == 1

    # duplicate id racing admission: the live record is never clobbered
    assert ledger.open("r1", tenant="evil") is None
    assert ledger.get("r1").tenant == "acme"

    ledger.note_tokens_out("r1", 3)
    ledger.note_tokens_out("r1", 2)
    ledger.note_adapter_swap("r1")
    ledger.note_tier_bytes("r1", 4096)
    ledger.note_spec("r1", proposed=8, accepted=5)
    ledger.note_restart("r1")
    ledger.note_resume("r1", "cross_replica")
    ledger.note_resume("r1", "handoff")  # bumps resumes AND handoffs

    closed = ledger.close("r1", "finish", request_metrics=_metrics())
    assert closed.tokens_out == 5
    assert closed.queue_s == pytest.approx(1.0)
    assert closed.prefill_s == pytest.approx(2.0)
    assert closed.decode_s == pytest.approx(6.0)
    assert closed.adapter_swaps == 1
    assert closed.tier_bytes == 4096
    assert (closed.spec_proposed, closed.spec_accepted) == (8, 5)
    assert closed.restarts == 1
    assert closed.resumes == 2
    assert closed.handoffs == 1

    # close is idempotent; totals folded exactly once
    assert ledger.close("r1", "finish") is None
    assert ledger.open_count == 0
    totals = ledger.tenant_totals()["acme"]["rag"]
    assert totals["tokens_out"] == 5
    assert totals["requests"] == 1

    # note_* on unknown ids are silent no-ops — telemetry never raises
    ledger.note_tokens_out("ghost", 5)
    ledger.note_shed("ghost", "queue_full")


def test_ledger_shed_wins_over_stream_outcome():
    """A TTL-shed request's stream exit looks like an abort; the ledger
    must still bill it as shed (refused, not cancelled)."""
    ledger = CostLedger()
    ledger.open("r2", tenant=None)
    ledger.note_shed("r2", "queue_deadline")
    rec = ledger.close("r2", "abort")
    assert rec.outcome == "shed"
    assert rec.shed_reason == "queue_deadline"
    assert ledger.by_outcome["shed"] == 1

    # unknown outcome strings coerce to failed, never KeyError
    ledger.open("r3", tenant=None)
    assert ledger.close("r3", "exploded").outcome == "failed"


def test_ledger_tenant_label_budget():
    """Unbounded tenant ids must not explode label cardinality: past
    the budget, new tenants fold into the 'other' label (per-request
    records in the JSONL sink keep the real id)."""
    from vllm_tgis_adapter_tpu.telemetry.ledger import (
        _MAX_TENANT_LABELS,
        _OVERFLOW_TENANT,
    )

    ledger = CostLedger()
    for i in range(_MAX_TENANT_LABELS + 10):
        ledger.open(f"r{i}", tenant=f"tenant-{i:04d}")
        ledger.close(f"r{i}", "finish")
    tenants = ledger.tenant_totals()
    assert len(tenants) == _MAX_TENANT_LABELS + 1
    assert tenants[_OVERFLOW_TENANT]["chat"]["requests"] == 10


def test_ledger_kv_page_sampling():
    ledger = CostLedger()
    ledger.open("r1", tenant=None)
    ledger.sample_kv({"r1": 4, "ghost": 9}, dt_s=0.5)
    ledger.sample_kv({"r1": 8}, dt_s=0.25)
    rec = ledger.close("r1", "finish")
    assert rec.hbm_page_seconds == pytest.approx(4 * 0.5 + 8 * 0.25)


def test_jsonl_sink(tmp_path):
    path = tmp_path / "ledger.jsonl"
    sink = JsonlSink(str(path))
    sink.append({"a": 1})
    sink.append({"b": 2})
    assert sink.pending == 2
    assert not path.exists()  # buffered: nothing hits disk on the loop

    asyncio.run(sink.flush())
    assert sink.pending == 0
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert lines == [{"a": 1}, {"b": 2}]

    sink.append({"c": 3})
    sink.flush_sync()
    assert json.loads(path.read_text().splitlines()[-1]) == {"c": 3}


def test_ledger_closed_record_reaches_sink_and_recorder(tmp_path):
    events = []
    sink = JsonlSink(str(tmp_path / "l.jsonl"))
    ledger = CostLedger(
        sink=sink,
        recorder=lambda kind, rid, **kw: events.append((kind, rid, kw)),
    )
    ledger.open("r1", tenant="t", request_class="chat", tokens_in=3)
    ledger.note_tokens_out("r1", 4)
    ledger.close("r1", "finish", step=17)

    assert sink.pending == 1
    sink.flush_sync()
    row = json.loads((tmp_path / "l.jsonl").read_text())
    assert (row["request_id"], row["outcome"]) == ("r1", "finish")
    assert (row["tokens_in"], row["tokens_out"]) == (3, 4)

    kind, rid, kw = events[0]
    assert (kind, rid) == ("ledger", "r1")
    assert kw["step"] == 17 and kw["outcome"] == "finish"

    # a raising recorder must not break close
    ledger.recorder = lambda *a, **kw: 1 / 0
    ledger.open("r2", tenant="t")
    assert ledger.close("r2", "finish") is not None


def test_spec_acceptance_ewma_feed():
    """The speculative decoder's acceptance EWMA (the
    spec_acceptance_rate_ewma gauge source) exists with the documented
    half-life and follows time-decay semantics."""
    from vllm_tgis_adapter_tpu.engine.speculative import SpeculativeDecoder

    spec = SpeculativeDecoder.__new__(SpeculativeDecoder)
    spec.acceptance_ewma = DecayedEwma(half_life_s=30.0)
    spec.acceptance_ewma.update(1.0, now=0.0)
    spec.acceptance_ewma.update(0.0, now=30.0)
    assert spec.acceptance_ewma.value == pytest.approx(0.5)


# ------------------------------------------------- conservation (engine)


def test_ledger_token_conservation_mixed_load(
    tiny_model_dir, adapter_cache_dir, tmp_path
):
    """The acceptance property: in a mixed chat + RAG + LoRA scenario,
    the sum of per-tenant ledger totals equals the engine's own token
    accounting — every delivered token billed to exactly one
    (tenant, class) cell, every request exactly one closed record."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_tgis_adapter_tpu.engine.lora import LoRARequest
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    ledger_log = tmp_path / "ledger.jsonl"
    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(block_size=16, num_blocks=64,
                                 cache_dtype=mcfg.dtype),
        scheduler_config=SchedulerConfig(max_num_seqs=4,
                                         prefill_buckets=(32,)),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(enabled=True, max_loras=2, max_lora_rank=2),
        ledger_log=str(ledger_log),
    )
    engine = AsyncLLMEngine.from_config(config)
    lora = LoRARequest(
        lora_name="tiny-lora", lora_int_id=1,
        lora_path=f"{adapter_cache_dir}/tiny-lora",
    )

    # (tenant, class-shape, lora, output_kind, prompt_len, max_tokens)
    plan = [
        ("acme", None, None, RequestOutputKind.DELTA, 8, 6),
        ("acme", "rag", None, RequestOutputKind.CUMULATIVE, 12, 4),
        ("globex", None, lora, RequestOutputKind.DELTA, 10, 5),
        (None, None, None, RequestOutputKind.FINAL_ONLY, 6, 7),
    ]

    async def drive(i, tenant, cls, lora_req, kind, n_in, n_out):
        streamed = 0
        async for out in engine.generate(
            prompt=None,
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=n_out, ignore_eos=True,
                output_kind=kind,
            ),
            request_id=f"mix-{i}",
            prompt_token_ids=list(range(3, 3 + n_in)),
            lora_request=lora_req,
            trace_headers={"x-request-class": cls} if cls else None,
            tenant_id=tenant,
        ):
            n = len(out.outputs[0].token_ids) if out.outputs else 0
            if kind == RequestOutputKind.DELTA:
                streamed += n
            else:
                streamed = n
        return streamed

    async def scenario():
        results = await asyncio.gather(*(
            drive(i, *spec) for i, spec in enumerate(plan)
        ))
        await engine.stop()
        return results

    streamed = asyncio.run(scenario())
    assert streamed == [spec[5] for spec in plan]  # engine-side truth

    # exactly one closed record per request, none left open
    assert engine.ledger.open_count == 0
    assert engine.ledger.closed_total == len(plan)
    assert engine.ledger.by_outcome["finish"] == len(plan)

    # conservation: sigma per-tenant totals == engine totals
    totals = engine.ledger.tenant_totals()
    billed_out = sum(
        cell["tokens_out"] for byclass in totals.values()
        for cell in byclass.values()
    )
    billed_in = sum(
        cell["tokens_in"] for byclass in totals.values()
        for cell in byclass.values()
    )
    assert billed_out == sum(streamed)
    assert billed_in == sum(spec[4] for spec in plan)

    # attribution: explicit header → rag cell; LoRA request with no
    # tenant header bills the adapter-owning tenant; bare requests
    # fall to the default tenant
    assert totals["acme"]["rag"]["tokens_out"] == plan[1][5]
    assert totals["acme"]["chat"]["tokens_out"] == plan[0][5]
    assert totals["globex"]["chat"]["tokens_out"] == plan[2][5]
    assert totals["default"]["chat"]["tokens_out"] == plan[3][5]

    # the --ledger-log sink got one JSONL row per request (flushed by
    # engine.stop), real tenant ids preserved
    rows = [
        json.loads(x) for x in ledger_log.read_text().splitlines()
    ]
    assert {r["request_id"] for r in rows} == {
        f"mix-{i}" for i in range(len(plan))
    }
    lora_row = next(r for r in rows if r["request_id"] == "mix-2")
    assert lora_row["lora_name"] == "tiny-lora"
    assert lora_row["decode_s"] >= 0.0

    # availability fed at close: all finished → burn 0, attainment 1
    assert engine.slo_engine.attainment("chat", "availability") == 1.0

    # debug-state sections exported for /debug/state
    state = engine.debug_state()
    assert state["ledger"]["open"] == 0
    assert state["ledger"]["closed_total"] == len(plan)
    assert "chat" in state["slo"]["classes"]
