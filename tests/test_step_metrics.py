"""Step-level engine telemetry (docs/OBSERVABILITY.md).

CPU-backed (tests/conftest.py forces JAX_PLATFORMS=cpu): TTFT and
inter-token-latency histogram feeding from the engine's commit phase,
batch-shape gauges from the plan phase, the XLA recompile tracker under
repeated and distinct dispatch shapes, and the on-demand profiler
controller behind /start_profile//stop_profile.
"""

from __future__ import annotations

import asyncio
import re

import pytest


def _sample(text: str, name: str, labels: tuple[str, ...] = ()) -> float:
    """Value of the first exposition line for ``name`` whose label set
    contains every string in ``labels`` (0.0 when absent)."""
    for line in text.splitlines():
        m = re.match(rf"^{re.escape(name)}(\{{[^}}]*\}})? (\S+)$", line)
        if m and all(lbl in (m.group(1) or "") for lbl in labels):
            return float(m.group(2))
    return 0.0


def _scrape() -> str:
    from vllm_tgis_adapter_tpu import metrics

    return metrics.render().decode()


def _build_engine(tiny_model_dir, **overrides):
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )

    mcfg = ModelConfig.from_pretrained(tiny_model_dir, dtype="float32")
    config = EngineConfig(
        model_config=mcfg,
        cache_config=CacheConfig(
            block_size=16, num_blocks=64, cache_dtype=mcfg.dtype
        ),
        scheduler_config=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(32, 64)
        ),
        parallel_config=ParallelConfig(),
        lora_config=LoRAConfig(),
        **overrides,
    )
    return AsyncLLMEngine.from_config(config)


async def _stream_one(engine, request_id: str, prompt_len: int = 17,
                      max_tokens: int = 8) -> int:
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    params = SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True
    )
    params.output_kind = RequestOutputKind.DELTA
    yields = 0
    async for _ in engine.generate(
        prompt=None,
        sampling_params=params,
        request_id=request_id,
        prompt_token_ids=list(range(3, 3 + prompt_len)),
    ):
        yields += 1
    return yields


def test_streaming_generation_feeds_step_metrics(tiny_model_dir):
    """Acceptance: one streaming generation leaves nonzero TTFT and
    inter-token sample counts on /metrics, and dispatching the same
    bucket shape twice increments the recompile counter exactly once."""
    engine = _build_engine(tiny_model_dir)

    before = _scrape()
    ttft_0 = _sample(before, "tgis_tpu_ttft_seconds_count")
    itl_0 = _sample(before, "tgis_tpu_inter_token_seconds_count")
    waves_0 = _sample(before, "tgis_tpu_decode_live_items_count")
    items_0 = _sample(before, "tgis_tpu_decode_live_items_sum")
    runs_0 = _sample(before, "tgis_tpu_attn_item_pages_count")
    pages_0 = _sample(before, "tgis_tpu_attn_item_pages_sum")
    # label deltas are per-engine: each engine owns fresh jitted fns, so
    # its first flat-bucket-16 ragged dispatch compiles exactly once
    prefill_lbl = ('fn="ragged_step"', 'shape="tokens=16"')
    compiles_0 = _sample(
        before, "tgis_tpu_xla_recompile_total", prefill_lbl
    )

    async def scenario() -> None:
        # two requests with the SAME prompt bucket: the second dispatch
        # must hit the compile cache
        assert await _stream_one(engine, "step-metrics-1") > 1
        await _stream_one(engine, "step-metrics-2")
        await engine.stop()

    asyncio.run(scenario())

    after = _scrape()
    assert _sample(after, "tgis_tpu_ttft_seconds_count") - ttft_0 == 2
    assert _sample(after, "tgis_tpu_inter_token_seconds_count") > itl_0
    assert (
        _sample(after, "tgis_tpu_xla_recompile_total", prefill_lbl)
        - compiles_0
        == 1
    ), "same prefill bucket dispatched twice must compile exactly once"
    # per-dispatch shape stats were fed by the plan phase
    assert _sample(after, "tgis_tpu_decode_batch_occupancy") > 0
    # every wave here holds ONE live row of 18-25 tokens of context:
    # two 16-token pages in one run of up to eight, so one live
    # (row, run of pages) item a wave, of two pages
    waves = _sample(after, "tgis_tpu_decode_live_items_count") - waves_0
    assert waves > 0
    assert (
        _sample(after, "tgis_tpu_decode_live_items_sum") - items_0
        == waves
    )
    # the CPU builds no mixed-step schedule, so only the waves observe
    # how full their items' runs are
    assert _sample(after, "tgis_tpu_attn_item_pages_count") - runs_0 == waves
    assert (
        _sample(after, "tgis_tpu_attn_item_pages_sum") - pages_0
        == 2 * waves
    )
    assert _sample(after, "tgis_tpu_packed_prefill_prompts_count") > 0
    assert _sample(after, "tgis_tpu_decode_step_seconds_count") > 0
    assert _sample(after, "tgis_tpu_prefill_step_seconds_count") > 0


def test_mixed_step_observes_its_real_item_count(tiny_model_dir,
                                                 monkeypatch):
    """Every served mixed ragged step observes the real-item count of
    its work schedule in ``tgis_tpu_ragged_live_items``: what the
    attention grid visits per KV head, not the schedule's compile width.
    The schedule exists where the Pallas path is taken, so the runner is
    steered there; the kernel's own choice is not, and the CPU serves
    through the XLA reference, which takes neither operand."""
    from vllm_tgis_adapter_tpu.ops import attention as attn_ops

    monkeypatch.setattr(attn_ops, "_use_pallas", lambda: True)
    engine = _build_engine(tiny_model_dir)
    runner = engine._replicas[0].engine.runner
    prepare = runner.prepare_ragged
    seen: list[tuple[int, int]] = []
    pages: list[int] = []

    def spy(plan):
        prep = prepare(plan)
        seen.append((prep.work_items, prep.work.shape[1]))
        pages.append(prep.work_pages)
        return prep

    monkeypatch.setattr(runner, "prepare_ragged", spy)
    before = _scrape()
    steps_0 = _sample(before, "tgis_tpu_ragged_live_items_count")
    items_0 = _sample(before, "tgis_tpu_ragged_live_items_sum")

    def kernel_items(text: str) -> float:
        """Every live item a ragged-kernel call of a plan visited: the
        mixed steps' and the waves', under either layout's label."""
        return sum(
            _sample(text, "tgis_tpu_ragged_items_total", (f'stats="{s}"',))
            for s in ("sublanes", "lanes"))

    def plan_items(text: str) -> float:
        return (_sample(text, "tgis_tpu_ragged_live_items_sum")
                + _sample(text, "tgis_tpu_decode_live_items_sum"))

    alone: list[int] = []

    async def scenario() -> None:
        await _stream_one(engine, "live-items-0", prompt_len=17)
        alone.extend(items for items, _ in seen)
        # a second prompt arrives while the first decodes: its prefill
        # shares mixed steps with the running row
        first = asyncio.create_task(
            _stream_one(engine, "live-items-1", prompt_len=17,
                        max_tokens=24)
        )
        await asyncio.sleep(0.5)
        await _stream_one(engine, "live-items-2", prompt_len=40)
        await first
        await engine.stop()

    asyncio.run(scenario())

    after = _scrape()
    # the planner cuts a lone 17-token prompt into its binary digits: a
    # 16-token step over the prompt's first page, then one token that
    # sees both of its 16-token pages in ONE run of up to eight; its
    # decode steps are waves
    assert alone == [1, 1]
    assert pages[:2] == [1, 2]
    assert len(seen) > len(alone)
    assert all(0 < items < width for items, width in seen)
    assert (
        _sample(after, "tgis_tpu_ragged_live_items_count") - steps_0
        == len(seen)
    )
    assert (
        _sample(after, "tgis_tpu_ragged_live_items_sum") - items_0
        == sum(items for items, _ in seen)
    )
    # one kernel call a plan in this family: the counter by layout holds
    # what the two histograms hold, and this model's tiles (4 query
    # heads over 2 KV heads, query blocks of at most 64) are all small
    assert kernel_items(after) - kernel_items(before) == (
        plan_items(after) - plan_items(before)) > 0
    assert _sample(after, "tgis_tpu_ragged_items_total",
                   ('stats="sublanes"',)) == _sample(
        before, "tgis_tpu_ragged_items_total", ('stats="sublanes"',))


def test_recompile_tracker_two_batch_shapes(tiny_model_dir):
    """Two distinct prefill bucket shapes each record their own labeled
    compile; re-dispatching either adds none."""
    engine = _build_engine(tiny_model_dir)
    lbl32 = ('fn="ragged_step"', 'shape="tokens=16"')
    lbl64 = ('fn="ragged_step"', 'shape="tokens=32"')
    before = _scrape()
    c32_0 = _sample(before, "tgis_tpu_xla_recompile_total", lbl32)
    c64_0 = _sample(before, "tgis_tpu_xla_recompile_total", lbl64)

    async def scenario() -> None:
        await _stream_one(engine, "shape-a", prompt_len=17)  # bucket 32
        await _stream_one(engine, "shape-b", prompt_len=40)  # bucket 64
        await _stream_one(engine, "shape-c", prompt_len=18)  # bucket 32 again
        await engine.stop()

    asyncio.run(scenario())

    after = _scrape()
    assert _sample(after, "tgis_tpu_xla_recompile_total", lbl32) - c32_0 == 1
    assert _sample(after, "tgis_tpu_xla_recompile_total", lbl64) - c64_0 == 1


def test_metrics_endpoint_serves_step_metrics(tiny_model_dir):
    """The HTTP /metrics route exposes the step-level families (the same
    bytes metrics.render() produces, via the real app dispatch)."""
    import argparse

    from vllm_tgis_adapter_tpu.http import HttpRequest, build_http_server

    engine = _build_engine(tiny_model_dir)
    args = argparse.Namespace(
        served_model_name=None, model=tiny_model_dir, api_key=None,
        root_path=None, profile_dir=None,
    )
    app = build_http_server(args, engine)

    async def scenario() -> bytes:
        response = await app.dispatch(
            HttpRequest("GET", "/metrics", {}, b"")
        )
        await engine.stop()
        return response.body

    body = asyncio.run(scenario()).decode()
    for family in (
        "tgis_tpu_ttft_seconds",
        "tgis_tpu_inter_token_seconds",
        "tgis_tpu_decode_step_seconds",
        "tgis_tpu_prefill_step_seconds",
        "tgis_tpu_decode_batch_occupancy",
        "tgis_tpu_decode_live_items",
        "tgis_tpu_ragged_live_items",
        'tgis_tpu_ragged_items_total{stats="sublanes"}',
        'tgis_tpu_ragged_items_total{stats="lanes"}',
        "tgis_tpu_attn_item_pages",
        "tgis_tpu_prefill_padding_waste",
        "tgis_tpu_padded_tokens_total",
        "tgis_tpu_packed_prefill_prompts",
        "tgis_tpu_preemptions_total",
        "tgis_tpu_xla_recompile_total",
        "tgis_tpu_xla_compile_seconds",
        "tgis_tpu_xla_compiled_shapes",
    ):
        assert family in body, f"{family} missing from /metrics"


def test_profiler_controller_lifecycle(tmp_path):
    from vllm_tgis_adapter_tpu.profiler import (
        ProfilerController,
        ProfilerError,
    )

    disabled = ProfilerController(None)
    assert not disabled.enabled
    with pytest.raises(ProfilerError):
        disabled.start()

    ctl = ProfilerController(str(tmp_path / "prof"))
    result = ctl.start()
    # CPU backends without a usable profiler degrade to a recorded no-op
    assert result["status"] in ("started", "noop")
    with pytest.raises(ProfilerError):
        ctl.start()  # double start
    result = ctl.stop()
    assert result["status"] in ("stopped", "noop")
    assert result["duration_seconds"] >= 0
    with pytest.raises(ProfilerError):
        ctl.stop()  # idle stop


def test_profile_http_routes(tiny_model_dir, tmp_path):
    import argparse

    from vllm_tgis_adapter_tpu import profiler
    from vllm_tgis_adapter_tpu.http import HttpRequest, build_http_server

    engine = _build_engine(tiny_model_dir)
    profiler.reset_controller()
    try:
        args = argparse.Namespace(
            served_model_name=None, model=tiny_model_dir, api_key=None,
            root_path=None, profile_dir=str(tmp_path / "prof"),
        )
        app = build_http_server(args, engine)

        async def scenario() -> list:
            statuses = []
            for route in ("/start_profile", "/start_profile",
                          "/stop_profile", "/stop_profile"):
                response = await app.dispatch(
                    HttpRequest("POST", route, {}, b"")
                )
                statuses.append(response.status)
            await engine.stop()
            return statuses

        # start, double-start conflict, stop, idle-stop conflict
        assert asyncio.run(scenario()) == [200, 409, 200, 409]
    finally:
        profiler.reset_controller()
