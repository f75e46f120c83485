"""Synchronous engine core: request admission → step loop → outputs.

The TPU-native engine beneath the serving layer.  Together with
``async_llm.AsyncLLMEngine`` it satisfies the capability surface the
reference adapter consumes from vLLM (SURVEY.md §2.3): add/abort requests,
continuous batching, per-step sampling, incremental detokenization, stop
detection, and per-request timing metrics (reference consumption points:
grpc_server.py:205-225, tgis_utils/logs.py:193-202).
"""

from __future__ import annotations

import time
from typing import Optional

from vllm_tgis_adapter_tpu.engine.config import EngineConfig
from vllm_tgis_adapter_tpu.engine.detokenizer import IncrementalDetokenizer
from vllm_tgis_adapter_tpu.engine.outputs import Logprob, RequestOutput
from vllm_tgis_adapter_tpu.engine.runner import (
    SYNC_DISPATCH,
    ModelRunner,
    PromptLogprobInfo,
    SampledToken,
)
from vllm_tgis_adapter_tpu.engine import sanitizer
from vllm_tgis_adapter_tpu.engine.sampling_params import (
    RequestOutputKind,
    SamplingParams,
)
from vllm_tgis_adapter_tpu.engine.scheduler import (
    DecodePlan,
    PrefillPlan,
    RaggedPlan,
    Scheduler,
)
from vllm_tgis_adapter_tpu.engine.sequence import Sequence, SequenceStatus
from vllm_tgis_adapter_tpu import metrics
from vllm_tgis_adapter_tpu.flight_recorder import (
    DECODE_PROGRESS_EVERY,
    FlightRecorder,
)
from vllm_tgis_adapter_tpu.supervisor import failpoints
from vllm_tgis_adapter_tpu.logging import init_logger

logger = init_logger(__name__)


def describe_plan(plan) -> Optional[dict]:  # noqa: ANN001
    """Small JSON-safe summary of a dispatch plan (the "in-flight batch
    plan" line of watchdog dumps and /debug/state)."""
    if plan is None:
        return None
    if isinstance(plan, RaggedPlan):
        return {
            "kind": "ragged",
            "bucket": plan.token_bucket,
            "total_tokens": plan.total_tokens,
            "num_decode": sum(1 for i in plan.items if i.is_decode),
            "num_prefill": sum(1 for i in plan.items if not i.is_decode),
            "num_verify": sum(
                1 for i in plan.items if i.spec_width > 0
            ),
            "fill_ratio": round(
                plan.total_tokens / plan.token_bucket, 4
            ) if plan.token_bucket else 0.0,
            "request_ids": [i.seq.request_id for i in plan.items],
        }
    if isinstance(plan, PrefillPlan):
        return {
            "kind": "prefill",
            "bucket": plan.bucket_len,
            "request_id": plan.seq.request_id,
            "start_pos": plan.start_pos,
            "chunk_tokens": len(plan.token_ids),
            "is_final": plan.is_final,
        }
    return {
        "kind": "decode",
        "batch_bucket": plan.batch_bucket,
        "num_seqs": len(plan.seqs),
        "num_steps": plan.num_steps,
        "request_ids": [s.request_id for s in plan.seqs],
    }


class LLMEngine:
    """Single-process engine: one model, one scheduler, one device program."""

    def __init__(self, config: EngineConfig, model, params, tokenizer,
                 mesh=None, memory_device=None, pp_devices=None):
        # device_report.py: how the pool came to its size, and how long
        # from_config spent loading weights (None for direct builds)
        import dataclasses as _dc

        self.kv_pool_sized_from = "config"
        self.weights_load_s: float | None = None
        if hasattr(model, "max_step_prefill_tokens"):
            # a model whose attention kernel bounds a step's work
            # schedule (models/deepseek_v2.py, models/cohere2_moe.py)
            # says how many prompt tokens a mixed step may hold; the
            # planner's chunk budget follows it, and with it the
            # flat-bucket ladder and the window group's band
            config = _dc.replace(
                config,
                scheduler_config=_dc.replace(
                    config.scheduler_config,
                    max_num_batched_tokens=min(
                        config.scheduler_config.max_num_batched_tokens,
                        model.max_step_prefill_tokens(
                            config.scheduler_config.max_num_seqs,
                            config.cache_config.block_size),
                    ),
                ),
            )
        if config.cache_config.num_blocks <= 0:
            # auto-size the KV pool from free HBM now that the weights are
            # resident (reference behavior: vLLM's gpu_memory_utilization)
            import jax

            from vllm_tgis_adapter_tpu.engine.kv_cache import (
                device_memory_stats,
                resolve_num_blocks,
            )

            if memory_device is None:
                memory_device = jax.local_devices()[0]
            self.kv_pool_sized_from = (
                "memory_stats"
                if device_memory_stats(memory_device).get("bytes_limit")
                else "static_fallback"
            )

            size_cfg = config
            pp = config.parallel_config.pipeline_parallel_size
            if pp > 1:
                # each device stores only its stage's layer slice, so a
                # block costs num_layers/pp of the whole-model estimate;
                # size against the LARGEST stage so every stage fits
                from vllm_tgis_adapter_tpu.engine.pipeline import (
                    split_layer_ranges,
                )

                stage_layers = max(
                    hi - lo
                    for lo, hi in split_layer_ranges(
                        config.model_config.num_layers, pp
                    )
                )
                size_cfg = _dc.replace(
                    config,
                    model_config=_dc.replace(
                        config.model_config, num_layers=stage_layers
                    ),
                )
            config = _dc.replace(
                config,
                cache_config=_dc.replace(
                    config.cache_config,
                    num_blocks=resolve_num_blocks(size_cfg, memory_device),
                ),
            )
        if (
            config.model_config.has_window_group
            and config.cache_config.num_window_blocks <= 0
        ):
            from vllm_tgis_adapter_tpu.engine.kv_cache import (
                window_blocks_for,
            )

            config = _dc.replace(
                config,
                cache_config=_dc.replace(
                    config.cache_config,
                    num_window_blocks=window_blocks_for(
                        config, config.cache_config.num_blocks),
                ),
            )
        self.config = config
        self.tokenizer = tokenizer
        if config.parallel_config.pipeline_parallel_size > 1:
            from vllm_tgis_adapter_tpu.engine.pipeline import PipelineRunner

            self.runner = PipelineRunner(config, model, params,
                                         devices=pp_devices)
        else:
            self.runner = ModelRunner(config, model, params, mesh=mesh)
        scheduler_config = config.scheduler_config
        self.scheduler = Scheduler(
            scheduler_config,
            config.cache_config,
            config.cache_config.num_blocks,
            max_model_len=config.max_model_len,
            num_window_blocks=(
                config.cache_config.num_window_blocks
                if config.model_config.has_window_group else 0
            ),
        )
        # ragged unified data path — THE serving planner
        # (docs/ATTENTION.md): the scheduler plans token-budgeted
        # RaggedPlans.  The legacy solo-prefill/fused-decode alternation
        # serves only pp>1 / sp>1 engines (no ragged plumbing through
        # the staged runner / sp ring yet) and prompt-logprob heads.
        # The RUNNER's mesh is authoritative for sp — callers (dp
        # replicas, the multichip dry run) may pass a mesh explicitly
        # without it appearing in parallel_config
        mcfg = config.model_config
        pcfg = config.parallel_config
        runner_mesh = getattr(self.runner, "mesh", None)
        mesh_sp = (
            dict(runner_mesh.shape).get("sp", 1)
            if runner_mesh is not None
            else 1
        )
        self.scheduler.ragged = (
            pcfg.pipeline_parallel_size == 1
            and pcfg.sequence_parallel_size == 1
            and mesh_sp == 1
        )
        # rolling-window KV eviction (scheduler docstring for the gates):
        # every layer banded, or the window layers' own group of pages
        # (which boot refuses prefix caching and speculation for)
        if (
            mcfg.sliding_window > 0
            and (not mcfg.full_layers or mcfg.has_window_group)
            and not config.cache_config.enable_prefix_caching
            and config.speculative is None
        ):
            self.scheduler.rolling_window = mcfg.group_window
        # --swap-space host KV swap for preemption victims.  Gates: the
        # flat ModelRunner cache only (pp stages split the layer axis),
        # and no rolling-window eviction (evicted low pages make the
        # [0, n) slot range unsaveable — recompute is cheap there anyway)
        self._swap_budget = int(config.swap_space_gib * (1 << 30))
        self._swap_used = 0
        if (
            self._swap_budget > 0
            and pcfg.pipeline_parallel_size == 1
            and self.scheduler.rolling_window == 0
        ):
            self.scheduler.swap_out_fn = self._swap_out_seq
            self.scheduler.swap_drop_fn = self._swap_drop_seq
        # host-RAM KV tier (--kv-host-cache-gb, engine/kv_tier.py): a
        # hash-addressed prefix-page store behind the swap machinery —
        # registered prompt pages demote device→host asynchronously,
        # prefix-cache misses the tier can cover PARK for an async
        # promotion, and preemption swap-out lands in the same store.
        # Same gates as --swap-space (flat ModelRunner cache only, no
        # rolling-window eviction).  0 (library default) is byte-
        # identical to the pre-tier engine; dp fleets and supervised
        # rebuilds re-attach a shared/surviving tier via adopt_kv_tier.
        self.kv_tier = None
        self._promotions: list = []  # (seq, ticket) awaiting apply
        self.kv_host_promoted_tokens = 0
        # fleet-level telemetry hooks (telemetry/): attached by the
        # async engine at build AND after every supervised rebuild —
        # None for direct core users, and every call site guards on it
        self.slo = None  # telemetry.slo.SloEngine
        self.ledger = None  # telemetry.ledger.CostLedger
        if (
            config.kv_host_cache_gb > 0
            and pcfg.pipeline_parallel_size == 1
            and self.scheduler.rolling_window == 0
            and not mcfg.has_recurrent_state
            and not mcfg.has_latent_cache
        ):
            from vllm_tgis_adapter_tpu.engine.kv_tier import HostKVTier

            self.kv_tier = HostKVTier(
                round(config.kv_host_cache_gb * (1 << 30)),
                config.cache_config.block_size,
            )
            if config.kv_disk_cache_gb > 0:
                # disk rung beneath host RAM (--kv-disk-cache-gb,
                # docs/MEMORY.md): host LRU victims — KV pages and
                # spilled adapters — cascade down; promotions walk
                # disk→host→device through the same park/promote gate
                from vllm_tgis_adapter_tpu.engine.kv_tier import (
                    DiskKVTier,
                )

                self.kv_tier.attach_disk(DiskKVTier(
                    round(config.kv_disk_cache_gb * (1 << 30)),
                    directory=config.kv_disk_cache_dir,
                    block_size=config.cache_config.block_size,
                ))
            self._wire_kv_tier()
        elif config.kv_host_cache_gb > 0:
            logger.warning(
                "--kv-host-cache-gb has no effect with pp > 1, "
                "rolling-window KV eviction, a model with recurrent "
                "state (a demoted page carries no scan state, "
                "docs/STATE.md) or a model with a latent cache (the "
                "tier's gather and scatter move per-head K and V pages, "
                "docs/LATENT_ATTENTION.md); host KV tier disabled, and "
                "with it decode resume"
            )
        # black-box lifecycle recorder (flight_recorder.py): every
        # admission/dispatch/preemption/finish appends one bounded ring
        # entry; the scheduler shares it for preemption events
        self.recorder = FlightRecorder()
        self.scheduler.recorder = self.recorder
        # step-time anatomy ring (telemetry/steptime.py): the step loop
        # stamps phase boundaries below and commit_step finalizes one
        # StepRecord per dispatch; per-engine like the recorder, so a
        # supervised rebuild starts a fresh ring with no re-attach
        from vllm_tgis_adapter_tpu.telemetry.steptime import (
            StepTimeline,
            backend_dispatch_blocks,
        )

        self.steptime = StepTimeline(
            dispatch_blocks=backend_dispatch_blocks()
        )
        # set by the async step loop while it plans every successor
        # beside its predecessor (engine/async_llm.py _run_loop): the
        # fused depth of a wave then follows the measured host turn
        # (_set_wave_depth).  Bails of that loop by reason, for
        # /debug/state (the counter is tgis_tpu_step_ahead_bails_total)
        self.overlapped = False
        self.ahead_bails: dict[str, int] = {}
        # monotonically increasing dispatch counter; stamps recorder
        # events so "which wave was in flight" is answerable post-hoc
        self.step_counter = 0
        # dp replica index (AsyncLLMEngine stamps it on every replica's
        # engine, and on rebuilt replacements): the `replica` label on
        # the per-dispatch step/occupancy metrics
        self.replica_index = 0
        # prefill/decode disaggregation (docs/SCALING.md "Disaggregated
        # roles"): stamped by the async layer via set_replica_role.  A
        # 'prefill' engine stages every sequence that samples its first
        # token into pending_handoffs at commit (the async layer drains
        # them onto decode-capable replicas); 'mixed' (default) is the
        # pre-disaggregation behavior.
        self.replica_role = "mixed"
        self.pending_handoffs: list = []
        self._seqs: dict[str, Sequence] = {}
        # explicit device slice (from_config sets it under dp/pp); the
        # supervisor's rebuild reuses it so a replacement engine lands
        # on the devices this replica owns
        self._devices = None
        self._lora_tokenizers: dict[str, object] = {}
        # adapter registry consumed by the gRPC adapter store
        # (grpc/adapters.py) and by the runner's device residency —
        # the paged pool (engine/adapter_pool.py) when the runner built
        # one, else the legacy sync_lora stacked tensors
        from vllm_tgis_adapter_tpu.engine.lora import LoRAManager

        pool = getattr(self.runner, "adapter_pool", None)
        self.lora_manager = LoRAManager(
            config.lora_config.max_loras,
            config.lora_config.max_lora_rank,
            moe_model=config.model_config.num_experts > 0,
            max_cpu_loras=(
                config.lora_config.resolved_max_cpu_loras()
                if pool is not None
                else 0
            ),
        )
        if pool is not None:
            pool.manager = self.lora_manager
            self.lora_manager.attach_pool(pool)
            # adapter-affinity scheduling: rows whose adapter is still
            # streaming PARK instead of blocking the batch
            self.scheduler.lora_gate = self._lora_gate
        elif config.lora_config.enabled:
            # legacy slow path: registry changes rebuild the stacks OFF
            # the event loop at load time (satellite of the pool work)
            self.lora_manager.add_resync(self)
        if self.kv_tier is not None and self.kv_tier.disk is not None:
            # cold adapters ride the same disk rung as cold KV pages:
            # host-registry evictions spill, later requests restore
            self.lora_manager.attach_disk_tier(self.kv_tier.disk)
        # unified paged HBM arena (engine/arena.py, docs/MEMORY.md):
        # adapter residency and KV pages draw from ONE block budget
        # with unified LRU + pinning.  Built only where both sides
        # exist (a paged adapter pool over the flat runner's
        # allocator); --no-unified-arena restores split budgets.
        self.arena = None
        if (
            pool is not None
            and config.unified_arena
            and config.parallel_config.pipeline_parallel_size == 1
        ):
            from vllm_tgis_adapter_tpu.engine.arena import UnifiedArena
            from vllm_tgis_adapter_tpu.engine.kv_cache import (
                _lora_stack_bytes,
                per_block_bytes,
            )

            alloc = self.scheduler.allocator
            page_bytes = per_block_bytes(config)
            self.arena = UnifiedArena(
                alloc,
                kv_page_bytes=page_bytes,
                min_kv_reserve=alloc.blocks_needed(config.max_model_len),
                # the padded slot stacks' boot-time HBM reservation, in
                # page units: adapter charges consume it before any KV
                # page is borrowed (resolve_num_blocks already priced
                # it out of the KV pool — charging the KV pool again
                # would double-count)
                adapter_budget_pages=-(
                    -_lora_stack_bytes(config) // page_bytes
                ),
            )
            alloc.arena = self.arena
            self.arena.attach_pool(pool)
            pool.arena = self.arena

    # ------------------------------------------------------------- lifecycle

    @classmethod
    def from_config(cls, config: EngineConfig, devices=None) -> "LLMEngine":
        """Build one engine replica.  ``devices``: explicit device slice
        this replica owns (dp replicas get disjoint slices from
        AsyncLLMEngine.from_config); None = all visible devices."""
        from transformers import AutoTokenizer

        from vllm_tgis_adapter_tpu.engine.weights import load_model_params
        from vllm_tgis_adapter_tpu.models import get_model_class

        from vllm_tgis_adapter_tpu.parallel import (
            make_place_fn,
            validate_tp_divisibility,
        )
        from vllm_tgis_adapter_tpu.parallel.mesh import (
            mesh_from_parallel_config,
        )

        if (
            config.parallel_config.data_parallel_size > 1
            or config.parallel_config.dp_replicas > 1
        ):
            # LLMEngine is always ONE dp rank: AsyncLLMEngine builds the
            # replica fleet and hands each LLMEngine a dp=1 config plus
            # its device slice.  Rejecting here (not per-branch) keeps
            # the pp and non-pp paths consistent — a dp>1 config (either
            # spelling) can never silently run at 1/dp capacity.
            raise ValueError(
                "LLMEngine is one dp replica; construct via "
                "AsyncLLMEngine.from_config for --data-parallel-size / "
                "--dp-replicas replicas"
            )
        mcfg = config.model_config
        pcfg = config.parallel_config
        if (
            mcfg.moe_dispatch == "capacity"
            and not mcfg.moe_record_drops
            and pcfg.tensor_parallel_size
            * pcfg.pipeline_parallel_size
            * pcfg.sequence_parallel_size == 1
        ):
            # observable capacity drops (metrics.py record_moe_dispatch);
            # multi-device meshes skip the host callback — it would run
            # per-shard inside the SPMD program and stall collectives
            import dataclasses as _dc

            mcfg = _dc.replace(mcfg, moe_record_drops=True)
        model_cls = get_model_class(mcfg.model_type)
        model = model_cls(mcfg)
        # build the mesh BEFORE loading so every tensor is sharded onto it
        # as it is read — sharding after a full single-device load would
        # OOM device 0 for models that need TP in the first place
        mesh = None
        pp = config.parallel_config.pipeline_parallel_size
        if pp > 1:
            # stage-routed placement: each layer's tensors land directly
            # on its pipeline stage's device group (engine/pipeline.py)
            from vllm_tgis_adapter_tpu.engine.pipeline import (
                make_pp_place_fn,
            )

            place = make_pp_place_fn(config, devices=devices)
        else:
            mesh = mesh_from_parallel_config(
                config.parallel_config, devices=devices
            )
            place = None
            if mesh is not None:
                validate_tp_divisibility(mcfg, mesh.shape["tp"])
                place = make_place_fn(mesh)
        logger.info("loading weights from %s", mcfg.model)
        load_t0 = time.monotonic()
        params = load_model_params(mcfg, mcfg.model, place=place)
        import jax

        # tpulint: disable=TPL201(boot, not the step loop: the load is timed to its end for the device report)
        jax.block_until_ready(params)
        weights_load_s = round(time.monotonic() - load_t0, 3)
        if config.quantization == "int8":
            # weight-only int8 after (possibly sharded) load; the KV pool
            # auto-sizing below sees the freed HBM.  The draft model (if
            # any) stays in the model dtype: it is small by construction
            # and its logits feed acceptance tests directly.
            from vllm_tgis_adapter_tpu.engine.weights import (
                quantize_params_int8,
            )

            params = quantize_params_int8(params)
            logger.info("quantized projection weights to int8 "
                        "(weight-only, per-out-channel scales)")

        # the draft loads BEFORE the engine so the KV-pool auto-sizing
        # (resolve_num_blocks, driven by post-weights free HBM) sees the
        # draft's parameter footprint too
        draft_model = draft_params = None
        if config.speculative is not None:
            spec = config.speculative
            logger.info(
                "loading speculative draft weights from %s", spec.draft_model
            )
            draft_cfg = spec.draft_model_config
            draft_model = get_model_class(draft_cfg.model_type)(draft_cfg)
            draft_params = load_model_params(
                draft_cfg, spec.draft_model, place=place
            )
            # calibrated kv-scale floors are a TARGET-cache feature
            # (runner pops them for the main params): the draft's
            # cache follows the target scheme and greedy acceptance
            # compares TARGET logits, so a calibrated draft checkpoint
            # must not leak this non-layer key into the draft pytree
            # (shard_llama_params / jitted programs would choke on it)
            if isinstance(draft_params, dict):
                draft_params.pop("kv_scale_floors", None)

        tokenizer = AutoTokenizer.from_pretrained(
            config.tokenizer or mcfg.model,
            revision=config.revision,
            trust_remote_code=config.trust_remote_code,
        )
        # KV auto-sizing must read free HBM from a device THIS replica
        # owns: under dp, device 0 belongs to replica 0 and is already
        # full of replica-0 weights by the time later replicas size
        # their pools
        memory_device = devices[0] if devices else None
        engine = cls(config, model, params, tokenizer, mesh=mesh,
                     memory_device=memory_device, pp_devices=devices)
        # remembered for supervised rebuild (supervisor/supervisor.py):
        # a replacement engine must own the SAME device slice — under dp
        # the other slices hold other replicas' weights and pools
        engine._devices = devices
        engine.weights_load_s = weights_load_s
        if draft_model is not None:
            engine.attach_speculative(draft_model, draft_params)
        from vllm_tgis_adapter_tpu.engine.device_report import (
            device_report,
            format_report,
        )

        logger.info(format_report(device_report(engine)))
        return engine

    def attach_speculative(self, draft_model, draft_params) -> None:  # noqa: ANN001
        """Attach the draft model (speculative decoding): the runner
        builds the propose + jitted ragged-verify programs and the
        scheduler starts planning verify spans for spec-eligible rows
        (docs/ATTENTION.md "Speculative decoding")."""
        self.runner.attach_speculative(draft_model, draft_params)
        if not self.scheduler.ragged:
            # legacy-planner engine (an explicitly passed sp mesh the
            # config-level refusals cannot see): the draft would sit
            # resident without a verify span ever planned
            logger.warning(
                "speculative draft attached to a legacy-planner engine "
                "(pp/sp): verify spans ride the ragged planner only — "
                "speculation will not run (docs/ATTENTION.md)"
            )
        if self.config.speculative is not None:
            self.scheduler.set_spec_gamma(
                self.config.speculative.num_speculative_tokens
            )

    def get_tokenizer(self, lora_request=None):  # noqa: ANN001
        """Base tokenizer, or the adapter's own if its directory ships
        tokenizer files (reference behavior: per-LoRA tokenizers,
        /root/reference/src/vllm_tgis_adapter/grpc/grpc_server.py:648-652).
        """
        path = getattr(lora_request, "lora_path", None)
        if not path:
            return self.tokenizer
        cached = self._lora_tokenizers.get(path)
        if cached is not None:
            return cached
        import os

        has_tok = any(
            os.path.exists(os.path.join(path, f))
            for f in ("tokenizer.json", "tokenizer_config.json",
                      "tokenizer.model")
        )
        tok = self.tokenizer
        if has_tok:
            from transformers import AutoTokenizer

            tok = AutoTokenizer.from_pretrained(
                path, trust_remote_code=self.config.trust_remote_code
            )
        self._lora_tokenizers[path] = tok
        return tok

    def get_model_config(self):
        return self.config.model_config

    # -------------------------------------------------------------- requests

    def add_request(
        self,
        request_id: str,
        prompt: Optional[str],
        params: SamplingParams,
        *,
        prompt_token_ids: Optional[list[int]] = None,
        arrival_time: Optional[float] = None,
        lora_name: Optional[str] = None,
        trace_id: Optional[str] = None,
        deadline: Optional[float] = None,
        tenant_id: Optional[str] = None,
        request_class: Optional[str] = None,
    ) -> None:
        if request_id in self._seqs:
            raise ValueError(f"duplicate request_id {request_id!r}")
        if prompt_token_ids is None:
            if prompt is None:
                raise ValueError("either prompt or prompt_token_ids required")
            prompt_token_ids = self.tokenizer(prompt).input_ids
        max_len = self.config.max_model_len
        if len(prompt_token_ids) >= max_len:
            raise ValueError(
                f"prompt length {len(prompt_token_ids)} exceeds "
                f"max_model_len {max_len}"
            )
        if (
            params.prompt_logprobs is not None
            and self.config.model_config.has_recurrent_state
        ):
            raise ValueError(
                "prompt logprobs are not served for model_type "
                f"{self.config.model_config.model_type!r} yet: they ride "
                "the legacy solo-prefill program, which does not carry "
                "recurrent state between chunks (docs/STATE.md); ask for "
                "generated-token logprobs only"
            )
        if (
            params.prompt_logprobs is not None
            and self.config.model_config.has_latent_cache
        ):
            raise ValueError(
                "prompt logprobs are not served for model_type "
                f"{self.config.model_config.model_type!r} yet: they ride "
                "the legacy solo-prefill program, which knows no latent "
                "pool (docs/LATENT_ATTENTION.md); ask for generated-token "
                "logprobs only"
            )
        if (
            params.prompt_logprobs is not None
            and self.config.model_config.has_window_group
        ):
            raise ValueError(
                "prompt logprobs are not served for model_type "
                f"{self.config.model_config.model_type!r} yet: they ride "
                "the legacy solo-prefill program, which knows one block "
                "table (docs/ATTENTION.md); ask for generated-token "
                "logprobs only"
            )
        seq = Sequence(
            request_id,
            prompt,
            list(prompt_token_ids),
            params,
            arrival_time=arrival_time,
            fallback_seed=self.runner.new_fallback_seed(),
            lora_name=lora_name,
        )
        seq.trace_id = trace_id
        seq.tenant_id = tenant_id
        if request_class is not None:
            seq.request_class = request_class
        # queue TTL (frontdoor): the async layer passes the effective
        # deadline (request SLO ∧ arrival + --queue-ttl, stamped before
        # any fair-queue parking); direct core users get the same
        # tightening from THEIR arrival time here
        fd = getattr(self.config, "frontdoor", None)
        if (
            fd is not None
            and fd.enabled
            and fd.queue_ttl_s > 0
            # precompile warmups (__warmup_*) wait behind tens of
            # seconds of XLA compiles by design — a TTL shed there
            # would silently lose bucket coverage
            and not request_id.startswith("__warmup")
        ):
            ttl_deadline = seq.metrics.arrival_time + fd.queue_ttl_s
            deadline = (
                ttl_deadline
                if deadline is None
                else min(deadline, ttl_deadline)
            )
        seq.deadline = deadline
        self._prepare_admission(seq)
        self._commit_admission(seq)
        self.recorder.record(
            "admit", request_id, step=self.step_counter, trace_id=trace_id,
            prompt_tokens=len(prompt_token_ids),
            **({"lora": lora_name} if lora_name else {}),
        )

    def _prepare_admission(self, seq: Sequence) -> None:
        """Per-request machinery shared by fresh admission and decode
        resume: adapter residency, speculative eligibility, FSM
        compilation (left at its init state — resume replays it), and
        the incremental detokenizer (empty — resume replays it)."""
        params = seq.params
        lora_name = seq.lora_name
        pool = getattr(self.runner, "adapter_pool", None)
        if pool is None:
            seq.lora_slot = self.lora_manager.slot_of(lora_name)
        else:
            # pool mode: the slot is resolved at SCHEDULE time by the
            # adapter gate once the weights are device-resident; issue
            # the prefetch NOW so the host→device stream overlaps the
            # queue wait (and a supervised rebuild re-streams exactly
            # the adapters its replayed/resumed requests reference)
            seq.lora_slot = 0
            if lora_name is not None:
                pool.note_lookup(lora_name, replica=self.replica_index)
                resident = pool.prefetch(lora_name)
                if not resident and self.ledger is not None:
                    # cost attribution: this admission is the one that
                    # pulls the adapter onto the device
                    self.ledger.note_adapter_swap(seq.request_id)
        if self.runner.spec is not None:
            from vllm_tgis_adapter_tpu.engine.speculative import (
                spec_eligible,
            )

            # greedy rows verify by argmax match, sampled rows by
            # rejection sampling; LoRA rows verify through the adapted
            # target (engine/speculative.py spec_eligible)
            seq.spec_eligible = spec_eligible(params)
        if params.structured_outputs is not None:
            from vllm_tgis_adapter_tpu.engine.constrained import compile_fsm

            seq.fsm = compile_fsm(
                params.structured_outputs,
                self.tokenizer,
                self.config.model_config.eos_token_id,
            )
            seq.fsm_state = seq.fsm.init_state
        seq.detokenizer = IncrementalDetokenizer(
            self.tokenizer,
            seq.prompt_token_ids,
            skip_special_tokens=params.skip_special_tokens,
        )

    def _commit_admission(self, seq: Sequence) -> None:
        """Hand a fully prepared sequence to the scheduler.

        Pinned only once admission can no longer fail — an exception in
        preparation must not leak a ref no finish path will release;
        the pin covers the sequence's whole lifetime (incl.
        preemption-resume: eviction must not reassign a slot a running
        row still indexes)."""
        self.lora_manager.pin(seq.lora_name)
        self._seqs[seq.request_id] = seq
        self.scheduler.add(seq)

    def abort_request(self, request_id: str) -> Optional[RequestOutput]:
        seq = self._seqs.pop(request_id, None)
        if seq is None or seq.is_finished:
            return None
        self.scheduler.abort(request_id)
        self.lora_manager.unpin(seq.lora_name)
        seq.metrics.finished_time = time.time()
        self.recorder.record(
            "abort", request_id, step=self.step_counter,
            trace_id=seq.trace_id, output_tokens=seq.num_output_tokens,
        )
        return seq.to_request_output()

    def has_unfinished_requests(self) -> bool:
        # newly_finished counts: a scheduler-rejected/shed request's
        # final output is emitted by the NEXT plan_step — the step loop
        # must not park before that drain or the client hangs
        return (
            self.scheduler.num_unfinished > 0
            or bool(self.scheduler.newly_finished)
        )

    # ---------------------------------------------------------------- LoRA

    def _lora_gate(self, seq: Sequence) -> bool:
        """Scheduler adapter gate (pool mode): True when ``seq``'s
        adapter is device-resident (slot resolved onto the sequence);
        False parks the request while the pool streams it in."""
        name = seq.lora_name
        if name is None:
            return True
        slot = self.runner.adapter_pool.ensure_resident(name)
        if slot is None:
            return False
        seq.lora_slot = slot
        return True

    def adopt_lora_manager(self, manager) -> None:  # noqa: ANN001
        """Point this engine at a shared/survivor adapter registry (dp
        fleet construction, supervised rebuild) and re-attach the
        runner's pool (or legacy resync hook) to it."""
        self.lora_manager = manager
        pool = getattr(self.runner, "adapter_pool", None)
        if pool is not None:
            pool.manager = manager
            manager.attach_pool(pool)
        elif self.config.lora_config.enabled:
            manager.add_resync(self)
        if (
            self.kv_tier is not None
            and self.kv_tier.disk is not None
        ):
            manager.attach_disk_tier(self.kv_tier.disk)

    # -------------------------------------------------------------- KV swap

    def _swap_out_seq(self, seq: Sequence) -> bool:
        """Preemption hook (scheduler._preempt_youngest): copy the
        victim's computed KV to host within the --swap-space budget.
        Cache coverage invariant between dispatches: positions
        [0, num_tokens-1) are written; the next decode writes
        num_tokens-1."""
        n = seq.num_tokens - 1
        if n <= 0 or seq.blocks is None:
            return False
        if self.kv_tier is not None:
            # the victim's full pages ALSO land in the hash-addressed
            # host tier: the per-seq swap copy below restores this one
            # request, the tier serves every future request sharing the
            # prefix (and survives an engine restart)
            self._tier_demote(seq, seq.all_token_ids, written=n)
        slots = seq.blocks.slots_for_range(0, n)
        k_cache, _ = self.runner.caches
        per_slot = (
            2 * k_cache.shape[0] * k_cache.shape[1] * k_cache.shape[3]
            * k_cache.dtype.itemsize
        )
        nbytes = per_slot * len(slots)
        if self._swap_used + nbytes > self._swap_budget:
            logger.info(
                "swap-space full (%d/%d bytes): request %s falls back to "
                "recompute", self._swap_used, self._swap_budget,
                seq.request_id,
            )
            return False
        k_host, v_host = self.runner.extract_kv(slots)
        seq.swapped = (k_host, v_host, n, nbytes)
        self._swap_used += nbytes
        seq.metrics.events.append(("swap_out", time.time_ns()))
        self.recorder.record(
            "swap_out", seq.request_id, step=self.step_counter,
            trace_id=seq.trace_id, tokens=n, bytes=nbytes,
        )
        metrics.kv_swap_out_total.labels(
            replica=str(self.replica_index)
        ).inc()
        # inc/dec (not set): dp replicas share the process-global gauge,
        # so absolute sets from different replicas would clobber
        metrics.kv_swap_used_bytes.inc(nbytes)
        return True

    def _swap_drop_seq(self, seq: Sequence) -> None:
        """Release a held host copy (recompute admission won the race)."""
        if seq.swapped is not None:
            self._swap_used -= seq.swapped[3]
            metrics.kv_swap_used_bytes.dec(seq.swapped[3])

    def _drain_swap_ins(self) -> None:
        """Restore swapped queue heads on a clean dispatch boundary (the
        caches rebind must not race an in-flight dispatch's commit)."""
        while True:
            seq = self.scheduler.try_swap_in()
            if seq is None:
                return
            k_host, v_host, n, nbytes = seq.swapped
            self.runner.restore_kv(
                seq.blocks.slots_for_range(0, n), k_host, v_host
            )
            # the new batch row may hold a stale seen-token matrix from a
            # previous occupant; prefill's seeding is skipped on swap-in
            self.runner.reseed_seen_row(seq.slot, seq.all_token_ids)
            seq.swapped = None
            self._swap_used -= nbytes
            seq.metrics.events.append(("swap_in", time.time_ns()))
            self.recorder.record(
                "swap_in", seq.request_id, step=self.step_counter,
                trace_id=seq.trace_id, tokens=n,
            )
            metrics.kv_swap_in_total.labels(
                replica=str(self.replica_index)
            ).inc()
            metrics.kv_swap_used_bytes.dec(nbytes)
            logger.info("restored request %s from host swap (%d tokens)",
                        seq.request_id, n)

    # --------------------------------------------------------- host KV tier

    def _wire_kv_tier(self) -> None:
        self.scheduler.kv_gate = self._kv_tier_gate
        if self.config.cache_config.enable_prefix_caching:
            # eviction → demotion: a registered page copies to the host
            # tier at the moment the device LRU reclaims it — never
            # earlier, so pages the device keeps (or that are never
            # reused) cost no transfer (ISSUE 9 integration point 1)
            self.scheduler.allocator.evict_hook = self._tier_evict_demote
        if self.scheduler.swap_out_fn is None:
            # no --swap-space: preemption victims demote their computed
            # full pages into the hash-addressed store instead (resume
            # then recomputes only the uncovered tail via promotion)
            self.scheduler.swap_out_fn = self._tier_swap_out

    def set_replica_role(self, role: str) -> None:
        """Stamp this replica's disaggregation role (async layer /
        supervisor rebuild).  A decode replica's admission throat is the
        in-flight-promotion bound — every handoff arrives as a parked
        promotion — so it gets a wider bound than the mixed default
        (each parked promotion still reserves its full prompt pages;
        the kv gate's recompute fallback stays the overflow valve)."""
        self.replica_role = role
        self.scheduler.role = role
        if role == "decode":
            self.MAX_INFLIGHT_PROMOTIONS = 32
        else:
            # restore the class default on re-role: a widened bound
            # left behind on a now-mixed replica would let 32 parked
            # promotions reserve full prompt capacity each — the
            # pool-thrash the default of 8 exists to prevent
            self.__dict__.pop("MAX_INFLIGHT_PROMOTIONS", None)

    def adopt_kv_tier(self, tier) -> None:  # noqa: ANN001
        """Point this engine at a shared/surviving host KV tier (dp
        fleet construction, supervised rebuild).  The construction-time
        fresh tier (if any) is discarded; in-flight promotion tickets
        stay with the engine that issued them — their target pages
        belong to that engine's (possibly dead) pool."""
        if tier is None:
            return
        if self.config.parallel_config.pipeline_parallel_size > 1:
            return  # no flat cache to gather/scatter against
        self.kv_tier = tier
        self._wire_kv_tier()
        if tier.disk is not None:
            # adapter spill/restore follows the surviving tier's disk
            self.lora_manager.attach_disk_tier(tier.disk)

    def _tier_demote(
        self,
        seq: Sequence,
        token_ids: list[int],
        written: Optional[int] = None,
    ) -> int:
        """Queue ``seq``'s full pages of ``token_ids`` that the host
        tier lacks: per-page jitted device gathers are ENQUEUED here
        (ordered before any later dispatch that could overwrite the
        pages, so the read content is the content current now), and the
        tier's worker thread completes the device→host copies off the
        event loop.  Returns the number of pages queued.

        ``written`` caps demotion at the cache-coverage frontier: a
        page may only tier when EVERY position it covers has its K/V
        written.  Preemption passes ``num_tokens - 1`` (the invariant
        ``_swap_out_seq`` documents: the just-sampled token's slot is
        written by the NEXT dispatch) — without the cap, the last page
        could carry one garbage position into the hash-addressed store
        and poison every future chain extension through it."""
        tier = self.kv_tier
        if tier is None or seq.blocks is None:
            return 0
        bs = self.config.cache_config.block_size
        limit = len(token_ids) if written is None else min(
            len(token_ids), written
        )
        pages = min(limit // bs, len(seq.blocks.blocks))
        if pages <= 0:
            return 0
        from vllm_tgis_adapter_tpu.engine.kv_cache import chain_digests

        digests = chain_digests(token_ids, bs, seq.lora_name, pages)
        batch = []
        for p in range(pages):
            if tier.has(digests[p]) or seq.blocks.blocks[p] < 0:
                continue
            start = p * bs
            # the gathered tuple is (k, v) — plus the per-head scale
            # columns under --kv-quantization — stored verbatim so the
            # eventual restore is bit-exact
            batch.append((
                digests[p],
                *self.runner.gather_kv_block(
                    seq.blocks.slots_for_range(start, start + bs)
                ),
            ))
        if not batch:
            return 0
        tier.submit(batch)
        self.recorder.record(
            "demote_host", seq.request_id, step=self.step_counter,
            trace_id=seq.trace_id, pages=len(batch),
        )
        if self.ledger is not None:
            self.ledger.note_tier_bytes(
                seq.request_id, len(batch) * self._tier_page_bytes()
            )
        return len(batch)

    def _tier_swap_out(self, seq: Sequence) -> bool:
        """Preemption hook when the tier is on and --swap-space is not:
        the victim's computed full pages land in the hash-addressed
        store (keyed over prompt ‖ generated tokens, so the resume's
        promotion walk matches), and re-admission recomputes only the
        uncovered tail.  Returns False — ``seq.swapped`` is never set;
        the store, not a per-sequence copy, owns the bytes."""
        self._tier_demote(
            seq, seq.all_token_ids, written=seq.num_tokens - 1
        )
        return False

    # cap on promotions in flight per engine: each parked promotion
    # reserves its request's full prompt pages, so an unbounded warm
    # backlog could thrash the pool via preemption of its own parked
    # work; excess candidates simply admit on the recompute path
    MAX_INFLIGHT_PROMOTIONS = 8

    def _tier_evict_demote(self, digest: bytes, block: int) -> None:
        """Allocator evict hook: ONE registered page is about to be
        reclaimed — gather it now (device-ordered before the reclaiming
        owner's first write) and hand it to the tier's async committer.
        Runs under the engine lock inside planning/admission."""
        tier = self.kv_tier
        if tier is None or tier.has(digest):
            return
        bs = self.config.cache_config.block_size
        tier.submit([(
            digest,
            *self.runner.gather_kv_block(
                list(range(block * bs, (block + 1) * bs))
            ),
        )])
        self.recorder.record(
            "demote_host", step=self.step_counter, pages=1, block=block,
        )

    def _register_prefix(self, seq: Sequence) -> None:
        """Publish a completed prefill's pages for reuse: the device
        prefix cache, whose LRU eviction then demotes to the host tier
        (``_tier_evict_demote``) — or, when --enable-prefix-caching is
        OFF and only the host tier serves reuse, demote the (final,
        fully written) prompt pages directly at this commit."""
        self.scheduler.register_prefix(seq)
        if (
            self.kv_tier is not None
            and not self.config.cache_config.enable_prefix_caching
        ):
            self._tier_demote(seq, seq.prompt_token_ids)

    def _kv_tier_gate(self, seq: Sequence, start: bool = True) -> bool:
        """Scheduler kv gate: True = admit normally; False = the request
        PARKS while its host-tier-resident prefix promotes to device.
        ``start=True`` (planning paths) may begin a promotion: target
        pages are allocated NOW (device prefix hits adopted first, the
        host span on fresh pages) and the tier stages the transfer off
        the loop; ``start=False`` is a pure in-flight probe."""
        if seq.kv_promotion is not None:
            return False  # parked until _drain_promotions applies it
        if not start:
            return True
        if len(self._promotions) >= self.MAX_INFLIGHT_PROMOTIONS:
            # bound the pages parked promotions hold (each reserves its
            # full prompt capacity) and the transfer backlog: excess
            # warm candidates admit on the plain recompute path NOW and
            # later candidates re-gate once a promotion applies
            return True
        if (
            seq.prefill_pos != 0
            or seq.blocks is not None
            or seq.swapped is not None
            or seq.params.prompt_logprobs is not None  # _adoptable rule
        ):
            return True
        token_ids = seq.all_token_ids
        bs = self.config.cache_config.block_size
        max_pages = (len(token_ids) - 1) // bs  # match_prefix's cap
        if max_pages <= 0:
            return True
        alloc = self.scheduler.allocator
        matched = (
            alloc.peek_prefix(token_ids, seq.lora_name)
            if alloc.enable_prefix_caching
            else 0
        )
        start_page = matched // bs
        if start_page >= max_pages:
            return True  # device cache already covers everything usable
        # incremental probe: hashes only through the covered span, so a
        # cold-tier miss costs O(start_page + 1) hashes, not O(prompt)
        extra = self.kv_tier.peek_prefix_pages(
            token_ids, seq.lora_name, start_page
        )
        if extra <= 0:
            return True
        from vllm_tgis_adapter_tpu.engine.kv_cache import (
            SequenceBlocks,
            chain_digests,
        )

        digests = chain_digests(
            token_ids, bs, seq.lora_name, start_page + extra
        )
        lead = digests[start_page]
        for _, other in self._promotions:
            if not other.cancelled and lead in other.digests:
                # a sibling request is already streaming this span:
                # park WITHOUT a duplicate ticket — when the sibling
                # applies, its pages re-register in the device cache
                # and this request admits with device hits; if the
                # sibling fails, the next gate pass starts our own
                return False
        # promotion must not demand more than plain admission would: if
        # the pool cannot hold the whole prompt, let the normal path
        # wait/reject — never park a request the tier cannot unblock
        if not alloc.can_allocate(alloc.blocks_needed(len(token_ids))):
            return True
        seq.blocks = SequenceBlocks(alloc)
        if matched:
            hit_blocks, adopted = alloc.match_prefix(
                token_ids, seq.lora_name
            )
            seq.blocks.adopt(hit_blocks)
            start_page = adopted // bs  # same lock, but stay exact
        end_tokens = (start_page + extra) * bs
        # FULL prompt capacity, exactly like first-chunk admission
        # (which does ensure_capacity(total)): the post-promotion
        # mid-chunk continuation assumes every prompt page exists
        seq.blocks.ensure_capacity(len(token_ids))
        from vllm_tgis_adapter_tpu.engine.kv_tier import PromotionTicket

        ticket = PromotionTicket(
            request_id=seq.request_id,
            digests=digests[start_page:start_page + extra],
            start_tokens=start_page * bs,
            end_tokens=end_tokens,
        )
        seq.kv_promotion = ticket
        self._promotions.append((seq, ticket))
        self.kv_tier.start_promotion(ticket, self.runner._put)  # noqa: SLF001
        return False

    def _drain_promotions(self) -> None:
        """Apply completed host→device promotions on a clean dispatch
        boundary (the per-page scatter rebinds ``runner.caches``, same
        contract as swap-in).  An applied request resumes as a mid-chunk
        prefill AFTER the restored span; a failed/shrunk-to-zero ticket
        un-parks the request onto the plain recompute path."""
        if not self._promotions:
            return
        rest: list = []
        bs = self.config.cache_config.block_size
        alloc = self.scheduler.allocator
        for seq, ticket in self._promotions:
            if (
                ticket.cancelled
                or seq.kv_promotion is not ticket
                or self._seqs.get(seq.request_id) is not seq
            ):
                # aborted / preempted / belongs to a previous engine
                # incarnation: finish()/teardown released its pages
                if seq.kv_promotion is ticket:
                    seq.kv_promotion = None
                continue
            if not ticket.ready:
                rest.append((seq, ticket))
                continue
            if ticket.failed:
                seq.kv_promotion = None
                if seq.blocks is not None:
                    seq.blocks.release()
                    seq.blocks = None
                seq.prefill_pos = 0  # un-park; plain admission serves it
                continue
            if not self.scheduler._free_slots:  # noqa: SLF001
                rest.append((seq, ticket))  # retry next boundary
                continue
            for i, arrays in enumerate(ticket.pages):
                pos = ticket.start_tokens + i * bs
                self.runner.restore_kv_block(
                    seq.blocks.slots_for_range(pos, pos + bs),
                    *arrays,
                )
            seq.slot = self.scheduler._free_slots.pop()  # noqa: SLF001
            seq.prefill_pos = ticket.end_tokens
            seq.kv_promotion = None
            promoted = ticket.end_tokens - ticket.start_tokens
            alloc.prefix_hits += ticket.end_tokens
            alloc.prefix_lookup_tokens += len(seq.all_token_ids)
            self.kv_host_promoted_tokens += promoted
            self.kv_tier.note_promoted(len(ticket.pages), promoted)
            metrics.kv_prefix_tokens_reused_total.labels(
                tier="host"
            ).inc(promoted)
            if ticket.start_tokens:
                metrics.kv_prefix_tokens_reused_total.labels(
                    tier="device"
                ).inc(ticket.start_tokens)
            # the restored pages are now device content like any other:
            # publish them so the NEXT request hits on device directly
            alloc.register_prefix(
                seq.all_token_ids[:ticket.end_tokens],
                seq.blocks.blocks,
                seq.lora_name,
            )
            remote_pages = getattr(ticket, "remote_pages", 0)
            if remote_pages:
                # pages a kvnet peer served into this promotion: prefill
                # compute another HOST did (docs/CROSS_HOST.md) — priced
                # apart from the local host/disk rungs
                metrics.kv_prefix_tokens_reused_total.labels(
                    tier="remote"
                ).inc(remote_pages * bs)
                self.recorder.record(
                    "remote_hit", seq.request_id,
                    step=self.step_counter, trace_id=seq.trace_id,
                    pages=remote_pages, tokens=remote_pages * bs,
                )
            self.recorder.record(
                "promote_host", seq.request_id, step=self.step_counter,
                trace_id=seq.trace_id, tokens=promoted,
                pages=len(ticket.pages),
            )
            if self.ledger is not None:
                self.ledger.note_tier_bytes(
                    seq.request_id,
                    len(ticket.pages) * self._tier_page_bytes(),
                )
            logger.info(
                "request %s: %d prefix tokens promoted from the host KV "
                "tier (%d already device-resident)",
                seq.request_id, promoted, ticket.start_tokens,
            )
        self._promotions = rest

    # ------------------------------------- mid-decode checkpoint / resume

    def _tier_page_bytes(self) -> int:
        """K+V bytes of one KV page at the device cache dtype — the
        unit the cost ledger bills tier transfers in."""
        caches = getattr(self.runner, "caches", None)
        if not caches:
            return 0
        k_cache = caches[0]
        bs = self.config.cache_config.block_size
        # tpulint: disable=TPL202(static shape/dtype metadata only — .shape and .itemsize are host ints, no device value is pulled)
        return int(
            2 * k_cache.shape[0] * k_cache.shape[1] * k_cache.shape[3]
            * k_cache.dtype.itemsize * bs
        )

    def kv_pages_by_request(self) -> dict[str, int]:
        """{request_id: device KV pages currently held} over live
        sequences — the cost ledger's commit-boundary HBM occupancy
        sample (telemetry/ledger.py ``sample_kv``); warmups excluded."""
        out: dict[str, int] = {}
        for rid, seq in self._seqs.items():
            if rid.startswith("__warmup"):
                continue
            blocks = seq.blocks
            if blocks is not None:
                out[rid] = len(blocks.blocks)
        return out

    def checkpoint_decode(self, seq: Sequence):
        """Quiesce-time capture of one mid-decode request
        (docs/RECOVERY.md): demote its fully WRITTEN KV pages into the
        host tier (frontier-capped at ``num_tokens - 1`` — the
        just-sampled token's slot is written by a dispatch that died)
        and stage a ``DecodeCheckpoint`` alongside.  Returns the staged
        record, or None when the degradation ladder applies (tier off,
        ``--no-decode-resume``, checkpoint over the tier budget, or the
        gather itself failing on a wedged device) — the caller then
        falls back to the retryable ``EngineRestartError`` floor.

        Called by the supervisor's triage under the replica lock with
        the step loop reaped; the gathers are the same fixed-shape
        jitted per-page programs ordinary demotion uses, so a
        checkpoint never adds a compile shape.
        """
        tier = self.kv_tier
        if tier is None or not self.config.decode_resume:
            return None
        bs = self.config.cache_config.block_size
        token_ids = seq.all_token_ids
        written = seq.num_tokens - 1
        pages = max(0, written // bs)
        caches = getattr(self.runner, "caches", None)
        if pages and caches is not None:
            k_cache = caches[0]
            per_page = (
                2 * k_cache.shape[0] * k_cache.shape[1]
                * k_cache.shape[3] * k_cache.dtype.itemsize * bs
            )
            if pages * per_page > tier.budget_bytes:
                # can never fit — the store would evict the checkpoint's
                # own head while inserting its tail
                return None
        t0 = time.perf_counter()
        try:
            if (
                seq.status == SequenceStatus.RUNNING
                and seq.kv_promotion is None
                and seq.blocks is not None
            ):
                # gather the device-resident frontier.  Non-RUNNING
                # mid-decode states already demoted at their transition
                # (preemption swap-out lands in the tier; a parked
                # promotion's SOURCE pages are the tier) — their device
                # pages are absent or unwritten, so gathering here
                # would poison the store; the validation read decides.
                self._tier_demote(seq, token_ids, written=written)
        except Exception:  # noqa: BLE001 — a wedged device fails the ladder, not recovery
            logger.exception(
                "decode-checkpoint gather failed for request %s; "
                "falling back to retryable failure", seq.request_id,
            )
            return None
        from vllm_tgis_adapter_tpu.engine.kv_cache import chain_digests
        from vllm_tgis_adapter_tpu.engine.kv_tier import DecodeCheckpoint

        m = seq.metrics
        ckpt = DecodeCheckpoint(
            request_id=seq.request_id,
            prompt=seq.prompt,
            prompt_token_ids=list(seq.prompt_token_ids),
            output_token_ids=list(seq.output_token_ids),
            params=seq.params,
            fallback_seed=seq.fallback_seed,
            arrival_time=m.arrival_time,
            deadline=seq.deadline,
            tenant_id=seq.tenant_id,
            lora_name=seq.lora_name,
            trace_id=seq.trace_id,
            emitted_token_len=seq._emitted_token_len,  # noqa: SLF001
            emitted_text_len=seq._emitted_text_len,  # noqa: SLF001
            stop_scan_pos=seq.stop_scan_pos,
            output_logprobs=(
                list(seq.output_logprobs)
                if seq.output_logprobs is not None
                else None
            ),
            prompt_logprobs=(
                list(seq.prompt_logprobs)
                if seq.prompt_logprobs is not None
                else None
            ),
            first_scheduled_time=m.first_scheduled_time,
            first_token_time=m.first_token_time,
            last_token_time=m.last_token_time,
            time_in_queue=m.time_in_queue,
            digests=(
                chain_digests(token_ids, bs, seq.lora_name, pages)
                if pages
                else []
            ),
            pages=pages,
            t0=t0,
            request_class=seq.request_class,
        )
        tier.stage_checkpoint(ckpt)
        self.recorder.record(
            "checkpoint", seq.request_id, step=self.step_counter,
            trace_id=seq.trace_id, output_tokens=seq.num_output_tokens,
            pages=pages,
        )
        return ckpt

    def resume_request(self, ckpt, path: str = "local") -> None:  # noqa: ANN001
        """Re-enter one checkpointed mid-decode request
        (docs/RECOVERY.md): rebuild its ``Sequence`` — emitted tokens,
        sampler seed, detokenizer/FSM state replayed, streaming
        bookkeeping restored so nothing re-emits — and hand it to the
        scheduler as a preemption-resume-shaped admission.  The kv gate
        then promotes the checkpointed pages from the host tier and the
        uncovered tail recomputes, so decode continues token-identically
        (the sampler folds the per-request POSITION into the per-request
        key, so the draw stream is scheduling-independent).

        ``path`` labels the flight-recorder event and metrics: 'local'
        (into the rebuilt replica) or 'cross_replica' (onto a healthy
        dp sibling before the rebuild).
        """
        rid = ckpt.request_id
        if rid in self._seqs:
            raise ValueError(f"duplicate request_id {rid!r}")
        params = ckpt.params
        seq = Sequence(
            rid,
            ckpt.prompt,
            list(ckpt.prompt_token_ids),
            params,
            arrival_time=ckpt.arrival_time,
            fallback_seed=ckpt.fallback_seed,
            lora_name=ckpt.lora_name,
        )
        seq.resumed = True
        seq.trace_id = ckpt.trace_id
        seq.tenant_id = ckpt.tenant_id
        seq.deadline = ckpt.deadline
        seq.request_class = getattr(ckpt, "request_class", "chat")
        seq.output_token_ids = list(ckpt.output_token_ids)
        if ckpt.output_logprobs is not None:
            seq.output_logprobs = list(ckpt.output_logprobs)
        if ckpt.prompt_logprobs is not None:
            seq.prompt_logprobs = list(ckpt.prompt_logprobs)
        m = seq.metrics
        # timing restore: a resumed request is NOT a new arrival — TTFT
        # was observed in its first life and must not re-observe
        m.first_scheduled_time = ckpt.first_scheduled_time
        m.first_token_time = ckpt.first_token_time
        m.last_token_time = ckpt.last_token_time
        m.time_in_queue = ckpt.time_in_queue
        m.events.append(("resumed", time.time_ns()))
        self._prepare_admission(seq)
        if seq.fsm is not None:
            state = seq.fsm.init_state
            for tok in seq.output_token_ids:
                # replay, don't carry: state ids are private to THIS
                # compile of the FSM
                state = seq.fsm.next_state(state, tok)
            seq.fsm_state = state
        if seq.output_token_ids:
            # deterministic replay: output_text lands exactly where the
            # dead engine left it, so DELTA offsets below stay valid
            seq.detokenizer.append(list(seq.output_token_ids))
        seq.stop_scan_pos = ckpt.stop_scan_pos
        seq._emitted_token_len = ckpt.emitted_token_len  # noqa: SLF001
        seq._emitted_text_len = ckpt.emitted_text_len  # noqa: SLF001
        self._commit_admission(seq)
        self.recorder.record(
            "resume", rid, step=self.step_counter, trace_id=ckpt.trace_id,
            output_tokens=len(seq.output_token_ids), path=path,
        )

    # --------------------------------------------- prefill→decode handoff

    def _stage_handoffs(self, plan) -> None:  # noqa: ANN001
        """Prefill-role commit hook (docs/SCALING.md "Disaggregated
        roles"): every sequence this commit left MID-DECODE — its
        prefill finished and its first token sampled (and, for DELTA
        streams, already emitted by ``_process_sampled``) — leaves this
        replica NOW, as a staged decode checkpoint the async layer
        resumes on a decode-capable replica.  Decode plans are scanned
        too: the only legitimately decoding rows here are precompile
        warmups (exempt) and requests a role-degraded resume parked on
        this replica — the latter must bounce back off rather than
        decode a prefill replica's bucket away."""
        if isinstance(plan, RaggedPlan):
            seqs = [item.seq for item in plan.items]
        elif isinstance(plan, PrefillPlan):
            seqs = [plan.seq]
        else:
            seqs = list(plan.seqs)
        for seq in seqs:
            if (
                seq.is_finished
                or seq.num_output_tokens < 1
                or seq.request_id.startswith("__warmup")
                or self._seqs.get(seq.request_id) is not seq
            ):
                continue
            if seq.status != SequenceStatus.RUNNING:
                # a resumed request MID-CHUNK through its recompute tail
                # (status WAITING, pages held, queued for the next
                # chunk): it carries output tokens from its first life
                # but has NOT finished prefill here — staging it now
                # would hand off a stale checkpoint while the scheduler
                # keeps (re)running it from the waiting queue, double-
                # executing the stream.  It stages at its final-chunk
                # commit, exactly like a fresh prompt.
                continue
            self._stage_handoff(seq)

    def _stage_handoff(self, seq: Sequence) -> None:
        """Capture one finished-prefill sequence for decode handoff:
        ``checkpoint_decode`` demotes its written pages into the
        fleet-shared host tier and stages the ``DecodeCheckpoint``
        (identity, sampler seed, stream offsets, digest-validated
        pages — the PR-10 record, verbatim); the sequence's device
        state is released immediately — the demotion gathers were
        ENQUEUED first, so the device reads its pages before any later
        program can overwrite them (the ``_tier_demote`` ordering
        contract).  A ``None`` checkpoint means the capture ladder
        failed (tier budget, gather failure); the async layer's drain
        turns that into a retryable ``HandoffError``."""
        ckpt = self.checkpoint_decode(seq)
        self.scheduler.finish(seq)
        self._seqs.pop(seq.request_id, None)
        self.lora_manager.unpin(seq.lora_name)
        self.pending_handoffs.append((seq.request_id, ckpt))
        self.recorder.record(
            "handoff_out", seq.request_id, step=self.step_counter,
            trace_id=seq.trace_id, staged=ckpt is not None,
            output_tokens=seq.num_output_tokens,
            pages=getattr(ckpt, "pages", 0),
        )

    # ------------------------------------------------------------- step loop

    def precompile(self, batch_widths: str = "all") -> int:
        """Boot-time shape warmup: drive dummy requests through every
        prefill bucket and decode batch-width bucket so production
        traffic never pays an XLA/Mosaic compile (first compiles run
        ~20-40s each on TPU; the persistent compilation cache then
        serves restarts).  Mirrors the TPU warmup the reference stack
        inherits from vLLM's TPU worker.

        ``batch_widths``: decode runs at ONE width (max_num_seqs);
        "all" additionally compiles the want_topn sampler variant and
        the full flat-bucket ladder, "max" keeps boot fast and lets
        rare variants compile as load ramps.

        Returns the number of warmup requests run.  Must be called
        before serving starts (asserts the engine is idle); leaves no
        residual state (all warmup requests run to completion).
        """
        if self.has_unfinished_requests():
            raise RuntimeError("precompile must run on an idle engine")
        sched = self.scheduler
        max_len = self.config.max_model_len
        # decode runs at ONE width (max_num_seqs) — the per-width
        # bucket ladder is retired, so one pass warms it
        widths = [sched.config.max_num_seqs]
        # "all" also compiles the want_topn=True decode variant (static
        # argnum: flipping it at serving time is a fresh full compile)
        topn_variants = [False, True] if batch_widths == "all" else [False]
        # two full fused waves: the first compiles the wave program (one
        # for every step count, planned ahead or not)
        steps = sched.config.num_decode_steps
        total = 0
        # solo-prefill buckets whose program ACTUALLY compiled: recorded
        # by _precompile_drain from the plans it dispatched (ADVICE r5:
        # recording at add_request time was optimistic — _extend_pack
        # swallows co-admitted warmup prompts into a PACKED dispatch, a
        # different entry point, leaving the solo shape cold and the
        # first real solo prompt at that bucket paying a serving-time
        # compile)
        covered: set[int] = set()

        def warm_len(bucket: int, headroom: int = 0) -> int:
            return max(1, min(bucket, max_len - (headroom or 2 * steps) - 2))

        for width in widths:
            for want_topn in topn_variants:
                for i in range(width):
                    bucket = sched.config.prefill_buckets[
                        i % len(sched.config.prefill_buckets)
                    ]
                    self.add_request(
                        f"__warmup_{width}_{want_topn}_{i}",
                        None,
                        SamplingParams(
                            temperature=0.0, max_tokens=2 * steps + 1,
                            ignore_eos=True,
                            logprobs=1 if want_topn else None,
                        ),
                        prompt_token_ids=[1] * warm_len(bucket),
                    )
                    total += 1
                self._precompile_drain(width, covered)
        if sched.ragged:
            # ragged shape set: the mixed step compiles per FLAT-LENGTH
            # bucket (scheduler.ragged_buckets), regardless of batch
            # mix.  Fill each reachable bucket with exactly enough
            # whole/chunked prompts — the floor-bucket + slice-to-fit
            # planner then dispatches at precisely that bucket.
            budget = min(sched.chunk_budget, sched.ragged_buckets[-1])
            for bucket in sched.ragged_buckets:
                if bucket in covered or bucket > budget:
                    continue
                # prompts of min(bucket, usable max_len): desired lands
                # in [bucket, bucket + warm) so the floor-bucket planner
                # dispatches at exactly this bucket
                warm = min(warm_len(max_len, headroom=1), bucket)
                n = -(-bucket // warm)
                if n > sched.config.max_num_seqs:
                    continue  # unreachable: admission is slot-bounded
                for i in range(n):
                    self.add_request(
                        f"__warmup_ragged_{bucket}_{i}",
                        None,
                        SamplingParams(temperature=0.0, max_tokens=1,
                                       ignore_eos=True),
                        prompt_token_ids=[1] * warm,
                    )
                    total += 1
                self._precompile_drain(n, covered)
            total += self._precompile_ragged_tail(covered)
        else:
            # prefill compiles key on the BUCKET, not the batch width:
            # any bucket whose solo shape no dispatched plan covered
            # (packed admission, narrow batches, long bucket lists)
            # gets a solo pass — one request at a time, so _extend_pack
            # has nothing to pack it with and the solo program truly
            # compiles
            for bucket in sched.config.prefill_buckets:
                if bucket in covered or bucket >= max_len:
                    continue
                self.add_request(
                    f"__warmup_bucket_{bucket}",
                    None,
                    SamplingParams(temperature=0.0, max_tokens=1,
                                   ignore_eos=True),
                    prompt_token_ids=[1] * warm_len(bucket, headroom=1),
                )
                total += 1
                self._precompile_drain(1, covered)
        logger.info(
            "precompile: %d warmup requests across %d batch widths, "
            "%d prefill buckets (topn variants: %s, chained: yes)",
            total, len(widths), len(covered), topn_variants,
        )
        return total

    def _precompile_drain(
        self, width: int, covered: Optional[set[int]] = None
    ) -> None:
        """Run the warmup batch to completion, planning the successor
        of the FIRST full-batch decode wave ahead (mirroring the async
        loop's plan_ahead_step -> dispatch_chained_step -> commit
        order, free-epoch discipline included): it runs the same
        programs as the serial order, so this warms no twin, but the
        path the server lives on has then run once at the production
        width before traffic does.

        ``covered`` (when given) collects the SOLO prefill buckets this
        drain actually dispatched — the ground truth precompile() needs
        to decide which buckets still want a solo pass (packed plans
        compile a different entry point and do not count).

        All prefills drain first (``prefill_only=True`` planning):
        organic interleaving would let early rows burn their max_tokens
        budget before the batch fills, and the wave would not run at
        its full width."""

        def note_plan(plan) -> None:  # noqa: ANN001
            if covered is None:
                return
            if isinstance(plan, PrefillPlan):
                covered.add(plan.bucket_len)
            elif isinstance(plan, RaggedPlan):
                covered.add(plan.token_bucket)

        guard = 0
        while True:
            guard += 1
            if guard > 50 * width + 500:  # pragma: no cover
                raise RuntimeError("precompile prefill did not converge")
            # the prefill/decode anti-starvation interleave
            # (scheduler._last_was_prefill) returns None after every
            # admission; there is nothing to starve during warmup, so
            # clear it — all prompts must be resident before the first
            # decode or early rows burn their budget pre-full-width
            self.scheduler._last_was_prefill = False
            outputs, plan, prepared = self.plan_step(prefill_only=True)
            if plan is None:
                break
            note_plan(plan)
            self.commit_step(
                plan,
                self.wait_step(
                    plan, prepared, self.dispatch_step(plan, prepared)
                ),
                prepared,
            )
        ahead_done = False
        guard = 0
        while self.has_unfinished_requests():
            guard += 1
            if guard > 200 * width + 2000:  # pragma: no cover
                raise RuntimeError("precompile did not converge")
            outputs, plan, prepared = self.plan_step()
            if plan is None:
                continue
            note_plan(plan)
            handle = self.dispatch_step(plan, prepared)
            c_plan = None
            if not ahead_done and isinstance(plan, DecodePlan):
                _, c_plan, c_prep = self.plan_ahead_step(plan)
            if c_plan is None:
                self.commit_step(
                    plan, self.wait_step(plan, prepared, handle), prepared
                )
                continue
            note_plan(c_plan)
            self.begin_free_epoch()
            try:
                c_handle = self.dispatch_chained_step(
                    c_plan, c_prep, handle
                )
                self.commit_step(
                    plan, self.wait_step(plan, prepared, handle), prepared
                )
                c_result = self.wait_step(c_plan, c_prep, c_handle)
            finally:
                # the successor retired — or died with the warmup: a
                # supervised re-warm failure is retried, and an epoch
                # left open here would quarantine every later free on
                # the retrying engine (tpulint TPL501)
                self.flush_free_epoch()
            self.commit_step(c_plan, c_result, c_prep)
            ahead_done = True

    def _precompile_ragged_tail(self, covered: set[int]) -> int:
        """Warm the flat-length buckets only DECODE-HEAVY mixed steps
        reach (--attention-backend=ragged).  Prompt warmups top out at
        the chunk budget per dispatch, but a serving step with ``base``
        running rows plans ``max(floor_bucket(base + take),
        _ragged_bucket(base + 1))`` — past the chunk budget whenever
        the running batch is large.  Park just enough one-token rows in
        decode, then ride one filler prompt with them: the planner
        dispatches at exactly the target bucket.  Best-effort — buckets
        this config cannot reach are skipped silently, and a KV pool
        too small for the parked rows downgrades to a serving-time
        compile (logged)."""
        sched = self.scheduler
        s_max = sched.config.max_num_seqs
        chunk = sched.chunk_budget
        block_size = self.config.cache_config.block_size
        total = 0
        prev = 0
        for bucket in sched.ragged_buckets:
            if bucket in covered:
                prev = bucket
                continue
            if prev and prev < s_max:
                # base past the previous ladder entry lifts
                # _ragged_bucket(base + 1) to this bucket on its own
                # (prev == s_max would park every slot and leave no
                # room to admit the filler prompt)
                base_rows, filler_len = prev, 1
            elif (
                1 <= bucket - chunk <= s_max
                and chunk <= self.config.max_model_len - 2
            ):
                # floor-bucket route: base + a full chunk lands exactly
                # (needs a legal chunk-length filler prompt)
                base_rows, filler_len = bucket - chunk, chunk
            else:
                prev = bucket
                continue  # no (base, chunk) mix reaches this bucket
            prev = bucket
            # one-token prompts admit whole rows even when the plan has
            # a single token of space left (no intra-prompt crawl); each
            # parked row decodes once per plan while the rest admit
            life = -(-base_rows // chunk) + 12
            pages = base_rows * (-(-(1 + life) // block_size))
            if pages > int(0.9 * sched.allocator.num_blocks):
                logger.warning(
                    "precompile: skipping ragged bucket %d — %d warm "
                    "rows need ~%d KV pages, pool has %d; the first "
                    "decode-heavy step there compiles at serving time",
                    bucket, base_rows, pages, sched.allocator.num_blocks,
                )
                continue
            for i in range(base_rows):
                self.add_request(
                    f"__warmup_mix_{bucket}_{i}", None,
                    SamplingParams(temperature=0.0, max_tokens=life,
                                   ignore_eos=True),
                    prompt_token_ids=[3],
                )
                total += 1
            guard = 0
            while sched.waiting:
                guard += 1
                if guard > 50 * base_rows + 500:  # pragma: no cover
                    raise RuntimeError(
                        "precompile mixed warm did not converge"
                    )
                sched._last_was_prefill = False
                self.step()
            self.add_request(
                f"__warmup_mix_{bucket}_filler", None,
                SamplingParams(temperature=0.0, max_tokens=1,
                               ignore_eos=True),
                prompt_token_ids=[3] + [1] * (filler_len - 1),
            )
            total += 1
            outputs, plan, prepared = self.plan_step()
            if isinstance(plan, RaggedPlan):
                covered.add(plan.token_bucket)
            if plan is not None:
                self.commit_step(
                    plan, self.execute_step(plan, prepared), prepared
                )
            if bucket not in covered:  # pragma: no cover
                logger.warning(
                    "precompile: mixed warm missed ragged bucket %d "
                    "(planned %s)", bucket,
                    type(plan).__name__ if plan is not None else None,
                )
            guard = 0
            while self.has_unfinished_requests():
                guard += 1
                if guard > 50 * base_rows + 2000:  # pragma: no cover
                    raise RuntimeError(
                        "precompile mixed drain did not converge"
                    )
                self.step()
        return total

    def step(self) -> list[RequestOutput]:
        """Run one device step; return outputs due for emission.

        Composes the three phases below; the async engine calls them
        separately so the engine lock is held only for the (fast) host
        phases and add_request/abort can land during the device dispatch.
        """
        outputs, plan, prepared = self.plan_step()
        if plan is None:
            return outputs
        result = self.execute_step(plan, prepared)
        return outputs + self.commit_step(plan, result, prepared)

    def plan_step(self, prefill_only: bool = False, ahead_of=None):  # noqa: ANN001
        """Phase 1 (host, engine lock held): drain scheduler-finished
        requests, pick the next plan, snapshot its dispatch inputs.

        ``ahead_of``: the plan of the dispatch still in flight — the
        overlapped loop's rule: the successor is planned from a
        projection of it (``plan_ahead_step``).  Without it, the serial
        order: every row is planned from committed state.
        ``prefill_only``: the serial loop (``enable_chained_decode``
        off) sets this while a dispatch is still in flight — admissions
        are independent of in-flight results and may be enqueued behind
        them, whereas a decode plan depends on the pending commit
        (tokens, page frees) and must wait.
        """
        failpoints.fire("core.plan_step")
        if ahead_of is not None:
            return self.plan_ahead_step(ahead_of)
        _st_enter = time.perf_counter()  # steptime: plan-phase origin
        outputs = self._drain_rejected()

        _drain_s = 0.0
        if not prefill_only and self.scheduler.swap_out_fn is not None:
            # prefill_only means a dispatch is in flight — restoring
            # would rebind runner.caches under it (runner.restore_kv)
            _t = time.perf_counter()
            self._drain_swap_ins()
            _drain_s += time.perf_counter() - _t
        if not prefill_only and self.kv_tier is not None:
            # same clean-boundary contract: the promotion scatter also
            # rebinds runner.caches (runner.restore_kv_block)
            _t = time.perf_counter()
            self._drain_promotions()
            _drain_s += time.perf_counter() - _t
        self.runner.sync_lora(self.lora_manager)
        self._set_wave_depth()
        plan = self.scheduler.schedule(prefill_only=prefill_only)
        return self._prepare_plan(
            outputs, plan, t_enter=_st_enter, drain_s=_drain_s,
        )

    def _drain_rejected(self) -> list[RequestOutput]:
        """Final outputs of the requests the scheduler itself finished
        (rejected prompts, queue-TTL sheds)."""
        outputs: list[RequestOutput] = []
        for seq in self.scheduler.newly_finished:
            self._seqs.pop(seq.request_id, None)
            self.lora_manager.unpin(seq.lora_name)
            seq.metrics.finished_time = time.time()
            self.recorder.record(
                "finish", seq.request_id, step=self.step_counter,
                trace_id=seq.trace_id, reason=seq.finish_reason,
                rejected=True,
            )
            outputs.append(seq.to_request_output())
        self.scheduler.newly_finished.clear()
        return outputs

    def _set_wave_depth(self) -> None:
        """A wave fuses no more token steps than hide the host's turn
        (telemetry/steptime.py ``wave_depth``) while the step loop
        overlaps the two; in the serial order every fused step saves a
        turn the device would wait for, so the ceiling stands.  In both
        orders a mixed step that carries running rows takes no larger a
        bucket than the measured step times keep within ``STALL_MULTIPLE``
        token steps (``stall_bucket``)."""
        sched = self.scheduler
        sched.wave_depth = (
            self.steptime.wave_depth(sched.config.num_decode_steps)
            if self.overlapped
            else None
        )
        sched.stall_bucket = self.steptime.stall_bucket(sched.ragged_buckets)

    def _prepare_plan(self, outputs, plan, *, t_enter: float,  # noqa: ANN001
                      drain_s: float = 0.0, ahead: bool = False):
        """Second half of both planning phases: snapshot ``plan``'s
        dispatch inputs, raise its telemetry, stamp its anatomy."""
        _st_sched = time.perf_counter()
        if plan is None:
            return outputs, None, None

        if isinstance(plan, RaggedPlan):
            now = time.time()
            for item in plan.items:
                m = item.seq.metrics
                if m.first_scheduled_time is None:
                    m.first_scheduled_time = now
                    m.time_in_queue = now - m.arrival_time
            prepared = self.runner.prepare_ragged(plan)
        elif isinstance(plan, PrefillPlan):
            seq = plan.seq
            if seq.metrics.first_scheduled_time is None:
                now = time.time()
                seq.metrics.first_scheduled_time = now
                seq.metrics.time_in_queue = now - seq.metrics.arrival_time
            prepared = self.runner.prepare_prefill(plan)
        else:
            prepared = self.runner.prepare_decode(plan)
        self._observe_plan(plan, prepared)
        self._record_dispatch(plan, ahead=ahead)
        self.steptime.stamp_plan(
            prepared, t_enter=t_enter, t_sched=_st_sched,
            drain_s=drain_s, chained=ahead,
        )
        return outputs, plan, prepared

    def _record_dispatch(self, plan, ahead: bool = False) -> None:  # noqa: ANN001
        """One recorder entry per dispatch (per prompt for prefills, so
        ``events_for(request_id)`` sees every wave that touched it;
        batch-level for decode — per-request decode cadence is the
        ``decode_progress`` marker in ``_process_sampled``), and the
        count of dispatches by whether they were planned ahead."""
        self.step_counter += 1
        step = self.step_counter
        metrics.step_dispatches_total.labels(
            ahead="true" if ahead else "false",
            replica=str(self.replica_index),
        ).inc()
        if isinstance(plan, RaggedPlan):
            for item in plan.items:
                self.recorder.record(
                    "ragged_step", item.seq.request_id, step=step,
                    trace_id=item.seq.trace_id,
                    bucket=plan.token_bucket,
                    tokens=len(item.token_ids),
                    start_pos=item.start_pos,
                    decode=item.is_decode,
                    is_final=item.is_final,
                    # the verify phase of the ragged step: this item is
                    # a speculative verify span (docs/OBSERVABILITY.md)
                    verify=item.spec_width > 0,
                )
            return
        if isinstance(plan, PrefillPlan):
            self.recorder.record(
                "prefill", plan.seq.request_id, step=step,
                trace_id=plan.seq.trace_id, bucket=plan.bucket_len,
                start_pos=plan.start_pos, tokens=len(plan.token_ids),
                is_final=plan.is_final,
            )
        else:
            self.recorder.record(
                "decode", step=step, num_seqs=len(plan.seqs),
                batch_bucket=plan.batch_bucket, num_steps=plan.num_steps,
            )

    def state_slot_usage(self) -> tuple[int, int]:
        """``(held, total)`` slots of the recurrent-state slab; a
        sequence's batch row IS its slot, held from its first chunk to
        finish or preemption.  ``(0, 0)`` for a model without state."""
        total = getattr(self.runner, "state_slots", 0)
        if not total:
            return 0, 0
        return total - self.scheduler.free_slot_count, total

    def _state_row_step_bytes(self) -> int:
        """Scan-state bytes one row's one-token update reads and writes
        over all layers (the float32 state alone, in and out)."""
        m = self.config.model_config
        return 2 * m.num_layers * m.mixer.scan_state_bytes

    def _observe_latent_attention(self, spans, steps: int) -> None:
        """Latent-attention work of a dispatched plan, from the host's
        copy of its shape: ``spans`` are ``(rows, first position)`` of
        each sequence's tokens, ``steps`` the kernel calls a layer."""
        from vllm_tgis_adapter_tpu.ops.mla_attention import (
            attended_pairs,
            attention_flops,
        )

        m = self.config.model_config
        pairs = sum(attended_pairs(n, pos0) for n, pos0 in spans)
        metrics.observe_latent_attention(
            flops=m.num_layers * attention_flops(
                pairs, m.num_heads, m.mla.latent_dim, m.mla.kv_lora_rank),
            calls=m.num_layers * steps,
        )

    def _observe_window_attention(self, spans, steps: int,
                                  items: tuple[int, int]) -> None:
        """Attention work of a dispatched plan of a model with window
        and full layers, from the host's copy of its shape: ``spans``
        are ``(rows, first position)`` of each sequence's tokens,
        ``steps`` the kernel calls a layer, ``items`` the work items of
        the causal and of the window layers' schedule."""
        from vllm_tgis_adapter_tpu.ops.mla_attention import attended_pairs

        m = self.config.model_config
        window = m.group_window
        causal = banded = 0
        for n, pos0 in spans:
            pairs = attended_pairs(n, pos0)
            causal += pairs
            # rows past the band's first `window` positions see `window`
            # tokens, not position + 1: take off what lies under the band
            under = n + pos0 - window  # the last row's hidden tokens
            first = max(under - n + 1, 1)  # the first row that hides any
            if under > 0:
                pairs -= (under + first) * (under - first + 1) // 2
            banded += pairs
        per_pair = m.num_heads * 2 * 2 * m.head_dim
        metrics.observe_window_attention(
            window_flops=len(m.window_layers) * banded * per_pair,
            window_calls=len(m.window_layers) * steps,
            full_flops=len(m.full_layers) * causal * per_pair,
            full_calls=len(m.full_layers) * steps,
            causal_items=items[0], window_items=items[1],
        )

    def _observe_result(self, plan, prepared) -> None:
        """Telemetry that waits for a step's results: the expert counts
        its program handed back (engine/runner.py)."""
        counts = getattr(prepared, "expert_counts", None)
        if counts is None:
            return
        try:
            import jax.numpy as jnp

            m = self.config.model_config
            ex = m.experts
            if isinstance(plan, RaggedPlan):
                tokens, steps = plan.total_tokens, 1
            else:
                tokens = sum(plan.steps_per_seq)
                steps = max(plan.steps_per_seq, default=0)
            layers = m.num_layers - ex.first_dense_layers
            item = jnp.dtype(m.dtype).itemsize
            metrics.observe_expert_step(
                routed_pairs=tokens * ex.top_k * layers,
                local_pairs=counts[0], experts_hit=counts[1],
                layer_steps=layers * steps, num_held=ex.num_held,
                expert_bytes=3 * m.hidden_size * ex.intermediate_size * item,
                pair_bytes=3 * (m.hidden_size + ex.intermediate_size) * item,
            )
        except Exception:  # pragma: no cover — metrics are best-effort
            logger.debug("expert metric observation failed", exc_info=True)

    def _items_by_stats(self, calls) -> dict:
        """The live items of a plan's ragged-kernel calls, each a
        ``(query block, items a KV head)``, by the layout the call's
        static shape selects (``ops/ragged_attention.py`` ``tile_stats``,
        which the kernel's wrapper asks too).  Empty for a model whose
        attention is another kernel."""
        from vllm_tgis_adapter_tpu.ops.ragged_attention import tile_stats

        mcfg = self.config.model_config
        by_stats: dict = {}
        if mcfg.has_latent_cache:
            return by_stats
        for block_q, items in calls:
            stats = tile_stats(mcfg.q_per_kv, block_q)
            by_stats[stats] = by_stats.get(stats, 0) + items
        return by_stats

    def _observe_plan(self, plan, prepared) -> None:
        """Step-level telemetry (metrics.py): batch occupancy / padding
        waste gauges for this dispatch's shape, plus the plan→commit
        timestamp the commit phase turns into a step-duration sample."""
        from vllm_tgis_adapter_tpu.ops.ragged_attention import (
            decode_block_q,
            pages_per_item,
            query_block,
        )

        try:
            if isinstance(plan, RaggedPlan):
                if self.config.model_config.has_latent_cache:
                    self._observe_latent_attention(
                        [(len(i.token_ids), i.start_pos)
                         for i in plan.items], steps=1)
                chunk_q = query_block(
                    getattr(self.runner.model, "ragged_block_q", 128),
                    prepared.bucket)
                calls = [(chunk_q, prepared.work_items)]
                if self.config.model_config.has_window_group:
                    # the running rows' spans are a kernel call of
                    # their own where the schedules leave them out
                    units = prepared.unit_items
                    calls += [
                        (chunk_q, prepared.window_work_items),
                        (decode_block_q(min(len(prepared.seq_starts) - 1,
                                            prepared.bucket)), sum(units)),
                    ]
                    self._observe_window_attention(
                        [(len(i.token_ids), i.start_pos)
                         for i in plan.items],
                        steps=1 if prepared.unit_rows is None else 2,
                        items=(prepared.work_items + units[0],
                               prepared.window_work_items + units[1]))
                if getattr(self.runner, "has_state", False):
                    spans = [len(i.token_ids) for i in plan.items]
                    ones = sum(1 for n in spans if n == 1)
                    metrics.observe_state_plan(
                        update_rows=ones, update_row_steps=ones,
                        update_calls=self.config.model_config.num_layers,
                        scan_tokens=sum(n for n in spans if n > 1),
                        wave=False,
                        row_step_bytes=self._state_row_step_bytes(),
                    )
                metrics.observe_ragged_plan(
                    real_tokens=plan.total_tokens,
                    bucket=plan.token_bucket,
                    num_prefill=sum(
                        1 for i in plan.items if not i.is_decode
                    ),
                    num_decode=sum(1 for i in plan.items if i.is_decode),
                    capped=plan.capped,
                    live_items=prepared.work_items,
                    live_pages=prepared.work_pages,
                    items_by_stats=self._items_by_stats(calls),
                )
            elif isinstance(plan, PrefillPlan):
                metrics.observe_prefill_plan(
                    real_tokens=len(plan.token_ids),
                    bucket=plan.bucket_len,
                    num_prompts=1,
                )
            else:
                # pages under the live rows' contexts and the runs the
                # kernel reads them in, from the host's copy of the
                # wave's inputs (no device read-back)
                if self.config.model_config.has_latent_cache:
                    # a row's steps are consecutive tokens from its
                    # position; the loop ends at the last live step
                    self._observe_latent_attention(
                        zip(plan.steps_per_seq,
                            prepared.positions[: len(plan.seqs)].tolist()),
                        steps=max(plan.steps_per_seq, default=0))
                if getattr(self.runner, "has_state", False):
                    metrics.observe_state_plan(
                        update_rows=len(plan.seqs),
                        update_row_steps=sum(plan.steps_per_seq),
                        # the wave's loop ends at its last live step
                        update_calls=self.config.model_config.num_layers
                        * max(plan.steps_per_seq, default=0),
                        scan_tokens=0, wave=True,
                        row_step_bytes=self._state_row_step_bytes(),
                    )
                ctx = prepared.context_lens[: len(plan.seqs)]
                pages = (ctx - 1) // self.runner.block_size + 1
                run = pages_per_item(self.runner.block_size)
                live_items = int((-(-pages // run)).sum())
                wave_q = decode_block_q(plan.batch_bucket)
                calls = [(wave_q, live_items)]
                if self.config.model_config.has_window_group:
                    # the wave's first step: a row's band starts at the
                    # page of the oldest token it sees
                    first = (
                        ctx - self.config.model_config.group_window
                    ).clip(min=0) // self.runner.block_size
                    window_items = int((-(-(pages - first) // run)).sum())
                    calls.append((wave_q, window_items))
                    self._observe_window_attention(
                        zip(plan.steps_per_seq,
                            prepared.positions[: len(plan.seqs)].tolist()),
                        steps=max(plan.steps_per_seq, default=0),
                        items=(live_items, window_items))
                metrics.observe_decode_plan(
                    num_seqs=len(plan.seqs),
                    batch_bucket=plan.batch_bucket,
                    num_steps=plan.num_steps,
                    live_items=live_items,
                    live_pages=int(pages.sum()),
                    replica=self.replica_index,
                    items_by_stats=self._items_by_stats(calls),
                )
        except Exception:  # pragma: no cover — metrics are best-effort
            logger.debug("step metric observation failed", exc_info=True)
        if prepared is not None:
            prepared._obs_plan_t0 = time.perf_counter()  # noqa: SLF001

    def execute_step(self, plan, prepared):
        """Phase 2 (device, lock-free): runs only against the snapshot and
        runner-owned device state — never reads scheduler structures."""
        self.steptime.begin_wait(prepared)
        if isinstance(plan, RaggedPlan):
            result = self.runner.execute_ragged(prepared)
        elif isinstance(plan, PrefillPlan):
            result = self.runner.execute_prefill(prepared)
        else:
            result = self.runner.execute_decode(prepared)
        self.steptime.end_wait(prepared)
        self._observe_result(plan, prepared)
        return result

    def _stamp_dispatched(self, prepared, handle) -> None:  # noqa: ANN001
        """steptime: close the dispatch window, noting whether the
        runner deferred the device work to wait (SYNC_DISPATCH) and any
        XLA compile in flight when this step was enqueued."""
        from vllm_tgis_adapter_tpu import compile_tracker

        inflight = compile_tracker.inflight_dispatch()
        self.steptime.end_dispatch(
            prepared,
            sync=handle is SYNC_DISPATCH,
            compile_fn=inflight[0] if inflight is not None else None,
        )

    def dispatch_step(self, plan, prepared):
        """Phase 2a (lock-free): enqueue the device work without blocking
        on results (JAX async dispatch).  Pair with ``wait_step``; the
        async engine plans and dispatches the NEXT step between the two,
        so host-side prep overlaps device execution."""
        failpoints.fire("core.dispatch_step")  # worker thread: hang-capable
        self.steptime.begin_dispatch(prepared)
        if isinstance(plan, RaggedPlan):
            handle = self.runner.dispatch_ragged(prepared)
        elif isinstance(plan, PrefillPlan):
            handle = self.runner.dispatch_prefill(prepared)
        else:
            handle = self.runner.dispatch_decode(prepared)
        self._stamp_dispatched(prepared, handle)
        return handle

    def wait_step(self, plan, prepared, handle):
        """Phase 2b (lock-free, blocking): pull the dispatched step's
        results to host."""
        failpoints.fire("core.wait_step")  # worker thread: hang-capable
        self.steptime.begin_wait(prepared)
        if isinstance(plan, RaggedPlan):
            result = self.runner.wait_ragged(prepared, handle)
        elif isinstance(plan, PrefillPlan):
            result = self.runner.wait_prefill(prepared, handle)
        else:
            result = self.runner.wait_decode(prepared, handle)
        self.steptime.end_wait(prepared)
        self._observe_result(plan, prepared)
        return result

    # ------------------------------------------------------- planning ahead

    def plan_ahead_step(self, prev_plan):  # noqa: ANN001
        """Phase 1' (host, engine lock held): plan the SUCCESSOR of the
        dispatch in flight, whatever the two are — the rule of the
        overlapped step loop (engine/async_llm.py ``_run_loop``).  Every
        row ``prev_plan`` samples is projected as having consumed its
        whole budget there; its input token is read on the device
        (scheduler.schedule_ahead, runner.last_tokens).

        Returns ``(outputs, plan, prepared)`` as ``plan_step`` does.
        ``plan`` is None where there is nothing to plan or where the
        projection cannot be right or safe; each such bail is counted by
        its reason (``tgis_tpu_step_ahead_bails_total``) and the caller
        commits the predecessor and plans in the serial order."""
        _st_enter = time.perf_counter()
        reason = self._ahead_refusal(prev_plan)
        if reason is not None:
            self.count_ahead_bail(reason)
            return [], None, None
        if isinstance(prev_plan, RaggedPlan):
            inflight = {
                id(item.seq): 1 for item in prev_plan.items if item.is_final
            }
        else:
            inflight = {
                id(seq): k
                for seq, k in zip(prev_plan.seqs, prev_plan.steps_per_seq)
            }
        outputs = self._drain_rejected()
        self.runner.sync_lora(self.lora_manager)
        self._set_wave_depth()
        plan = self.scheduler.schedule_ahead(inflight)
        if plan is None and self.scheduler.ahead_bail is not None:
            self.count_ahead_bail(self.scheduler.ahead_bail)
        return self._prepare_plan(
            outputs, plan, t_enter=_st_enter, ahead=True,
        )

    def _ahead_refusal(self, prev_plan) -> Optional[str]:  # noqa: ANN001
        """The engine's own reasons against planning ahead (the
        scheduler's are in ``Scheduler.schedule_ahead``)."""
        if isinstance(prev_plan, PrefillPlan):
            # the solo-prefill path of prompt-logprob heads and of the
            # engines without a ragged planner
            return "legacy_planner"
        if self.replica_role == "prefill" and self.scheduler.running:
            # a prompt that finished in ``prev_plan`` leaves for a decode
            # replica at its commit (_stage_handoffs): it decodes nothing
            # here
            return "handoff"
        if any(t.ready or t.cancelled for _, t in self._promotions):
            # a host-tier promotion is ready to land (or to be dropped):
            # the scatter rebinds the caches, so it gets a clean
            # boundary (_drain_promotions)
            return "kv_tier"
        return None

    def count_ahead_bail(self, reason: str) -> None:
        metrics.step_ahead_bails_total.labels(
            reason=reason, replica=str(self.replica_index)
        ).inc()
        self.ahead_bails[reason] = self.ahead_bails.get(reason, 0) + 1

    def dispatch_chained_step(self, plan, prepared, prev_handle):  # noqa: ARG002
        """Phase 2a' (lock-free): enqueue a dispatch planned ahead behind
        the one in flight.  The device orders the two; the successor's
        input tokens are on it already."""
        return self.dispatch_step(plan, prepared)

    def begin_free_epoch(self) -> None:
        self.scheduler.begin_free_epoch()

    def flush_free_epoch(self) -> None:
        self.scheduler.flush_free_epoch()

    def flush_all_free_epochs(self) -> None:
        """Step-loop teardown: nothing can be in flight any more, so any
        epochs left open (loop died between a planned-ahead dispatch and
        its commit) release their quarantined pages and batch rows."""
        self.scheduler.flush_all_free_epochs()

    def commit_step(self, plan, result, prepared=None) -> list[RequestOutput]:
        """Phase 3 (host, engine lock held): fold sampled tokens back into
        sequences; requests aborted mid-dispatch are skipped here.  On a
        'prefill'-role replica (docs/SCALING.md "Disaggregated roles"),
        sequences left mid-decode by this commit — their first token just
        sampled — are then staged for handoff to a decode replica."""
        outputs = self._commit_inner(plan, result, prepared)
        if self.replica_role == "prefill":
            self._stage_handoffs(plan)
        # step-boundary invariant sanitizer (TGIS_TPU_SANITIZE=1, zero
        # cost off): every commit leaves the allocator/arena/tier/pool
        # accounting closed, or we fail HERE rather than serving from
        # corrupt state (engine/sanitizer.py, docs/STATIC_ANALYSIS.md)
        sanitizer.maybe_check(self)
        self._finish_step_record(plan, prepared)
        return outputs

    def _finish_step_record(self, plan, prepared) -> None:  # noqa: ANN001
        """Commit boundary: finalize this dispatch's StepRecord
        (telemetry/steptime.py) with the plan's shape facts."""
        if prepared is None or plan is None:
            return
        steps = 1
        bucket = None
        running = 0
        if isinstance(plan, RaggedPlan):
            kind = "ragged"
            tokens = plan.total_tokens
            bucket = plan.token_bucket
            running = sum(1 for i in plan.items if i.is_decode)
            fill = tokens / bucket if bucket else 0.0
        elif isinstance(plan, PrefillPlan):
            kind = "solo"
            tokens = len(plan.token_ids)
            fill = tokens / plan.bucket_len if plan.bucket_len else 0.0
        else:
            kind = "decode-wave"
            steps = plan.num_steps
            tokens = len(plan.seqs) * plan.num_steps
            fill = (
                len(plan.seqs) / plan.batch_bucket
                if plan.batch_bucket
                else 0.0
            )
        self.steptime.finish(
            prepared, step=self.step_counter,
            replica=self.replica_index, kind=kind, tokens=tokens,
            fill_ratio=fill, steps=steps, bucket=bucket, running=running,
        )

    def _commit_inner(self, plan, result, prepared=None) -> list[RequestOutput]:
        failpoints.fire("core.commit_step")
        t0 = getattr(prepared, "_obs_plan_t0", None)
        if t0 is not None:
            duration = time.perf_counter() - t0
            rep = str(self.replica_index)
            role = self.replica_role
            if isinstance(plan, DecodePlan):
                metrics.decode_step_seconds.labels(
                    replica=rep, replica_role=role
                ).observe(duration)
            else:
                metrics.prefill_step_seconds.labels(
                    replica=rep, replica_role=role
                ).observe(duration)
        if isinstance(plan, RaggedPlan):
            seqs, toks = [], []
            spec_ran = prepared is not None and getattr(
                prepared, "spec_ran", False
            )
            for item, tok_list in zip(plan.items, result):
                seq = item.seq
                if seq.is_finished:
                    continue  # aborted while the ragged dispatch ran
                if item.is_final and not item.is_decode:
                    # the prompt's K/V is now fully resident: publish
                    # its pages for prefix reuse (device cache + host
                    # tier demotion)
                    self._register_prefix(seq)
                if tok_list is None:
                    continue  # mid-prompt chunk: nothing emitted yet
                seqs.append(seq)
                toks.append(tok_list)
                if (
                    spec_ran
                    and item.spec_width > 0
                    and self.ledger is not None
                ):
                    # per-request speculative attribution: the row
                    # proposed spec_width drafts and consumed
                    # len(tok_list) tokens, of which all but the bonus
                    # token were accepted drafts
                    self.ledger.note_spec(
                        seq.request_id,
                        item.spec_width,
                        max(0, len(tok_list) - 1),
                    )
            outputs = self._process_sampled(seqs, toks)
            if spec_ran:
                for item in plan.items:
                    if item.spec_width > 0 and not item.seq.is_finished:
                        # propose wrote draft K/V through the last
                        # consumed token's predecessor; everything
                        # beyond is stale-by-design (next catch-up /
                        # propose re-inputs the corrected token)
                        item.seq.draft_pos = item.seq.num_tokens - 1
            return outputs
        if isinstance(plan, PrefillPlan):
            seq = plan.seq
            sampled, prompt_info = result
            # draft-cache accounting: this chunk was mirrored into the
            # draft only if it extends the draft's contiguous prefix
            # (prefix-cache-adopted regions are target-only and get
            # re-run through the draft by the catch-up path)
            if (
                not seq.is_finished
                and prepared is not None
                and getattr(prepared, "spec_eligible", False)
                and seq.draft_pos == plan.start_pos
            ):
                seq.draft_pos = plan.start_pos + len(plan.token_ids)
            if seq.is_finished:
                return []  # aborted while the dispatch was in flight
            if (
                seq.params.prompt_logprobs is not None
                and seq.prompt_logprobs is None
                and plan.start_pos == 0
            ):
                # the table always exists once prefill ran — a 1-token
                # prompt has zero computable rows but still reports
                # [None] (position 0 never has a logprob)
                seq.prompt_logprobs = [None]
            if prompt_info is not None:
                # chunked prompt-logprobs: each chunk appends its rows
                self._append_prompt_logprobs(
                    seq, prompt_info, plan.start_pos
                )
            if sampled is None:
                return []  # mid-prompt chunk: nothing emitted yet
            # the prompt's K/V is now fully resident: publish its full
            # pages for prefix reuse (device cache + host tier demotion)
            self._register_prefix(seq)
            return self._process_sampled([seq], [[sampled]])
        return self._process_sampled(plan.seqs, result)

    # -------------------------------------------------------------- internal

    def _process_sampled(
        self, seqs: list[Sequence], sampled: list[list[SampledToken]]
    ) -> list[RequestOutput]:
        """Consume each row's sampled tokens (one per fused device step).

        A row that finishes (EOS / stop string / length) mid-list simply
        discards its remaining speculatively decoded tokens — their KV
        writes targeted pages the sequence owned, which are freed with it.
        """
        now = time.time()
        outputs = []
        for seq, toks in zip(seqs, sampled):
            if seq.is_finished:
                continue  # aborted mid-step
            # per-token latency telemetry: a fused wave commits all its
            # tokens with one host timestamp, so the wave's gap since the
            # previous commit is spread evenly over its tokens — sample
            # count stays the token count and the histogram sum stays the
            # true wall time (metrics.inter_token_seconds doc)
            first_wave = seq.metrics.first_token_time is None
            if first_wave:
                ttft = max(0.0, now - seq.metrics.arrival_time)
                metrics.ttft_seconds.observe(ttft)
                if (
                    self.slo is not None
                    and not seq.request_id.startswith("__warmup")
                ):
                    # SLO feed shares the histogram's observation point
                    # (telemetry/slo.py); resumed requests keep their
                    # restored first_token_time, so TTFT never
                    # re-observes across a migration
                    self.slo.observe_ttft(seq.request_class, ttft)
            prev_commit = seq.metrics.last_token_time
            consumed = 0
            for tok in toks:
                seq.output_token_ids.append(tok.token_id)
                consumed += 1
                if seq.fsm is not None:
                    seq.fsm_state = seq.fsm.next_state(
                        seq.fsm_state, tok.token_id
                    )
                if seq.metrics.first_token_time is None:
                    seq.metrics.first_token_time = now
                seq.metrics.last_token_time = now
                detok_t0 = time.perf_counter()
                seq.detokenizer.append([tok.token_id])
                seq.metrics.detokenize_time += (
                    time.perf_counter() - detok_t0
                )
                if seq.output_logprobs is not None:
                    seq.output_logprobs.append(
                        self._build_logprob_dict(seq, tok)
                    )
                self._maybe_finish(seq, tok.token_id)
                if seq.is_finished:
                    seq.metrics.finished_time = now
                    self.scheduler.finish(seq)
                    self._seqs.pop(seq.request_id, None)
                    self.lora_manager.unpin(seq.lora_name)
                    self.recorder.record(
                        "finish", seq.request_id, step=self.step_counter,
                        trace_id=seq.trace_id, reason=seq.finish_reason,
                        output_tokens=seq.num_output_tokens,
                    )
                    outputs.append(seq.to_request_output())
                    break
                if seq.num_output_tokens % DECODE_PROGRESS_EVERY == 0:
                    # bounded per-request decode cadence marker: one ring
                    # entry per N tokens, not per token
                    self.recorder.record(
                        "decode_progress", seq.request_id,
                        step=self.step_counter, trace_id=seq.trace_id,
                        output_tokens=seq.num_output_tokens,
                    )
                if seq.params.output_kind != RequestOutputKind.FINAL_ONLY:
                    # DELTA with an empty text delta still carries the token
                    outputs.append(seq.to_request_output())
            if not first_wave and prev_commit is not None and consumed:
                itl = max(0.0, now - prev_commit) / consumed
                for _ in range(consumed):
                    metrics.inter_token_seconds.observe(itl)
                if (
                    self.slo is not None
                    and not seq.request_id.startswith("__warmup")
                ):
                    for _ in range(consumed):
                        self.slo.observe_itl(seq.request_class, itl)
        return outputs

    def _maybe_finish(self, seq: Sequence, token_id: int) -> None:
        params = seq.params
        eos = self.config.model_config.eos_token_id
        if not params.ignore_eos and token_id == eos:
            seq.status = SequenceStatus.FINISHED_STOPPED
            seq.stop_reason = None
            return
        if params.stop:
            text = seq.output_text
            best: Optional[tuple[int, str]] = None
            # scan only the tail that new text could have completed: every
            # char before stop_scan_pos was already cleared on an earlier
            # token, so a first match can only start within len(s)-1 chars
            # of the old frontier (keeps the per-token cost O(delta), not
            # O(total output) — the earliest-match result is unchanged)
            frontier = seq.stop_scan_pos
            for s in params.stop:
                idx = text.find(s, max(0, frontier - len(s) + 1))
                if idx != -1 and (best is None or idx < best[0]):
                    best = (idx, s)
            seq.stop_scan_pos = len(text)
            if best is not None:
                idx, s = best
                seq.status = SequenceStatus.FINISHED_STOPPED
                seq.stop_reason = s
                end = idx + len(s) if params.include_stop_str_in_output else idx
                seq.detokenizer.output_text = text[:end]
                return
        max_tokens = params.max_tokens
        if max_tokens is not None and seq.num_output_tokens >= max_tokens:
            seq.status = SequenceStatus.FINISHED_LENGTH
            return
        if seq.num_tokens >= self.config.max_model_len:
            seq.status = SequenceStatus.FINISHED_LENGTH

    def _decode_token_text(self, token_id: int) -> str:
        return self.tokenizer.convert_ids_to_tokens(token_id)

    def _build_logprob_dict(
        self, seq: Sequence, tok: SampledToken
    ) -> dict[int, Logprob]:
        """{token_id: Logprob} for the chosen token + requested top-N."""
        n = seq.params.logprobs or 0
        entry: dict[int, Logprob] = {}
        for i in range(min(n, len(tok.topn_ids))):
            tid = tok.topn_ids[i]
            entry[tid] = Logprob(
                logprob=tok.topn_logprobs[i],
                rank=i + 1,
                decoded_token=self._decode_token_text(tid),
            )
        if tok.token_id not in entry:
            entry[tok.token_id] = Logprob(
                logprob=tok.logprob,
                rank=tok.rank,
                decoded_token=self._decode_token_text(tok.token_id),
            )
        return entry

    def _append_prompt_logprobs(
        self, seq: Sequence, info: PromptLogprobInfo, start_pos: int
    ) -> None:
        """Fold one (chunk's) prompt-logprob rows into the sequence's
        table.  Row i describes position ``start_pos + i + 1``; chunks
        commit in order, so appends only happen when the table's length
        is exactly the chunk's start — a preemption-resume re-running
        chunks over an already-recorded span is a no-op."""
        if seq.prompt_logprobs is None:
            seq.prompt_logprobs = [None]  # position 0 has no logprob
        if len(seq.prompt_logprobs) != start_pos + 1:
            return
        n = seq.params.prompt_logprobs or 0
        for i in range(len(info.logprobs)):
            pos = start_pos + i + 1
            if pos >= len(seq.prompt_token_ids):
                break
            token_id = seq.prompt_token_ids[pos]
            entry: dict[int, Logprob] = {}
            for j in range(min(n, len(info.topn_ids[i]))):
                tid = info.topn_ids[i][j]
                entry[tid] = Logprob(
                    logprob=info.topn_logprobs[i][j],
                    rank=j + 1,
                    decoded_token=self._decode_token_text(tid),
                )
            if token_id not in entry:
                entry[token_id] = Logprob(
                    logprob=info.logprobs[i],
                    rank=info.ranks[i],
                    decoded_token=self._decode_token_text(token_id),
                )
            seq.prompt_logprobs.append(entry)
