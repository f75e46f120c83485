"""Async engine client: the interface the serving layer programs against.

Shape-compatible with the ``EngineClient`` surface the reference adapter
consumes from vLLM (SURVEY.md §2.3; consumption points grpc_server.py:68,
205-225, 292, 648-660): ``generate(...)`` returns an async stream of
RequestOutput, ``abort`` cancels and evicts, ``errored``/``is_running``
surface engine death to the servers, and the tokenizer/model-config
accessors feed validation.

Concurrency model: the jitted device step is blocking, so each step loop
dispatches it to a worker thread (device work is serialized per replica
by construction) while asyncio queues fan results out to per-request
streams.

Data parallelism (in-process): ``--data-parallel-size N`` builds N full
engine replicas, each owning a disjoint ``pp × sp × tp`` device slice
(a replica can be a whole pipeline), its own scheduler/KV pool, and its
own step loop — DP for inference is
independent batches, so replicas share nothing on the critical path
(SURVEY.md §2.4: replica groups; no cross-replica collectives needed).
New requests route to the least-loaded replica; the LoRA registry is
shared so one hot-load serves the whole fleet; any replica death is
whole-engine death (crash-fast, same as the reference's engine-death
semantics).  This is the same replica-per-device-group shape the
reference stack gets from deployment-level DP, minus the extra pods: one
process, one tokenizer, both servers, N device groups.
"""

from __future__ import annotations

import asyncio
import os
import time
from collections.abc import AsyncGenerator, Mapping
from typing import Optional

from vllm_tgis_adapter_tpu import metrics
from vllm_tgis_adapter_tpu.engine.config import EngineConfig
from vllm_tgis_adapter_tpu.engine.core import LLMEngine, describe_plan
from vllm_tgis_adapter_tpu.engine.outputs import (
    CompletionOutput,
    RequestOutput,
)
from vllm_tgis_adapter_tpu.engine.sampling_params import (
    RequestOutputKind,
    SamplingParams,
)
from vllm_tgis_adapter_tpu.frontdoor.errors import (
    SHED_TTL,
    AdmissionShedError,
)
from vllm_tgis_adapter_tpu.logging import init_logger
from vllm_tgis_adapter_tpu.telemetry import (
    CostLedger,
    JsonlSink,
    SloEngine,
)
from vllm_tgis_adapter_tpu.telemetry.doctor import Doctor, ReplicaSignals
from vllm_tgis_adapter_tpu.telemetry.slo import (
    estimate_tokens,
    parse_slo_config,
    resolve_request_class,
)
from vllm_tgis_adapter_tpu.supervisor.lifecycle import (
    LIFECYCLE_DEAD,
    LIFECYCLE_RECOVERING,
    LIFECYCLE_SERVING,
)
from vllm_tgis_adapter_tpu.utils import spawn_task, write_termination_log

logger = init_logger(__name__)


class EngineDeadError(RuntimeError):
    pass


# replica-role capability sets: the router owns the one canonical
# table (frontdoor/placement.py) — admission filtering and routing can
# never diverge on what a role may serve
from vllm_tgis_adapter_tpu.frontdoor.placement import ROLE_CAPABLE

_PREFILL_CAPABLE = ROLE_CAPABLE["prefill"]
_DECODE_CAPABLE = ROLE_CAPABLE["decode"]

#: engine-resident admission window per replica when the front door is
#: on: enough waiting candidates for the ragged planner to fill a flat
#: bucket per step, while ordering beyond it stays WFQ-controlled
#: (frontdoor/admission.py).  Historically MAX_PACK of the retired
#: packed-prefill planner.
ADMIT_WINDOW = 8


class _Replica:
    """One engine + the concurrency state serializing access to it."""

    __slots__ = ("engine", "lock", "new_work", "task", "index",
                 "last_beat", "in_flight_desc", "serving", "role")

    def __init__(self, engine: LLMEngine, index: int):
        self.engine = engine
        self.index = index
        # prefill/decode disaggregation role (docs/SCALING.md
        # "Disaggregated roles"), stamped by apply_replica_roles;
        # "mixed" = pre-disaggregation behavior
        self.role = "mixed"
        # False while this replica's supervisor has it quiesced for a
        # rebuild: the placement router excludes it, the front door's
        # drain estimator stops counting its capacity, and new arrivals
        # land on its healthy siblings (capacity loss, not an outage —
        # docs/SCALING.md).  Flipped back on lifecycle→serving.
        self.serving = True
        # serializes engine-state mutations (add/abort) against the step
        # host phases — scheduler state is not thread-safe
        self.lock = asyncio.Lock()
        self.new_work = asyncio.Event()
        self.task: Optional[asyncio.Task] = None
        # stall-watchdog heartbeat: the step loop touches this every
        # iteration; request submission touches it too so a dead loop
        # gets exactly one deadline of grace from when work arrives
        self.last_beat = time.monotonic()
        # describe_plan() summary of the dispatch currently in flight
        # (None between dispatches) — the watchdog dump's "what was the
        # device doing" line
        self.in_flight_desc: Optional[dict] = None


class AsyncLLMEngine:
    def __init__(self, engine: LLMEngine | list[LLMEngine]):
        engines = engine if isinstance(engine, list) else [engine]
        # replica 0 doubles as the host-side singleton surface (tokenizer,
        # model config, shared LoRA registry) the serving layer reads
        self.engine = engines[0]
        self._replicas = [_Replica(e, i) for i, e in enumerate(engines)]
        for rep in self._replicas:
            # the `replica` label on the per-dispatch step metrics
            rep.engine.replica_index = rep.index
        # affinity-aware placement over the replica fleet
        # (frontdoor/placement.py): prefix-cache residency > tenant/
        # adapter stickiness > least-loaded.  Built even at dp=1 (it
        # also carries per-replica committed-token attribution), but
        # generate() short-circuits single-replica routing so dp=1
        # placement costs nothing and scores nothing.
        from vllm_tgis_adapter_tpu.frontdoor.placement import (
            PlacementRouter,
        )

        self.router = PlacementRouter()
        # prefill/decode disaggregation (docs/SCALING.md): flipped by
        # apply_replica_roles when any replica serves a dedicated role;
        # lifetime handoff outcomes feed /debug/state
        self._roles_active = False
        self.handoff_outcomes = {"completed": 0, "fallback": 0}
        self._owner: dict[str, _Replica] = {}
        self._queues: dict[str, asyncio.Queue] = {}
        # request_ids whose abort() arrived while add_request was still
        # in flight on the owner replica (see generate()/abort())
        self._early_aborts: set[str] = set()
        self._dead_error: Optional[BaseException] = None
        self._stopped = False
        # lifecycle state machine (supervisor/lifecycle.py): serving →
        # recovering → serving under supervision; → dead when terminal.
        # Every health surface reads THIS, not the raw booleans.
        self.lifecycle = LIFECYCLE_SERVING
        # set exactly once, on terminal death — __main__ waits on it so
        # the process exits promptly instead of at the next RPC
        self.dead_event = asyncio.Event()
        # precompile() remembers its batch-widths argument so a
        # supervised rebuild can re-warm the same serving shapes
        self._precompile_widths: Optional[str] = None
        # the replica the last stall snapshot blamed (consumed by
        # --watchdog-action=restart so the restart hits that replica)
        self._last_stalled_rep: Optional[_Replica] = None
        # periodic operational stats line (vLLM-style), unless
        # --disable-log-stats
        self._stats_task: Optional[asyncio.Task] = None
        # one server span per request when --otlp-traces-endpoint is set
        self._tracer = None
        endpoint = self.engine.config.otlp_traces_endpoint
        if endpoint:
            from vllm_tgis_adapter_tpu.tracing import RequestTracer

            self._tracer = RequestTracer(endpoint)
        # telemetry signal layer (telemetry/, docs/OBSERVABILITY.md):
        # the cost ledger and SLO engine live HERE, above the replicas —
        # supervised restarts and cross-replica resumes swap engine
        # cores underneath a request, but its open ledger record and
        # SLO class stay put, so a migrated request bills exactly once
        cfg = self.engine.config
        self.slo_engine = SloEngine(parse_slo_config(cfg.slo_config))
        self._ledger_sink = (
            JsonlSink(cfg.ledger_log) if cfg.ledger_log else None
        )
        self.ledger = CostLedger(
            sink=self._ledger_sink,
            recorder=self.engine.recorder.record,
        )
        # bottleneck doctor (telemetry/doctor.py): fleet-level regime
        # classifier over the per-replica step-anatomy windows.  The
        # record hook lands `doctor` events on the BLAMED replica's
        # recorder (batch-scoped, no request_id); the profiler hook
        # resolves the shared controller lazily so a later
        # --profile-dir enables episode auto-capture without re-wiring
        self.doctor = Doctor(
            record=self._doctor_record,
            profiler=self._doctor_profiler,
        )
        # request_id -> server Span while the stream is live: resume
        # and handoff spans link back to it (tracing.py resume_span)
        self._spans: dict[str, object] = {}
        # the clock of each replica's last KV page-seconds sample
        self._kv_sample_t: dict[int, float] = {}
        for rep in self._replicas:
            # engine cores feed SLO latency observations and ledger
            # attributions through these refs (None-guarded call sites;
            # restart_replica re-attaches them on the rebuilt core)
            rep.engine.slo = self.slo_engine
            rep.engine.ledger = self.ledger
        # front door (frontdoor/admission.py): bounded admission, per-
        # tenant weighted fair queuing, rate limits, queue TTLs, drain.
        # The serving layer hands requests here; the engine's own
        # waiting queue keeps only a small admission window (enough for
        # the ragged planner to fill its flat bucket with candidates)
        # and everything beyond it parks in the fair queue.
        self.frontdoor = None
        fd_config = getattr(self.engine.config, "frontdoor", None)
        if fd_config is not None and fd_config.enabled:
            from vllm_tgis_adapter_tpu.frontdoor.admission import FrontDoor

            window = min(
                self.engine.config.scheduler_config.max_num_seqs,
                ADMIT_WINDOW,
            )
            self.frontdoor = FrontDoor(
                fd_config,
                admit_window=window,
                room_fn=self._frontdoor_room,
                waiting_depth_fn=lambda: sum(
                    len(rep.engine.scheduler.waiting)
                    for rep in self._replicas
                ),
                # drain-estimate inputs count SERVING replicas only: a
                # recovering replica's backlog is being replayed onto
                # its siblings and its capacity is gone until re-admit,
                # so pricing it would fire --admission-deadline sheds
                # spuriously during a partial outage
                backlog_tokens_fn=lambda: float(sum(
                    rep.engine.scheduler.waiting_token_backlog()
                    for rep in self._serving_replicas()
                )),
                kv_token_capacity_fn=self._kv_token_capacity,
                # the TRUE serving set — deliberately NOT
                # _serving_replicas(), whose full-fleet fallback would
                # make a full outage unrepresentable here and leave the
                # estimator summing dead replicas' stale EWMAs instead
                # of falling back to the capacity prior
                serving_replicas_fn=lambda: frozenset(
                    rep.index
                    for rep in self._replicas
                    if rep.serving
                ),
                record_shed=self._record_shed,
            )
            for rep in self._replicas:
                # scheduler-side TTL sheds count toward the same
                # lifetime total /debug/state reports
                rep.engine.scheduler.shed_hook = (
                    self.frontdoor.note_external_shed
                )
        # stall watchdog (watchdog.py): heartbeat-fed; fires a full
        # diagnostic snapshot when a step loop with unfinished work stops
        # beating past the configured deadline.  0 disables.
        self.watchdog = None
        config = self.engine.config
        if config.watchdog_deadline_s > 0:
            from vllm_tgis_adapter_tpu.watchdog import StallWatchdog

            self.watchdog = StallWatchdog(
                snapshot_fn=self._stall_snapshot,
                active_fn=lambda: any(
                    rep.engine.has_unfinished_requests()
                    for rep in self._replicas
                ),
                age_fn=self._stall_age,
                deadline_s=config.watchdog_deadline_s,
                dump_dir=config.dump_dir,
                action=config.watchdog_action,
                restart_fn=self._watchdog_restart,
            )
        # engine supervision (supervisor/): --max-engine-restarts > 0
        # turns engine death from terminal into quiesce → replay-safe
        # triage → rebuild → re-arm, with a crash-loop circuit breaker.
        # 0 (the library/config default) keeps crash-fast semantics.
        if (
            config.watchdog_action == "restart"
            and config.watchdog_deadline_s <= 0
        ):
            # same loud-downgrade courtesy as the pp gate below: the
            # operator asked for stall restarts, but with the watchdog
            # disabled no stall is ever detected
            logger.warning(
                "--watchdog-action=restart has no effect with "
                "--watchdog-deadline 0: the stall watchdog is disabled, "
                "so stalls are never detected"
            )
        self.supervisor = None
        if config.max_engine_restarts > 0:
            if config.parallel_config.pipeline_parallel_size > 1:
                # the rebuild path reuses runner.params, which the
                # PipelineRunner splits into per-stage state at
                # construction — supervised rebuild under pp needs
                # per-stage plumbing that doesn't exist yet.  Refuse
                # loudly at boot (crash-fast semantics preserved)
                # rather than crash-looping on the first real death.
                logger.warning(
                    "engine supervision is not supported with "
                    "--pipeline-parallel-size > 1 yet; running with "
                    "crash-fast engine-death semantics"
                )
            else:
                from vllm_tgis_adapter_tpu.supervisor.supervisor import (
                    EngineSupervisor,
                )

                self.supervisor = EngineSupervisor(
                    self,
                    max_restarts=config.max_engine_restarts,
                    window_s=config.engine_restart_window_s,
                    backoff_base_s=config.engine_restart_backoff_s,
                )
        # networked KV tier (kvnet/, docs/CROSS_HOST.md): cross-host
        # prefix sharing + remote handoffs + machine-loss resume.
        # Default OFF — with no --kvnet-* flags nothing below changes.
        self.kvnet = None
        if getattr(config, "kvnet_listen", None) or getattr(
            config, "kvnet_peers", ()
        ):
            if getattr(self.engine, "kv_tier", None) is None:
                logger.warning(
                    "--kvnet-* requires the host KV tier "
                    "(--kv-host-cache-gb > 0); kvnet disabled"
                )
            else:
                from vllm_tgis_adapter_tpu.kvnet.manager import (
                    KvNetManager,
                )

                self.kvnet = KvNetManager(self, config)

    # ------------------------------------------------------------ frontdoor

    def _serving_replicas(self) -> list[_Replica]:
        """Replicas placement may use.  Falls back to the full fleet
        when every replica is quiesced (full-outage recovery: the front
        door is paused then, so nothing is placed anyway, but the
        estimator and gauges must not divide by an empty fleet)."""
        serving = [rep for rep in self._replicas if rep.serving]
        return serving or self._replicas

    def _role_capable(self, kind: str) -> list[_Replica]:
        """Serving replicas able to take ``kind`` work ("prefill" =
        fresh prompts/replays, "decode" = handoff/checkpoint resumes).
        With roles inactive this is exactly the serving set; with roles
        active and NO capable replica serving (partial outage) it falls
        open to the serving set — the same availability-over-purity
        fallback the router's role tier makes (callers that must not
        degrade, like the handoff drain, pre-check capability
        themselves)."""
        serving = self._serving_replicas()
        if not self._roles_active:
            return serving
        want = (
            _PREFILL_CAPABLE if kind == "prefill" else _DECODE_CAPABLE
        )
        return [rep for rep in serving if rep.role in want] or serving

    def apply_replica_roles(self, roles) -> None:  # noqa: ANN001
        """Stamp per-replica disaggregation roles (from_config; tests).
        The role reaches three layers: the replica record (placement,
        front-door estimators), the engine core (handoff staging at
        prefill commit, promotion bound), and the scheduler
        (role-aware backlog estimation)."""
        roles = tuple(roles)
        if len(roles) != len(self._replicas):
            raise ValueError(
                f"{len(roles)} role(s) for {len(self._replicas)} "
                "replica(s)"
            )
        for rep, role in zip(self._replicas, roles):
            rep.role = role
            rep.engine.set_replica_role(role)
        self._roles_active = any(r != "mixed" for r in roles)

    def _frontdoor_room(self, pending: int) -> bool:
        """Can some PREFILL-CAPABLE serving replica take another
        admission, counting grants already issued but not yet turned
        into ``add_request``?  Fresh admissions only ever place onto
        prefill-capable replicas (role tier), so a decode replica's
        near-empty waiting queue must not open the window."""
        depth = min(
            len(rep.engine.scheduler.waiting)
            for rep in self._role_capable("prefill")
        )
        return depth + pending < self.frontdoor.admit_window

    def _kv_token_capacity(self) -> float:
        """Total KV pool size in tokens (the resolve_num_blocks budget)
        — the admission estimator's throughput prior.  A quiesced
        replica's pool is not capacity; under disaggregated roles only
        DECODE-CAPABLE replicas count — tokens are produced there, and
        a prefill replica's pool turns over into the host tier rather
        than into output throughput."""
        total = 0
        for rep in self._role_capable("decode"):
            scheduler = rep.engine.scheduler
            total += scheduler.allocator.num_blocks * scheduler.block_size
        return float(total)

    def _place_replica(
        self,
        prompt_token_ids,  # noqa: ANN001 — Optional[list[int]]
        tenant: Optional[str],
        lora_name: Optional[str],
        kind: str = "prefill",
    ) -> _Replica:
        """Route one request onto a replica (frontdoor/placement.py).

        ``kind`` drives the router's role tier ("prefill" = fresh
        prompts and replays, "decode" = handoff/checkpoint resumes).
        Single-replica fleets short-circuit — dp=1 routing is exactly
        the pre-router behavior, with no peek_prefix probe and no
        placement accounting."""
        if len(self._replicas) == 1:
            return self._replicas[0]
        from vllm_tgis_adapter_tpu.frontdoor.placement import (
            ReplicaSnapshot,
        )

        candidates = self._serving_replicas()
        # host-tier residency (engine/kv_tier.py): probed ONCE — the
        # tier is shared fleet-wide, so every replica could promote the
        # same pages; the router scores it below device residency
        # (docs/SCALING.md placement tiers)
        host_tokens = 0
        remote_tokens = 0
        tier = self.engine.kv_tier
        if prompt_token_ids and tier is not None:
            # incremental walk: one hash on a cold tier, O(covered)
            # when warm — this runs per request on the admission path
            local_pages = tier.peek_prefix_pages(
                prompt_token_ids, lora_name, include_remote=False
            )
            host_tokens = tier.block_size * local_pages
            if getattr(tier, "remote", None) is not None:
                # the covered-minus-local split: pages only a kvnet
                # peer holds score at the (lower) remote-tier weight —
                # the fetch + host→device transfer both still have to
                # happen (docs/CROSS_HOST.md).  start_page resumes the
                # chain walk where local coverage broke, so the return
                # IS the remote-only extension
                remote_tokens = tier.block_size * tier.peek_prefix_pages(
                    prompt_token_ids, lora_name, start_page=local_pages
                )
        snapshots = []
        for rep in candidates:
            scheduler = rep.engine.scheduler
            prefix_tokens = 0
            if (
                prompt_token_ids
                and scheduler.allocator.enable_prefix_caching
            ):
                # pure hash walk (no refcounts, no LRU mutation) — the
                # same read-only probe the chained-decode admissibility
                # check uses, safe from the event loop
                prefix_tokens = scheduler.allocator.peek_prefix(
                    prompt_token_ids, lora_name
                )
            # TRUE adapter-pool residency, not just remembered
            # stickiness: landing on a replica whose pool already holds
            # the adapter skips the host→device stream entirely
            pool = getattr(rep.engine.runner, "adapter_pool", None)
            snapshots.append(ReplicaSnapshot(
                index=rep.index,
                load=scheduler.num_unfinished,
                prefix_tokens=prefix_tokens,
                host_prefix_tokens=host_tokens,
                remote_prefix_tokens=remote_tokens,
                adapter_resident=(
                    pool is not None and pool.resident(lora_name)
                ),
                replica_role=rep.role,
            ))
        index, _policy = self.router.place(
            snapshots,
            # anonymous default-tenant traffic gets no stickiness: bulk
            # untagged load must spread by depth, not pile onto one
            # replica behind a sticky "default" entry
            affinity_key=tenant or lora_name,
            kind=kind,
        )
        for rep in candidates:
            if rep.index == index:
                return rep
        return candidates[0]  # unreachable; defensive

    def _record_shed(
        self, request_id: str, tenant: str, reason: str, **detail
    ) -> None:
        """Flight-recorder hook for front-door sheds; the request never
        reached a replica, so the event lands on the host-surface
        (replica 0) recorder.  The noted reason makes the ledger close
        with outcome "shed" whatever the stream-level exit looks like
        (scheduler TTL sheds surface as graceful aborted frames)."""
        self.ledger.note_shed(request_id, reason)
        self.engine.recorder.record(
            "shed", request_id, step=self.engine.step_counter,
            tenant=tenant, reason=reason, **detail,
        )

    @staticmethod
    def _plan_tokens(plan) -> int:  # noqa: ANN001 — any engine plan type
        """Committed-token estimate of one dispatch, for the front
        door's throughput EWMA.  Tolerant of every plan shape."""
        items = getattr(plan, "items", None)
        if items is not None:  # packed prefill
            return sum(len(i.token_ids) for i in items)
        token_ids = getattr(plan, "token_ids", None)
        if token_ids is not None:  # solo prefill chunk
            return len(token_ids)
        steps = getattr(plan, "steps_per_seq", None)
        if steps:  # fused decode
            return sum(steps)
        seqs = getattr(plan, "seqs", None)
        if seqs is not None:
            return len(seqs) * getattr(plan, "num_steps", 1)
        return 0

    # ------------------------------------------------------------- lifecycle

    @classmethod
    def from_config(cls, config: EngineConfig) -> "AsyncLLMEngine":
        import dataclasses

        pcfg = config.parallel_config
        # two spellings of the replica count (config.py validates that
        # at most one is > 1): data_parallel_size requires disjoint
        # device slices, dp_replicas tolerates sharing them
        dp = max(pcfg.data_parallel_size, pcfg.dp_replicas)
        if dp <= 1:
            fleet = cls(LLMEngine.from_config(config))
            # a dp=1 host may still serve a dedicated role when the
            # missing capability lives across the kvnet (a lone
            # prefill host handing decodes to peers, docs/CROSS_HOST.md)
            # — config validation already demanded peers for that shape
            fleet.apply_replica_roles(config.resolved_replica_roles())
            return fleet
        import jax

        # each replica owns a full sp×tp slice — or, under pp, a full
        # pipeline's pp×tp worth of devices
        per_replica = (
            pcfg.tensor_parallel_size
            * pcfg.sequence_parallel_size
            * pcfg.pipeline_parallel_size
        )
        devices = jax.devices()
        shared_devices = False
        if dp * per_replica > len(devices):
            if pcfg.dp_replicas <= 1:
                raise ValueError(
                    f"data_parallel_size={dp} needs {dp * per_replica} "
                    f"devices (pp×sp×tp={per_replica} each) but only "
                    f"{len(devices)} are visible"
                )
            # --dp-replicas shared-device mode: every replica runs on
            # the same device slice with its own KV pool.  Correct, and
            # what the CPU and chaos tests use; on a real
            # accelerator N pools on one HBM is almost never what you
            # want — say so loudly.
            shared_devices = True
            logger.warning(
                "--dp-replicas %d exceeds the %d visible device(s): "
                "replicas will SHARE the device set (each with its own "
                "KV pool).  Fine on CPU hosts; on accelerators prefer "
                "--data-parallel-size with disjoint slices",
                dp, len(devices),
            )
        replica_config = dataclasses.replace(
            config,
            parallel_config=dataclasses.replace(
                pcfg, data_parallel_size=1, dp_replicas=1
            ),
            # roles are a FLEET property: the per-replica config must
            # re-validate as an ordinary dp=1 engine (a one-replica
            # config can never satisfy the fleet-level role demands);
            # apply_replica_roles stamps each engine below
            replica_role="mixed",
            dp_replica_roles=(),
        )
        engines = []
        for rank in range(dp):
            logger.info("building dp replica %d/%d", rank + 1, dp)
            engines.append(
                LLMEngine.from_config(
                    replica_config,
                    devices=(
                        devices[:per_replica]
                        if shared_devices
                        else devices[
                            rank * per_replica:(rank + 1) * per_replica
                        ]
                    ),
                )
            )
        # one adapter registry fleet-wide: a hot-load registers once
        # (host RAM) and every replica's POOL streams its own device
        # residency from the shared weights on demand; pin/unpin
        # refcounts sum across replicas so no replica can evict an
        # adapter another replica's running row still indexes.  Safe
        # unsynchronized: all mutations happen in host phases on the one
        # event-loop thread.
        shared = engines[0].lora_manager
        for e in engines[1:]:
            e.adopt_lora_manager(shared)
        # one host KV tier fleet-wide (engine/kv_tier.py): KV content is
        # a pure function of tokens ‖ adapter ‖ model, so pages demoted
        # by any replica serve every replica — and the shared store is
        # what a rebuilt replica re-warms from (docs/KV_TIERING.md)
        if engines[0].kv_tier is not None:
            for e in engines[1:]:
                e.adopt_kv_tier(engines[0].kv_tier)
        fleet = cls(engines)
        # prefill/decode disaggregation (docs/SCALING.md): stamp each
        # replica's role — placement, handoff staging, and the front
        # door's estimators all read it
        fleet.apply_replica_roles(config.resolved_replica_roles())
        return fleet

    STATS_INTERVAL_S = 10.0

    async def precompile(self, batch_widths: str = "all") -> int:
        """Warm every serving shape on every replica before ``start()``
        (--precompile): delegates to each core engine's precompile off
        the event loop.  Returns total warmup requests run."""
        # remembered so a supervised rebuild re-warms the same shapes
        self._precompile_widths = batch_widths
        total = 0
        for rep in self._replicas:
            total += await asyncio.to_thread(
                rep.engine.precompile, batch_widths
            )
        return total

    async def start(self) -> None:
        for rep in self._replicas:
            if rep.task is None:
                rep.task = spawn_task(
                    self._run_loop(rep),
                    name=f"engine-step-loop-{rep.index}",
                )
        if self._stats_task is None:
            # always runs: it also feeds the /metrics engine-state gauges
            # (KV usage, queue depth); --disable-log-stats gates only the
            # periodic log LINE inside the loop
            self._stats_task = spawn_task(
                self._log_stats_loop(), name="engine-stats-loop"
            )
        if self.watchdog is not None:
            self.watchdog.start()
        if self.kvnet is not None:
            # after the step loops: a peer's first INDEX sync may land
            # as soon as the service port is open
            await self.kvnet.start()

    async def stop(self) -> None:
        self._stopped = True
        if self.supervisor is not None:
            # an in-flight recovery must not race the teardown below
            await self.supervisor.stop()
        if self.kvnet is not None:
            # before the replicas: output pumps and the peer service
            # must not observe half-torn engines
            await self.kvnet.stop()
        if self.frontdoor is not None:
            # parked waiters fail fast instead of hanging on a pump
            # that is about to be cancelled
            await self.frontdoor.shutdown()
        if self.watchdog is not None:
            await self.watchdog.stop()
        if self._stats_task is not None:
            self._stats_task.cancel()
            self._stats_task = None
        for rep in self._replicas:
            rep.new_work.set()
            # terminal shutdown: in-flight adapter streams must not
            # outlive the loop (pending-task noise, pinned device stacks)
            pool = getattr(rep.engine.runner, "adapter_pool", None)
            if pool is not None:
                pool.close()
            if rep.task is not None:
                rep.task.cancel()
                try:
                    await rep.task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
                rep.task = None
        tier = getattr(self.engine, "kv_tier", None)
        if tier is not None:
            # terminal shutdown: stop accepting demotions and release
            # the host pages (restart-survival is the SUPERVISOR's path,
            # which never calls stop())
            tier.close()
        # final drain: records closed since the last stats tick must
        # reach the JSONL file before the process exits
        sink = self._ledger_sink
        if sink is not None and sink.pending:
            await asyncio.to_thread(sink.flush_sync)
        if self._tracer is not None:
            # flush buffered spans before the exporter thread dies with
            # the process
            await asyncio.to_thread(self._tracer.shutdown)

    # ----------------------------------------------------- EngineClient-like

    @property
    def errored(self) -> bool:
        return self._dead_error is not None

    @property
    def dead_error(self) -> BaseException:
        return self._dead_error or EngineDeadError("engine is dead")

    @property
    def is_running(self) -> bool:
        """Every SERVING replica's step loop is alive.  A replica the
        supervisor has quiesced (serving=False, task reaped) does not
        count against the fleet — a partial outage still serves; with
        every replica quiesced (dp=1 recovery, or a full-fleet fault)
        this is False, exactly the pre-router behavior."""
        if self.errored or self._stopped:
            return False
        serving = [rep for rep in self._replicas if rep.serving]
        return bool(serving) and all(
            rep.task is not None and not rep.task.done()
            for rep in serving
        )

    async def get_tokenizer(self, lora_request=None):  # noqa: ANN001
        if lora_request is None:
            return self.engine.get_tokenizer()
        path = getattr(lora_request, "lora_path", None)
        cached = self.engine._lora_tokenizers.get(path)
        if cached is not None:
            return cached
        # cold path does filesystem probes + a tokenizer load; keep it off
        # the event loop
        return await asyncio.to_thread(
            self.engine.get_tokenizer, lora_request
        )

    async def get_model_config(self):
        return self.engine.get_model_config()

    async def is_tracing_enabled(self) -> bool:
        return self.engine.config.otlp_traces_endpoint is not None

    async def check_health(self) -> None:
        if self.errored:
            raise self.dead_error

    async def generate(
        self,
        prompt: Optional[str] = None,
        sampling_params: Optional[SamplingParams] = None,
        request_id: str = "",
        *,
        prompt_token_ids: Optional[list[int]] = None,
        lora_request=None,  # noqa: ANN001 — adapter-store LoRARequest
        trace_headers: Optional[Mapping[str, str]] = None,
        tenant_id: Optional[str] = None,
        deadline: Optional[float] = None,
    ) -> AsyncGenerator[RequestOutput, None]:
        """Submit a request and stream its outputs.

        Yield cadence follows ``sampling_params.output_kind``: DELTA and
        CUMULATIVE yield every step, FINAL_ONLY yields exactly once.

        ``tenant_id`` keys front-door fair queuing / rate limits;
        ``deadline`` (epoch seconds) lets the queue TTL early-abort the
        request if it would only start prefill after its SLO.  May raise
        ``AdmissionShedError`` (frontdoor/errors.py) before any engine
        state is touched.
        """
        if self.errored:
            raise self.dead_error
        if self.lifecycle == LIFECYCLE_RECOVERING and self.frontdoor is None:
            # without a front door there is nowhere to park the request
            # while the engine rebuilds — refuse retryable (UNAVAILABLE
            # + Retry-After), never with the terminal dead error
            from vllm_tgis_adapter_tpu.frontdoor.errors import (
                EngineRestartError,
            )

            raise EngineRestartError(
                "engine is restarting after a fault; retry shortly",
                retry_after_s=2.0,
            )
        if self._replicas[0].task is None and self.lifecycle == LIFECYCLE_SERVING:
            await self.start()
        sampling_params = sampling_params or SamplingParams()
        if request_id in self._queues:
            # reject WITHOUT touching the existing request's queue
            raise ValueError(f"duplicate request_id {request_id!r}")
        # admission-time request-class resolution + ledger open
        # (telemetry/): opened BEFORE the front door so a shed closes a
        # record too; settle() below closes it exactly once at the
        # stream's terminal outcome
        lora_name = getattr(lora_request, "name", None)
        request_class = resolve_request_class(
            trace_headers,
            estimate_tokens(prompt_token_ids, prompt),
            sampling_params.max_tokens,
        )
        opened = self.ledger.open(
            request_id,
            tenant=tenant_id or lora_name,
            request_class=request_class,
            tokens_in=(
                len(prompt_token_ids)
                if prompt_token_ids is not None
                else 0
            ),
            lora_name=lora_name,
        ) is not None

        def settle(outcome: str, final=None) -> None:  # noqa: ANN001
            nonlocal opened
            if not opened:
                return
            opened = False
            rec = self.ledger.close(
                request_id, outcome,
                request_metrics=getattr(final, "metrics", None),
                step=self.engine.step_counter,
            )
            if rec is None:
                return
            # availability feed (telemetry/slo.py): the terminal
            # outcome under the class resolved at admission.  Warmup
            # traffic is exempt like the TTFT/ITL feeds (core.py):
            # precompile passes stall on XLA by design and must not
            # burn real error budget — the ledger still bills them
            if not request_id.startswith("__warmup"):
                self.slo_engine.observe_outcome(
                    rec.request_class, rec.outcome
                )

        if self.frontdoor is None:
            # --disable-frontdoor restores pre-PR4 semantics entirely:
            # no queue-TTL deadline reaches the scheduler either
            deadline = None
        else:
            # the queue-TTL clock starts NOW — time parked in the fair
            # queue counts against --queue-ttl, not just engine time
            ttl = self.frontdoor.config.queue_ttl_s
            if ttl > 0:
                ttl_deadline = time.time() + ttl
                deadline = (
                    ttl_deadline
                    if deadline is None
                    else min(deadline, ttl_deadline)
                )
            # the front door may park us (fair-queue order, engine
            # admission window) or shed us (bounds/limits/drain); a shed
            # leaves zero engine state behind
            est_tokens = (
                len(prompt_token_ids)
                if prompt_token_ids is not None
                else max(1, len(prompt or "") // 4)
            ) + (sampling_params.max_tokens or 16)
            try:
                await self.frontdoor.acquire(
                    request_id=request_id,
                    tenant=tenant_id or getattr(lora_request, "name", None),
                    tokens=float(est_tokens),
                    deadline=deadline,
                )
            except AdmissionShedError as e:
                # the _record_shed hook already noted the reason;
                # note again here for direct-raise paths that bypass it
                self.ledger.note_shed(request_id, e.reason)
                settle("shed")
                if e.reason != SHED_TTL:
                    raise
                # deadline passed while parked: the SAME graceful wire
                # shape as a scheduler-side TTL shed — one final empty
                # aborted frame, not an RPC error.  A batched RPC's
                # timed-out sub-request must not abort its siblings,
                # and TGIS time_limit semantics are a partial (here:
                # empty) response, not DEADLINE_EXCEEDED.
                yield RequestOutput(
                    request_id=request_id,
                    prompt=prompt,
                    prompt_token_ids=list(prompt_token_ids or []),
                    outputs=[CompletionOutput(
                        index=0, text="", token_ids=[],
                        finish_reason="abort",
                    )],
                    finished=True,
                )
                return
            if request_id in self._queues:
                # re-check after the suspension: a same-id request may
                # have registered while we were parked — clobbering its
                # queue would orphan its output stream
                self.frontdoor.note_admitted()
                raise ValueError(f"duplicate request_id {request_id!r}")
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[request_id] = queue
        # affinity-aware placement (frontdoor/placement.py): prefix-
        # cache residency > tenant/adapter stickiness > least-loaded,
        # over SERVING replicas only.  dp=1 short-circuits to replica 0
        # — exactly the pre-router code path.
        rep = self._place_replica(
            prompt_token_ids,
            tenant_id,
            getattr(lora_request, "name", None),
        )
        span = None
        if self._tracer is not None:
            span = self._tracer.start_span(request_id, trace_headers)
            # registered while the stream is live so recovery paths can
            # LINK their resume spans to this one (satellite: span links)
            self._spans[request_id] = span
        # owner is registered BEFORE the awaited admission critical
        # section: an abort() arriving in that window must find the
        # replica rather than silently no-op and leave the request
        # generating until the consumer-gone reap
        self._owner[request_id] = rep
        aborted_out = None
        try:
            async with rep.lock:
                rep.engine.add_request(
                    request_id,
                    prompt,
                    sampling_params,
                    prompt_token_ids=prompt_token_ids,
                    lora_name=lora_name,
                    trace_id=getattr(span, "trace_id", None),
                    deadline=deadline,
                    tenant_id=tenant_id,
                    request_class=request_class,
                )
                if request_id in self._early_aborts:
                    # abort() ran before the engine knew the request; it
                    # left a tombstone instead — honor it now, before a
                    # single step is scheduled.  (tpulint's call graph
                    # aliases core's `scheduler.abort` to THIS class's
                    # lock-taking `abort` by bare name; abort_request
                    # takes no lock — see the suppression below.)
                    self._early_aborts.discard(request_id)
                    # tpulint: disable=TPL402(bare-name aliasing: abort_request -> scheduler.abort resolves to AsyncLLMEngine.abort; the scheduler method takes no lock)
                    aborted_out = rep.engine.abort_request(request_id)
        except BaseException as e:
            # BaseException, not Exception: a client disconnect lands
            # here as CancelledError/GeneratorExit thrown into the
            # generator while it waits for the replica lock — leaking
            # the owner entry would make a later abort() plant a
            # tombstone nothing ever clears
            self._owner.pop(request_id, None)
            self._queues.pop(request_id, None)
            self._early_aborts.discard(request_id)
            self._spans.pop(request_id, None)
            settle(
                "abort"
                if isinstance(e, (asyncio.CancelledError, GeneratorExit))
                else "failed"
            )
            if span is not None:
                # rejected admissions are precisely the requests tracing
                # must not lose
                span.attributes["error.type"] = type(e).__name__
                self._tracer.finish_span(span, None)
            raise
        finally:
            if self.frontdoor is not None:
                # the admission-window slot the front door granted is
                # now the scheduler's (or vacated, on failure) — runs on
                # every exit from the critical section, exactly once
                self.frontdoor.note_admitted()
        if aborted_out is not None:
            queue.put_nowait(aborted_out)
        # submission counts as a beat: a parked loop gets one full
        # watchdog deadline to pick this request up before it's a stall
        rep.last_beat = time.monotonic()
        rep.new_work.set()
        final = None
        # "failed" is the floor: an exit with no terminal frame (engine
        # death on the queue, mid-stream error) is a server failure; a
        # cancel/disconnect flips it to "abort"; a terminal frame
        # settles finish/abort; a noted shed wins over all of them
        outcome = "failed"
        is_delta = (
            sampling_params.output_kind == RequestOutputKind.DELTA
        )
        tokens_seen = 0
        noted_in = False
        try:
            while True:
                item = await queue.get()
                if isinstance(item, BaseException):
                    raise item
                final = item
                if not noted_in and item.prompt_token_ids:
                    # the true tokenized prompt length (the admission
                    # estimate may have come from raw text)
                    self.ledger.note_tokens_in(
                        request_id, len(item.prompt_token_ids)
                    )
                    noted_in = True
                if item.outputs:
                    # DELTA frames carry only new tokens; CUMULATIVE /
                    # FINAL_ONLY carry the whole output — bill the
                    # increment either way (a resumed request's restored
                    # emission offsets keep deltas duplicate-free)
                    n = len(item.outputs[0].token_ids)
                    inc = n if is_delta else max(0, n - tokens_seen)
                    if not is_delta:
                        tokens_seen = n
                    if inc:
                        self.ledger.note_tokens_out(request_id, inc)
                yield item
                if item.finished:
                    reason = (
                        item.outputs[0].finish_reason
                        if item.outputs else None
                    )
                    outcome = "abort" if reason == "abort" else "finish"
                    return
        except (asyncio.CancelledError, GeneratorExit):
            outcome = "abort"  # client hung up — not server failure
            raise
        finally:
            self._queues.pop(request_id, None)
            self._owner.pop(request_id, None)
            self._early_aborts.discard(request_id)
            self._spans.pop(request_id, None)
            settle(outcome, final)
            if span is not None:
                self._tracer.finish_span(span, final)

    async def abort(self, request_id: str) -> None:
        rep = self._owner.get(request_id)
        if rep is None:
            return
        async with rep.lock:
            out = rep.engine.abort_request(request_id)
            if out is None:
                # abort-mid-recovery: the request may be a staged decode
                # checkpoint (its dead engine forgot it at triage) —
                # cancel the record NOW and answer with the final
                # aborted frame instead of leaving the client to wait
                # out the rebuild
                out = self._abort_checkpointed(request_id)
            if out is None and request_id in self._owner:
                # the owner exists but the engine does not know the
                # request yet: generate() is between owner registration
                # and add_request.  Leave a tombstone; generate() aborts
                # the request immediately after admission.
                self._early_aborts.add(request_id)
        queue = self._queues.get(request_id)
        if queue is not None and out is not None:
            queue.put_nowait(out)
        if self.frontdoor is not None:
            # an aborted waiting request vacates admission-window room
            self.frontdoor.kick()

    # -------------------------------------------------------- introspection

    def _stall_age(self) -> float:
        """Max heartbeat age over replicas that actually have work; a
        parked idle loop never counts as stalled."""
        now = time.monotonic()
        return max(
            (
                now - rep.last_beat
                for rep in self._replicas
                if rep.engine.has_unfinished_requests()
            ),
            default=0.0,
        )

    def _stalled_replica(self) -> _Replica:
        """The replica the watchdog is (or would be) complaining about:
        oldest heartbeat among replicas with unfinished work."""
        now = time.monotonic()
        return max(
            (
                rep for rep in self._replicas
                if rep.engine.has_unfinished_requests()
            ),
            key=lambda rep: now - rep.last_beat,
            default=self._replicas[0],
        )

    def _watchdog_restart(self) -> None:
        """--watchdog-action=restart hand-off (called by the watchdog
        AFTER its snapshot is written)."""
        if self.supervisor is None:
            logger.warning(
                "--watchdog-action=restart but engine supervision is "
                "disabled (--max-engine-restarts 0); snapshot only"
            )
            return
        # restart the replica the SNAPSHOT blamed: re-resolving now,
        # after the dump I/O, could pick a healthy replica if the
        # stall cleared in that window
        rep, self._last_stalled_rep = self._last_stalled_rep, None
        self.supervisor.request_restart(rep=rep)

    def _stall_snapshot(self) -> dict:
        # mark the episode in the ring FIRST so the dump (and any later
        # /debug/state read) self-locates the stall in the event
        # timeline.  The marker lands on the STALLED replica's recorder
        # (oldest beat among replicas with work), stamped with ITS step
        # counter — under dp the healthy replicas' timelines must not
        # absorb a stall that is not theirs.
        now = time.monotonic()
        stalled = self._stalled_replica()
        # remembered for a subsequent --watchdog-action=restart: the
        # restart must hit the replica THIS snapshot describes
        self._last_stalled_rep = stalled
        stalled.engine.recorder.record(
            "stall", step=stalled.engine.step_counter,
            replica=stalled.index,
            heartbeat_age_s=round(now - stalled.last_beat, 3),
        )
        state = self.debug_state()
        # the blamed replica's recent step anatomy rides in the dump:
        # the first question a stall triage asks is "what did its last
        # steps look like", and the dump must answer without a live
        # process to query
        state["stalled_replica"] = {
            "replica": stalled.index,
            "heartbeat_age_s": round(now - stalled.last_beat, 3),
            "step_records": stalled.engine.steptime.records(last_n=64),
        }
        return state

    def debug_state(self, last_events: int = 256) -> dict:
        """The one engine-state snapshot every introspection surface
        serves: GET /debug/state, the DumpState RPC, and the stall
        watchdog's dump all call exactly this (flight_recorder.py
        serializers), so the three views can never diverge."""
        from vllm_tgis_adapter_tpu import compile_tracker
        from vllm_tgis_adapter_tpu.engine.device_report import (
            device_report,
        )
        from vllm_tgis_adapter_tpu.flight_recorder import (
            engine_introspection,
        )

        replicas = []
        now = time.monotonic()
        role_depths: dict[str, int] = {}
        for rep in self._replicas:
            state = engine_introspection(rep.engine)
            state["replica"] = rep.index
            state["serving"] = rep.serving
            state["role"] = rep.role
            state["in_flight"] = rep.in_flight_desc
            state["heartbeat_age_s"] = round(now - rep.last_beat, 3)
            replicas.append(state)
            role_depths[rep.role] = (
                role_depths.get(rep.role, 0)
                + rep.engine.scheduler.num_unfinished
            )
        events: list[dict] = []
        for rep in self._replicas:
            events.extend(rep.engine.recorder.events())
        events.sort(key=lambda e: e["mono_ns"])
        inflight = compile_tracker.inflight_dispatch()
        return {
            # what device this is and what the engine decided because of
            # it (engine/device_report.py; the boot log's line)
            "device": device_report(self.engine),
            "engine": {
                "running": self.is_running,
                "errored": self.errored,
                "lifecycle": self.lifecycle,
                "replicas": len(self._replicas),
            },
            "supervisor": (
                self.supervisor.debug_state()
                if self.supervisor is not None
                else None
            ),
            "frontdoor": (
                self.frontdoor.debug_state()
                if self.frontdoor is not None
                else None
            ),
            "router": {
                **self.router.debug_state(),
                # prefill/decode disaggregation (docs/SCALING.md):
                # waiting+running per replica role, and lifetime
                # handoff outcomes
                "role_queue_depths": role_depths,
                "handoffs": dict(self.handoff_outcomes),
            },
            # shared host KV tier (engine/kv_tier.py); None when
            # --no-kv-host-cache / library default off
            "kv_host_tier": (
                self.engine.kv_tier.debug_state()
                if getattr(self.engine, "kv_tier", None) is not None
                else None
            ),
            # telemetry signal layer (telemetry/): per-tenant cost
            # aggregates and per-class SLO attainment/burn
            "ledger": self.ledger.debug_state(),
            "slo": self.slo_engine.debug_state(),
            # step-time anatomy (telemetry/steptime.py): per-replica
            # phase-decomposed StepRecords — the rows the chrome-trace
            # exporter (telemetry/timeline.py) turns into tracks
            "step_timeline": {
                "replicas": [
                    {
                        "replica": rep.index,
                        # bails of the overlapped loop by reason (the
                        # counter: tgis_tpu_step_ahead_bails_total)
                        "ahead_bails": dict(rep.engine.ahead_bails),
                        **rep.engine.steptime.debug_state(),
                    }
                    for rep in self._replicas
                ],
            },
            # bottleneck doctor (telemetry/doctor.py): active/recent
            # regime episodes with their rule evidence
            "doctor": self.doctor.debug_state(),
            "replicas": replicas,
            "compile_tracker": {
                "compiled_shapes": compile_tracker.num_shapes(),
                "total_compiles": compile_tracker.total_recompiles(),
                "compile_seconds": round(
                    compile_tracker.total_compile_seconds(), 3
                ),
                "inflight_dispatch": (
                    {"fn": inflight[0], "age_s": round(inflight[1], 3)}
                    if inflight is not None
                    else None
                ),
            },
            "watchdog": (
                {
                    "deadline_s": self.watchdog.deadline_s,
                    "heartbeat_age_s": round(
                        self.watchdog.heartbeat_age(), 3
                    ),
                    "stalls": self.watchdog.stalls,
                    "last_dump": self.watchdog.last_dump_path,
                }
                if self.watchdog is not None
                else None
            ),
            "events": events[-last_events:],
        }

    def request_trace(self, request_id: str) -> Optional[dict]:
        """One request's flight-recorder timeline + live state, or None
        when the request was never seen (or its events aged out)."""
        events = []
        live = None
        for rep in self._replicas:
            events.extend(rep.engine.recorder.events_for(request_id))
            seq = rep.engine._seqs.get(request_id)  # noqa: SLF001
            if seq is not None:
                from vllm_tgis_adapter_tpu.flight_recorder import _seq_info

                live = _seq_info(seq, time.time())
                live["replica"] = rep.index
        if not events and live is None:
            return None
        events.sort(key=lambda e: e["mono_ns"])
        return {
            "request_id": request_id,
            "live": live,
            "events": events,
        }

    def _note_step_telemetry(self, rep: _Replica) -> None:
        """Per-commit telemetry feeds (telemetry/): each open request's
        current KV page count accrues page-seconds for the interval
        since this replica's previous commit.  dt is capped so an idle
        gap before a commit cannot bill a full idle period at the
        current occupancy."""
        now = time.monotonic()
        last = self._kv_sample_t.get(rep.index)
        self._kv_sample_t[rep.index] = now
        if last is not None:
            dt = min(now - last, 1.0)
            if dt > 0:
                try:
                    self.ledger.sample_kv(
                        rep.engine.kv_pages_by_request(), dt
                    )
                except Exception:  # noqa: BLE001 — telemetry must never raise
                    logger.debug(
                        "kv page-seconds sample failed", exc_info=True
                    )
        # bottleneck doctor: throttled internally (cheap clock check
        # before signals are even built), so this rides every commit
        self.doctor.maybe_evaluate(self._doctor_signals)

    # ------------------------------------------------------------- doctor

    def _doctor_record(self, replica: int, **detail) -> None:
        """Doctor event hook: one batch-scoped ``doctor`` event on the
        blamed replica's recorder (falls back to replica 0 when the
        blamed index is gone mid-rescale)."""
        rep = (
            self._replicas[replica]
            if 0 <= replica < len(self._replicas)
            else self._replicas[0]
        )
        rep.engine.recorder.record(
            "doctor", step=rep.engine.step_counter,
            replica=replica, **detail,
        )

    @staticmethod
    def _doctor_profiler():  # noqa: ANN205 — ProfilerController (lazy import)
        from vllm_tgis_adapter_tpu.profiler import get_controller

        # the shared singleton: passing None never clobbers a real
        # --profile-dir configured elsewhere, and start() on a
        # disabled controller raises ProfilerError (doctor degrades)
        return get_controller(None)

    def _doctor_signals(self) -> "list[ReplicaSignals]":
        """One ReplicaSignals per replica.  Process-global compile
        signals are attributed to replica 0 only — one compile storm
        must open ONE episode, not one per dp replica; same for the
        shared host KV tier's page-movement counters."""
        from vllm_tgis_adapter_tpu import compile_tracker
        from vllm_tgis_adapter_tpu.flight_recorder import allocator_stats

        inflight = compile_tracker.inflight_dispatch()
        inflight_age = inflight[1] if inflight is not None else 0.0
        recompiles = compile_tracker.total_recompiles()
        tier = getattr(self.engine, "kv_tier", None)
        tier_pages = (
            tier.demoted_pages + tier.promoted_pages
            if tier is not None
            else 0
        )
        signals = []
        for rep in self._replicas:
            eng = rep.engine
            alloc = allocator_stats(eng.scheduler.allocator)
            spec = getattr(eng.runner, "spec", None)
            acceptance = None
            if spec is not None and spec.acceptance_ewma.initialized:
                acceptance = spec.acceptance_ewma.value
            first = rep.index == 0
            signals.append(ReplicaSignals(
                replica=rep.index,
                steps=min(len(eng.steptime), eng.steptime.window),
                host_gap_frac=eng.steptime.host_gap_frac(),
                waiting=len(eng.scheduler.waiting),
                running=len(eng.scheduler.running),
                max_num_seqs=(
                    eng.config.scheduler_config.max_num_seqs
                ),
                recompiles=recompiles if first else 0,
                compile_inflight_age_s=inflight_age if first else 0.0,
                fragmentation=alloc["fragmentation"],
                occupancy=alloc["occupancy"],
                tier_pages_moved=tier_pages if first else 0,
                spec_active=spec is not None,
                spec_acceptance=acceptance,
            ))
        return signals

    def _link_resume(self, request_id: str, path: str) -> None:
        """Zero-duration resume span LINKED to the request's live
        server span (tracing.py resume_span): a restart resume, a
        cross-replica migration, or a prefill→decode handoff shows up
        in the trace waterfall attached to the originating trace."""
        if self._tracer is None:
            return
        origin = self._spans.get(request_id)
        if origin is None:
            return
        try:
            self._tracer.resume_span(origin, request_id, path)
        except Exception:  # noqa: BLE001 — telemetry must never raise
            logger.debug("resume span emit failed", exc_info=True)

    def refresh_engine_gauges(self) -> tuple[int, int]:
        """Push current engine state into the Prometheus gauges
        (metrics.update_engine_gauges): KV page usage, waiting-queue
        depth, prefix-hit tokens — aggregated over dp replicas.  Called
        every stats tick AND on each /metrics scrape so scraped values
        are never a tick stale.  Returns (kv_used, kv_total) so the
        stats log line reuses the same aggregation (single source for
        the usage formula)."""
        engines = [rep.engine for rep in self._replicas]
        allocators = [e.scheduler.allocator for e in engines]
        num_blocks = sum(a.num_blocks for a in allocators)
        used = num_blocks - sum(a.num_free for a in allocators)
        state = [e.state_slot_usage() for e in engines]
        windows = [w for e in engines
                   if (w := e.scheduler.window_allocator) is not None]
        # requests parked in the front-door fair queue are "waiting" in
        # every operational sense (they count against the bound and the
        # autoscaler should see them), they just haven't reached a
        # scheduler deque yet
        parked = 0
        if self.frontdoor is not None:
            parked = self.frontdoor.parked
            self.frontdoor.refresh_gauges()
        try:
            from vllm_tgis_adapter_tpu import metrics

            metrics.update_engine_gauges(
                waiting=parked
                + sum(len(e.scheduler.waiting) for e in engines),
                kv_used=used,
                kv_total=num_blocks,
                prefix_hits=sum(a.prefix_hits for a in allocators),
                state_used=sum(u for u, _ in state),
                state_total=sum(t for _, t in state),
                window_used=sum(w.num_blocks - w.num_free for w in windows),
                window_total=sum(w.num_blocks for w in windows),
            )
            # per-tier prefix hit rates (docs/KV_TIERING.md): tokens
            # served from each tier over prompt tokens that consulted
            # the prefix cache, per replica.  Device hits include
            # promoted pages once re-registered; the host series counts
            # the promotions themselves.
            for rep in self._replicas:
                alloc = rep.engine.scheduler.allocator
                lookups = max(1, alloc.prefix_lookup_tokens)
                host_tokens = getattr(
                    rep.engine, "kv_host_promoted_tokens", 0
                )
                metrics.kv_prefix_hit_rate.labels(
                    tier="device", replica=str(rep.index)
                ).set((alloc.prefix_hits - host_tokens) / lookups)
                metrics.kv_prefix_hit_rate.labels(
                    tier="host", replica=str(rep.index)
                ).set(host_tokens / lookups)
            tier = getattr(self.engine, "kv_tier", None)
            if tier is not None:
                metrics.kv_host_tier_bytes.labels(tier="host").set(
                    tier.bytes_used
                )
                if tier.disk is not None:
                    metrics.kv_host_tier_bytes.labels(tier="disk").set(
                        tier.disk.bytes_used
                    )
            for rep in self._replicas:
                arena = getattr(rep.engine, "arena", None)
                if arena is not None:
                    arena.observe(rep.index)
            for rep in self._replicas:
                # page capacity labeled by the page storage dtype: the
                # --kv-quantization capacity lift reads directly off
                # this gauge (docs/QUANTIZATION.md)
                ccfg = rep.engine.config.cache_config
                metrics.kv_page_capacity_blocks.labels(
                    dtype=ccfg.kv_dtype_label(), replica=str(rep.index)
                ).set(rep.engine.scheduler.allocator.num_blocks)
                pool = getattr(rep.engine.runner, "adapter_pool", None)
                if pool is not None:
                    metrics.lora_adapters_resident.labels(
                        replica=str(rep.index)
                    ).set(pool.num_resident)
            manager = getattr(self.engine, "lora_manager", None)
            if manager is not None:
                metrics.lora_adapters_registered.set(
                    len(manager.lora_requests)
                )
            for rep in self._replicas:
                spec = getattr(rep.engine.runner, "spec", None)
                if spec is not None and spec.stats.proposed:
                    metrics.spec_acceptance_rate.labels(
                        replica=str(rep.index)
                    ).set(spec.stats.acceptance_rate)
                    # time-decayed companion (telemetry/ewma.py): what
                    # acceptance looks like NOW, not since boot — the
                    # signal an adaptive-spec policy would act on
                    if spec.acceptance_ewma.initialized:
                        metrics.spec_acceptance_rate_ewma.labels(
                            replica=str(rep.index)
                        ).set(spec.acceptance_ewma.value)
            # SLO attainment/burn gauges refresh with the same cadence
            # (every stats tick and every /metrics scrape)
            self.slo_engine.refresh_gauges()
            # doctor rides the same cadence so open episodes CLOSE
            # even when commits stop (an idle engine must not pin a
            # stale regime — or a profiler capture — forever)
            self.doctor.maybe_evaluate(self._doctor_signals)
        except Exception:  # pragma: no cover — metrics are best-effort
            logger.debug("engine gauge refresh failed", exc_info=True)
        return used, num_blocks

    # ------------------------------------------------------------ stats loop

    async def _log_stats_loop(self) -> None:
        """One operational stats line every STATS_INTERVAL_S while work is
        in flight (the --disable-log-stats flag's actual behavior)."""
        was_active = False
        while not self._stopped:
            await asyncio.sleep(self.STATS_INTERVAL_S)
            if self.errored or self.lifecycle == LIFECYCLE_DEAD:
                # terminal: nothing can bring this engine back — exit
                # instead of sleeping forever in embeddings that never
                # call stop()
                break
            if self.lifecycle == LIFECYCLE_RECOVERING:
                # a rebuilding engine must not report "running: N" —
                # but the loop stays ALIVE (continue, not break): after
                # the supervised restart it resumes reporting.  The
                # pre-PR5 `while not errored` was a one-way latch that
                # silenced stats on an engine that later recovered.
                # Draining still reports: the operator is watching
                # exactly this line to see how much work remains.
                continue
            engines = [rep.engine for rep in self._replicas]
            active = any(e.has_unfinished_requests() for e in engines)
            allocators = [e.scheduler.allocator for e in engines]
            used, num_blocks = self.refresh_engine_gauges()
            # drain the ledger JSONL buffer off the event loop
            # (JsonlSink.flush runs the write in asyncio.to_thread)
            sink = self._ledger_sink
            if sink is not None and sink.pending:
                await sink.flush()
            if self.engine.config.disable_log_stats or (
                not active and not was_active
            ):
                continue  # idle or log line disabled: stay quiet
            was_active = active
            line = (
                f"running: "
                f"{sum(len(e.scheduler.running) for e in engines)} reqs, "
                f"waiting: "
                f"{sum(len(e.scheduler.waiting) for e in engines)} reqs, "
                f"KV pages: {used}/{num_blocks} used"
            )
            if len(engines) > 1:
                line += (
                    ", per-replica running: "
                    + "/".join(
                        str(len(e.scheduler.running)) for e in engines
                    )
                )
            if allocators[0].enable_prefix_caching:
                hits = sum(a.prefix_hits for a in allocators)
                line += f", prefix-cache hit tokens: {hits}"
            specs = [
                e.runner.spec for e in engines if e.runner.spec is not None
            ]
            proposed = sum(s.stats.proposed for s in specs)
            if proposed:
                accepted = sum(s.stats.accepted for s in specs)
                line += (
                    f", spec acceptance: {100 * accepted / proposed:.1f}%"
                )
            # per-class error-budget burn (telemetry/slo.py) — the one
            # number the operator pages on, in the line they tail
            line += ", " + self.slo_engine.stats_fragment()
            # step-level telemetry mirror (metrics.step_snapshot /
            # compile_tracker): the SAME values the gauges export, so the
            # log line and /metrics can never tell different stories.
            # Collection happens in the engine core unconditionally —
            # --disable-log-stats gates only this line (the invariant
            # documented at metrics.py update_engine_gauges).
            from vllm_tgis_adapter_tpu import compile_tracker, metrics

            snap = metrics.step_snapshot
            if snap.decode_steps:
                line += (
                    f", decode occupancy: {100 * snap.decode_occupancy:.0f}%"
                )
            if snap.prefill_steps:
                line += (
                    ", prefill padding: "
                    f"{100 * snap.prefill_padding_waste:.0f}%"
                )
            shapes = compile_tracker.num_shapes()
            if shapes:
                line += (
                    f", XLA shapes: {shapes} "
                    f"({compile_tracker.total_recompiles()} compiles)"
                )
            # step anatomy + doctor verdict in the line operators tail:
            # host_gap% is the "is the device waiting on the host"
            # number, and an active regime set is the doctor paging
            gaps = [
                e.steptime.host_gap_frac()
                for e in engines
                if len(e.steptime)
            ]
            if gaps:
                line += f", host gap: {100 * max(gaps):.1f}%"
            regimes = self.doctor.active_regimes()
            if regimes:
                line += f", doctor: {'+'.join(regimes)}"
            logger.info("Engine stats: %s", line)

    # ------------------------------------------------------------- step loop

    async def _run_loop(self, rep: _Replica) -> None:
        """The step loop: every successor is planned while its
        predecessor runs.

        The lock covers only the fast host phases (plan/commit); device
        work runs WITHOUT it so aborts and new requests land mid-dispatch
        instead of queueing behind a device program.

        Overlap: ``dispatch_step`` only ENQUEUES device work (JAX async
        dispatch).  While dispatch *n* executes, the loop plans, prepares
        and enqueues dispatch *n+1* (``plan_step(ahead_of=n)``), whatever the
        two are — wave after wave, mixed step after wave, wave after
        mixed step, mixed step after mixed step — and only then blocks
        on *n*'s results.  *n+1* is a projection: every row *n* samples
        is taken to consume its whole budget there, its input token is
        read on the device from *n*'s sample, admissions and prompt
        chunks come from the host as always.  A row that ends inside *n*
        has its *n+1* tokens discarded at commit; what it frees — pages
        and its batch row — is quarantined until *n+1* has retired
        (free epochs).  The device therefore runs back-to-back programs
        and a wave needs to be no deeper than hides the host's turn
        (``LLMEngine._set_wave_depth``), so the gap a client sees
        between frames is one token step and not one fused wave.  A
        committed dispatch's frames go to their streams once the next
        dispatch is enqueued (``commit_in_flight`` / ``deliver``).

        Where a projection cannot be right or safe the loop commits *n*
        first and plans in the serial order (``plan_step``); each such
        bail is counted by its reason (``plan_ahead_step``).  With
        ``enable_chained_decode`` off the loop is strictly serial — only
        a cold-start admission may follow a dispatch in flight — and is
        the control the tests compare against.  The staged runner
        answers ``SYNC_DISPATCH``: its work happens inside the wait, so
        nothing can be enqueued behind it.
        """
        from vllm_tgis_adapter_tpu.engine.runner import SYNC_DISPATCH

        engine = rep.engine
        engine.overlapped = (
            engine.config.scheduler_config.enable_chained_decode
        )
        # (plan, prepared, handle, ahead) — a dispatch planned ahead
        # holds a free quarantine epoch open until it retires
        in_flight: Optional[tuple] = None

        async def emit(outputs) -> None:
            for out in outputs:
                queue = self._queues.get(out.request_id)
                if queue is not None:
                    queue.put_nowait(out)
                elif not out.finished:
                    # stream consumer went away → stop generating
                    async with rep.lock:
                        engine.abort_request(out.request_id)

        async def commit_in_flight(then_dispatching: bool = False) -> None:
            """Wait for the dispatch in flight, commit it and hand its
            outputs to their streams — at once, or, ``then_dispatching``,
            once the loop has enqueued the dispatch it plans next
            (``deliver``): the stream consumers run on this event loop
            and the dispatch runs in a worker thread under the same
            interpreter lock, so consumers woken before the enqueue
            delay it, and the device waits."""
            nonlocal in_flight, undelivered
            plan, prepared, handle, ahead = in_flight
            result = await asyncio.to_thread(
                engine.wait_step, plan, prepared, handle
            )
            async with rep.lock:
                if ahead:
                    # this dispatch has retired: the frees quarantined
                    # when it was planned can no longer be stale-written
                    engine.flush_free_epoch()
                outs = engine.commit_step(plan, result, prepared)
            in_flight = None
            rep.in_flight_desc = None
            rep.last_beat = time.monotonic()
            await deliver()  # streams get their frames in commit order
            undelivered = (outs, self._plan_tokens(plan))
            if not then_dispatching or engine.pending_handoffs:
                await deliver()
            if engine.pending_handoffs:
                # prefill-role commit staged finished prompts: move
                # them onto decode-capable replicas NOW, before the
                # next prefill wave (docs/SCALING.md); their first
                # tokens were delivered just above
                await self._drain_handoffs(rep)

        async def deliver() -> None:
            """Hand the committed dispatch's outputs to their streams
            and feed the per-commit telemetry."""
            nonlocal undelivered
            if undelivered is None:
                return
            (outs, committed), undelivered = undelivered, None
            await emit(outs)
            # per-replica committed-token attribution: the placement
            # router's load tiebreak
            self.router.note_committed(rep.index, committed)
            # telemetry feed at the same boundary: KV page-seconds
            # sampling for the cost ledger
            self._note_step_telemetry(rep)
            if self.frontdoor is not None:
                # finished rows free batch slots/pages and the commit's
                # tokens feed the admission estimator's PER-REPLICA
                # throughput EWMA
                self.frontdoor.note_progress(committed, replica=rep.index)

        # outputs of a committed dispatch that wait for ``deliver``
        undelivered: Optional[tuple] = None

        def hand_over_undelivered() -> None:
            """The loop is ending (death, cancel): what was committed is
            in the sequences' state, so it must reach the streams too —
            a checkpointed request resumes after its last COMMITTED
            token."""
            nonlocal undelivered
            if undelivered is not None:
                for out in undelivered[0]:
                    queue = self._queues.get(out.request_id)
                    if queue is not None:
                        queue.put_nowait(out)
                undelivered = None

        try:
            while not self._stopped:
                rep.last_beat = time.monotonic()
                if not engine.has_unfinished_requests() and in_flight is None:
                    rep.new_work.clear()
                    await rep.new_work.wait()
                    continue
                ahead = in_flight is not None and engine.overlapped
                async with rep.lock:
                    if ahead:
                        outputs, plan, prepared = engine.plan_step(
                            ahead_of=in_flight[0]
                        )
                        if plan is not None:
                            # the quarantine epoch opens in the SAME
                            # critical section that planned the
                            # successor: from this point any free — an
                            # abort sneaking in during the dispatch
                            # await, or the predecessor's commit reaping
                            # finished rows — buffers until the successor
                            # (whose block tables and batch rows
                            # reference them) has retired
                            engine.begin_free_epoch()
                    else:
                        outputs, plan, prepared = engine.plan_step(
                            prefill_only=in_flight is not None
                        )
                await emit(outputs)
                if self.frontdoor is not None:
                    # planning admits waiting rows (and sheds expired
                    # ones): admission-window room may have opened
                    self.frontdoor.kick()
                if plan is None:
                    await deliver()
                    if in_flight is not None:
                        # nothing to enqueue behind it (or a bail):
                        # commit, then plan in the serial order
                        await commit_in_flight()
                    elif engine.has_unfinished_requests():
                        # nothing plannable right now — e.g. every
                        # waiting row parked on an adapter stream, or a
                        # blocked swapped head.  Yield briefly instead
                        # of spinning the host phase at full rate while
                        # the background transfer completes.
                        await asyncio.sleep(0.001)
                    continue
                if ahead:
                    handle = await asyncio.to_thread(
                        engine.dispatch_chained_step, plan, prepared,
                        in_flight[2],
                    )
                else:
                    handle = await asyncio.to_thread(
                        engine.dispatch_step, plan, prepared
                    )
                # the device has its next work: now the streams
                await deliver()
                new_desc = describe_plan(plan)
                if ahead:
                    new_desc = {**(new_desc or {}), "chained": True}
                if in_flight is not None:
                    # commits stay in dispatch order: drain the older
                    # dispatch (its device work overlapped our planning);
                    # in the overlapped loop the next iteration enqueues
                    # its successor before the streams get their turn
                    await commit_in_flight(
                        then_dispatching=engine.overlapped
                    )
                # set AFTER the older commit (which clears the field):
                # the watchdog dump should describe the newest dispatch
                rep.in_flight_desc = new_desc
                in_flight = (plan, prepared, handle, ahead)
                if handle is SYNC_DISPATCH:
                    # not enqueue-only (the staged pipeline runner —
                    # speculative verify is enqueue-only since it moved
                    # onto the ragged span path): the device work
                    # happens inside wait_step, so it must NOT sit in
                    # flight — a later eagerly-dispatched step would
                    # then execute BEFORE it on device, breaking the
                    # plan-order invariant (stale K/V writes onto
                    # re-allocated pages).  Execute and commit
                    # synchronously instead: nothing is ever planned
                    # ahead of such a runner, and its waves keep the
                    # configured depth.
                    if engine.overlapped:
                        engine.count_ahead_bail("sync_dispatch")
                        engine.overlapped = False
                    await commit_in_flight()
        except asyncio.CancelledError:
            hand_over_undelivered()
            raise
        except BaseException as e:  # noqa: BLE001 — engine death boundary
            hand_over_undelivered()
            logger.exception("engine step loop %d died", rep.index)
            engine.recorder.record(
                "error", step=engine.step_counter, replica=rep.index,
                error=f"{type(e).__name__}: {e}",
            )
            # typed at the boundary (frontdoor/errors.py): XLA OOM text
            # becomes DeviceOOMError here, so the servers (and the
            # supervisor's cause label) classify engine death by
            # isinstance, never by substring
            from vllm_tgis_adapter_tpu.frontdoor.errors import (
                wrap_engine_error,
            )

            err = wrap_engine_error(e)
            if (
                self.supervisor is not None
                and not self._stopped
                and self.supervisor.accepts()
            ):
                # supervised death: the supervisor quiesces the front
                # door, replays pre-prefill work into a rebuilt engine,
                # and fails mid-decode requests retryable.  This task
                # just exits — NOT errored: the pod is recovering, not
                # dead (supervisor/supervisor.py).
                self.supervisor.notify_death(rep, err)
                return
            # terminal death (no supervisor / breaker tripped / engine
            # stopping): pre-PR5 crash-fast semantics
            self._terminal_death(err)
            # flush BEFORE the first await below: a consumer woken by
            # the failed queue must never observe a still-open epoch
            # (the finally-flush would otherwise run one yield too late)
            engine.flush_all_free_epochs()
            await asyncio.to_thread(
                write_termination_log,
                self._death_report(err),
                os.getenv("TERMINATION_LOG_DIR", "/dev/termination-log"),
            )
            # only NOW wake __main__: the report write above has
            # completed, so its final appended traceback cannot be
            # truncated by an unfinished mode-'w' write
            self.dead_event.set()
            raise
        finally:
            hand_over_undelivered()  # whichever way the loop ended
            # epochs left open by a death between a planned-ahead
            # dispatch and its commit would quarantine pages and batch
            # rows forever
            engine.flush_all_free_epochs()

    # ----------------------------------------------------- death & recovery

    def _terminal_death(self, err: BaseException) -> None:
        """The engine is done for good: mark it dead and fail every
        consumer.  Called by the step loop (unsupervised death) and by
        the supervisor's circuit breaker.  Callers set ``dead_event``
        themselves, AFTER their termination-log checkpoint completes —
        waking __main__ first would let its final append race (and be
        truncated by) the still-in-flight mode-'w' report write."""
        self._dead_error = err
        self.lifecycle = LIFECYCLE_DEAD
        for queue in self._queues.values():
            queue.put_nowait(err)
        if self.frontdoor is not None:
            # parked waiters must observe the death too
            self.frontdoor.fail_all(err)

    def _death_report(self, err: BaseException) -> str:
        """Termination-log body for terminal engine death: the error,
        any restart history, and a flight-recorder/engine snapshot —
        everything a post-mortem needs after the pod is gone."""
        import json

        lines = [f"engine died: {type(err).__name__}: {err}"]
        if self.supervisor is not None and self.supervisor.restart_history:
            lines.append("restart history:")
            lines.extend(self.supervisor.history_lines())
        try:
            snapshot = self.debug_state(last_events=64)
            lines.append(
                "engine state snapshot: "
                + json.dumps(snapshot, default=str)
            )
        except Exception:  # noqa: BLE001 — a broken engine is the expected case
            logger.exception("death-report snapshot collection failed")
            lines.append("engine state snapshot unavailable")
        return "\n".join(lines)

    def _arm_replica(self, rep: _Replica) -> None:
        """(Re)start one replica's step loop (supervisor re-arm)."""
        rep.last_beat = time.monotonic()
        rep.task = spawn_task(
            self._run_loop(rep), name=f"engine-step-loop-{rep.index}"
        )
        rep.new_work.set()

    async def fail_unreplayable(
        self, rep: _Replica, fail_error: BaseException
    ) -> tuple[int, list]:
        """Quiesce-time triage of requests whose outcome is already
        fixed at death: finished-but-undrained requests deliver their
        completed output; mid-decode requests (tokens the client
        already holds — replay would duplicate them) CHECKPOINT into
        the host KV tier for a token-identical resume
        (docs/RECOVERY.md), or — down the degradation ladder (tier
        disabled, ``--no-decode-resume``, checkpoint over the tier
        budget, validation read failing) — fail with ``fail_error``
        NOW, before the multi-second rebuild/re-warm, so their clients
        can retry immediately.  Runs under the replica lock with the
        step loop reaped; returns ``(failed, checkpoints)``."""
        failed = 0
        checkpoints: list = []
        async with rep.lock:
            old = rep.engine
            # handoffs staged at a commit the step loop died before
            # draining: records in the tier are adopted by
            # staged_checkpoints (they resume on a decode-capable
            # sibling); capture-ladder failures (no record) must fail
            # retryable HERE — their sequences already left _seqs
            pending, old.pending_handoffs = old.pending_handoffs, []
            for rid, ckpt in pending:
                if ckpt is not None:
                    continue  # staged fleet-visible; adoption owns it
                if rid in self._queues:
                    # the same accounting every exhausted handoff rung
                    # gets (handoffs_total{outcome="fallback"} +
                    # handoff_out event + typed HandoffError): an
                    # operator alerting on the handoff metric must see
                    # capture failures triaged at death too
                    self._handoff_fallback(rep, rid, "capture")
                    failed += 1
            for seq in list(old._seqs.values()):  # noqa: SLF001
                if not seq.is_finished and seq.num_output_tokens == 0:
                    continue  # replay-safe: restart_replica re-queues it
                queue = self._queues.get(seq.request_id)
                ckpt = None
                if (
                    not seq.is_finished
                    and queue is not None
                    and seq.request_id not in self._early_aborts
                ):
                    # the tentpole: checkpoint instead of fail.  None
                    # means the ladder applies — fall through to the
                    # PR-5 retryable-failure floor below.
                    ckpt = old.checkpoint_decode(seq)
                old._seqs.pop(seq.request_id, None)  # noqa: SLF001
                old.lora_manager.unpin(seq.lora_name)
                if ckpt is not None:
                    checkpoints.append(ckpt)
                    continue
                if queue is None:
                    continue
                if seq.is_finished:
                    # completed (e.g. scheduler-shed awaiting its
                    # drain) exactly at death: deliver, don't retry
                    queue.put_nowait(seq.to_request_output())
                else:
                    self._count_fallback(old, seq.request_id, "ladder")
                    queue.put_nowait(fail_error)
                    failed += 1
        # validation read: the quiesce-time gathers commit off the loop
        # — wait them out, then verify every checkpointed page reads
        # back valid.  A short checkpoint (demotion dropped under
        # backpressure, LRU raced the commit, corrupt entry) falls back
        # to the retryable floor rather than resuming a request whose
        # KV it cannot restore.
        tier = getattr(rep.engine, "kv_tier", None)
        if checkpoints and tier is not None:
            await tier.drain_transfers()
            validated = []
            for ckpt in checkpoints:
                if tier.validate_checkpoint(ckpt):
                    metrics.checkpoint_seconds.observe(
                        max(0.0, time.perf_counter() - ckpt.t0)
                    )
                    validated.append(ckpt)
                    continue
                tier.pop_checkpoint(ckpt.request_id)
                self._count_fallback(
                    rep.engine, ckpt.request_id, "validation"
                )
                queue = self._queues.get(ckpt.request_id)
                if queue is not None:
                    queue.put_nowait(fail_error)
                    failed += 1
            checkpoints = validated
        return failed, checkpoints

    def _count_fallback(
        self, engine: LLMEngine, request_id: str, reason: str
    ) -> None:
        """One mid-decode request kept the pre-resume semantics
        (counted + flight-recorded, docs/RECOVERY.md ladder)."""
        metrics.decode_checkpoints_total.labels(outcome="fallback").inc()
        engine.recorder.record(
            "checkpoint", request_id, step=engine.step_counter,
            outcome="fallback", reason=reason,
        )

    def staged_checkpoints(self, fresh: list) -> list:
        """``fresh`` plus any checkpoint a FAILED recovery attempt left
        staged in the (surviving) tier: the records outlive the attempt
        exactly like the KV pages, so a retry resumes them instead of
        losing them.  Staged records whose consumer vanished are
        dropped here."""
        tier = getattr(self.engine, "kv_tier", None)
        if tier is None:
            return fresh
        seen = {ckpt.request_id for ckpt in fresh}
        out = list(fresh)
        for ckpt in tier.pending_checkpoints():
            rid = ckpt.request_id
            if rid in seen:
                continue
            if rid not in self._queues:
                tier.pop_checkpoint(rid)  # disconnected while staged
                continue
            if any(
                rid in r.engine._seqs  # noqa: SLF001
                for r in self._replicas
            ):
                continue  # already resumed somewhere live
            out.append(ckpt)
        return out

    async def resume_to_replicas(
        self, rep: _Replica, checkpoints: list,
        fail_error: BaseException,
    ) -> tuple[int, int, list]:
        """Cross-replica resume (docs/RECOVERY.md): move validated
        checkpoints onto HEALTHY dp siblings NOW, before the dead
        replica's multi-second rebuild — the same placement-scored hop
        zero-token replays take, so a streaming client sees only a
        pause.  Returns ``(resumed, failed, remaining)``: with no
        healthy sibling everything remains for the rebuilt engine
        (``resume_into``); with siblings present every checkpoint is
        consumed here (resumed, failed retryable, or dropped with its
        vanished consumer) and ``remaining`` is empty."""
        healthy = [
            r for r in self._replicas if r.serving and r is not rep
        ]
        if not healthy or not checkpoints:
            return 0, 0, checkpoints
        tier = getattr(self.engine, "kv_tier", None)
        resumed = failed = 0
        targets: set[int] = set()
        for ckpt in checkpoints:
            if not self._resume_consumer_alive(ckpt, tier):
                continue
            target = self._place_replica(
                list(ckpt.prompt_token_ids) + list(ckpt.output_token_ids),
                ckpt.tenant_id,
                ckpt.lora_name,
                kind="decode",  # resumes decode; role tier steers
            )
            if target is rep:  # defensive: never resume onto the dead
                target = healthy[resumed % len(healthy)]
            try:
                async with target.lock:
                    # re-checked INSIDE the lock: abort() serializes on
                    # the DEAD owner's lock, not this target's, so a
                    # cancel/disconnect can land while we awaited here
                    if not self._resume_consumer_alive(ckpt, tier):
                        continue
                    target.engine.resume_request(
                        ckpt, path="cross_replica"
                    )
            except Exception:  # noqa: BLE001 — one bad resume must not sink the rest
                logger.exception(
                    "cross-replica resume of %s failed; falling back "
                    "to retryable failure", ckpt.request_id,
                )
                if tier is not None:
                    tier.pop_checkpoint(ckpt.request_id)
                self._count_fallback(
                    target.engine, ckpt.request_id, "resume"
                )
                queue = self._queues.get(ckpt.request_id)
                if queue is not None:
                    queue.put_nowait(fail_error)
                    failed += 1
                continue
            if tier is not None:
                tier.pop_checkpoint(ckpt.request_id)
            self._owner[ckpt.request_id] = target
            targets.add(target.index)
            resumed += 1
            self.ledger.note_resume(ckpt.request_id, "cross_replica")
            self._link_resume(ckpt.request_id, "cross_replica")
            metrics.requests_resumed_total.labels(
                path="cross_replica"
            ).inc()
            metrics.decode_checkpoints_total.labels(
                outcome="resumed"
            ).inc()
        for r in self._replicas:
            if r.index in targets:
                r.last_beat = time.monotonic()
                r.new_work.set()
        return resumed, failed, []

    async def resume_into(
        self, rep: _Replica, checkpoints: list,
        fail_error: BaseException,
    ) -> tuple[int, int]:
        """Local resume: re-enter the remaining checkpoints into the
        REBUILT engine (already swapped onto ``rep`` by
        ``restart_replica``).  Returns ``(resumed, failed)``."""
        tier = getattr(self.engine, "kv_tier", None)
        resumed = failed = 0
        async with rep.lock:
            for ckpt in checkpoints:
                if not self._resume_consumer_alive(ckpt, tier):
                    continue
                try:
                    rep.engine.resume_request(ckpt, path="local")
                except Exception:  # noqa: BLE001
                    logger.exception(
                        "resume of %s into the rebuilt engine failed; "
                        "falling back to retryable failure",
                        ckpt.request_id,
                    )
                    if tier is not None:
                        tier.pop_checkpoint(ckpt.request_id)
                    self._count_fallback(
                        rep.engine, ckpt.request_id, "resume"
                    )
                    queue = self._queues.get(ckpt.request_id)
                    if queue is not None:
                        queue.put_nowait(fail_error)
                        failed += 1
                    continue
                if tier is not None:
                    tier.pop_checkpoint(ckpt.request_id)
                self._owner[ckpt.request_id] = rep
                resumed += 1
                self.ledger.note_resume(ckpt.request_id, "local")
                self._link_resume(ckpt.request_id, "local")
                metrics.requests_resumed_total.labels(path="local").inc()
                metrics.decode_checkpoints_total.labels(
                    outcome="resumed"
                ).inc()
        return resumed, failed

    # ------------------------------------------- prefill→decode handoff

    async def _drain_handoffs(self, src: _Replica) -> None:
        """Consume the handoffs ``src``'s prefill-role engine staged at
        its last commit (docs/SCALING.md "Disaggregated roles"): for
        each, wait out the in-flight tier transfers, validate the
        staged pages by digest, place a decode-capable replica (role
        tier + the usual affinity policies over prompt ‖ output), and
        ``resume_request`` onto it — the kv gate then promotes the
        pages at that replica's next clean dispatch boundary and decode
        continues token-identically (zero duplicate or missing streamed
        tokens: the checkpoint carries the stream offsets).

        Degradation ladder (each rung counted in
        ``handoffs_total{outcome="fallback"}`` and failed retryable
        with ``HandoffError``): capture failed on the prefill replica →
        validation read failed → no decode-capable replica serving →
        the resume itself raised.  An abort or disconnect between
        prefill commit and decode admission drops the record with zero
        engine state (``_resume_consumer_alive``)."""
        engine = src.engine
        pending, engine.pending_handoffs = engine.pending_handoffs, []
        tier = getattr(self.engine, "kv_tier", None)
        # capture-ladder failures settle synchronously, before the
        # first await below: a death mid-drain must never strand a
        # request that has no staged record to be adopted from
        staged = []
        for rid, ckpt in pending:
            if ckpt is None or tier is None:
                self._handoff_fallback(src, rid, "capture")
            else:
                staged.append(ckpt)
        if staged:
            await self._resume_handoffs(src, staged, tier)

    async def _resume_handoffs(
        self, src: _Replica, staged: list, tier
    ) -> None:
        from vllm_tgis_adapter_tpu.supervisor import failpoints

        # chaos site (tools/chaos_soak.py): a raise here kills the
        # prefill replica BETWEEN stage and resume — the records
        # survive in the fleet-shared tier and supervisor recovery
        # adopts them onto a decode-capable sibling
        failpoints.fire("async.handoff")
        await tier.drain_transfers()
        for ckpt in staged:
            rid = ckpt.request_id
            if not self._resume_consumer_alive(ckpt, tier):
                continue  # aborted/disconnected pre-admission
            if not tier.validate_checkpoint(ckpt):
                tier.pop_checkpoint(rid)
                self._handoff_fallback(src, rid, "validation")
                continue
            targets = [
                rep for rep in self._replicas
                if rep.serving
                and rep is not src
                and rep.role in _DECODE_CAPABLE
            ]
            if not targets:
                # no local decode-capable replica: the networked tier
                # extends the ladder ACROSS hosts before the retryable
                # floor (docs/CROSS_HOST.md) — on success the peer owns
                # decode and its OUTPUT frames feed this still-open
                # stream; handoff_to_peer retires the local record
                if self.kvnet is not None and (
                    await self.kvnet.handoff_to_peer(ckpt, tier)
                ):
                    continue
                tier.pop_checkpoint(rid)
                self._handoff_fallback(src, rid, "no_decode_replica")
                continue
            target = self._place_replica(
                list(ckpt.prompt_token_ids) + list(ckpt.output_token_ids),
                ckpt.tenant_id,
                ckpt.lora_name,
                kind="decode",
            )
            if target not in targets:  # defensive: router fell open
                target = min(
                    targets,
                    key=lambda r: r.engine.scheduler.num_unfinished,
                )
            try:
                async with target.lock:
                    # re-checked INSIDE the lock: abort serializes on
                    # the SOURCE owner's lock, so a cancel can land
                    # while we awaited this one
                    if not self._resume_consumer_alive(ckpt, tier):
                        continue
                    target.engine.resume_request(ckpt, path="handoff")
            except Exception:  # noqa: BLE001 — one bad handoff must not sink the rest
                logger.exception(
                    "handoff resume of %s onto replica %d failed; "
                    "falling back to retryable failure",
                    rid, target.index,
                )
                tier.pop_checkpoint(rid)
                self._handoff_fallback(target, rid, "resume")
                continue
            tier.pop_checkpoint(rid)
            self._owner[rid] = target
            target.last_beat = time.monotonic()
            target.new_work.set()
            self.ledger.note_resume(rid, "handoff")
            self._link_resume(rid, "handoff")
            self.handoff_outcomes["completed"] += 1
            metrics.handoffs_total.labels(outcome="completed").inc()
            metrics.handoff_seconds.observe(
                max(0.0, time.perf_counter() - ckpt.t0)
            )
            target.engine.recorder.record(
                "handoff_in", rid, step=target.engine.step_counter,
                trace_id=ckpt.trace_id, from_replica=src.index,
                output_tokens=len(ckpt.output_token_ids),
            )

    def _handoff_fallback(
        self, rep: _Replica, request_id: str, reason: str
    ) -> None:
        """One handoff exhausted its ladder: fail the stream retryable
        (HandoffError → UNAVAILABLE/503 + Retry-After — the retry is
        cheap, the prompt's pages usually still promote from the
        tier)."""
        from vllm_tgis_adapter_tpu.frontdoor.errors import HandoffError

        self.handoff_outcomes["fallback"] += 1
        metrics.handoffs_total.labels(outcome="fallback").inc()
        rep.engine.recorder.record(
            "handoff_out", request_id, step=rep.engine.step_counter,
            outcome="fallback", reason=reason,
        )
        queue = self._queues.get(request_id)
        if queue is not None:
            queue.put_nowait(HandoffError(
                "prefill→decode handoff failed "
                f"({reason}); partial output was discarded — retry "
                "shortly",
                retry_after_s=2.0,
            ))

    def _abort_checkpointed(self, request_id: str):
        """Cancel a staged decode checkpoint (explicit abort during
        recovery).  Returns the final aborted RequestOutput, or None
        when no checkpoint is staged under this id."""
        tier = getattr(self.engine, "kv_tier", None)
        if tier is None:
            return None
        ckpt = tier.pop_checkpoint(request_id)
        if ckpt is None:
            return None
        ckpt.cancelled = True  # a resume path may still hold a reference
        return self._aborted_output(ckpt)

    def _resume_consumer_alive(self, ckpt, tier) -> bool:  # noqa: ANN001
        """Disconnect/abort-mid-resume hardening: a checkpoint whose
        stream is gone (or was aborted while staged) is dropped — no
        engine state is created, the staged record is discarded, and an
        explicit abort gets its final aborted frame."""
        rid = ckpt.request_id
        if ckpt.cancelled:
            return False  # abort() already delivered the final frame
        queue = self._queues.get(rid)
        if queue is None:
            if tier is not None:
                tier.pop_checkpoint(rid)
            return False
        if rid in self._early_aborts:
            self._early_aborts.discard(rid)
            if tier is not None:
                tier.pop_checkpoint(rid)
            queue.put_nowait(self._aborted_output(ckpt))
            return False
        return True

    @staticmethod
    def _aborted_output(ckpt) -> RequestOutput:  # noqa: ANN001
        """Final aborted frame for a checkpointed request that was
        aborted before its resume (same graceful wire shape as a
        TTL shed: an empty delta, finished, reason 'abort')."""
        return RequestOutput(
            request_id=ckpt.request_id,
            prompt=ckpt.prompt,
            prompt_token_ids=list(ckpt.prompt_token_ids),
            outputs=[CompletionOutput(
                index=0, text="", token_ids=[], finish_reason="abort",
            )],
            finished=True,
        )

    async def replay_to_replicas(self, rep: _Replica) -> int:
        """Cross-replica replay (docs/SCALING.md): move the dead
        replica's replay-safe requests (zero emitted tokens — parked in
        its scheduler or mid-prefill) onto HEALTHY replicas NOW, before
        the multi-second rebuild, so their TTFT pays a placement hop
        instead of a full recovery.  Runs under the dead replica's lock
        with its step loop reaped; ``fail_unreplayable`` has already
        triaged everything else out.  Returns the number moved; 0 when
        no healthy replica exists (dp=1 — ``restart_replica`` then
        replays into the rebuilt engine, the pre-router behavior).
        """
        healthy = [
            r for r in self._replicas if r.serving and r is not rep
        ]
        if not healthy:
            return 0
        moved = 0
        targets: set[int] = set()
        async with rep.lock:
            old = rep.engine
            for seq in list(old._seqs.values()):  # noqa: SLF001
                if seq.is_finished or seq.num_output_tokens > 0:
                    continue  # fail_unreplayable owns these
                if seq.request_id not in self._queues:
                    # consumer vanished while the replica was down
                    old._seqs.pop(seq.request_id, None)  # noqa: SLF001
                    old.lora_manager.unpin(seq.lora_name)
                    continue
                # tenant threaded through so stickiness FOLLOWS the
                # replay: place() re-pins the tenant's sticky entry to
                # the replica the request lands on
                target = self._place_replica(
                    list(seq.prompt_token_ids), seq.tenant_id,
                    seq.lora_name,
                )
                if target is rep:  # defensive: never replay onto the dead
                    target = healthy[moved % len(healthy)]
                old._seqs.pop(seq.request_id, None)  # noqa: SLF001
                old.lora_manager.unpin(seq.lora_name)
                # no target.lock needed: add_request is synchronous and
                # every engine-state mutation runs on this one event-loop
                # thread, so it cannot interleave a target critical
                # section (taking target.lock here, inside rep.lock,
                # would create the fleet's only nested-lock site)
                target.engine.add_request(
                    seq.request_id,
                    seq.prompt,
                    seq.params,
                    prompt_token_ids=list(seq.prompt_token_ids),
                    arrival_time=seq.metrics.arrival_time,
                    lora_name=seq.lora_name,
                    trace_id=seq.trace_id,
                    deadline=seq.deadline,
                    tenant_id=seq.tenant_id,
                    request_class=seq.request_class,
                )
                # abort()/stream bookkeeping must follow the request to
                # its new home — the dead replica's engine no longer
                # knows it
                self._owner[seq.request_id] = target
                targets.add(target.index)
                moved += 1
                # the request survived its replica's death: one restart
                # on its (still-open) ledger record
                self.ledger.note_restart(seq.request_id)
        for r in self._replicas:
            if r.index in targets:
                r.last_beat = time.monotonic()
                r.new_work.set()
        if moved:
            from vllm_tgis_adapter_tpu import metrics

            # counted HERE, not on the recovery attempt: a cross-replica
            # move happens exactly once even when the dead replica's
            # rebuild later fails and retries
            metrics.requests_replayed_total.inc(moved)
        return moved

    async def restart_replica(
        self, rep: _Replica, new_engine: LLMEngine,
        fail_error: BaseException,
    ) -> tuple[int, int]:
        """Swap a dead replica's engine for a freshly built one.

        Called by the supervisor with the replica's step loop already
        reaped.  Under the replica lock (serializing against concurrent
        ``add_request``/``abort``), engine-resident requests are triaged:

        * zero emitted tokens (scheduler-waiting, or mid-prefill) —
          transparently re-queued into the new engine with their
          original arrival time and deadline: the client's stream never
          notices the restart;
        * one or more emitted tokens (mid-decode) — failed with
          ``fail_error`` (EngineRestartError → UNAVAILABLE +
          Retry-After): replaying them would re-emit tokens the client
          already holds.

        Front-door-parked requests never reached the engine and simply
        stay parked (the pump is paused during recovery).  Returns
        ``(replayed, failed)`` counts.
        """
        from vllm_tgis_adapter_tpu.supervisor import failpoints

        replayed = 0
        fails: list[str] = []
        async with rep.lock:
            failpoints.fire("supervisor.replay")
            old = rep.engine
            # the adapter registry survives the restart (hot-loaded
            # LoRAs stay served); pins held by the dead engine's
            # sequences are released — replayed ones re-pin on re-add,
            # and each re-add prefetches into the rebuilt engine's
            # (cold) pool, so exactly the adapters live requests
            # reference re-stream
            new_engine.adopt_lora_manager(old.lora_manager)
            replays = []
            for seq in list(old._seqs.values()):  # noqa: SLF001
                old.lora_manager.unpin(seq.lora_name)
                if seq.is_finished or seq.num_output_tokens > 0:
                    # fail_unreplayable (quiesce triage) already
                    # delivered/failed these under this same lock;
                    # anything still here is a bug — fail it retryable
                    # rather than replaying tokens the client holds
                    fails.append(seq.request_id)
                    continue
                replays.append(seq)
            new_engine.replica_index = rep.index
            # the replacement serves the SAME disaggregation role the
            # dead engine did (a rebuilt prefill replica must resume
            # staging handoffs, not decode)
            new_engine.set_replica_role(rep.role)
            # the rebuilt core feeds the SAME fleet-level SLO engine
            # and cost ledger (open records survive the swap — a
            # restarted request bills once)
            new_engine.slo = self.slo_engine
            new_engine.ledger = self.ledger
            rep.engine = new_engine
            rep.in_flight_desc = None
            # the replacement's committed-token rates start fresh, in
            # BOTH consumers: the router's load tiebreak and the front
            # door's drain estimator
            self.router.forget_replica_rate(rep.index)
            if self.frontdoor is not None:
                self.frontdoor.forget_replica_rate(rep.index)
            if rep is self._replicas[0]:
                # replica 0 doubles as the host-side singleton surface
                self.engine = new_engine
                # ledger flight-recorder events follow replica 0's ring
                self.ledger.recorder = new_engine.recorder.record
            for seq in replays:
                if seq.request_id not in self._queues:
                    continue  # consumer vanished while the engine was down
                new_engine.add_request(
                    seq.request_id,
                    seq.prompt,
                    seq.params,
                    prompt_token_ids=list(seq.prompt_token_ids),
                    arrival_time=seq.metrics.arrival_time,
                    lora_name=seq.lora_name,
                    trace_id=seq.trace_id,
                    deadline=seq.deadline,
                    tenant_id=seq.tenant_id,
                    request_class=seq.request_class,
                )
                replayed += 1
                # the request survived a supervised engine restart:
                # count it on the still-open ledger record
                self.ledger.note_restart(seq.request_id)
        failed = 0
        for request_id in fails:
            queue = self._queues.get(request_id)
            if queue is not None:
                queue.put_nowait(fail_error)
                failed += 1
        return replayed, failed
