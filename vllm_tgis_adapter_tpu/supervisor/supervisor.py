"""The engine supervisor: engine death → quiesce → triage → rebuild → re-arm.

Before PR 5 any engine death — step-loop exception, XLA OOM, Mosaic
kernel failure, watchdog-declared stall — was terminal: the error
propagated out of ``__main__.run`` and killed the whole pod, dropping
every queued request.  The reference serving stack survives engine
faults through *process* supervision (systemd/k8s restart the pod); a
TPU-native single-process design restarts in-process instead, which is
both faster (weights stay resident — only the KV pool, scheduler, and
compiled programs are rebuilt) and lossless for work that never reached
the device.

One recovery (``docs/RECOVERY.md``; every step failpoint-tested in
``tests/test_supervisor.py``):

1. **quiesce** — lifecycle → ``recovering`` (health NOT_SERVING), front
   door paused (parked requests HOLD, nothing sheds), the dead replica's
   step-loop task reaped;
2. **triage** — engine-resident requests split by whether replay is
   safe: zero emitted tokens (waiting, or mid-prefill) → captured for
   replay; one or more emitted tokens (mid-decode) → failed with
   ``EngineRestartError`` (UNAVAILABLE + Retry-After — the client
   retries, this pod included);
3. **rebuild** — a fresh ``LLMEngine`` over the SAME weights/tokenizer/
   device slice (no checkpoint reload): new KV pool, new scheduler, new
   jitted programs, ``precompile()`` re-warm when the boot warmed;
4. **replay + re-arm** — captured requests re-enter the new engine with
   their original arrival times and deadlines, the step loop restarts,
   the front door resumes, lifecycle → ``serving``.

Exponential backoff separates attempts; a crash-loop circuit breaker
(``--max-engine-restarts`` within ``--engine-restart-window``) escalates
to clean process death with the full restart history in the termination
log — a pod that cannot hold an engine up must say so and die, not
flap forever.

Under ``--data-parallel-size N`` only the dead replica is rebuilt;
healthy replicas keep serving their in-flight work throughout (one
replica's fault must not take down the fleet's queue).
"""

from __future__ import annotations

import asyncio
import os
import time
import traceback
from typing import TYPE_CHECKING, Callable, Optional

from vllm_tgis_adapter_tpu import metrics
from vllm_tgis_adapter_tpu.engine import sanitizer
from vllm_tgis_adapter_tpu.frontdoor.errors import (
    DeviceOOMError,
    EngineRestartError,
    wrap_engine_error,
)
from vllm_tgis_adapter_tpu.logging import init_logger
from vllm_tgis_adapter_tpu.supervisor import failpoints
from vllm_tgis_adapter_tpu.supervisor.lifecycle import (
    LIFECYCLE_DEAD,
    LIFECYCLE_DRAINING,
    LIFECYCLE_RECOVERING,
    LIFECYCLE_SERVING,
)
from vllm_tgis_adapter_tpu.utils import spawn_task, write_termination_log

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine, _Replica
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

logger = init_logger(__name__)

BACKOFF_MAX_S = 30.0

# death causes (the engine_restarts_total{cause} label values)
CAUSE_STEP_LOOP = "step_loop"
CAUSE_OOM = "oom"
CAUSE_STALL = "stall"
CAUSE_RECOVERY_FAILURE = "recovery_failure"


def classify_cause(err: BaseException) -> str:
    """Death-cause label for one wrapped engine error."""
    return CAUSE_OOM if isinstance(err, DeviceOOMError) else CAUSE_STEP_LOOP


class EngineSupervisor:
    """Owns the restart lifecycle of one ``AsyncLLMEngine``'s replicas.

    Constructed by ``AsyncLLMEngine.__init__`` when
    ``config.max_engine_restarts > 0``; the step loops report deaths via
    ``notify_death`` and the watchdog requests stall restarts via
    ``request_restart`` — both are synchronous and safe to call from any
    event-loop context (the actual recovery runs as its own task).
    """

    def __init__(
        self,
        engine: "AsyncLLMEngine",
        *,
        max_restarts: int,
        window_s: float,
        backoff_base_s: float = 0.5,
        termination_log: Optional[str] = None,
    ):
        self.engine = engine
        self.max_restarts = max(1, max_restarts)
        self.window_s = max(1.0, window_s)
        self.backoff_base_s = max(0.0, backoff_base_s)
        self._termination_log = termination_log or os.getenv(
            "TERMINATION_LOG_DIR", "/dev/termination-log"
        )
        #: One dict per completed or failed restart attempt — the
        #: termination-log checkpoint and /debug/state both render this.
        self.restart_history: list[dict] = []
        # monotonic stamps of attempts PER REPLICA, for the sliding-
        # window breaker: on a dp fleet each replica gets its own
        # restart budget and backoff ladder, so independent transient
        # faults on different replicas never pool into an escalation
        # that kills healthy replicas (docs/SCALING.md: the pod dies
        # only when ONE replica crash-loops or the last replica dies).
        # At dp=1 this is exactly the old single-engine budget.
        self._attempt_times: dict[int, list[float]] = {}
        self._pending: list[tuple["_Replica", BaseException, str]] = []
        self._pending_reps: set[int] = set()
        self._task: Optional[asyncio.Task] = None
        self._listeners: list[Callable[[str], None]] = []
        self._stopping = False

    # ------------------------------------------------------------ reporting

    def add_listener(self, listener: Callable[[str], None]) -> None:
        """Lifecycle-transition hook (called with the new state); the
        gRPC server registers one to flip health SERVING ↔ NOT_SERVING."""
        self._listeners.append(listener)

    def _set_lifecycle(self, state: str) -> None:
        # lifecycle-grammar edge check (TGIS_TPU_SANITIZE=1): the
        # transition must be a declared edge of the lifecycle machine in
        # tools/dettest/lifecycle_grammar.py — including the
        # schedule-dependent rule that recovery never flips a draining
        # pod back to serving
        frontdoor = getattr(self.engine, "frontdoor", None)
        sanitizer.check_lifecycle_edge(
            getattr(self.engine, "lifecycle", None), state,
            draining=bool(frontdoor is not None and frontdoor.draining),
        )
        self.engine.lifecycle = state
        for listener in self._listeners:
            try:
                listener(state)
            except Exception:  # noqa: BLE001 — one listener must not stall recovery
                logger.exception("supervisor lifecycle listener failed")

    def debug_state(self) -> dict:
        """Supervisor section of the /debug/state snapshot."""
        return {
            "max_restarts": self.max_restarts,
            "window_s": self.window_s,
            "restarts": len(
                [h for h in self.restart_history if h.get("recovered")]
            ),
            "attempts": len(self.restart_history),
            "recovering": bool(self._pending)
            or (self._task is not None and not self._task.done()),
            "history": self.restart_history[-8:],
        }

    def history_lines(self) -> list[str]:
        """Human-readable restart history (termination log, escalation
        error message)."""
        lines = []
        for h in self.restart_history:
            outcome = (
                f"recovered in {h['recovery_s']:.2f}s "
                f"(replayed={h['replayed']}, "
                f"resumed={h.get('resumed', 0)}, failed={h['failed']})"
                if h.get("recovered")
                else f"recovery FAILED: {h.get('error', '?')}"
            )
            lines.append(
                f"  #{h['attempt']} at {h['at']} replica={h['replica']} "
                f"cause={h['cause']} [{h.get('death', '?')}] {outcome}"
            )
        return lines

    # ------------------------------------------------------- death intake

    def accepts(self) -> bool:
        """May the supervisor take this death, or is it terminal?"""
        return (
            not self._stopping
            and self.engine.lifecycle != LIFECYCLE_DEAD
        )

    def notify_death(
        self, rep: "_Replica", err: BaseException, cause: Optional[str] = None
    ) -> None:
        """A step loop died (already-wrapped error).  Synchronous: by
        the time it returns the dead replica is out of placement and
        the recovery task is scheduled.

        Scope depends on the fleet (docs/SCALING.md): while at least
        one OTHER replica is serving, this is a PARTIAL outage —
        lifecycle stays ``serving``, the front door keeps admitting
        (the placement router routes around the quiesced replica), and
        the pod's health surfaces never flinch.  Only when the LAST
        serving replica dies does the whole pod quiesce: lifecycle →
        ``recovering``, admission paused — exactly the dp=1 behavior.
        """
        if not self.accepts():
            return
        if rep.index in self._pending_reps:
            return  # this replica's recovery is already queued
        # out of placement BEFORE anything else: new arrivals and the
        # drain estimator must stop seeing this replica immediately
        rep.serving = False
        self._pending_reps.add(rep.index)
        self._pending.append((rep, err, cause or classify_cause(err)))
        healthy = [
            r for r in self.engine._replicas  # noqa: SLF001
            if r.serving
        ]
        if healthy:
            logger.warning(
                "engine supervisor: replica %d quiesced; %d replica(s) "
                "keep serving (capacity loss, not an outage)",
                rep.index, len(healthy),
            )
        else:
            self._set_lifecycle(LIFECYCLE_RECOVERING)
            frontdoor = self.engine.frontdoor
            if frontdoor is not None:
                frontdoor.pause()
        if self._task is None or self._task.done():
            self._task = spawn_task(
                self._recover_all(), name="engine-supervisor"
            )

    def request_restart(
        self, cause: str = CAUSE_STALL, rep: Optional["_Replica"] = None
    ) -> None:
        """Watchdog ``--watchdog-action=restart`` entry point: declare
        the stalled replica dead and recover it.  Its stuck step-loop
        task is cancelled during quiesce (the dispatch thread it was
        blocked on is abandoned — on real hardware a truly wedged device
        program cannot be interrupted from the host; the rebuilt engine
        dispatches fresh programs).

        ``rep`` is the replica captured at DETECTION time (the snapshot
        identified it before the dump I/O); re-resolving here could
        blame a healthy replica if the stall cleared in that window."""
        if rep is None:
            rep = self.engine._stalled_replica()  # noqa: SLF001 — supervisor owns this view
        err = EngineRestartError(
            "watchdog declared a step-loop stall; the engine is being "
            "restarted"
        )
        self.notify_death(rep, err, cause)

    # ------------------------------------------------------------- recovery

    async def stop(self) -> None:
        self._stopping = True
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._task = None

    def _recent_attempts(self, rep_index: int, now: float) -> int:
        stamps = [
            t
            for t in self._attempt_times.get(rep_index, [])
            if now - t <= self.window_s
        ]
        self._attempt_times[rep_index] = stamps
        return len(stamps)

    async def _recover_all(self) -> None:
        """Drain the pending-death queue; one recovery at a time."""
        while self._pending:
            rep, err, cause = self._pending.pop(0)
            now = time.monotonic()
            if self._recent_attempts(rep.index, now) >= self.max_restarts:
                await self._escalate(err, cause)
                return
            self._attempt_times[rep.index].append(now)
            attempt = len(self.restart_history) + 1
            # base * 2^(n-1) over THIS replica's attempts in the window
            # — exactly the formula the --engine-restart-backoff help
            # documents, per replica
            backoff = 0.0
            if self.backoff_base_s > 0:
                backoff = min(
                    BACKOFF_MAX_S,
                    self.backoff_base_s
                    * (2 ** (len(self._attempt_times[rep.index]) - 1)),
                )
            entry = {
                "attempt": attempt,
                "at": time.strftime("%Y-%m-%dT%H:%M:%S"),
                "replica": rep.index,
                "cause": cause,
                "death": f"{type(err).__name__}: {err}",
                "backoff_s": round(backoff, 3),
            }
            self.restart_history.append(entry)
            metrics.engine_restarts_total.labels(
                cause=cause, replica=str(rep.index)
            ).inc()
            logger.warning(
                "engine supervisor: replica %d died (%s); restart attempt "
                "%d/%d in its window, backoff %.2fs",
                rep.index, cause, len(self._attempt_times[rep.index]),
                self.max_restarts, backoff,
            )
            t0 = time.monotonic()
            try:
                moved, rebuilt_replayed, failed, resumed = (
                    await self._recover_one(rep, err, backoff)
                )
                replayed = moved + rebuilt_replayed
            except asyncio.CancelledError:
                raise
            except BaseException as exc:  # noqa: BLE001 — death DURING recovery
                wrapped = wrap_engine_error(exc)
                entry["recovered"] = False
                entry["error"] = f"{type(wrapped).__name__}: {wrapped}"
                logger.exception(
                    "engine recovery attempt %d failed; re-queueing",
                    attempt,
                )
                # drop the frame references BEFORE re-queueing (after
                # the log above consumed them): the traceback pins
                # _recover_one's locals — possibly a fully built
                # replacement engine whose KV pool must be collectable
                # before the retry's rebuild (two pools cannot coexist
                # on TPU)
                exc.__traceback__ = None
                wrapped.__traceback__ = None
                self._pending_reps.discard(rep.index)
                self.notify_death(rep, wrapped, CAUSE_RECOVERY_FAILURE)
                continue
            duration = time.monotonic() - t0
            entry.update(
                recovered=True,
                recovery_s=round(duration, 3),
                replayed=replayed,
                failed=failed,
                resumed=resumed,
            )
            metrics.recovery_seconds.observe(duration)
            # counted only on the attempt that SUCCEEDED: a failed
            # attempt's partial replays get re-triaged and re-counted
            # by its retry, which would overstate the metric.  Cross-
            # replica moves are NOT re-counted here — replay_to_replicas
            # counted them at move time (they happen exactly once, even
            # across rebuild retries).
            metrics.requests_replayed_total.inc(rebuilt_replayed)
            rep.engine.recorder.record(
                "restart", step=rep.engine.step_counter, replica=rep.index,
                cause=cause, attempt=attempt, replayed=replayed,
                failed=failed, resumed=resumed,
                recovery_s=round(duration, 3),
            )
            self._pending_reps.discard(rep.index)
            logger.warning(
                "engine supervisor: replica %d recovered in %.2fs "
                "(%d requests replayed, %d mid-decode resumed, "
                "%d failed retryable)",
                rep.index, duration, replayed, resumed, failed,
            )
            # checkpoint: if the pod dies later for an unrelated reason,
            # the post-mortem still sees that (and why) restarts happened
            await asyncio.to_thread(
                write_termination_log,
                "engine restarted under supervision "
                f"({len(self.restart_history)} attempt(s)):\n"
                + "\n".join(self.history_lines()),
                self._termination_log,
            )
        # every pending death recovered: back to serving — unless a
        # SIGTERM drain began mid-recovery, which wins (the listeners
        # guard the same way, so health never flips back to SERVING on
        # a draining pod).  This tail MUST stay await-free: notify_death
        # only interleaves at await points, so a death arriving after
        # the while-condition saw an empty queue would otherwise strand
        # in _pending with this task already exiting.
        frontdoor = self.engine.frontdoor
        draining = (
            (frontdoor is not None and frontdoor.draining)
            # --disable-frontdoor drains too: the coordinator stamps the
            # lifecycle directly, and recovery must not clobber it
            or self.engine.lifecycle == LIFECYCLE_DRAINING
        )
        self._set_lifecycle(
            LIFECYCLE_DRAINING if draining else LIFECYCLE_SERVING
        )
        if frontdoor is not None:
            frontdoor.resume()

    async def _recover_one(
        self, rep: "_Replica", err: BaseException, backoff: float = 0.0
    ) -> tuple[int, int, int, int]:
        """Quiesce + rebuild + replay/resume one replica.  Returns
        ``(moved_to_healthy, replayed_into_rebuilt, failed, resumed)``;
        raises on failure (the caller converts that into another
        attempt)."""
        # reap the dead (or stuck) step-loop task; a stalled task is
        # blocked in to_thread — cancelling abandons the worker thread
        task = rep.task
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                # ambiguous: the reaped task's cancellation, or OUR
                # cancellation (supervisor.stop() during shutdown).
                # Honor our own — recovery must not continue into a
                # minutes-long rebuild on an engine being torn down.
                if self._stopping:
                    raise
            except Exception:  # noqa: BLE001 — the reaped loop's death error
                pass
        rep.task = None
        fail_error = EngineRestartError(
            "engine restarted mid-request after a fault; partial output "
            f"was discarded (cause: {type(err).__name__}: {err})",
            retry_after_s=2.0,
        )
        fail_error.__cause__ = err
        # triage the fixed-outcome requests FIRST: finished output
        # delivers, and each mid-decode request either CHECKPOINTS into
        # the host KV tier for a token-identical resume or — down the
        # degradation ladder — gets its retryable UNAVAILABLE now, not
        # after the rebuild and precompile re-warm it cannot benefit
        # from (docs/RECOVERY.md)
        failed, checkpoints = await self.engine.fail_unreplayable(
            rep, fail_error
        )
        # a FAILED earlier attempt's checkpoints survive in the tier
        # (like the KV pages themselves): adopt them so the retry
        # resumes instead of losing them
        checkpoints = self.engine.staged_checkpoints(checkpoints)
        # then move replay-safe work onto HEALTHY replicas immediately
        # (cross-replica replay, docs/SCALING.md): those requests reach
        # prefill while this replica is still rebuilding.  dp=1 (no
        # healthy sibling) moves nothing — restart_replica replays into
        # the rebuilt engine below, the pre-router behavior.
        moved = await self.engine.replay_to_replicas(rep)
        # checkpointed mid-decode work takes the same hop when a healthy
        # sibling exists (the tier is shared fleet-wide): decode resumes
        # BEFORE the rebuild, placement-scored like the replays above
        resumed, cross_failed, checkpoints = (
            await self.engine.resume_to_replicas(
                rep, checkpoints, fail_error
            )
        )
        failed += cross_failed
        # crash-loop backoff delays only the REBUILD: triage and cross-
        # replica replay above already ran, so no request waits out the
        # backoff of a crash-looping replica — only the replica's own
        # capacity restoration does
        if backoff > 0:
            await asyncio.sleep(backoff)
        old = rep.engine
        self._release_frames(err)
        new_engine = await asyncio.to_thread(self._rebuild, old)
        # stamp the replica index AND role BEFORE the precompile
        # re-warm: its warmup dispatches record per-replica/role step
        # metrics, which must not land in replica 0's histograms, and a
        # prefill-role engine's warmups must run under the handoff
        # exemption it will serve with (restart_replica stamps both
        # again, harmlessly)
        new_engine.replica_index = rep.index
        new_engine.set_replica_role(rep.role)
        # re-warm the serving shapes the boot warmed: the rebuilt
        # runner's jitted programs are cold, and the first real request
        # must not pay a multi-second compile sweep
        widths = self.engine._precompile_widths  # noqa: SLF001
        if widths is not None:
            await asyncio.to_thread(new_engine.precompile, widths)
        replayed, late_failed = await self.engine.restart_replica(
            rep, new_engine, fail_error
        )
        # checkpoints no healthy sibling took resume into the rebuilt
        # engine (dp=1: all of them) — the kv gate promotes their pages
        # back from the surviving tier and decode continues
        local_resumed, resume_failed = await self.engine.resume_into(
            rep, checkpoints, fail_error
        )
        self.engine._arm_replica(rep)  # noqa: SLF001
        # re-admit to placement only now, with the rebuilt engine armed:
        # the router starts routing to it again from the next request
        rep.serving = True
        return (
            moved,
            replayed,
            failed + late_failed + resume_failed,
            resumed + local_resumed,
        )

    @staticmethod
    def _release_frames(err: Optional[BaseException]) -> None:
        """Drop the locals of every frame the death's tracebacks hold.
        The frame that died inside a dispatch has the KV pool among its
        locals (``k_cache, v_cache = self.caches``), and the death error
        lives on in the restart history — so the pool would outlive
        ``runner.caches = None`` and the rebuild's pool could not be
        allocated beside it (seen on a v5e in PR 23: 1.57 GB free)."""
        seen: set[int] = set()
        while err is not None and id(err) not in seen:
            seen.add(id(err))
            if err.__traceback__ is not None:
                traceback.clear_frames(err.__traceback__)
            err = err.__cause__ or err.__context__

    def _rebuild(self, old: "LLMEngine") -> "LLMEngine":
        """Build the replacement engine (worker thread; slow is fine).

        Reuses the resident weights, tokenizer, and device slice —
        everything stateful (KV pool, scheduler, block allocator, jitted
        programs, flight recorder) is constructed fresh.
        """
        failpoints.fire("supervisor.rebuild")
        from vllm_tgis_adapter_tpu.engine.core import LLMEngine

        runner = old.runner
        spec = getattr(runner, "spec", None)
        draft = (spec.draft_model, spec.draft_params) if spec else None
        # release the dead engine's device pools BEFORE allocating the
        # replacement: on TPU the boot pool was sized to ~all free HBM,
        # and two of them cannot coexist — holding the old reference
        # here would make every rebuild die in RESOURCE_EXHAUSTED.  The
        # weights (runner.params) stay resident; only KV goes — and the
        # adapter pool's slot stacks, whose HBM reservation the
        # replacement's own (cold) pool re-claims.  The rebuilt engine
        # re-streams ONLY the adapters its replayed requests reference:
        # each replayed add_request issues a pool prefetch
        # (engine/core.py), so dead tenants' weights stay on the host.
        runner.caches = None
        pool = getattr(runner, "adapter_pool", None)
        if pool is not None:
            pool.release()
            runner.lora_stacks = None
        if spec is not None:
            spec.draft_caches = None
        # old.config already carries the boot-resolved num_blocks, so no
        # re-sizing happens here; memory_device is still passed so any
        # future re-size path reads THIS replica's device, not device 0
        devices = old._devices  # noqa: SLF001
        new = LLMEngine(
            old.config,
            runner.model,
            runner.params,
            old.tokenizer,
            mesh=getattr(runner, "mesh", None),
            memory_device=devices[0] if devices else None,
            pp_devices=devices,
        )
        new._devices = old._devices  # noqa: SLF001
        if draft is not None:
            # engine-level attach: re-arms the scheduler's verify-span
            # planning (spec_gamma) along with the runner's programs
            new.attach_speculative(*draft)
        # the host KV tier SURVIVES the restart (it is host memory, not
        # part of the dead engine): the replacement adopts it, so warm
        # prefixes promote instead of recomputing — in-flight tickets
        # stay with the dead engine (their target pages died with its
        # pool) and are simply never applied (docs/KV_TIERING.md)
        if old.kv_tier is not None:
            new.adopt_kv_tier(old.kv_tier)
        return new

    # ------------------------------------------------------------ escalation

    async def _escalate(self, err: BaseException, cause: str) -> None:
        """Crash-loop circuit breaker tripped: die cleanly and loudly."""
        from vllm_tgis_adapter_tpu.engine.async_llm import EngineDeadError

        history = "\n".join(self.history_lines())
        msg = (
            f"engine crash-loop: "
            f"{max(map(len, self._attempt_times.values()), default=0)} "
            f"restarts of one replica within {self.window_s:.0f}s hit "
            f"--max-engine-restarts={self.max_restarts}; giving up and "
            f"exiting. Last death "
            f"({cause}): {type(err).__name__}: {err}\n"
            f"restart history:\n{history}"
        )
        logger.error("%s", msg)
        final = EngineDeadError(msg)
        final.__cause__ = err
        self._set_lifecycle(LIFECYCLE_DEAD)
        # checkpoint the history FIRST: the final traceback write in
        # __main__ embeds this same message, but a SIGKILL between here
        # and there must not lose the evidence
        await asyncio.to_thread(
            write_termination_log, msg, self._termination_log
        )
        self.engine._terminal_death(final)  # noqa: SLF001 — the one sanctioned caller
        # wake __main__ only after the checkpoint write above finished
        # (its final traceback APPENDS to what we just wrote)
        self.engine.dead_event.set()
