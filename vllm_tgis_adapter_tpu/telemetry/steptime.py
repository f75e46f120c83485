"""Step-time anatomy: a bounded per-replica ring of ``StepRecord``s.

Metrics already say a step was slow (``decode_step_seconds``); this
module says **where the time went**.  The engine core stamps monotonic
probes at the phase boundaries its step loop already crosses —
plan / prepare / dispatch / device_wait / commit — and the commit
finalizes one :class:`StepRecord` per dispatched plan into a
``deque``-bounded ring (O(capacity) memory forever, one object per
step, no locks: stamps ride on the ``prepared`` snapshot exactly like
``_obs_plan_t0``, so the depth-1 pipelined loop can interleave two
steps across threads without shared mutable state).

The decomposition is **contiguous by construction** — the five
measured phases telescope over ``[t_enter, t_end]``::

    plan        = t_sched  - t_enter   (scheduler.schedule, drains)
    prepare     = t_prep   - t_sched   (runner.prepare_*)
    dispatch    = t_disp1  - t_prep    (enqueue + thread handoff)
    device_wait = t_wait1  - t_disp1   (in-flight window)
    commit      = t_end    - t_wait1   (lock wait + commit + sanitizer)

so ``plan+prepare+dispatch+device_wait+commit == t_end - t_enter``
*exactly* (tests/test_steptime.py holds this as the anatomy-sums-to-
step-wall invariant).  ``host_gap`` is the sixth component: the time
the **device sat idle waiting on the host** before this step's work was
enqueued — ``device_start - previous step's device_end``, clamped to
``[0, GAP_CAP]`` and zeroed past ``IDLE_CUTOFF_S`` (an idle engine is
not host-bound).  In the overlapped async loop the next step is
dispatched while the previous executes, so host_gap ~ 0; with
``SYNC_DISPATCH`` (or any un-overlapped loop) every step pays the full
host phase as device idle and host_gap measures exactly the overlap
the pipeline would have bought.  ``wall_s = host_gap + (t_end -
t_enter)`` keeps the six-way sum exact.

Where the device-busy interval lives depends on how the backend
dispatches (:func:`backend_dispatch_blocks`):

* JAX async dispatch (TPU, default CPU): ``dispatch_*`` enqueues and
  returns — device busy ~ ``[t_disp1, t_wait1]``;
* blocking dispatch (the CPU backend with
  ``jax_cpu_enable_async_dispatch`` off): the device work runs INSIDE
  ``dispatch_*`` — device busy ``[t_disp0, t_disp1]`` and the paired
  wait returns instantly, so the gap must be measured against the
  dispatch window or it degenerates to ~0 and hides exactly the
  host-boundness the flag exists to surface;
* ``SYNC_DISPATCH`` sentinel (staged pipeline runner): the device work
  runs inside ``wait_*`` — device busy ``[t_wait0, t_wait1]``.

Consumers: ``step_anatomy_seconds{phase,replica}`` histograms and the
``host_gap_frac{replica}`` gauge (metrics.py), the ``step_timeline``
section of ``/debug/state``, the doctor's sliding windows
(telemetry/doctor.py), watchdog stall dumps (last 64 records of the
blamed replica), and the chrome-trace exporter (telemetry/timeline.py).
"""

from __future__ import annotations

import math
import statistics
import time
from collections import deque
from typing import Any, Optional

from vllm_tgis_adapter_tpu.logging import init_logger

logger = init_logger(__name__)

#: The six phases, in within-step order.  ``host_gap`` precedes
#: ``plan`` on the wall clock (it is the device-idle lead-in).
PHASES = (
    "host_gap", "plan", "prepare", "dispatch", "device_wait", "commit",
)

#: A single leading device-idle gap is capped here: a longer gap is a
#: scheduling artifact (burst edge), not per-step host overhead.
GAP_CAP_S = 0.25
#: Gaps beyond this are an idle engine (no traffic), never host-bound.
IDLE_CUTOFF_S = 1.0
#: Ring capacity per replica (~minutes of saturated serving) and the
#: sliding window the host_gap_frac gauge / doctor read.
DEFAULT_CAPACITY = 256
DEFAULT_WINDOW = 32
#: Dispatches the fused-depth rule (StepTimeline.wave_depth) takes its
#: two medians over: enough that one compile or one stalled turn does
#: not move them, few enough that a change of load shows within a second.
#: The mixed steps of one flat-token bucket (StepTimeline.stall_bucket)
#: are kept as deep.
DEPTH_WINDOW = 16
#: A wave's device seconds are known from the host's clock only where
#: the host had to wait for them: a wait that returns at once says the
#: device had finished earlier and idled since — the distance between
#: two such results is the host's turn, not the device's work.
WAITED_MIN_S = 0.001
#: Token steps a mixed step may cost the rows that ride it
#: (StepTimeline.stall_bucket): one number for every model, because the
#: step times it is held against are the model's own.  The smallest
#: mixed step costs 1.4-1.8 token steps on a v5e whatever it holds and
#: one of 128 tokens 1.7-2.2 (PERF.md section 5): at 2.0 the rule cut
#: prompts into 64-token steps and paid three steps for one.
STALL_MULTIPLE = 2.5
#: Readings the rule needs before it judges: of the token step, and of
#: a bucket before that bucket may stop the walk (one late result among
#: a bucket's first does not shut it).
STALL_MIN_SAMPLES = 3
#: A bucket found over the limit comes back only once it reads this
#: share under it: a ratio that drifts a few per cent with the rows in
#: flight (2.5-4 % between runs of one cell, PERF.md section 5) does
#: not flip the cap between two sizes of step.  No wider: a bucket shut
#: while few rows ran (a cheap token step) has to come back when many do.
STALL_HYSTERESIS = 0.08
#: A bucket over the limit is used by no step with running rows, so
#: nothing measures it again: after this many seconds without a reading
#: it is given one step, whose reading joins its median.
STALL_REPROBE_S = 30.0


def backend_dispatch_blocks() -> bool:
    """True when ``dispatch_*`` executes the device work before
    returning: the JAX CPU backend with async dispatch disabled
    (``jax_cpu_enable_async_dispatch`` off).  The engine core samples
    this once per StepTimeline so the gap computation reads the right
    device-busy interval."""
    try:
        import jax

        return (
            jax.default_backend() == "cpu"
            and not jax.config.read("jax_cpu_enable_async_dispatch")
        )
    except Exception:  # noqa: BLE001 — anatomy must never break serving
        return False


class _Stamps:
    """Per-step probe stamps, attached to the ``prepared`` snapshot so
    they travel with the step through the pipelined loop's threads."""

    __slots__ = (
        "t_enter", "t_sched", "t_prep", "t_disp0", "t_disp1",
        "t_wait0", "t_wait1", "drain_s", "chained", "sync",
        "compile_fn", "compiles",
    )

    def __init__(self) -> None:
        self.t_enter: Optional[float] = None
        self.t_sched: Optional[float] = None
        self.t_prep: Optional[float] = None
        self.t_disp0: Optional[float] = None
        self.t_disp1: Optional[float] = None
        self.t_wait0: Optional[float] = None
        self.t_wait1: Optional[float] = None
        self.drain_s = 0.0
        self.chained = False
        self.sync = False
        self.compile_fn: Optional[str] = None
        # compile_tracker's count of compiles when the step was planned
        self.compiles: Optional[int] = None


def _compiles() -> Optional[int]:
    try:
        from vllm_tgis_adapter_tpu import compile_tracker

        return compile_tracker.total_recompiles()
    except Exception:  # noqa: BLE001 — anatomy must never break serving
        return None


class StepRecord:
    """One finalized step's anatomy (see module docstring for the
    decomposition contract)."""

    __slots__ = (
        "step", "replica", "kind", "tokens", "fill_ratio", "chained",
        "sync", "t_enter", "t_sched", "t_prep", "t_disp1", "t_wait0",
        "t_wait1", "t_end", "wall_end", "host_gap_s", "drain_s",
        "compile_fn", "steps", "device_s", "turn_s", "bucket",
        "running", "compiled",
    )

    def __init__(self, *, step: int, replica: int, kind: str,
                 tokens: int, fill_ratio: float, stamps: _Stamps,
                 t_end: float, wall_end: float,
                 host_gap_s: float, steps: int = 1,
                 device_s: float = 0.0,
                 turn_s: Optional[float] = None,
                 bucket: Optional[int] = None,
                 running: int = 0, compiled: bool = False) -> None:
        self.step = step
        self.replica = replica
        self.kind = kind
        self.tokens = tokens
        self.fill_ratio = fill_ratio
        self.chained = stamps.chained
        self.sync = stamps.sync
        self.t_enter = stamps.t_enter
        self.t_sched = stamps.t_sched
        self.t_prep = stamps.t_prep
        self.t_disp1 = stamps.t_disp1
        self.t_wait0 = stamps.t_wait0
        self.t_wait1 = stamps.t_wait1
        self.t_end = t_end
        self.wall_end = wall_end
        self.host_gap_s = host_gap_s
        self.drain_s = stamps.drain_s
        self.compile_fn = stamps.compile_fn
        # token steps the dispatch ran (a wave's fused depth; 1 for a
        # mixed step) and the seconds the device spent on it alone: from
        # its start, or its predecessor's end where it was enqueued
        # behind one, to its own end
        self.steps = steps
        self.device_s = device_s
        # a mixed step's flat-token bucket (its compile shape), else
        # None, and the running rows that rode it for their one token
        self.bucket = bucket
        self.running = running
        # a program compiled between this step's plan and its commit:
        # where dispatch blocks or the work runs in the wait, its
        # device window holds the compile
        self.compiled = compiled
        # the host's turn that this dispatch's device time has to hide.
        # Planned ahead: everything the loop did between its two waits
        # for the device, from the predecessor's results to the start of
        # the wait for these — the predecessor's commit, the successor's
        # plan, prepare and dispatch, and whatever else ran on the event
        # loop between them (the streams).  Else the four host phases
        self.turn_s = (
            turn_s if turn_s is not None
            else self.plan_s + self.prepare_s + self.dispatch_s
            + self.commit_s
        )

    # ------------------------------------------------ derived durations

    @property
    def plan_s(self) -> float:
        return self.t_sched - self.t_enter

    @property
    def prepare_s(self) -> float:
        return self.t_prep - self.t_sched

    @property
    def dispatch_s(self) -> float:
        return self.t_disp1 - self.t_prep

    @property
    def device_wait_s(self) -> float:
        return self.t_wait1 - self.t_disp1

    @property
    def commit_s(self) -> float:
        return self.t_end - self.t_wait1

    @property
    def wall_s(self) -> float:
        """Six-way total: ``host_gap + (t_end - t_enter)`` exactly."""
        return self.host_gap_s + (self.t_end - self.t_enter)

    def phases(self) -> dict[str, float]:
        return {
            "host_gap": self.host_gap_s,
            "plan": self.plan_s,
            "prepare": self.prepare_s,
            "dispatch": self.dispatch_s,
            "device_wait": self.device_wait_s,
            "commit": self.commit_s,
        }

    def to_dict(self) -> dict[str, Any]:
        """JSON-safe form for /debug/state and the timeline exporter.
        ``ts`` anchors ``t_enter`` on the wall clock; the contiguous
        decomposition means phase start offsets need no extra fields."""
        return {
            "step": self.step,
            "replica": self.replica,
            "kind": self.kind,
            "tokens": self.tokens,
            "bucket": self.bucket,
            "running": self.running,
            "fill_ratio": round(self.fill_ratio, 4),
            "chained": self.chained,
            "sync": self.sync,
            "ts": round(self.wall_end - (self.t_end - self.t_enter), 6),
            "wall_s": round(self.wall_s, 6),
            "drain_s": round(self.drain_s, 6),
            "steps": self.steps,
            "device_s": round(self.device_s, 6),
            "turn_s": round(self.turn_s, 6),
            "compile_fn": self.compile_fn,
            "compiled": self.compiled,
            "phases": {
                name: round(value, 6)
                for name, value in self.phases().items()
            },
        }


class StepTimeline:
    """The per-engine bounded ring + the stamp helpers the core calls.

    Every helper is a cheap attribute write and None-tolerant: a missing
    ``prepared`` (plan was None, legacy sync callers) degrades to a
    no-op, and :meth:`finish` refuses to build a record from incomplete
    stamps rather than emit garbage — anatomy must never break serving.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 window: int = DEFAULT_WINDOW,
                 dispatch_blocks: bool = False) -> None:
        self._ring: deque[StepRecord] = deque(maxlen=capacity)
        self.window = window
        # blocking dispatch (backend_dispatch_blocks): the device-busy
        # interval is the dispatch window, not [t_disp1, t_wait1]
        self.dispatch_blocks = dispatch_blocks
        # previous step's device_end (finish order == commit order ==
        # dispatch order), feeding the host_gap computation
        self._last_device_end: Optional[float] = None
        # when the previous step's results reached the host
        self._last_wait_end: Optional[float] = None
        # seconds a token step of the recent waves the host had to wait
        # for (WAITED_MIN_S): the depth rule's denominator
        self._token_steps: deque[float] = deque(maxlen=DEPTH_WINDOW)
        # device seconds of the recent mixed steps that carried running
        # rows and that the host had to wait for, by flat-token bucket:
        # what a running row pays for riding a step of that size
        # (stall_bucket); when each bucket was last read; and the
        # buckets last found over the limit
        self._bucket_steps: dict[int, deque[float]] = {}
        self._bucket_seen: dict[int, float] = {}
        self._over: set[int] = set()

    # ------------------------------------------------------ stamp helpers

    @staticmethod
    def _stamps(prepared) -> Optional[_Stamps]:  # noqa: ANN001
        return getattr(prepared, "_steptime", None)

    def stamp_plan(self, prepared, *, t_enter: float, t_sched: float,  # noqa: ANN001
                   drain_s: float = 0.0, chained: bool = False) -> None:
        """End of the plan phase (engine lock held, after prepare_*)."""
        if prepared is None:
            return
        st = _Stamps()
        st.t_enter = t_enter
        st.t_sched = t_sched
        st.t_prep = time.perf_counter()
        st.drain_s = drain_s
        st.chained = chained
        st.compiles = _compiles()
        prepared._steptime = st  # noqa: SLF001 — same carrier as _obs_plan_t0
        return

    def begin_dispatch(self, prepared) -> None:  # noqa: ANN001
        st = self._stamps(prepared)
        if st is not None:
            st.t_disp0 = time.perf_counter()

    def end_dispatch(self, prepared, *, sync: bool = False,  # noqa: ANN001
                     compile_fn: Optional[str] = None) -> None:
        st = self._stamps(prepared)
        if st is not None:
            st.t_disp1 = time.perf_counter()
            st.sync = sync
            st.compile_fn = compile_fn

    def begin_wait(self, prepared) -> None:  # noqa: ANN001
        st = self._stamps(prepared)
        if st is not None:
            st.t_wait0 = time.perf_counter()

    def end_wait(self, prepared) -> None:  # noqa: ANN001
        st = self._stamps(prepared)
        if st is not None:
            st.t_wait1 = time.perf_counter()

    # ----------------------------------------------------------- finalize

    def finish(self, prepared, *, step: int, replica: int, kind: str,  # noqa: ANN001
               tokens: int, fill_ratio: float, steps: int = 1,
               bucket: Optional[int] = None,
               running: int = 0) -> Optional[StepRecord]:
        """Commit boundary: close the record, feed the metrics, append
        to the ring.  ``bucket`` is a mixed step's flat-token bucket,
        ``running`` the rows that rode it for one token.  Returns the
        record (tests) or None when stamps are missing/incomplete."""
        st = self._stamps(prepared)
        if st is None:
            return None
        t_end = time.perf_counter()
        if st.t_disp0 is None:
            # pure-sync step() path never dispatched separately: the
            # execute window was stamped as the wait window
            st.t_disp0 = st.t_disp1 = st.t_wait0
        required = (st.t_enter, st.t_sched, st.t_prep, st.t_disp1,
                    st.t_wait0, st.t_wait1)
        if any(v is None for v in required):
            return None
        if st.sync:
            device_start, device_end = st.t_wait0, st.t_wait1
        elif self.dispatch_blocks:
            device_start, device_end = st.t_disp0, st.t_disp1
        else:
            device_start, device_end = st.t_disp1, st.t_wait1
        gap = 0.0
        busy_from = device_start
        if self._last_device_end is not None:
            raw = device_start - self._last_device_end
            if 0.0 < raw <= IDLE_CUTOFF_S:
                gap = min(raw, GAP_CAP_S)
            # enqueued behind its predecessor: the device took it up
            # when that one ended
            busy_from = max(device_start, self._last_device_end)
        turn = None
        if st.chained and self._last_wait_end is not None:
            turn = st.t_wait0 - self._last_wait_end
        record = StepRecord(
            step=step, replica=replica, kind=kind, tokens=tokens,
            fill_ratio=fill_ratio, stamps=st, t_end=t_end,
            wall_end=time.time(), host_gap_s=gap, steps=max(1, steps),
            device_s=max(0.0, device_end - busy_from), turn_s=turn,
            bucket=bucket, running=running,
            compiled=st.compiles is not None
            and (_compiles() or 0) > st.compiles,
        )
        if not record.compiled and (
            st.sync or self.dispatch_blocks
            or st.t_wait1 - st.t_wait0 >= WAITED_MIN_S
        ):
            if kind == "decode-wave":
                self._token_steps.append(record.device_s / record.steps)
            elif bucket is not None and running:
                self._bucket_steps.setdefault(
                    bucket, deque(maxlen=DEPTH_WINDOW)
                ).append(record.device_s)
                self._bucket_seen[bucket] = t_end
        self._last_device_end = device_end
        self._last_wait_end = st.t_wait1
        self._ring.append(record)
        self._observe(record)
        return record

    def _observe(self, record: StepRecord) -> None:
        try:
            from vllm_tgis_adapter_tpu import metrics

            rep = str(record.replica)
            for phase, seconds in record.phases().items():
                metrics.step_anatomy_seconds.labels(
                    phase=phase, replica=rep
                ).observe(max(0.0, seconds))
            metrics.host_gap_frac.labels(rep).set(self.host_gap_frac())
        except Exception:  # pragma: no cover — metrics are best-effort
            logger.debug("step anatomy observation failed", exc_info=True)

    # ------------------------------------------------------------- reads

    def __len__(self) -> int:
        return len(self._ring)

    def last_records(self, n: int) -> list[StepRecord]:
        if n <= 0:
            return []
        return list(self._ring)[-n:]

    def host_gap_frac(self, window: Optional[int] = None) -> float:
        """Window fraction of step wall the device idled on the host —
        the ``host_gap_frac{replica}`` gauge and the doctor's
        ``host_bound`` input."""
        records = self.last_records(window or self.window)
        wall = sum(r.wall_s for r in records)
        if wall <= 0:
            return 0.0
        return sum(r.host_gap_s for r in records) / wall

    def wave_depth(self, ceiling: int,
                   window: int = DEPTH_WINDOW) -> int:
        """Token steps a decode wave should fuse so that the device is
        still busy when the host's turn ends: ``ceil(host turn / token
        step)``, at least 1 and at most ``ceiling``.  Both are medians
        (one compile, one stalled turn or one deep wave moves neither):
        the turn (``turn_s``) over the last ``window`` dispatches, the
        token step (``device_s / steps``) over the last ``window`` waves
        that compiled nothing and whose results the host had to wait
        for — while the host is the slower of the two, the host's clock
        shows its own turn between two results and not the device's
        work, so those waves say nothing about the token step and the
        last ones that did stand.
        With nothing measured yet the ceiling stands, as in the serial
        order (and its deep waves are the first to be measured)."""
        records = self.last_records(window)
        if not records or not self._token_steps:
            return ceiling
        turn = statistics.median(r.turn_s for r in records)
        token_step = statistics.median(self._token_steps)
        if token_step <= 0.0:
            return ceiling
        return max(1, min(ceiling, math.ceil(turn / token_step)))

    def stall_bucket(self, ladder: list[int]) -> int:
        """The largest flat-token bucket of ``ladder`` (ascending) a
        mixed step may take while rows are running: the last one before
        the first whose median device time passes ``STALL_MULTIPLE``
        token steps.  Medians over the last ``DEPTH_WINDOW`` mixed steps
        of a bucket that carried running rows and token steps of a wave,
        each only where the host had to wait for the results and no
        program compiled (as for ``wave_depth``).  A bucket with fewer
        than ``STALL_MIN_SAMPLES`` readings counts as within — its first
        uses measure it — and so does everything while the token step
        has as few.  A bucket once over comes back at
        ``STALL_HYSTERESIS`` under the limit, and one that no step has
        read for ``STALL_REPROBE_S`` is returned itself, for the one
        step that reads it again."""
        if len(self._token_steps) < STALL_MIN_SAMPLES:
            return ladder[-1]
        limit = STALL_MULTIPLE * statistics.median(self._token_steps)
        within = ladder[0]
        for bucket in ladder:
            seen = self._bucket_steps.get(bucket)
            if seen is not None and len(seen) >= STALL_MIN_SAMPLES:
                was = bucket in self._over
                cost = statistics.median(seen)
                over = cost > (
                    limit * (1.0 - STALL_HYSTERESIS) if was else limit)
                if over != was:
                    self._over.symmetric_difference_update((bucket,))
                    logger.info(
                        "stall rule: a mixed step of %d tokens costs %.2f "
                        "token steps: %s rows running", bucket,
                        cost * STALL_MULTIPLE / limit,
                        "shut to steps with" if over else "open again to",
                    )
                if over:
                    idle = time.perf_counter() - self._bucket_seen[bucket]
                    return bucket if idle >= STALL_REPROBE_S else within
            within = bucket
        return within

    def records(self, last_n: Optional[int] = None) -> list[dict]:
        items = list(self._ring)
        if last_n is not None:
            items = items[-last_n:]
        return [r.to_dict() for r in items]

    def debug_state(self, last_n: int = 128) -> dict:
        return {
            "steps": len(self._ring),
            "window": self.window,
            "host_gap_frac": round(self.host_gap_frac(), 4),
            "records": self.records(last_n),
        }
