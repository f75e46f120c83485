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
DEPTH_WINDOW = 16
#: A wave's device seconds are known from the host's clock only where
#: the host had to wait for them: a wait that returns at once says the
#: device had finished earlier and idled since — the distance between
#: two such results is the host's turn, not the device's work.
WAITED_MIN_S = 0.001


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
        "compile_fn",
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


class StepRecord:
    """One finalized step's anatomy (see module docstring for the
    decomposition contract)."""

    __slots__ = (
        "step", "replica", "kind", "tokens", "fill_ratio", "chained",
        "sync", "t_enter", "t_sched", "t_prep", "t_disp1", "t_wait0",
        "t_wait1", "t_end", "wall_end", "host_gap_s", "drain_s",
        "compile_fn", "steps", "device_s", "turn_s",
    )

    def __init__(self, *, step: int, replica: int, kind: str,
                 tokens: int, fill_ratio: float, stamps: _Stamps,
                 t_end: float, wall_end: float,
                 host_gap_s: float, steps: int = 1,
                 device_s: float = 0.0,
                 turn_s: Optional[float] = None) -> None:
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
               tokens: int, fill_ratio: float,
               steps: int = 1) -> Optional[StepRecord]:
        """Commit boundary: close the record, feed the metrics, append
        to the ring.  Returns the record (tests) or None when stamps
        are missing/incomplete."""
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
        )
        if kind == "decode-wave" and (
            st.sync or self.dispatch_blocks
            or st.t_wait1 - st.t_wait0 >= WAITED_MIN_S
        ):
            self._token_steps.append(record.device_s / record.steps)
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
        whose results the host had to wait for — while the host is the
        slower of the two, the host's clock shows its own turn between
        two results and not the device's work, so those waves say
        nothing about the token step and the last ones that did stand.
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
