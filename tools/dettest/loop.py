"""``DetLoop``: a deterministic, seeded asyncio event loop on virtual time.

The real selector loop runs co-ready callbacks in FIFO order, so one
process run explores exactly one interleaving.  ``DetLoop`` is an
``asyncio.AbstractEventLoop`` whose *only* nondeterminism source is an
injected :class:`Chooser`: whenever more than one callback is ready, the
chooser picks which runs next.  A :class:`SeededChooser` draws from a
seeded PRNG (K seeds = K schedules); a :class:`TraceChooser` replays a
recorded schedule exactly (a race is a reproducible artifact, not a
flake); a :class:`PrefixChooser` drives the explorer's bounded
co-ready-permutation DFS.

Time is virtual: ``loop.time()`` only advances when the ready set is
empty, jumping straight to the earliest timer — ``sleep``/``wait_for``/
TTL timeouts cost zero wall-clock.  ``run_in_executor`` (and therefore
``asyncio.to_thread``) schedules the function as an ordinary loop
callback instead of a worker thread, so thread-offloaded sections are
single-threaded, deterministic, and *visible to the chooser* as
schedule points — exactly the suspension points where production races
live.

Scheduling decisions with a single ready callback are forced and not
recorded; the recorded trace is the list of genuine ``(n_ready,
chosen_index, label)`` choices, which is the schedule's identity for
distinct-schedule counting and byte-for-byte replay.
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import heapq
import random
import time as _time
from typing import Any, Callable, Optional

__all__ = [
    "Chooser",
    "DeadlockError",
    "DetLoop",
    "HangError",
    "PrefixChooser",
    "ReplayDivergence",
    "SeededChooser",
    "TraceChooser",
    "det_run",
    "format_trace",
    "virtual_wall_clock",
]

# livelock guards: a scenario that spins past either bound is a bug in
# the scenario (or a genuine livelock) — fail loudly instead of hanging
# the gate
DEFAULT_MAX_STEPS = 200_000
DEFAULT_TIME_LIMIT_S = 600.0


class DeadlockError(RuntimeError):
    """Ready set and timer heap both empty with work still pending."""


class HangError(RuntimeError):
    """Virtual time or step budget exhausted (livelock guard)."""


class ReplayDivergence(RuntimeError):
    """A trace replay saw a different ready-set shape than recorded —
    the scenario is not deterministic for its seed."""


# --------------------------------------------------------------- choosers


class Chooser:
    """Schedule oracle: ``choose(n, labels)`` picks which of the ``n``
    co-ready callbacks runs next.  Every genuine choice (n > 1) is
    appended to ``trace`` as ``(n, index, label)``."""

    def __init__(self) -> None:
        self.trace: list[tuple[int, int, str]] = []

    def choose(self, n: int, labels: list[str]) -> int:  # pragma: no cover
        raise NotImplementedError

    def _record(self, n: int, idx: int, labels: list[str]) -> int:
        self.trace.append((n, idx, labels[idx]))
        return idx


class SeededChooser(Chooser):
    """Uniform choice from a seeded PRNG: one seed, one schedule."""

    def __init__(self, seed: int):
        super().__init__()
        self.seed = seed
        self._rng = random.Random(seed)

    def choose(self, n: int, labels: list[str]) -> int:
        return self._record(n, self._rng.randrange(n), labels)


class TraceChooser(Chooser):
    """Replay a recorded trace exactly; raise on any divergence."""

    def __init__(self, trace: list[tuple[int, int, str]]):
        super().__init__()
        self._replay = list(trace)
        self._pos = 0

    def choose(self, n: int, labels: list[str]) -> int:
        if self._pos >= len(self._replay):
            raise ReplayDivergence(
                f"trace exhausted at choice {self._pos}: live run has an "
                f"extra {n}-way choice over {labels}"
            )
        rec_n, rec_idx, rec_label = self._replay[self._pos]
        self._pos += 1
        if rec_n != n or rec_idx >= n:
            raise ReplayDivergence(
                f"choice {self._pos - 1}: recorded {rec_n}-way pick of "
                f"{rec_label!r}, live run offers {n}-way {labels}"
            )
        return self._record(n, rec_idx, labels)


class PrefixChooser(Chooser):
    """DFS driver for the bounded co-ready-permutation mode: follow a
    fixed choice prefix, then always pick index 0.  The explorer
    backtracks by bumping the last non-exhausted prefix position."""

    def __init__(self, prefix: list[int]):
        super().__init__()
        self._prefix = list(prefix)
        self._pos = 0
        # (n, idx) actually taken at each choice — the backtrack input
        self.taken: list[tuple[int, int]] = []

    def choose(self, n: int, labels: list[str]) -> int:
        idx = self._prefix[self._pos] if self._pos < len(self._prefix) else 0
        self._pos += 1
        if idx >= n:
            raise ReplayDivergence(
                f"DFS prefix position {self._pos - 1} wants index {idx} "
                f"but only {n} callbacks are ready — scenario is not "
                "deterministic across runs"
            )
        self.taken.append((n, idx))
        return self._record(n, idx, labels)


def format_trace(trace: list[tuple[int, int, str]]) -> str:
    """Canonical byte-stable rendering of a schedule trace (the
    acceptance criterion's byte-for-byte replay comparison)."""
    return ";".join(f"{n}:{idx}:{label}" for n, idx, label in trace)


# ------------------------------------------------------------------- loop


def _callback_label(callback: Callable) -> str:
    """Deterministic, address-free display name for a ready callback."""
    owner = getattr(callback, "__self__", None)
    if isinstance(owner, asyncio.Task):
        return owner.get_name()
    if isinstance(owner, asyncio.Future):
        return "future"
    if isinstance(callback, functools.partial):
        inner = callback.func
        # asyncio.to_thread wraps as partial(context.run, func, ...)
        if getattr(inner, "__name__", "") == "run" and callback.args:
            inner = callback.args[0]
            if isinstance(inner, functools.partial):
                inner = inner.func
        return getattr(inner, "__qualname__", None) or type(inner).__name__
    return (
        getattr(callback, "__qualname__", None)
        or getattr(callback, "__name__", None)
        or type(callback).__name__
    )


class DetLoop(asyncio.AbstractEventLoop):
    """Deterministic event loop: single-threaded, seeded, virtual-time.

    Supports exactly the surface the control plane uses — ``call_soon``
    / ``call_later`` / ``call_at``, tasks, futures, and an inline
    ``run_in_executor`` — and deliberately nothing selector-based (no
    sockets, no signals, no subprocesses): scenarios exercise host-side
    state machines, not I/O.
    """

    def __init__(
        self,
        chooser: Optional[Chooser] = None,
        *,
        max_steps: int = DEFAULT_MAX_STEPS,
        time_limit: float = DEFAULT_TIME_LIMIT_S,
    ):
        self.chooser = chooser if chooser is not None else SeededChooser(0)
        self.max_steps = max_steps
        self.time_limit = time_limit
        self._time = 0.0
        self._ready: list[asyncio.Handle] = []
        self._timers: list[tuple[float, int, asyncio.TimerHandle]] = []
        self._tiebreak = 0  # FIFO order within one timer deadline
        self._task_counter = 0  # deterministic default task names
        self._steps = 0
        self._running = False
        self._stopping = False
        self._closed = False
        self._debug = False
        #: contexts passed to call_exception_handler during the run
        #: (unretrieved task exceptions, callback failures)
        self.exceptions: list[dict] = []
        #: every task made on this loop: ``det_run`` asks each, when
        #: the run is over, whether it died with nobody looking
        self._tasks: list[asyncio.Task] = []

    # ------------------------------------------------------------- clock

    def time(self) -> float:
        return self._time

    # --------------------------------------------------------- callbacks

    def _check_closed(self) -> None:
        if self._closed:
            raise RuntimeError("DetLoop is closed")

    def call_soon(self, callback, *args, context=None):  # noqa: ANN001, ANN002
        self._check_closed()
        handle = asyncio.Handle(callback, args, self, context)
        self._ready.append(handle)
        return handle

    # single-threaded by construction (run_in_executor is inline), so
    # threadsafe scheduling is ordinary scheduling
    call_soon_threadsafe = call_soon

    def call_later(self, delay, callback, *args, context=None):  # noqa: ANN001, ANN002
        return self.call_at(
            self._time + max(0.0, delay), callback, *args, context=context
        )

    def call_at(self, when, callback, *args, context=None):  # noqa: ANN001, ANN002
        self._check_closed()
        handle = asyncio.TimerHandle(when, callback, args, self, context)
        self._tiebreak += 1
        heapq.heappush(self._timers, (when, self._tiebreak, handle))
        return handle

    def _timer_handle_cancelled(self, handle) -> None:  # noqa: ANN001
        pass  # cancelled handles are skipped at pop time

    # ----------------------------------------------------- futures/tasks

    def create_future(self) -> asyncio.Future:
        return asyncio.Future(loop=self)

    def create_task(self, coro, *, name=None, context=None):  # noqa: ANN001
        return self._new_task(coro, name=name)

    def _new_task(self, coro, name=None):  # noqa: ANN001
        self._check_closed()
        task = asyncio.Task(coro, loop=self, name=name)
        if name is None:
            # override CPython's process-global Task-N counter with a
            # per-loop one: labels (and so traces) must not depend on
            # how many tasks earlier runs created
            self._task_counter += 1
            task.set_name(f"dtask-{self._task_counter}")
        self._tasks.append(task)
        return task

    def note_unretrieved_task_exceptions(self) -> None:
        """What ``Task.__del__`` would report, without waiting for the
        collector: a finished task is often held by a cycle through its
        exception's traceback, so its ``__del__`` runs at some later
        collection and not before ``det_run`` has looked."""
        for task in self._tasks:
            if (
                task.done()
                and not task.cancelled()
                and task._log_traceback  # noqa: SLF001 — set iff never retrieved
            ):
                self.exceptions.append({
                    "message": "Task exception was never retrieved",
                    "exception": task.exception(),  # marks it retrieved
                    "task": task,
                })

    def run_in_executor(self, executor, func, *args):  # noqa: ANN001, ANN002
        """Run ``func`` as a loop callback instead of a worker thread:
        deterministic, and a genuine schedule point the chooser can
        reorder against other ready work (where to_thread races live)."""
        self._check_closed()
        future = self.create_future()

        def _invoke() -> None:
            try:
                result = func(*args)
            except BaseException as exc:  # noqa: BLE001 — routed to the awaiter
                if not future.cancelled():
                    future.set_exception(exc)
            else:
                if not future.cancelled():
                    future.set_result(result)

        _invoke.__qualname__ = f"executor:{_callback_label(func)}"
        self.call_soon(_invoke)
        return future

    # ----------------------------------------------------------- running

    def is_running(self) -> bool:
        return self._running

    def is_closed(self) -> bool:
        return self._closed

    def stop(self) -> None:
        self._stopping = True

    def close(self) -> None:
        if self._running:
            raise RuntimeError("cannot close a running DetLoop")
        self._closed = True
        self._ready.clear()
        self._timers.clear()

    async def shutdown_asyncgens(self) -> None:
        pass

    async def shutdown_default_executor(self) -> None:
        pass

    def run_until_complete(self, future):  # noqa: ANN001
        self._check_closed()
        if asyncio.iscoroutine(future):
            future = self._new_task(future, name="det-main")
        if not asyncio.isfuture(future):
            raise TypeError(f"coroutine or Future required, got {future!r}")

        def _done(_fut) -> None:  # noqa: ANN001
            self.stop()

        future.add_done_callback(_done)
        try:
            self.run_forever()
        finally:
            future.remove_done_callback(_done)
        if not future.done():
            raise DeadlockError(
                "ready set and timer heap drained with the main future "
                "still pending — tasks are deadlocked on each other"
            )
        return future.result()

    def run_forever(self) -> None:
        self._check_closed()
        if self._running:
            raise RuntimeError("DetLoop is already running")
        self._running = True
        self._stopping = False
        asyncio.events._set_running_loop(self)  # noqa: SLF001 — the loop-runner contract
        try:
            while not self._stopping:
                if not self._run_once():
                    break
        finally:
            asyncio.events._set_running_loop(None)  # noqa: SLF001
            self._running = False

    # one scheduling step; False = nothing left to run
    def _run_once(self) -> bool:
        self._steps += 1
        if self._steps > self.max_steps:
            raise HangError(
                f"DetLoop exceeded {self.max_steps} steps at virtual "
                f"time {self._time:.3f}s — livelock in the scenario"
            )
        self._ready = [h for h in self._ready if not h.cancelled()]
        if not self._ready:
            if not self._advance_to_next_timer():
                return False
            self._ready = [h for h in self._ready if not h.cancelled()]
            if not self._ready:
                return True  # popped timers were all cancelled
        self._pump_due_timers()
        n = len(self._ready)
        if n == 1:
            handle = self._ready.pop(0)  # forced: not a choice
        else:
            labels = [_callback_label(h._callback) for h in self._ready]  # noqa: SLF001
            handle = self._ready.pop(self.chooser.choose(n, labels))
        handle._run()  # noqa: SLF001 — the loop-runner contract
        return True

    def _pump_due_timers(self) -> None:
        while self._timers and self._timers[0][0] <= self._time:
            _, _, handle = heapq.heappop(self._timers)
            if not handle.cancelled():
                self._ready.append(handle)

    def _advance_to_next_timer(self) -> bool:
        while self._timers:
            when, _, handle = heapq.heappop(self._timers)
            if handle.cancelled():
                continue
            if when > self.time_limit:
                raise HangError(
                    f"DetLoop virtual time would pass {self.time_limit}s "
                    f"(next timer at {when:.3f}s) — the scenario is "
                    "waiting on something that never happens"
                )
            self._time = max(self._time, when)
            self._ready.append(handle)
            return True
        return False

    def drain_pending(self) -> None:
        """Cancel every still-pending task and run them to completion —
        scenarios end with a quiet loop, so no nondeterministic
        GC-time "task was destroyed pending" noise survives a run."""
        for _ in range(64):  # cancellation can spawn cleanup tasks
            pending = [
                t for t in asyncio.all_tasks(self) if not t.done()
            ]
            if not pending:
                return
            for task in pending:
                task.cancel()
            gather = asyncio.gather(*pending, return_exceptions=True)
            self.run_until_complete(gather)

    # -------------------------------------------------------- diagnostics

    def get_debug(self) -> bool:
        return self._debug

    def set_debug(self, enabled: bool) -> None:
        self._debug = enabled

    def default_exception_handler(self, context) -> None:  # noqa: ANN001
        self.exceptions.append(context)

    def call_exception_handler(self, context) -> None:  # noqa: ANN001
        self.exceptions.append(context)


# ---------------------------------------------------------- wall clock


@contextlib.contextmanager
def virtual_wall_clock(loop: DetLoop):
    """Patch ``time.time``/``time.monotonic`` to follow the loop's
    virtual clock (each keeps its own base).  Admission TTLs and queue
    ages read ``time.time`` and LRU/throughput state reads
    ``time.monotonic`` — under exploration both must advance with
    virtual sleeps, not the wall.  ``perf_counter`` and the ``*_ns``
    stamps stay real (they feed logs/metrics, never control flow)."""
    wall_base = _time.time()
    mono_base = _time.monotonic()
    real_time, real_mono = _time.time, _time.monotonic
    _time.time = lambda: wall_base + loop.time()
    _time.monotonic = lambda: mono_base + loop.time()
    try:
        yield
    finally:
        _time.time = real_time
        _time.monotonic = real_mono


# ----------------------------------------------------------------- runner


def det_run(
    main_factory: Callable[[], Any],
    *,
    chooser: Optional[Chooser] = None,
    seed: int = 0,
    max_steps: int = DEFAULT_MAX_STEPS,
    time_limit: float = DEFAULT_TIME_LIMIT_S,
) -> tuple[Any, list[tuple[int, int, str]]]:
    """Run ``main_factory()`` (a coroutine factory) to completion on a
    fresh ``DetLoop`` under a virtual wall clock.  Returns ``(result,
    schedule_trace)``.  Unhandled exceptions from background callbacks
    or tasks re-raise after the main coroutine finishes — a scenario
    whose spawned task died must fail, not pass silently."""
    if chooser is None:
        chooser = SeededChooser(seed)
    loop = DetLoop(chooser, max_steps=max_steps, time_limit=time_limit)
    try:
        with virtual_wall_clock(loop):
            result = loop.run_until_complete(main_factory())
            loop.drain_pending()
    finally:
        loop.close()
    loop.note_unretrieved_task_exceptions()
    fatal = [
        ctx
        for ctx in loop.exceptions
        if not isinstance(ctx.get("exception"), asyncio.CancelledError)
    ]
    if fatal:
        first = fatal[0]
        exc = first.get("exception")
        raise RuntimeError(
            f"unhandled exception in background callback/task: "
            f"{first.get('message', '')} ({type(exc).__name__ if exc else '?'}: {exc})"
        ) from exc
    return result, chooser.trace
