"""Small shared utilities (reference: utils.py:7-45).

Covers the failed-task scan used by the dual-server orchestrator, the
Kubernetes termination-log writer, and tiny sequence helpers.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from typing import TYPE_CHECKING, Optional

if TYPE_CHECKING:
    from collections.abc import Iterable, Sequence


#: Module-global strong-reference task set for :func:`spawn_task`.
#: The event loop holds only WEAK references to tasks (bpo-44665), so a
#: task whose handle is dropped can be garbage-collected mid-flight —
#: the PR 9 GC'd-promotion-task bug class: a lost transfer task parks
#: its request forever with no error anywhere.  Every task spawned
#: through spawn_task stays referenced here (or in the caller-provided
#: container) until it completes.
_BACKGROUND_TASKS: set = set()


def spawn_task(coro, *, name: Optional[str] = None, retain=None, loop=None):  # noqa: ANN001, ANN201
    """Create an asyncio task holding a STRONG reference until it is done.

    The one sanctioned ``create_task`` wrapper in this codebase (tpulint
    TPL502 enforces it): the returned task is retained in ``retain`` (any
    container with ``add``/``discard``; defaults to the module-global
    set) and discarded by a done callback, so it can never be
    garbage-collected mid-flight.  Callers that need the handle (cancel,
    await, staleness checks) keep the return value exactly as with
    ``create_task``.

    ``loop`` runs the task on an explicit (possibly not-yet-running)
    event loop — ``__main__``'s boot path; default is the running loop.
    """
    target = loop if loop is not None else asyncio.get_running_loop()
    task = target.create_task(coro, name=name)
    bucket = _BACKGROUND_TASKS if retain is None else retain
    bucket.add(task)
    task.add_done_callback(bucket.discard)
    return task


#: most that :func:`close_server` waits for open connections to go away
SERVER_STOP_TIMEOUT_S = 5.0


async def close_server(server, writers, name: str) -> None:  # noqa: ANN001
    """Stop an ``asyncio`` stream server without waiting on its peers:
    the listener, then every connection still open (``writers``), and
    only then a bounded wait.  From Python 3.12 ``Server.wait_closed()``
    waits for every accepted connection, so close-the-listener-and-wait
    (``serve_forever()``'s own cancel path too) holds a shutdown for as
    long as one idle peer stays connected."""
    server.close()
    for writer in list(writers):
        writer.close()
    try:
        await asyncio.wait_for(server.wait_closed(), SERVER_STOP_TIMEOUT_S)
    except asyncio.TimeoutError:
        logging.getLogger(__name__).warning(
            "%s: connections still open %.0fs after close; shutdown "
            "goes on without them", name, SERVER_STOP_TIMEOUT_S,
        )


def check_for_failed_tasks(tasks: Iterable[asyncio.Task]) -> Optional[asyncio.Task]:
    """Return the first task that finished with an exception, if any."""
    for task in tasks:
        try:
            if task.exception() is not None:
                return task
        except (asyncio.InvalidStateError, asyncio.CancelledError):
            continue
    return None


def write_termination_log(
    msg: str, file: str = "/dev/termination-log", *, append: bool = False
) -> None:
    """Record the cause of death where Kubernetes probes can read it.

    Mirrors the reference semantics (utils.py:20-41): silently skip when the
    file does not exist (not running under k8s), and never let logging errors
    mask the original failure.

    ``append`` preserves an earlier checkpoint in the same process — the
    final traceback write in ``__main__`` must not clobber the engine
    death report / restart history the supervisor already recorded.
    """
    if not os.path.exists(file):
        from .logging import DEFAULT_LOGGER_NAME, init_logger

        init_logger(DEFAULT_LOGGER_NAME).debug(
            "Not writing to termination log %s since it does not exist", file
        )
        return
    try:
        with open(file, "a" if append else "w") as f:
            f.write(f"{msg}\n")
    except Exception:
        from .logging import DEFAULT_LOGGER_NAME, init_logger

        init_logger(DEFAULT_LOGGER_NAME).exception(
            "Unable to write termination logs to %s", file
        )


def to_list(seq: "Sequence[int]") -> list[int]:
    return seq if isinstance(seq, list) else list(seq)


async def merge_async_iterators(*iterators):  # noqa: ANN001, ANN201
    """Merge async iterators into one stream of ``(index, item)`` pairs.

    The batched Generate RPC fans one engine stream per sub-request and
    consumes them as a single merged stream (the reference borrows vLLM's
    helper for this, grpc_server.py:274-276).  Cancellation propagates to
    every underlying iterator.
    """
    queue: asyncio.Queue = asyncio.Queue()
    done_sentinel = object()

    async def produce(i: int, iterator) -> None:  # noqa: ANN001
        try:
            async for item in iterator:
                await queue.put((i, item))
        except BaseException as e:  # noqa: BLE001 — forwarded to the consumer
            await queue.put(e)
        finally:
            # put_nowait: the queue is unbounded and this must run even
            # while this producer task is being cancelled
            queue.put_nowait(done_sentinel)

    tasks = [
        spawn_task(produce(i, iterator), name=f"merge-stream-{i}")
        for i, iterator in enumerate(iterators)
    ]
    try:
        remaining = len(tasks)
        while remaining:
            item = await queue.get()
            if item is done_sentinel:
                remaining -= 1
            elif isinstance(item, BaseException):
                raise item
            else:
                yield item
    finally:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)


class TTLCache:
    """Minimal dict-like cache with max size + per-entry TTL.

    Replacement for ``cachetools.TTLCache`` (not installed here), used as the
    correlation-ID blackboard (reference: tgis_utils/logs.py:29).  Expiry is
    enforced lazily on access and insertion; eviction is oldest-inserted-first
    once ``maxsize`` is reached.
    """

    def __init__(self, maxsize: int, ttl: float, timer=time.monotonic):
        self.maxsize = maxsize
        self.ttl = ttl
        self._timer = timer
        self._data: dict = {}  # key -> (expiry, value); insertion-ordered

    def _expire(self) -> None:
        now = self._timer()
        dead = [k for k, (exp, _) in self._data.items() if exp <= now]
        for k in dead:
            del self._data[k]

    def __setitem__(self, key, value) -> None:
        self._expire()
        self._data.pop(key, None)
        while len(self._data) >= self.maxsize:
            self._data.pop(next(iter(self._data)))
        self._data[key] = (self._timer() + self.ttl, value)

    def __getitem__(self, key):
        exp, value = self._data[key]
        if exp <= self._timer():
            del self._data[key]
            raise KeyError(key)
        return value

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def __contains__(self, key) -> bool:
        return self.get(key, _MISSING) is not _MISSING

    def __len__(self) -> int:
        self._expire()
        return len(self._data)


_MISSING = object()
