"""XLA recompile tracker: makes every compile-cache miss observable.

On TPU a fresh XLA/Mosaic compile costs 20-40s of serving latency, so the
whole scheduler is built around bounded shape buckets (SURVEY.md §7).
This module closes the loop: each jitted entry point the runner dispatches
through is wrapped so a compile-cache miss is recorded as

* ``tgis_tpu_xla_recompile_total{fn, shape}`` — which program compiled and
  the (bucket, batch, steps) shape that triggered it,
* ``tgis_tpu_xla_compile_seconds`` — how long the compiling dispatch took,
* one WARNING log line per novel shape — a shape appearing *after* warmup
  means the bucket discipline leaked.

Miss detection uses the jitted function's executable-cache size
(``PjitFunction._cache_size``), which has been stable across JAX releases;
when a runtime does not expose it the wrapper degrades to a transparent
passthrough rather than guessing.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

from vllm_tgis_adapter_tpu import metrics
from vllm_tgis_adapter_tpu.logging import init_logger

logger = init_logger(__name__)

# process-global view across engines/replicas (dp replicas share one
# metrics registry anyway); guarded because step loops run in worker
# threads
_lock = threading.Lock()
_seen_shapes: set[tuple[str, str]] = set()
_total_recompiles = 0
_total_compile_seconds = 0.0

# dispatches currently executing inside a tracked jit entry point,
# token -> (fn_name, monotonic entry time).  The stall watchdog
# (watchdog.py) reads this to tell "the loop is hung" apart from "the
# runtime is grinding through a 20-40s Mosaic compile": while a tracked
# dispatch is in flight the stall deadline is suspended (bounded by the
# watchdog's compile grace).
_inflight: dict[int, tuple[str, float]] = {}
_next_token = 0


def begin_dispatch(fn_name: str) -> int:
    """Mark a tracked dispatch as in flight; returns the token for
    ``end_dispatch``.  Public so watchdog tests can simulate a compile
    in flight without a real device."""
    global _next_token
    with _lock:
        _next_token += 1
        token = _next_token
        _inflight[token] = (fn_name, time.monotonic())
    return token


def end_dispatch(token: int) -> None:
    with _lock:
        _inflight.pop(token, None)


def inflight_dispatch() -> Optional[tuple[str, float]]:
    """(fn_name, age_seconds) of the OLDEST tracked dispatch still
    executing, or None when the runtime is idle at the jit boundary."""
    with _lock:
        if not _inflight:
            return None
        name, t0 = min(_inflight.values(), key=lambda v: v[1])
    return name, time.monotonic() - t0


def record_compile(fn_name: str, shape: str, seconds: float) -> None:
    """Fold one observed compile into the counters (also the hook tests
    and non-jit compile sites can feed directly)."""
    global _total_recompiles, _total_compile_seconds
    with _lock:
        novel = (fn_name, shape) not in _seen_shapes
        if novel:
            _seen_shapes.add((fn_name, shape))
            metrics.xla_compiled_shapes.set(len(_seen_shapes))
            # compile-count-by-backend: ragged entry points are named
            # ragged_* by the runner, so the data-path split needs no
            # extra plumbing (docs/ATTENTION.md expected counts)
            backend = (
                "ragged" if fn_name.startswith("ragged_") else "bucketed"
            )
            metrics.xla_compiled_shapes_by_backend.labels(
                backend=backend
            ).set(sum(
                1 for fn, _ in _seen_shapes
                if fn.startswith("ragged_") == (backend == "ragged")
            ))
        _total_recompiles += 1
        _total_compile_seconds += seconds
    metrics.xla_recompile_total.labels(fn=fn_name, shape=shape).inc()
    metrics.xla_compile_seconds.observe(seconds)
    if novel:
        logger.warning(
            "XLA compiled novel shape: fn=%s shape=%s (%.2fs); shapes "
            "appearing after warmup mean a bucket leak",
            fn_name, shape, seconds,
        )


def num_shapes() -> int:
    with _lock:
        return len(_seen_shapes)


def shapes() -> set[tuple[str, str]]:
    """Snapshot of the distinct (fn, shape) programs compiled since boot
    (per-entry-point compile-discipline assertions, e.g. the KV tier's
    fixed-block-shape gather/scatter gate in tests/test_kv_tier.py)."""
    with _lock:
        return set(_seen_shapes)


def total_recompiles() -> int:
    with _lock:
        return _total_recompiles


def total_compile_seconds() -> float:
    """Wall seconds of every compiling dispatch since boot (each
    includes that dispatch's enqueue, not its device time)."""
    with _lock:
        return _total_compile_seconds


def reset() -> None:
    """Test hook: forget seen shapes (Prometheus counters keep history)."""
    global _total_recompiles, _total_compile_seconds
    with _lock:
        _seen_shapes.clear()
        _total_recompiles = 0
        _total_compile_seconds = 0.0
        _inflight.clear()


def track_jit(
    name: str,
    fn: Callable,
    label: Optional[Callable[[tuple, dict], str]] = None,
) -> Callable:
    """Wrap a jitted callable so cache misses are recorded.

    ``label(args, kwargs)`` renders the dispatch-shape label for a miss
    (e.g. ``"tokens=512"``); it runs only when a compile actually
    happened, so it can be as lazy as it likes.  Without a usable cache
    probe the original function is returned unchanged.
    """
    cache_size = getattr(fn, "_cache_size", None)
    if cache_size is None:
        logger.debug(
            "jit cache probe unavailable for %s; recompile tracking off",
            name,
        )
        return fn

    def tracked(*args, **kwargs):  # noqa: ANN002, ANN003, ANN202
        before = cache_size()
        t0 = time.perf_counter()
        token = begin_dispatch(name)
        try:
            out = fn(*args, **kwargs)
        finally:
            end_dispatch(token)
        if cache_size() > before:
            shape = ""
            if label is not None:
                try:
                    shape = label(args, kwargs)
                except Exception:  # noqa: BLE001 — telemetry must not raise
                    shape = "?"
            record_compile(name, shape, time.perf_counter() - t0)
        return out

    return tracked
