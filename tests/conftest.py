"""Test session setup.

Force the JAX CPU backend with 8 virtual devices so the whole suite
(including SPMD mesh tests) runs on CPU-only CI — the capability the
reference lacks entirely (its CI compiles vLLM for CPU but has no
distributed tests, SURVEY.md §4).

Both JAX_PLATFORMS and XLA_FLAGS are read at backend initialisation, so
they are set here before jax is imported.
"""

from __future__ import annotations

import os
from pathlib import Path

# RUN_TPU_TESTS=1 keeps the real backend so `pytest -m tpu` compiles the
# Pallas kernels through Mosaic on hardware (tests/test_tpu_kernels.py) —
# the gate that interpreter-mode parity structurally cannot provide
_TPU_RUN = os.environ.get("RUN_TPU_TESTS") == "1"

# Step-boundary invariant sanitizer (engine/sanitizer.py): on for the
# WHOLE tier-1 suite, so every existing test doubles as an invariant
# test over allocator/arena/tier/pool accounting.  setdefault so a
# developer can still run with TGIS_TPU_SANITIZE=0 to bisect whether a
# failure is the bug itself or the sanitizer tripping on it.
os.environ.setdefault("TGIS_TPU_SANITIZE", "1")

if not _TPU_RUN:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import faulthandler  # noqa: E402
import hashlib  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# ------------------------------------------------------ per-test time limit
#
# One limit for every test, its fixtures' set-up and teardown included.
# The slowest honest test of the suite takes 33 s under the driver's
# command (six workers on eight cores; CHANGES.md PR 26 has the measured
# list), so a test past the limit is hung, not slow: it fails alone,
# with the place it hung in its report, and the worker goes on.
TEST_TIME_LIMIT_S = 120.0
#: past the limit by this much the interpreter never came back to run
#: the handler (a hang inside a C call): the worker dumps and exits, and
#: xdist reports the item as failed and starts another worker
HARD_EXIT_AFTER_S = 60.0
#: set in xdist workers.  ``--dist loadfile`` hands the file of a worker
#: that died to the next one *from the item that killed it* (xdist 3.8,
#: ``LoadScopeScheduling.remove_node``), so an item in flight leaves a
#: file behind and is failed at once when it is met again: one worker
#: lost to a hard hang, not one for each restart xdist allows
_XDIST_RUN = os.environ.get("PYTEST_XDIST_TESTRUNUID")


class TestTimeLimitExceeded(Exception):
    __test__ = False  # not a test class, whatever its name starts with


def _in_flight_marker(item) -> Path:
    name = hashlib.sha1(item.nodeid.encode()).hexdigest()  # noqa: S324
    return Path(tempfile.gettempdir()) / f"in-flight-{_XDIST_RUN}-{name}"


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if threading.current_thread() is not threading.main_thread():
        yield  # signals reach the main thread only
        return
    limit = TEST_TIME_LIMIT_S
    # between phases nothing is captured, so this is the real stderr:
    # what the last-resort dump writes must outlive the worker
    real_stderr = os.fdopen(os.dup(2), "w")
    marker = _in_flight_marker(item) if _XDIST_RUN else None
    if marker is not None:
        item.killed_a_worker = marker.exists()
        marker.touch()

    def on_limit(signum, frame):  # noqa: ANN001, ARG001
        # fd 2 is the captured one here: the dump lands in the item's
        # "Captured stderr" section, under the traceback of this raise
        faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        raise TestTimeLimitExceeded(
            f"{item.nodeid} ran past its {limit:.0f}s limit "
            "(every thread's stack is in the captured stderr)"
        )

    previous = signal.signal(signal.SIGALRM, on_limit)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(
        limit + HARD_EXIT_AFTER_S, exit=True, file=real_stderr
    )
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)
        real_stderr.close()
        if marker is not None:
            marker.unlink(missing_ok=True)


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    if getattr(item, "killed_a_worker", False):
        pytest.fail(
            f"{item.nodeid} hung past {TEST_TIME_LIMIT_S:.0f}s + "
            f"{HARD_EXIT_AFTER_S:.0f}s in an earlier worker of this run, "
            "which dumped its threads to stderr and exited",
            pytrace=False,
        )


@pytest.fixture(scope="session")
def tiny_model_dir(tmp_path_factory) -> str:
    """A tiny randomly-initialised llama-style model + tokenizer on disk."""
    from tests.fixture_models import build_tiny_llama

    path = tmp_path_factory.mktemp("tiny-llama")
    build_tiny_llama(str(path))
    return str(path)


def _build_args(argv: list[str]):
    """Run the REAL parser chain, as the reference's conftest does
    (conftest.py:80-98), instead of constructing a namespace by hand."""
    import sys

    from vllm_tgis_adapter_tpu.tgis_utils.args import (
        make_parser,
        postprocess_tgis_args,
    )

    old_argv = sys.argv
    sys.argv = ["__main__.py", *argv]
    try:
        return postprocess_tgis_args(make_parser().parse_args())
    finally:
        sys.argv = old_argv


@pytest.fixture(scope="session")
def adapter_cache_dir(tmp_path_factory) -> str:
    """Adapter cache with one real tiny-llama LoRA fixture + one non-LoRA
    peft dir (exercised as the unsupported-type path, like the
    reference's bloomz prompt-tuning fixture)."""
    import json

    from tests.fixture_models import build_tiny_lora_adapter

    cache = tmp_path_factory.mktemp("adapters")
    build_tiny_lora_adapter(str(cache / "tiny-lora"))
    prompt_dir = cache / "tiny-prompt-adapter"
    prompt_dir.mkdir()
    json.dump({"peft_type": "PROMPT_TUNING"},
              open(prompt_dir / "adapter_config.json", "w"))
    return str(cache)


def _require_pb() -> None:
    """Skip (don't error) when the protoc-generated gRPC bindings are
    unavailable: the pb package probes every pb2 module at import and
    regenerates stale ones, which needs protoc on PATH."""
    try:  # pragma: no cover - environment probe
        import vllm_tgis_adapter_tpu.grpc.pb  # noqa: F401
    except ImportError as e:
        pytest.skip(
            f"protoc-generated gRPC bindings unavailable ({e}); install "
            "protoc (or a wheel with prebuilt pb2 modules) to run the "
            "dual-server suites"
        )


@pytest.fixture(scope="session")
def server_args(tiny_model_dir, adapter_cache_dir):
    _require_pb()
    from tests.utils import get_random_port

    return _build_args(
        [
            "--model",
            tiny_model_dir,
            "--max-model-len",
            "512",
            "--dtype",
            "float32",
            "--grpc-port",
            str(get_random_port()),
            "--port",
            str(get_random_port()),
            "--max-num-seqs",
            "8",
            "--adapter-cache",
            adapter_cache_dir,
        ]
    )


@pytest.fixture(scope="session")
def _servers(server_args):
    """Boot the REAL dual-server stack (no mock engine) in a background
    thread's event loop, mirroring the reference's integration strategy."""
    _require_pb()
    import asyncio
    import threading
    import urllib.request
    from contextlib import suppress

    from tests.utils import GrpcClient, wait_until

    from vllm_tgis_adapter_tpu.__main__ import start_servers

    loop = asyncio.new_event_loop()
    server_task = None

    def target() -> None:
        nonlocal server_task
        asyncio.set_event_loop(loop)
        server_task = loop.create_task(start_servers(server_args))
        with suppress(asyncio.CancelledError):
            loop.run_until_complete(server_task)

    thread = threading.Thread(target=target, daemon=True)
    thread.start()

    def http_healthy() -> bool:
        with urllib.request.urlopen(
            f"http://localhost:{server_args.port}/health", timeout=5
        ) as resp:
            return resp.status == 200

    def grpc_healthy() -> bool:
        with GrpcClient("localhost", server_args.grpc_port) as client:
            return client.health_check()

    try:
        wait_until(http_healthy, timeout=300)
        wait_until(grpc_healthy, timeout=60)
        yield server_args
    finally:

        def cancel_all() -> None:
            for task in asyncio.all_tasks(loop):
                task.cancel()

        loop.call_soon_threadsafe(cancel_all)
        thread.join(timeout=60)
        if not loop.is_closed():
            loop.close()


@pytest.fixture
def grpc_client(_servers):
    from tests.utils import GrpcClient

    with GrpcClient("localhost", _servers.grpc_port) as client:
        yield client


@pytest.fixture
def http_base_url(_servers) -> str:
    return f"http://localhost:{_servers.port}"
