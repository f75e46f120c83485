"""Process entrypoint: boot the engine, run gRPC + HTTP servers together.

Same lifecycle contract as the reference (__main__.py:38-131): bind the
HTTP socket before engine boot, build ONE shared engine, wrap it with the
TGIS logging hooks, launch both servers as tasks, cancel the survivor when
either exits, re-raise the first failure, and record the cause of death in
the Kubernetes termination log.
"""

from __future__ import annotations

import asyncio
import os
import socket
import traceback
from typing import TYPE_CHECKING

from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
from vllm_tgis_adapter_tpu.http import build_http_server, run_http_server
from vllm_tgis_adapter_tpu.logging import init_logger
from vllm_tgis_adapter_tpu.tgis_utils import logs
from vllm_tgis_adapter_tpu.tgis_utils.args import (
    make_parser,
    postprocess_tgis_args,
)
from vllm_tgis_adapter_tpu.utils import (
    check_for_failed_tasks,
    spawn_task,
    write_termination_log,
)

if TYPE_CHECKING:
    import argparse

logger = init_logger(__name__)


class TaskFailedError(RuntimeError):
    pass


def create_server_socket(host: str | None, port: int) -> socket.socket:
    """Bind the HTTP port before the (slow) engine boot so probes can't
    race a half-started process (reference workaround, __main__.py:41-45)."""
    family = socket.AF_INET6 if host and ":" in host else socket.AF_INET
    sock = socket.socket(family=family, type=socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sock.bind((host or "", port))
    return sock


async def start_servers(args: "argparse.Namespace") -> None:
    level = getattr(args, "uvicorn_log_level", None)
    if level and level != "info":
        # flag name kept for reference compat; here it drives the HTTP
        # server module's own logger ("trace" maps below DEBUG)
        import logging as _logging

        _logging.getLogger("vllm_tgis_adapter_tpu.http").setLevel(
            5 if level == "trace" else level.upper()
        )
    sock = create_server_socket(args.host, args.port)

    if getattr(args, "jax_profiler_port", None):
        # device-level profiling story (SURVEY §5): TensorBoard/XProf
        # connects here to capture XLA/TPU traces of the live engine
        import jax

        jax.profiler.start_server(args.jax_profiler_port)
        logger.info(
            "jax.profiler server listening on port %d", args.jax_profiler_port
        )

    if getattr(args, "failpoints", None):
        # deliberate chaos-testing fault injection
        # (supervisor/failpoints.py; also via TGIS_TPU_FAILPOINTS) —
        # armed BEFORE engine boot so boot-path sites can fire too
        from vllm_tgis_adapter_tpu.supervisor import failpoints

        failpoints.arm(args.failpoints)

    engine = None
    drain = None
    tasks: list[asyncio.Task] = []
    drain_waiter: asyncio.Task | None = None
    dead_waiter: asyncio.Task | None = None
    loop = asyncio.get_running_loop()
    try:
        from vllm_tgis_adapter_tpu.engine.config import EngineConfig

        engine = AsyncLLMEngine.from_config(EngineConfig.from_args(args))
        if getattr(args, "enable_lora", False) and getattr(
            args, "lora_modules", None
        ):
            # static boot registration (name=path ...): adapters are
            # host-registered up front; device residency streams on
            # demand through the paged pool (docs/LORA.md)
            manager = engine.engine.lora_manager
            for spec in args.lora_modules:
                name, _, path = spec.partition("=")
                if not name or not path:
                    raise ValueError(
                        f"--lora-modules entry {spec!r} is not name=path"
                    )
                await manager.load_lora_adapter(name, path)
        if getattr(args, "precompile", None):
            # warm every serving shape BEFORE the servers bind: the
            # first real request then never pays a 20-40s TPU compile
            await engine.precompile(args.precompile)
        await engine.start()

        # uniform TGIS-style request logging for both servers
        logs.add_logging_wrappers(engine)

        # graceful drain (frontdoor/drain.py): SIGTERM stops admission
        # (health → DRAINING/503), in-flight generations finish up to
        # --drain-grace, the termination log is checkpointed, and only
        # then are the server tasks torn down
        from vllm_tgis_adapter_tpu.frontdoor.drain import DrainCoordinator

        drain = DrainCoordinator(
            engine,
            grace_s=engine.engine.config.frontdoor.drain_grace_s,
        )
        drain.install(loop)

        # imported at point of use, not module top: the pb2 modules
        # behind the gRPC server are protoc-generated, and a boot
        # failure BEFORE the servers (bad model path, config
        # validation) must still reach the termination log on hosts
        # without protoc — tests/test_termination_log.py exercises
        # exactly that
        from vllm_tgis_adapter_tpu.grpc.grpc_server import (
            run_grpc_server,
        )

        http_app = build_http_server(args, engine)

        tasks = [
            spawn_task(
                run_http_server(args, engine, http_app, sock),
                name="http_server", loop=loop,
            ),
            spawn_task(
                run_grpc_server(args, engine),
                name="grpc_server", loop=loop,
            ),
        ]

        with_task_names = ", ".join(t.get_name() for t in tasks)
        logger.info("Started tasks: %s", with_task_names)

        drain_waiter = spawn_task(
            drain.shutdown_event.wait(), name="drain_shutdown", loop=loop,
        )
        # terminal engine death (unsupervised, or the supervisor's
        # crash-loop circuit breaker) wakes this wait directly — the
        # process must exit promptly, not at the next RPC.  Supervised
        # restarts never set this: the engine recovers in place.
        dead_waiter = spawn_task(
            engine.dead_event.wait(), name="engine_dead", loop=loop,
        )
        done, _pending = await asyncio.wait(
            [*tasks, drain_waiter, dead_waiter],
            return_when=asyncio.FIRST_COMPLETED,
        )

        if drain_waiter in done:
            # drained to completion: this is the clean exit path — the
            # finally block cancels the (idle) servers
            logger.info("drain complete; shutting down servers")
            return

        if engine.errored:
            # surface the engine failure rather than a generic task error
            raise engine.dead_error

        for task in done:
            if (exception := task.exception()) is not None:
                raise TaskFailedError(
                    f"task {task.get_name()} failed"
                ) from exception
    finally:
        if drain is not None:
            drain.uninstall(loop)
        for waiter in (drain_waiter, dead_waiter):
            if waiter is not None and not waiter.done():
                waiter.cancel()
        for task in tasks:
            if not task.done():
                task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        if engine is not None:
            await engine.stop()
        sock.close()

    failed = check_for_failed_tasks(tasks)
    if failed is not None:
        raise TaskFailedError(f"task {failed.get_name()} failed") from (
            failed.exception()
        )


def run_and_catch_termination_cause(
    loop: asyncio.AbstractEventLoop, task: asyncio.Task
) -> None:
    try:
        loop.run_until_complete(task)
    except BaseException:
        # report the first exception as the cause of termination;
        # APPENDED so an engine-death report / restart-history
        # checkpoint already written this process survives alongside it
        msg = traceback.format_exc()
        write_termination_log(
            msg, os.getenv("TERMINATION_LOG_DIR", "/dev/termination-log"),
            append=True,
        )
        raise


def main() -> None:
    parser = make_parser()
    args = postprocess_tgis_args(parser.parse_args())
    if not args.model:
        parser.error("--model (or --model-name / MODEL_NAME env) is required")

    try:
        # faster event loop for the per-token wire hot path (reference
        # installs it unconditionally, __main__.py:10,128); optional here
        # so the framework runs on images without the wheel
        import uvloop

        asyncio.set_event_loop_policy(uvloop.EventLoopPolicy())
        logger.info("using uvloop event loop")
    except ImportError:
        pass

    from vllm_tgis_adapter_tpu.compile_cache import configure_compile_cache

    logger.info("XLA compile cache at %s", configure_compile_cache())

    loop = asyncio.new_event_loop()
    try:
        task = spawn_task(start_servers(args), name="start_servers", loop=loop)
        run_and_catch_termination_cause(loop, task)
    finally:
        loop.close()


if __name__ == "__main__":
    main()
