"""The real server as the one chip-holding child, and what the parent
asks of it over HTTP.  After ``chip_smoke.Server`` / ``check_device``
(ran on the chip in PR 23).  Nothing here imports JAX."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def child_env(on_cpu: bool) -> dict[str, str]:
    """The caller's environment, minus everything that could move the
    child to another device or kernel.  ``JAX_COMPILATION_CACHE_DIR``
    passes through untouched (``compile_cache.py`` honours it)."""
    env = dict(os.environ)
    for name in ("ATTENTION_BACKEND", "XLA_FLAGS", "JAX_PLATFORM_NAME"):
        env.pop(name, None)
    env["JAX_PLATFORMS"] = "cpu" if on_cpu else "tpu"
    return env


class Server:
    def __init__(self, model_dir: Path, serve_args: list[str], log_path: Path,
                 *, on_cpu: bool, profiler: bool = False):
        self.http_port, self.grpc_port = free_port(), free_port()
        self.profiler_port = free_port() if profiler else None
        self.log_path = log_path
        log_path.parent.mkdir(parents=True, exist_ok=True)
        cmd = [
            sys.executable, "-m", "vllm_tgis_adapter_tpu",
            "--model", str(model_dir), *serve_args,
            "--port", str(self.http_port),
            "--grpc-port", str(self.grpc_port),
        ]
        if profiler:
            # not --profile-dir: that also arms the doctor's auto-capture,
            # which traces Python through every compile storm of the
            # warm-up (PERF.md section 6, PR 25)
            cmd += ["--jax-profiler-port", str(self.profiler_port)]
        self.started = time.monotonic()
        self.log = open(log_path, "w")
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=child_env(on_cpu),
            stdout=self.log, stderr=subprocess.STDOUT,
        )

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.http_port}{path}"

    def get_text(self, path: str, timeout: float = 30) -> str:
        with urllib.request.urlopen(self.url(path), timeout=timeout) as r:
            return r.read().decode()

    def get_json(self, path: str, timeout: float = 60) -> dict:
        return json.loads(self.get_text(path, timeout))

    def wait_healthy(self, timeout: float = 900) -> float:
        while time.monotonic() - self.started < timeout:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with code {self.proc.returncode} "
                    f"before it was healthy:\n{self.log_tail()}"
                )
            try:
                with urllib.request.urlopen(self.url("/health"), timeout=2):
                    return time.monotonic() - self.started
            except OSError:
                time.sleep(0.25)
        raise RuntimeError(
            f"server not healthy in {timeout}s:\n{self.log_tail()}"
        )

    def log_tail(self, lines: int = 40) -> str:
        self.log.flush()
        return "\n".join(
            self.log_path.read_text(errors="replace").splitlines()[-lines:]
        )

    def compiles(self) -> int:
        """Jit cache misses since boot (loads from the persistent cache
        count too: each is a program the window must not meet first)."""
        state = self.get_json("/debug/state?section=compile_tracker")
        return int(state["compile_tracker"]["total_compiles"])

    def drain(self) -> None:
        """SIGTERM, then the graceful-drain exit code 0."""
        self.proc.send_signal(signal.SIGTERM)
        code = self.proc.wait(timeout=120)
        if code != 0:
            raise RuntimeError(
                f"server exited {code} after SIGTERM, not 0:\n"
                f"{self.log_tail()}"
            )

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.log.close()


def check_device(report: dict, *, on_cpu: bool, chips: int) -> None:
    """The server's own account of its device must be the chip's: there
    is no CPU fallback outside an explicit rehearsal."""
    want = (
        {"platform": "cpu"} if on_cpu else
        {"platform": "tpu", "attention": "pallas",
         "pallas_mode": "compiled", "caches_donated": True}
    )
    wrong = {k: report.get(k) for k, v in want.items() if report.get(k) != v}
    if not on_cpu and report["kv_pool"]["sized_from"] != "memory_stats":
        wrong["kv_pool.sized_from"] = report["kv_pool"]["sized_from"]
    if report["engine_devices"] != chips:
        wrong["engine_devices"] = report["engine_devices"]
    if report["device_count"] < chips:
        wrong["device_count"] = report["device_count"]
    if wrong:
        raise RuntimeError(
            f"server is not where it should be: {wrong} (wanted {want}, "
            f"chips={chips})"
        )
