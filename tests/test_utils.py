"""Unit tests for shared utilities (TTL cache, termination log, task scan)."""

from __future__ import annotations

import asyncio

from vllm_tgis_adapter_tpu import utils
from vllm_tgis_adapter_tpu.utils import (
    TTLCache,
    check_for_failed_tasks,
    spawn_task,
    to_list,
    write_termination_log,
)


class FakeTimer:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def test_ttl_cache_basic():
    cache = TTLCache(maxsize=4, ttl=10)
    cache["a"] = 1
    assert cache["a"] == 1
    assert cache.get("missing") is None
    assert "a" in cache


def test_ttl_cache_expiry():
    timer = FakeTimer()
    cache = TTLCache(maxsize=4, ttl=10, timer=timer)
    cache["a"] = 1
    timer.now = 11
    assert cache.get("a") is None
    assert len(cache) == 0


def test_ttl_cache_eviction():
    cache = TTLCache(maxsize=2, ttl=100)
    cache["a"] = 1
    cache["b"] = 2
    cache["c"] = 3
    assert cache.get("a") is None
    assert cache["b"] == 2
    assert cache["c"] == 3


def test_termination_log_roundtrip(tmp_path):
    log = tmp_path / "termination-log"
    log.touch()
    write_termination_log("boom", str(log))
    assert log.read_text() == "boom\n"


def test_termination_log_missing_file(tmp_path):
    # must be a silent no-op
    write_termination_log("boom", str(tmp_path / "nope"))


def test_to_list():
    assert to_list([1, 2]) == [1, 2]
    assert to_list((1, 2)) == [1, 2]


def test_check_for_failed_tasks():
    async def run():
        async def ok():
            return 1

        async def bad():
            raise RuntimeError("x")

        t1 = asyncio.ensure_future(ok())
        t2 = asyncio.ensure_future(bad())
        await asyncio.gather(t1, t2, return_exceptions=True)
        return check_for_failed_tasks([t1, t2]) is t2

    assert asyncio.run(run())


# ------------------------------------------------------------- spawn_task


def test_spawn_task_holds_a_strong_ref_until_done():
    """The PR 9 GC'd-task regression: the loop keeps only weak task
    refs, so the spawner must retain the task until completion."""

    async def main():
        gate = asyncio.Event()

        async def job():
            await gate.wait()
            return 41

        task = spawn_task(job(), name="ref-test")
        # strongly referenced while in flight, even if the caller drops
        # its handle
        assert task in utils._BACKGROUND_TASKS
        gate.set()
        assert await task == 41
        await asyncio.sleep(0)  # let the done callback run
        assert task not in utils._BACKGROUND_TASKS
        assert task.get_name() == "ref-test"

    asyncio.run(main())


def test_spawn_task_retains_in_caller_container():
    async def main():
        mine: set = set()

        async def job():
            return "ok"

        task = spawn_task(job(), retain=mine)
        assert task in mine and task not in utils._BACKGROUND_TASKS
        await task
        await asyncio.sleep(0)
        assert not mine

    asyncio.run(main())


def test_spawn_task_explicit_loop():
    loop = asyncio.new_event_loop()
    try:
        async def job():
            return 7

        # schedule on a not-yet-running loop (the __main__ boot shape)
        task = spawn_task(job(), loop=loop)
        assert loop.run_until_complete(task) == 7
    finally:
        loop.close()


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """compile_cache.py: with JAX_COMPILATION_CACHE_DIR set nothing is
    set in code (JAX reads the variable itself); unset, the cache goes to
    the one fixed path under the checkout — never /tmp, a temporary
    name, a pid or the time, because a path that moves never hits."""
    import jax

    from vllm_tgis_adapter_tpu import compile_cache

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda *args: updates.append(args)
    )
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert updates == []

    monkeypatch.delenv(compile_cache.ENV_VAR)
    repo_root = compile_cache.Path(__file__).resolve().parent.parent
    assert compile_cache.configure_compile_cache() == str(
        repo_root / ".jax_cache"
    )
    assert updates == [
        ("jax_compilation_cache_dir", str(repo_root / ".jax_cache"))
    ]
