"""The per-test time limit of ``tests/conftest.py``
(``pytest_runtest_protocol`` hookwrapper: ``setitimer`` + a raising
handler, ``faulthandler.dump_traceback_later`` behind it).

Each case runs a real ``pytest`` process on a temporary test file whose
``conftest.py`` takes the hook from this tree's ``tests/conftest.py``
with the limit set to one second: a hung test, or a hung teardown of a
module-scoped fixture, fails that item alone, with the place it hung in
the report, and the run goes on to the next test and to its end.
"""

from __future__ import annotations

import re
import subprocess
import sys
import textwrap
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

CONFTEST = """
import tests.conftest as suite

suite.TEST_TIME_LIMIT_S = 1.0
suite.HARD_EXIT_AFTER_S = 1.0
from tests.conftest import (  # noqa: E402, F401
    pytest_runtest_protocol,
    pytest_runtest_setup,
)
"""


def _run_pytest(
    tmp_path: Path, *test_sources: str, xdist: tuple[str, ...] = ("-p", "no:xdist")
) -> str:
    (tmp_path / "conftest.py").write_text(CONFTEST)
    for n, source in enumerate(test_sources):
        (tmp_path / f"test_probe_{n}.py").write_text(
            textwrap.dedent(source)
        )
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-v", "-rA",
         "--rootdir", str(tmp_path), "-c", "/dev/null",
         "-p", "no:cacheprovider", "-p", "no:randomly", *xdist],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=90,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr  # tests failed
    return proc.stdout + proc.stderr


def test_hung_test_fails_alone_with_its_stack(tmp_path):
    out = _run_pytest(tmp_path, """
        import threading
        import time

        def hang_here():
            time.sleep(30)

        def test_hangs():
            parked = threading.Thread(
                target=threading.Event().wait, args=(3,), name="parked",
                daemon=True,
            )
            parked.start()
            hang_here()

        def test_after_it():
            assert True
    """)
    assert "test_hangs FAILED" in out
    assert "test_after_it PASSED" in out
    assert "1 failed, 1 passed" in out
    assert "TestTimeLimitExceeded" in out
    assert "ran past its 1s limit" in out
    # the place it hung: in the failure's own traceback, and in the dump
    # of every thread (the parked one too) under "Captured stderr"
    assert "in hang_here" in out
    assert "Captured stderr call" in out
    assert "most recent call first" in out
    assert out.count("Thread 0x") >= 1  # the other thread's stack


def test_hung_module_fixture_teardown_fails_that_item_only(tmp_path):
    out = _run_pytest(tmp_path, """
        import time

        import pytest

        @pytest.fixture(scope="module")
        def engine():
            yield "engine"
            time.sleep(30)  # a stop() that never returns

        def test_uses_it(engine):
            assert engine == "engine"

        def test_last_of_the_module(engine):
            assert engine == "engine"
    """, """
        def test_of_the_next_module():
            assert True
    """)
    assert "test_uses_it PASSED" in out
    assert "test_last_of_the_module PASSED" in out
    assert "test_of_the_next_module PASSED" in out
    # the teardown belongs to the module's last item: an error of that
    # item, and the session still ends with its summary line
    assert "ERROR at teardown of test_last_of_the_module" in out
    assert "TestTimeLimitExceeded" in out
    assert "3 passed, 1 error" in out


def test_hang_the_interpreter_never_returns_from_costs_one_worker(tmp_path):
    """A hang with the signal blocked (as inside a C call): the handler
    never runs, so the worker dumps its threads to the real stderr and
    exits.  ``--dist loadfile`` gives the rest of the file to a new
    worker starting with the same item, which is failed at once there,
    and the tests after it run."""
    out = _run_pytest(tmp_path, """
        import signal
        import time

        def test_before_it():
            assert True

        def test_hangs_hard():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            time.sleep(30)

        def test_after_it():
            assert True
    """, """
        def test_of_another_file():
            assert True
    """, xdist=("-p", "xdist", "-n", "2", "--dist", "loadfile"))
    assert out.count("node down") == 1
    assert "crashed while running 'test_probe_0.py::test_hangs_hard'" in out
    assert "Timeout (0:00:02)!" in out  # faulthandler's dump, on stderr
    assert "in test_hangs_hard" in out
    assert "ERROR at setup of test_hangs_hard" in out
    assert "in an earlier worker of this run" in out
    assert re.search(r"PASSED \S*test_probe_0\.py::test_after_it", out)
    assert "1 failed, 3 passed, 1 error" in out
