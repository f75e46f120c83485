"""Nox automation: CPU test suite, lint, wheel build.

Same session layout as the reference's noxfile (tests/lint/build) but
against the JAX CPU backend — the suite forces
JAX_PLATFORMS=cpu + an 8-device virtual mesh itself (tests/conftest.py),
so every session runs on plain CI runners with no accelerator.
"""

from __future__ import annotations

import nox

nox.options.sessions = (
    "lint", "tpulint", "race_check", "typecheck", "tests",
    "overload_check", "chaos_check", "chaos_soak",
)
nox.options.reuse_existing_virtualenvs = True

PYTHON_VERSIONS = ["3.12", "3.11"]


@nox.session(python=PYTHON_VERSIONS)
def tests(session: nox.Session) -> None:
    session.install("-e", ".[tests]")
    session.run(
        "pytest", "tests/", "-q",
        *session.posargs,
        env={"JAX_PLATFORMS": "cpu", "TGIS_TPU_SANITIZE": "1"},
    )


@nox.session(python="3.12")
def tpu_tests(session: nox.Session) -> None:
    """On-hardware kernel gate; requires an attached TPU."""
    session.install("-e", ".[tests]")
    session.run(
        "pytest", "tests", "-m", "tpu", "-q",
        env={"RUN_TPU_TESTS": "1"},
    )


@nox.session(python="3.12")
def obs_check(session: nox.Session) -> None:
    """Docs ↔ metrics-registry drift gate: boot the HTTP server
    in-process, scrape /metrics, fail if any metric documented in
    docs/OBSERVABILITY.md is absent from the scrape.  Also exercises
    /debug/state ?section= filtering, /debug/doctor, and
    /debug/timeline, and cross-checks the doc's doctor-regime table
    against telemetry/doctor.py's REGIMES tuple."""
    session.install("-e", ".[tests]")
    session.run(
        "python", "tools/obs_check.py",
        env={"JAX_PLATFORMS": "cpu"},
    )


@nox.session(python="3.12")
def overload_check(session: nox.Session) -> None:
    """Synthetic-overload gate (docs/FRONTDOOR.md): flood a small
    engine through the front door and assert bounded queue depth,
    correct shed statuses + Retry-After, per-tenant fairness, and a
    lossless SIGTERM drain.  Also runs inside the tier-1 suite; this
    session is the fast standalone entry point."""
    session.install("-e", ".[tests]")
    session.run(
        "pytest", "tests/test_frontdoor.py", "-q",
        *session.posargs,
        env={"JAX_PLATFORMS": "cpu"},
    )


@nox.session(python="3.12")
def chaos_check(session: nox.Session) -> None:
    """Failpoint-driven recovery gate (docs/RECOVERY.md): inject
    step-loop crashes, OOMs, stuck dispatches, and death-during-recovery
    through supervisor/failpoints.py and assert the supervisor replays
    pre-prefill work losslessly, checkpoints mid-decode work into the
    host KV tier and resumes it token-identically (locally and onto a
    healthy dp sibling; retryable failure only down the degradation
    ladder), re-arms health, and trips the crash-loop circuit breaker.
    Includes the dp
    partial-outage scenario (docs/SCALING.md): a replica dying mid-load
    replays its zero-token requests token-identically onto a healthy
    sibling while that sibling's TTFT stays bounded; and the adapter-
    pool suite (docs/LORA.md) with its adapter-swap-during-restart
    scenario — replayed requests carry LoRA identity onto the rebuilt
    engine's cold pool and reproduce the uncrashed tokens; and the
    tiered-KV suite (docs/KV_TIERING.md) with its cross-restart
    acceptance — a failpoint-killed engine rebuilds and re-serves a
    warm prefix from the SURVIVING host tier, token-identically; and
    the disaggregation suite (docs/SCALING.md "Disaggregated roles")
    with its dead-prefill-replica scenario — a prefill replica killed
    mid-handoff recovers with its role while the staged handoff
    resumes on the decode sibling, token-identically; and the
    unified-arena suite (docs/MEMORY.md) with its mixed-churn
    acceptance — an engine killed with a mixed KV+adapter working set
    over HBM recovers with no cross-type page corruption.  Also
    runs inside the tier-1 suite; this session is the fast standalone
    entry point."""
    session.install("-e", ".[tests]")
    session.run(
        "pytest", "tests/test_supervisor.py", "tests/test_adapter_pool.py",
        "tests/test_kv_tier.py", "tests/test_disagg.py",
        "tests/test_arena.py",
        "-q",
        *session.posargs,
        env={"JAX_PLATFORMS": "cpu", "TGIS_TPU_SANITIZE": "1"},
    )


@nox.session(python="3.12")
def chaos_soak(session: nox.Session) -> None:
    """Randomized chaos soak (docs/RECOVERY.md): a seeded schedule
    draws faults (raise/oom/hang) across the failpoint sites under
    mixed chat/RAG/LoRA load on a supervised, KV-tiered engine (some
    seeds dp=2) and asserts the global recovery invariants — every
    request exactly one terminal outcome, no harness-bound hangs,
    resumed outputs token-identical to the uncrashed baseline, zero
    new checkpoint/resume compile shapes.  N >= 5 seeds, bounded
    ~120 s; reproduce one schedule with
    `python tools/chaos_soak.py --seed <n>`."""
    session.install("-e", ".[tests]")
    session.run(
        "python", "tools/chaos_soak.py",
        *session.posargs,
        env={"JAX_PLATFORMS": "cpu", "TGIS_TPU_SANITIZE": "1"},
    )


@nox.session(python="3.12")
def lint(session: nox.Session) -> None:
    # rule set pinned in pyproject.toml [tool.ruff.lint] — reproducible
    # across ruff releases instead of the floating defaults
    session.install("ruff")
    session.run("ruff", "check", "vllm_tgis_adapter_tpu", "tests")


@nox.session(python="3.12")
def tpulint(session: nox.Session) -> None:
    """Project hazard analyzer (docs/STATIC_ANALYSIS.md): recompile,
    host-sync, async-blocking, lock-discipline (TPL4xx) and
    resource-pairing (TPL5xx) gates over the package, plus the
    compile-lattice manifest diff (TPL6xx;
    `python -m tools.tpulint --write-lattice` regenerates after an
    intentional jit change).  Pure stdlib — nothing to install; exit
    codes are scriptable (0/1/2) like tools/obs_check.py."""
    session.run(
        "python", "tools/tpulint/cli.py",
        *(session.posargs or ["vllm_tgis_adapter_tpu", "tools/dettest"]),
    )


@nox.session(python="3.12")
def race_check(session: nox.Session) -> None:
    """Deterministic async-schedule exploration gate
    (docs/STATIC_ANALYSIS.md "Deterministic schedule exploration"):
    run the owned control-plane scenarios (front-door admit/cancel/
    TTL/drain, supervisor recovery vs SIGTERM, kv-tier promotion vs
    abort/preempt, adapter-pool prefetch vs evict, doctor episode
    lifecycle, ledger terminal close) under tools/dettest's seeded
    deterministic event loop —
    >= 50 distinct schedules each, every schedule checked against the
    scenario invariants AND the lifecycle grammar — plus a bounded
    co-ready-permutation DFS and a seeded-failpoint proof that a
    recorded failing seed replays its schedule byte-for-byte.
    Deterministic (two runs print identical output) and bounded
    well under 120 s; reproduce one schedule with
    ``explorer.replay(scenario, seed=N)`` (or ``trace=...``)."""
    session.install("-e", ".[tests]")
    session.run(
        "python", "-m", "tools.dettest.race_check",
        env={"JAX_PLATFORMS": "cpu", "TGIS_TPU_SANITIZE": "1"},
    )


@nox.session(python="3.12")
def typecheck(session: nox.Session) -> None:
    """mypy over the whole package; pyproject's [[tool.mypy.overrides]]
    alone defines the typed core subset (everything else is
    override-ignored until annotated), so there is exactly ONE module
    list to maintain."""
    session.install("mypy")
    session.run(
        "mypy", "--config-file", "pyproject.toml",
        "vllm_tgis_adapter_tpu",
    )


@nox.session(python="3.12")
def build(session: nox.Session) -> None:
    session.install("build")
    session.run("python", "-m", "build")
