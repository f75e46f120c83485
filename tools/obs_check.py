"""Docs ↔ registry drift gate (nox -s obs_check).

Boots the real HTTP server in-process against a tiny fixture model,
scrapes ``/metrics`` over a real socket, and fails if any metric name
documented in docs/OBSERVABILITY.md is missing from the scrape (the
three flight-recorder/watchdog metrics included).  Also hits
``GET /debug/state`` and fails if the snapshot is missing any of the
top-level sections the doc promises — the introspection surface and its
documentation cannot drift silently either.  The step-anatomy/doctor
surfaces are gated the same way: ``?section=`` filtering,
``GET /debug/doctor``, and the ``GET /debug/timeline`` chrome trace are
exercised over the live server, and the doc's regime rule table must
match ``telemetry.doctor.REGIMES`` exactly.  Run directly with
``JAX_PLATFORMS=cpu python tools/obs_check.py``.
"""

from __future__ import annotations

import asyncio
import os
import re
import socket
import sys
import tempfile
import urllib.request
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def documented_event_kinds(doc_path: Path) -> set[str]:
    """Backticked kind names from the first column of the
    "Event schema" table — every flight-recorder kind the doc
    promises (combined rows like ``swap_out`` / ``swap_in`` yield both
    names)."""
    kinds: set[str] = set()
    in_table = False
    for line in doc_path.read_text().splitlines():
        if line.startswith("| Kind |"):
            in_table = True
            continue
        if in_table:
            if not line.startswith("|"):
                break
            first_cell = line.split("|")[1]
            kinds.update(re.findall(r"`([a-z_]+)`", first_cell))
    return kinds


def documented_metrics(doc_path: Path) -> set[str]:
    """Backticked ``tgis_tpu_*`` names from the observability doc
    (placeholder suffixes like ``pp{N}`` never name a whole metric)."""
    text = doc_path.read_text()
    return {
        name
        for name in re.findall(r"`(tgis_tpu_[a-z0-9_]+)`", text)
    }


# top-level sections docs/OBSERVABILITY.md documents for the
# /debug/state snapshot; a missing key means code and doc diverged
DEBUG_STATE_KEYS = (
    "engine", "supervisor", "frontdoor", "router", "kv_host_tier",
    "ledger",
    "slo",
    "step_timeline",
    "doctor",
    "replicas",
    "compile_tracker",
    "watchdog",
    "events",
)
REPLICA_KEYS = ("scheduler", "kv_cache", "in_flight", "step_counter",
                "serving", "role", "adapter_pool", "arena")
# kv_host_tier section: the per-rung split (ISSUE 14 satellite — the
# host and disk budgets must never read as one silently-summed number)
KV_TIER_KEYS = ("tiers",)
# "remote" is always a key (None when no kvnet manager is attached) so
# the networked rung can't silently drop out of the hierarchy snapshot
KV_TIER_TIERS = ("host", "disk", "remote")
# router-section keys the doc promises (incl. the disaggregation
# additions: per-role queue depths and handoff outcomes)
ROUTER_KEYS = ("placed_by_policy", "affinity_hit_rate",
               "role_queue_depths", "handoffs")

# the front-door metric surface (docs/FRONTDOOR.md) must BOTH be
# documented in docs/OBSERVABILITY.md and appear on /metrics — adding a
# frontdoor metric without documenting it fails here, not in review
REQUIRED_FRONTDOOR_METRICS = (
    "tgis_tpu_frontdoor_queue_depth",
    "tgis_tpu_frontdoor_queue_age_seconds",
    "tgis_tpu_frontdoor_sheds_total",
    "tgis_tpu_frontdoor_tenant_tokens_total",
    "tgis_tpu_frontdoor_placement_total",
)

# the telemetry signal layer (docs/OBSERVABILITY.md "Cost ledger" /
# "SLO burn rates"): the cost-attribution counters, the SLO gauges,
# and the live efficiency gauges must all BOTH be documented and
# served — the elastic control plane reads these, so silent drift here
# is an autoscaler flying blind
REQUIRED_TELEMETRY_METRICS = (
    "tgis_tpu_tenant_cost_tokens_total",
    "tgis_tpu_tenant_cost_hbm_page_seconds_total",
    "tgis_tpu_tenant_cost_tier_bytes_total",
    "tgis_tpu_slo_attainment",
    "tgis_tpu_slo_burn_rate",
    "tgis_tpu_spec_acceptance_rate_ewma",
)

# step anatomy + bottleneck doctor (docs/OBSERVABILITY.md "Step
# anatomy & doctor"): the phase histograms, the device-idle gauge, and
# the episode counters must be documented AND served
REQUIRED_STEPTIME_METRICS = (
    "tgis_tpu_step_anatomy_seconds",
    "tgis_tpu_host_gap_frac",
    "tgis_tpu_doctor_episodes_total",
    "tgis_tpu_doctor_active_regimes",
)

# networked KV tier (kvnet/, docs/CROSS_HOST.md): the cross-host
# sharing/handoff surface must be documented AND served — operators
# diagnose a partitioned or slow peer from exactly these names, so
# drift here means a fleet incident debugged blind
REQUIRED_KVNET_METRICS = (
    "tgis_tpu_kvnet_remote_lookups_total",
    "tgis_tpu_kvnet_remote_hits_total",
    "tgis_tpu_kvnet_remote_hit_ratio",
    "tgis_tpu_kvnet_transfer_bytes_total",
    "tgis_tpu_kvnet_peer_rtt_seconds",
    "tgis_tpu_kvnet_peers",
    "tgis_tpu_kvnet_handoffs_total",
)


def documented_regimes(doc_path: Path) -> set[str]:
    """Backticked regime names from the first column of the doctor's
    "Regime rule table" in docs/OBSERVABILITY.md — cross-checked
    against ``telemetry.doctor.REGIMES`` so the doc's rule table and
    the classifier cannot drift."""
    regimes: set[str] = set()
    in_table = False
    for line in doc_path.read_text().splitlines():
        if line.startswith("| Regime |"):
            in_table = True
            continue
        if in_table:
            if not line.startswith("|"):
                break
            first_cell = line.split("|")[1]
            regimes.update(re.findall(r"`([a-z_]+)`", first_cell))
    return regimes


async def scrape_metrics() -> tuple[str, dict]:
    from tests.fixture_models import build_tiny_llama

    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import EngineConfig
    from vllm_tgis_adapter_tpu.http import build_http_server, run_http_server
    from vllm_tgis_adapter_tpu.tgis_utils.args import (
        make_parser,
        postprocess_tgis_args,
    )

    model_dir = tempfile.mkdtemp(prefix="obs-check-model-")
    build_tiny_llama(model_dir)

    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]

    old_argv = sys.argv
    sys.argv = [
        "obs_check", "--model", model_dir, "--max-model-len", "512",
        "--dtype", "float32", "--max-num-seqs", "4",
        "--port", str(port),
    ]
    try:
        args = postprocess_tgis_args(make_parser().parse_args())
    finally:
        sys.argv = old_argv

    engine = AsyncLLMEngine.from_config(EngineConfig.from_args(args))
    await engine.start()
    app = build_http_server(args, engine)
    server_task = asyncio.create_task(
        run_http_server(args, engine, app, sock)
    )
    try:
        for _ in range(50):
            await asyncio.sleep(0.1)
            try:
                body = await asyncio.to_thread(
                    lambda: urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=5
                    ).read()
                )
            except OSError:
                continue
            import json

            def fetch(path: str) -> bytes:
                return urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5
                ).read()

            state_body = await asyncio.to_thread(fetch, "/debug/state")
            # the ?section= filter, the doctor view, and the chrome
            # trace exercised over the SAME live server, so the new
            # debug surfaces are gated end-to-end, not just imported
            section_body = await asyncio.to_thread(
                fetch, "/debug/state?section=doctor,step_timeline"
            )
            doctor_body = await asyncio.to_thread(fetch, "/debug/doctor")
            timeline_body = await asyncio.to_thread(
                fetch, "/debug/timeline?format=chrome"
            )
            return (
                body.decode(),
                json.loads(state_body),
                json.loads(section_body),
                json.loads(doctor_body),
                json.loads(timeline_body),
            )
        raise RuntimeError("HTTP server never became scrapeable")
    finally:
        server_task.cancel()
        try:
            await server_task
        except (asyncio.CancelledError, Exception):  # noqa: BLE001
            pass
        await engine.stop()


def check_event_kinds(doc_path: Path) -> list[str]:
    """Three-way flight-recorder kind agreement: the doc's event-schema
    table, ``flight_recorder.EVENT_KINDS``, and the lifecycle-grammar
    manifest (request ∪ batch kinds) must list the SAME set — adding a
    kind without documenting it AND declaring its grammar edges fails
    here, not in review."""
    from tools.dettest import lifecycle_grammar

    from vllm_tgis_adapter_tpu.flight_recorder import EVENT_KINDS

    code_kinds = set(EVENT_KINDS)
    problems: list[str] = []
    for label, other in (
        ("docs/OBSERVABILITY.md event-schema table",
         documented_event_kinds(doc_path)),
        ("lifecycle grammar manifest "
         "(tools/dettest/lifecycle_grammar.py request ∪ batch kinds)",
         set(lifecycle_grammar.all_kinds())),
    ):
        missing = sorted(code_kinds - other)
        extra = sorted(other - code_kinds)
        if missing:
            problems.append(
                f"{label} is missing kind(s): {', '.join(missing)}"
            )
        if extra:
            problems.append(
                f"{label} lists kind(s) absent from "
                f"flight_recorder.EVENT_KINDS: {', '.join(extra)}"
            )
    return problems


def main() -> int:
    doc_path = REPO_ROOT / "docs" / "OBSERVABILITY.md"
    kind_problems = check_event_kinds(doc_path)
    if kind_problems:
        print("obs_check: flight-recorder kind lists diverged:")
        for problem in kind_problems:
            print(f"  {problem}")
        return 1
    documented = documented_metrics(REPO_ROOT / "docs" / "OBSERVABILITY.md")
    if not documented:
        print("obs_check: no metrics documented — parse failure?")
        return 1
    undocumented = sorted(
        name
        for name in REQUIRED_FRONTDOOR_METRICS
        + REQUIRED_TELEMETRY_METRICS
        + REQUIRED_STEPTIME_METRICS
        + REQUIRED_KVNET_METRICS
        if name not in documented
    )
    if undocumented:
        print(
            "obs_check: required metrics missing from "
            "docs/OBSERVABILITY.md: " + ", ".join(undocumented)
        )
        return 1
    # doc's regime rule table ↔ the classifier's REGIMES tuple
    from vllm_tgis_adapter_tpu.telemetry.doctor import REGIMES

    doc_regimes = documented_regimes(doc_path)
    if doc_regimes != set(REGIMES):
        print(
            "obs_check: doctor regime rule table diverged from "
            "telemetry.doctor.REGIMES: doc-only "
            f"{sorted(doc_regimes - set(REGIMES))}, code-only "
            f"{sorted(set(REGIMES) - doc_regimes)}"
        )
        return 1
    scraped, state, section_state, doctor_view, timeline = asyncio.run(
        scrape_metrics()
    )
    missing = sorted(
        name for name in documented if name not in scraped
    )
    if missing:
        print(
            "obs_check: metrics documented in docs/OBSERVABILITY.md but "
            "missing from the /metrics scrape:"
        )
        for name in missing:
            print(f"  {name}")
        return 1
    state_missing = [k for k in DEBUG_STATE_KEYS if k not in state]
    replicas = state.get("replicas") or [{}]
    state_missing += [
        f"replicas[0].{k}" for k in REPLICA_KEYS if k not in replicas[0]
    ]
    router = state.get("router") or {}
    state_missing += [
        f"router.{k}" for k in ROUTER_KEYS if k not in router
    ]
    kv_tier = state.get("kv_host_tier") or {}
    state_missing += [
        f"kv_host_tier.{k}" for k in KV_TIER_KEYS if k not in kv_tier
    ]
    tiers = kv_tier.get("tiers") or {}
    state_missing += [
        f"kv_host_tier.tiers.{k}" for k in KV_TIER_TIERS
        if k not in tiers
    ]
    if state_missing:
        print(
            "obs_check: /debug/state is missing documented sections: "
            + ", ".join(state_missing)
        )
        return 1
    # ?section= filtering returned exactly the asked-for sections
    if set(section_state) != {"doctor", "step_timeline"}:
        print(
            "obs_check: ?section=doctor,step_timeline returned "
            f"{sorted(section_state)} instead of exactly the two "
            "requested sections"
        )
        return 1
    # the /debug/doctor view serves the classifier's full shape
    doctor_missing = [
        k for k in ("regimes", "active", "recent", "thresholds")
        if k not in doctor_view
    ]
    if doctor_missing or doctor_view.get("regimes") != list(REGIMES):
        print(
            "obs_check: /debug/doctor is missing keys "
            f"{doctor_missing} or its regime list diverged from "
            "telemetry.doctor.REGIMES"
        )
        return 1
    # the chrome trace is well-formed enough for Perfetto to load
    events = timeline.get("traceEvents")
    if not isinstance(events, list) or not any(
        e.get("ph") == "M" for e in events
    ):
        print(
            "obs_check: /debug/timeline?format=chrome returned no "
            "traceEvents/metadata — not a loadable chrome trace"
        )
        return 1
    print(
        f"obs_check: all {len(documented)} documented metrics present "
        "on /metrics; /debug/state (+?section=), /debug/doctor, and "
        "/debug/timeline serve every documented section"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
