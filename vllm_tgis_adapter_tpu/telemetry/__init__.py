"""The signal layer for the elastic control plane (docs/OBSERVABILITY.md).

Composable pieces, each consumable on its own:

* :mod:`~vllm_tgis_adapter_tpu.telemetry.ledger` — per-request cost
  accounting closed exactly once at the terminal outcome, rolled up
  into bounded per-tenant aggregates (``tenant_cost_*`` metrics, the
  ``ledger`` /debug/state section, ``ledger`` flight-recorder events,
  and the ``--ledger-log`` JSONL sink);
* :mod:`~vllm_tgis_adapter_tpu.telemetry.slo` — declarative per-class
  objectives (``--slo-config``) with multi-window attainment and
  error-budget burn-rate gauges fed from the same observation points
  the request-latency histograms use;
* :mod:`~vllm_tgis_adapter_tpu.telemetry.steptime` — step-time anatomy:
  every engine step decomposed into host_gap/plan/prepare/dispatch/
  device_wait/commit phases that sum exactly to wall time, kept in a
  bounded per-replica ring (``step_anatomy_seconds`` histograms, the
  ``host_gap_frac`` gauge, the ``step_timeline`` /debug/state section);
* :mod:`~vllm_tgis_adapter_tpu.telemetry.doctor` — the bottleneck
  doctor: a rule-table regime classifier over the anatomy windows that
  opens bounded, evidence-carrying episodes (``host_bound``,
  ``compile_storm``, ...) and brackets the worst of them with automatic
  profiler captures;
* :mod:`~vllm_tgis_adapter_tpu.telemetry.timeline` — unified Perfetto
  timeline export: StepRecords + flight-recorder events + doctor
  episodes + ledger records merged into one chrome-trace JSON
  (``GET /debug/timeline``, the ``GetTimeline`` RPC, and
  ``tools/timeline_export.py`` offline);
* :mod:`~vllm_tgis_adapter_tpu.telemetry.ewma` — the decayed EWMA
  behind the live ``spec_acceptance_rate_ewma`` gauge.

ROADMAP item 4 (the fleet reshaping itself under live load) keys its
placement/role/capacity decisions off exactly these signals.
"""

from vllm_tgis_adapter_tpu.telemetry.doctor import (
    REGIMES,
    Doctor,
    Episode,
    ReplicaSignals,
)
from vllm_tgis_adapter_tpu.telemetry.ewma import DecayedEwma
from vllm_tgis_adapter_tpu.telemetry.ledger import (
    CostLedger,
    CostRecord,
    JsonlSink,
)
from vllm_tgis_adapter_tpu.telemetry.slo import (
    REQUEST_CLASSES,
    SloEngine,
    resolve_request_class,
)
from vllm_tgis_adapter_tpu.telemetry.steptime import (
    PHASES,
    StepRecord,
    StepTimeline,
)
from vllm_tgis_adapter_tpu.telemetry.timeline import (
    chrome_trace_from_state,
    chrome_trace_json,
)

__all__ = [
    "PHASES",
    "REGIMES",
    "REQUEST_CLASSES",
    "CostLedger",
    "CostRecord",
    "DecayedEwma",
    "Doctor",
    "Episode",
    "JsonlSink",
    "ReplicaSignals",
    "SloEngine",
    "StepRecord",
    "StepTimeline",
    "chrome_trace_from_state",
    "chrome_trace_json",
    "resolve_request_class",
]
