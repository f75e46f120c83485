"""Affinity-aware replica placement for data-parallel serving.

With ``--dp-replicas N`` (or ``--data-parallel-size N``) the front door
fronts N independent engine replicas, each with its own scheduler, KV
pool, and prefix cache.  WHERE a request lands then matters twice over:

* **prefix-cache affinity** — a replica whose paged cache already holds
  the request's prompt prefix serves prefill nearly for free
  (``BlockAllocator.peek_prefix``: a pure hash walk, no refcounts);
  routing the request anywhere else re-computes KV that exists on the
  fleet.  This is the cache-aware routing the data-parallel serving
  literature converges on (PAPERS.md: Orca-style continuous-batching
  replicas; the SGLang/Mooncake cache-aware router family).
* **tenant/adapter affinity** — a tenant's LoRA stack and its WFQ
  virtual-time state live wherever its requests land; sticky placement
  keeps an adapter resident on one replica instead of faulting it into
  every pool in rotation.
* **load** — both affinities yield to load: a replica more than
  ``load_slack`` requests deeper than the least-loaded one is not
  eligible for affinity placement, so a hot prefix or a chatty tenant
  cannot pile a replica over while its siblings idle.

``place()`` is a pure function of the snapshots handed to it — the
async engine builds one ``ReplicaSnapshot`` per SERVING replica (dead
and recovering replicas are excluded by the caller, so placement drains
away from a replica the moment its supervisor quiesces it) and routes
the request to the returned index.  Scoring order: role (prefill/
decode disaggregation, docs/SCALING.md "Disaggregated roles") >
prefix > adapter > tenant > least-loaded.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Optional

from vllm_tgis_adapter_tpu import metrics
from vllm_tgis_adapter_tpu.logging import init_logger

logger = init_logger(__name__)

POLICY_PREFIX = "prefix"
POLICY_ADAPTER = "adapter"
POLICY_TENANT = "tenant"
POLICY_LOAD = "load"
POLICIES = (POLICY_PREFIX, POLICY_ADAPTER, POLICY_TENANT, POLICY_LOAD)

# replica-role capability sets (docs/SCALING.md "Disaggregated roles"):
# the role TIER sits above every affinity policy — fresh requests run
# their prompt on prefill-capable replicas, handoff/checkpoint resumes
# decode on decode-capable ones.  A 'mixed' replica is both.
ROLE_CAPABLE = {
    "prefill": ("prefill", "mixed"),
    "decode": ("decode", "mixed"),
}

# EWMA weight for the per-replica committed-token rate (load
# tiebreak); one sample ~= one committed dispatch
_EWMA_ALPHA = 0.3

# host-tier residency scores below device residency (a promotion still
# pays a host→device transfer; an adopted device page is free): one
# host-resident token is worth this fraction of a device-resident one
HOST_TIER_WEIGHT = 0.25

# kvnet-peer residency scores below even the host tier (a remote hit
# pays a network fetch AND the host→device transfer; docs/CROSS_HOST.md
# "degradation ladder"): better than recompute, worse than any local rung
REMOTE_TIER_WEIGHT = 0.1


@dataclasses.dataclass
class ReplicaSnapshot:
    """One serving replica's placement-relevant state at decision time.

    ``load`` is the scheduler's queue depth (waiting + running);
    ``prefix_tokens`` is the length of THIS request's prompt prefix
    already resident in the replica's paged cache (0 when prefix
    caching is off or the caller skipped the probe).
    """

    index: int
    load: float
    prefix_tokens: int = 0
    # prompt tokens the HOST KV tier could promote for this request
    # (engine/kv_tier.py; the tier is fleet-shared, so the caller stamps
    # the same value on every snapshot) — scored at a lower weight than
    # device residency: a promotion still pays a host→device transfer
    host_prefix_tokens: int = 0
    # prompt tokens only a kvnet PEER could serve (fleet coverage minus
    # local coverage — engine/async_llm.py computes the split with two
    # peek_prefix_pages walks); scored below the host tier: a remote
    # hit pays a network fetch on top of the host→device transfer
    remote_prefix_tokens: int = 0
    # this request's LoRA adapter is live in the replica's device pool
    # (engine/adapter_pool.py) — TRUE residency, read at decision time,
    # unlike the sticky map which only remembers past placements
    adapter_resident: bool = False
    # the replica's disaggregation role (prefill/decode/mixed) — the
    # role TIER filters candidates before any affinity policy scores
    replica_role: str = "mixed"


class PlacementRouter:
    """Scores replicas for each request and remembers tenant stickiness.

    Host-side only, event-loop confined (no locks needed): ``place()``
    runs in ``generate()`` and ``note_committed()`` in the step loops'
    commit phase, both on the one event-loop thread.
    """

    def __init__(
        self,
        *,
        load_slack: float = 2.0,
        max_sticky_tenants: int = 1024,
    ):
        # affinity placement is only allowed onto replicas within this
        # many queued requests of the least-loaded one — the guard that
        # keeps a hot prefix or sticky tenant from overloading a replica
        self.load_slack = load_slack
        # tenant/adapter -> replica index of the last placement; bounded
        # LRU because tenant ids are client-controlled
        self._sticky: "OrderedDict[str, int]" = OrderedDict()
        self._max_sticky = max_sticky_tenants
        #: lifetime placements by policy (debug_state)
        self.placed_by_policy: dict[str, int] = {p: 0 for p in POLICIES}
        #: lifetime placements per replica index
        self.placed_by_replica: dict[int, int] = {}
        # per-replica committed-token accounting (commit-phase feed):
        # lifetime totals for debug_state, EWMA rate for the load
        # tiebreak between equally-deep queues
        self._committed_total: dict[int, float] = {}
        self._committed_rate: dict[int, float] = {}

    # ------------------------------------------------------------- feeds

    def note_committed(self, replica: int, tokens: float) -> None:
        """One committed dispatch's token count on ``replica``."""
        self._committed_total[replica] = (
            self._committed_total.get(replica, 0.0) + tokens
        )
        prev = self._committed_rate.get(replica)
        self._committed_rate[replica] = (
            tokens
            if prev is None
            else (1 - _EWMA_ALPHA) * prev + _EWMA_ALPHA * tokens
        )

    def forget_replica_rate(self, replica: int) -> None:
        """A replica was rebuilt: its in-flight rate is history."""
        self._committed_rate.pop(replica, None)

    def committed_by_replica(self) -> dict[int, float]:
        """Lifetime committed-token totals per replica."""
        return dict(self._committed_total)

    # --------------------------------------------------------- placement

    def _sticky_get(self, key: str) -> Optional[int]:
        idx = self._sticky.get(key)
        if idx is not None:
            self._sticky.move_to_end(key)
        return idx

    def _sticky_set(self, key: str, idx: int) -> None:
        self._sticky[key] = idx
        self._sticky.move_to_end(key)
        while len(self._sticky) > self._max_sticky:
            self._sticky.popitem(last=False)

    def place(
        self,
        snapshots: list[ReplicaSnapshot],
        *,
        affinity_key: Optional[str] = None,
        kind: str = "prefill",
    ) -> tuple[int, str]:
        """Pick a replica for one request.

        ``snapshots`` must be non-empty and contain only replicas the
        caller is willing to use (serving ones; the caller's fallback
        for a fleet with zero serving replicas is its own).
        ``affinity_key`` is the tenant id or adapter name — ``None``
        (anonymous default-tenant traffic) gets no stickiness, so bulk
        un-tenanted load spreads purely by depth.

        ``kind`` drives the ROLE tier above every other policy
        (docs/SCALING.md "Disaggregated roles"): ``"prefill"`` (fresh
        requests and replays — they must run their prompt) restricts to
        prefill-capable replicas, ``"decode"`` (handoff/checkpoint
        resumes) to decode-capable ones.  If no capable replica is in
        the candidate set, the filter falls open to the full set —
        availability beats role purity during a partial outage (callers
        that must NOT degrade, like the handoff drain, pre-check
        capability and fail retryable instead).

        Returns ``(replica_index, policy)`` with policy one of
        ``prefix`` / ``adapter`` / ``tenant`` / ``load``.
        """
        capable_roles = ROLE_CAPABLE.get(kind, ROLE_CAPABLE["prefill"])
        capable = [
            s for s in snapshots if s.replica_role in capable_roles
        ]
        snapshots = capable or snapshots
        best_load = min(s.load for s in snapshots)
        eligible = [
            s for s in snapshots if s.load <= best_load + self.load_slack
        ]

        chosen: Optional[ReplicaSnapshot] = None
        policy = POLICY_LOAD

        # 1. prefix affinity: the most resident prompt tokens wins,
        # provided that replica is not already over the load slack.
        # Host-tier residency counts at HOST_TIER_WEIGHT below device
        # residency (docs/SCALING.md) — but only as an EXTENSION of a
        # device match: the tier is fleet-shared, so host-only coverage
        # carries no replica-discriminating information and must not
        # claim the prefix policy ahead of adapter/tenant affinity
        # (step 2c below is its weaker, post-affinity slot).
        def prefix_score(s: ReplicaSnapshot) -> float:
            host_extra = max(0, s.host_prefix_tokens - s.prefix_tokens)
            return (
                s.prefix_tokens
                + HOST_TIER_WEIGHT * host_extra
                + REMOTE_TIER_WEIGHT * s.remote_prefix_tokens
            )

        prefix_best = max(
            eligible, key=lambda s: (prefix_score(s), -s.load, -s.index)
        )
        if prefix_best.prefix_tokens > 0:
            chosen, policy = prefix_best, POLICY_PREFIX
        # 2a. true adapter-pool residency: a replica already holding the
        # adapter's device weights beats the sticky map's memory of past
        # placements (the adapter may have been evicted there since, or
        # streamed elsewhere by a replay)
        if chosen is None:
            resident = [s for s in eligible if s.adapter_resident]
            if resident:
                chosen = min(resident, key=lambda s: (s.load, s.index))
                policy = POLICY_ADAPTER
        # 2b. tenant/adapter stickiness
        if chosen is None and affinity_key is not None:
            sticky_idx = self._sticky_get(affinity_key)
            if sticky_idx is not None:
                for s in eligible:
                    if s.index == sticky_idx:
                        chosen, policy = s, POLICY_TENANT
                        break
        # 2c. host-only prefix coverage: every eligible replica can
        # promote the shared tier's pages equally, so take the least
        # loaded — still a prefix placement (the request skips the
        # prefill recompute), just subordinate to every affinity that
        # actually distinguishes replicas
        if chosen is None:
            hosted = [
                s for s in eligible
                if s.host_prefix_tokens > 0 or s.remote_prefix_tokens > 0
            ]
            if hosted:
                chosen = min(hosted, key=lambda s: (s.load, s.index))
                policy = POLICY_PREFIX
        # 3. least-loaded fallback; committed-rate EWMA breaks depth
        # ties toward the replica currently grinding fewer tokens
        if chosen is None:
            chosen = min(
                snapshots,
                key=lambda s: (
                    s.load,
                    self._committed_rate.get(s.index, 0.0),
                    s.index,
                ),
            )
            policy = POLICY_LOAD

        if affinity_key is not None:
            self._sticky_set(affinity_key, chosen.index)
        self.placed_by_policy[policy] += 1
        self.placed_by_replica[chosen.index] = (
            self.placed_by_replica.get(chosen.index, 0) + 1
        )
        try:
            metrics.frontdoor_placement_total.labels(
                policy=policy, replica_role=chosen.replica_role
            ).inc()
        except Exception:  # pragma: no cover — telemetry must not raise
            pass
        return chosen.index, policy

    # ------------------------------------------------------ introspection

    @property
    def placed_total(self) -> int:
        return sum(self.placed_by_policy.values())

    def affinity_hit_rate(self) -> float:
        """Fraction of placements won by an affinity policy (prefix or
        tenant) rather than the least-loaded fallback."""
        total = self.placed_total
        if total == 0:
            return 0.0
        hits = (
            self.placed_by_policy[POLICY_PREFIX]
            + self.placed_by_policy[POLICY_ADAPTER]
            + self.placed_by_policy[POLICY_TENANT]
        )
        return hits / total

    def debug_state(self) -> dict:
        """Router section of the engine's /debug/state snapshot."""
        return {
            "placed_by_policy": dict(self.placed_by_policy),
            "placed_by_replica": {
                str(k): v
                for k, v in sorted(self.placed_by_replica.items())
            },
            "affinity_hit_rate": round(self.affinity_hit_rate(), 4),
            "sticky_tenants": len(self._sticky),
            "committed_tokens_by_replica": {
                str(k): round(v, 1)
                for k, v in sorted(self._committed_total.items())
            },
        }
