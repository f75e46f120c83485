"""Runtime invariant sanitizer: step-boundary accounting checks.

The static analyzer (tools/tpulint, docs/STATIC_ANALYSIS.md) catches
the lock/pairing bug *shapes*; this module catches the bugs that slip
through anyway, at the moment they corrupt state instead of minutes
later as a wedged request or a silently shrinking pool.  Gated by
``TGIS_TPU_SANITIZE=1`` (off by default — zero cost beyond one env
read per step) and wired on in ``nox -s chaos_soak`` and the tier-1
conftest, so every existing test doubles as an invariant test.

Checked after every ``commit_step`` (the step boundary — all host
mutators of this state run on the loop/main thread, so the reads here
are race-free by the engine's own threading discipline):

* **Arena page conservation** — every page id of the allocator's budget
  is in exactly one of {free list, cached-free LRU, refcounted-live};
  epoch-quarantined frees are still refcounted; the prefix-cache hash
  maps are mutually consistent; the arena's adapter/borrow accounting
  sums match its charge table (pinned + LRU + free == budget).
* **Tier byte budgets** — host (and disk) tier ``bytes_used`` equals
  the actual entry sizes and respects the configured budget.
* **Adapter-pool slots and pins** — slot accounting closes (free +
  resident + streaming == max_loras), the LRU mirror matches residency,
  and the registry's pin counts agree with the engine's live requests
  (an unpaired pin/unpin is invisible until an eviction serves a live
  row the wrong weights — the exact PR 5/PR 9 bug class).

A violation raises :class:`SanitizerError` with every failed invariant
in one actionable message; ``check_engine`` can also be called with
``raise_on_violation=False`` to collect the list (the unit tests and
any external prober).
"""

from __future__ import annotations

import os
from typing import TYPE_CHECKING, Optional

if TYPE_CHECKING:  # pragma: no cover - typing only
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

ENV_VAR = "TGIS_TPU_SANITIZE"


class SanitizerError(AssertionError):
    """An engine accounting invariant failed (state is corrupt NOW;
    the message lists every violated invariant)."""


def enabled() -> bool:
    return os.environ.get(ENV_VAR, "") == "1"


# ------------------------------------------------------------- allocator


def check_allocator(alloc, out: list) -> None:  # noqa: ANN001
    """Page conservation + refcount/free-epoch + prefix-map coherence
    over one ``kv_cache.BlockAllocator``."""
    free = list(alloc._free)  # noqa: SLF001
    cached = list(alloc._cached_free)  # noqa: SLF001
    refcounted = dict(alloc._refcount)  # noqa: SLF001
    n = alloc.num_blocks

    sets = {
        "free": set(free),
        "cached-free": set(cached),
        "refcounted": set(refcounted),
    }
    if len(sets["free"]) != len(free):
        out.append(
            f"allocator: duplicate page ids on the free list "
            f"(double free): {len(free)} entries, "
            f"{len(sets['free'])} distinct"
        )
    for a in ("free", "cached-free", "refcounted"):
        for b in ("free", "cached-free", "refcounted"):
            if a < b and sets[a] & sets[b]:
                out.append(
                    f"allocator: page(s) {sorted(sets[a] & sets[b])[:8]} "
                    f"in both {a} and {b}"
                )
    union = sets["free"] | sets["cached-free"] | sets["refcounted"]
    if len(union) != n or any(b < 0 or b >= n for b in union):
        missing = sorted(set(range(n)) - union)[:8]
        out.append(
            f"allocator: page conservation broken — "
            f"free({len(free)}) + cached({len(cached)}) + "
            f"live({len(refcounted)}) covers {len(union)} of {n} pages "
            f"(missing e.g. {missing}; a leaked or double-freed page)"
        )
    for block, count in refcounted.items():
        if count < 1:
            out.append(
                f"allocator: page {block} refcount {count} < 1 while "
                f"still tracked as live"
            )

    # epoch-quarantined frees: each buffered free must still hold a
    # matching refcount (free() defers the decrement to the flush)
    from collections import Counter

    buffered: Counter = Counter()
    for epoch in alloc._free_epochs:  # noqa: SLF001
        for blocks in epoch:
            buffered.update(blocks)
    for block, count in buffered.items():
        if refcounted.get(block, 0) < count:
            out.append(
                f"allocator: page {block} freed {count}x into open "
                f"epoch(s) but refcount is {refcounted.get(block, 0)} "
                f"(double free into the quarantine)"
            )

    # prefix-cache maps must be a consistent partial bijection
    h2b = dict(alloc._hash_to_block)  # noqa: SLF001
    b2h = dict(alloc._block_hash)  # noqa: SLF001
    for h, block in h2b.items():
        if b2h.get(block) != h:
            out.append(
                f"allocator: prefix hash map asymmetry for page {block}"
            )
    cached_at = set(alloc._cached_at)  # noqa: SLF001
    if cached_at != sets["cached-free"]:
        out.append(
            "allocator: cached-free LRU and park-timestamp key sets "
            f"disagree ({len(cached_at)} vs {len(cached)})"
        )


# ----------------------------------------------------------------- arena


def check_arena(arena, out: list) -> None:  # noqa: ANN001
    """Arena charge-table sums vs its published counters."""
    if arena is None:
        return
    charges = dict(arena._charges)  # noqa: SLF001
    reserve = sum(c[0] for c in charges.values())
    borrowed_blocks = [b for c in charges.values() for b in c[1]]
    borrowed = len(borrowed_blocks)
    total = sum(c[0] + len(c[1]) for c in charges.values())
    if arena.adapter_reserve_used != reserve:
        out.append(
            f"arena: adapter_reserve_used={arena.adapter_reserve_used} "
            f"but charge table sums to {reserve}"
        )
    if arena.borrowed_blocks != borrowed:
        out.append(
            f"arena: borrowed_blocks={arena.borrowed_blocks} but charge "
            f"table holds {borrowed} borrowed page(s)"
        )
    if arena.adapter_blocks != total:
        out.append(
            f"arena: adapter_blocks={arena.adapter_blocks} but charge "
            f"table sums to {total}"
        )
    if arena.adapter_reserve_used > arena.adapter_budget_pages:
        out.append(
            f"arena: reserve overdrawn "
            f"({arena.adapter_reserve_used} > budget "
            f"{arena.adapter_budget_pages})"
        )
    live = set(arena.allocator._refcount)  # noqa: SLF001
    leaked = [b for b in borrowed_blocks if b not in live]
    if leaked:
        out.append(
            f"arena: borrowed page(s) {leaked[:8]} not refcounted in "
            f"the allocator (charge/release desync)"
        )


# ----------------------------------------------------------------- tiers


def check_tier(tier, out: list) -> None:  # noqa: ANN001
    """Host (and disk) tier byte accounting vs actual entry sizes."""
    if tier is None:
        return
    actual = sum(
        e.nbytes for e in tier._entries.values()  # noqa: SLF001
    )
    if tier.bytes_used != actual:
        out.append(
            f"kv host tier: bytes_used={tier.bytes_used} but entries "
            f"actually hold {actual} bytes (accounting drift)"
        )
    if actual > tier.budget_bytes:
        out.append(
            f"kv host tier: {actual} bytes resident over the "
            f"{tier.budget_bytes}-byte budget"
        )
    for entry in tier._entries.values():  # noqa: SLF001
        declared = entry.nbytes
        real = sum(int(a.nbytes) for a in entry.arrays)
        if declared != real:
            out.append(
                f"kv host tier: entry declares {declared} bytes but "
                f"its arrays hold {real}"
            )
            break
    if tier._inflight_bytes < 0:  # noqa: SLF001
        out.append(
            f"kv host tier: negative in-flight demotion bytes "
            f"({tier._inflight_bytes})"  # noqa: SLF001
        )
    disk = tier.disk
    if disk is not None:
        with disk._lock:  # noqa: SLF001 — index mutates on worker threads
            indexed = (
                sum(disk._index.values())  # noqa: SLF001
                + sum(disk._adapters.values())  # noqa: SLF001
            )
            used = disk.bytes_used
        if used != indexed:
            out.append(
                f"kv disk tier: bytes_used={used} but index sums to "
                f"{indexed}"
            )


# ----------------------------------------------------- adapter pool/pins


def check_adapter_pool(engine: "LLMEngine", out: list) -> None:
    """Slot conservation + LRU mirror + pin counts vs live requests."""
    pool = getattr(engine.runner, "adapter_pool", None)
    if pool is not None and not pool._closed:  # noqa: SLF001
        slots = set(pool._slots)  # noqa: SLF001
        streaming = set(pool._streaming)  # noqa: SLF001
        free = len(pool._free)  # noqa: SLF001
        in_use = len(slots | streaming)
        if free + in_use != pool.max_loras:
            out.append(
                f"adapter pool: slot conservation broken — "
                f"{free} free + {in_use} held "
                f"(resident {len(slots)}, streaming "
                f"{len(streaming - slots)}) != {pool.max_loras} slots"
            )
        lru = set(pool._lru)  # noqa: SLF001
        if lru != slots:
            out.append(
                f"adapter pool: LRU keys disagree with residents "
                f"({sorted(lru ^ slots)[:8]})"
            )

    manager = getattr(engine, "lora_manager", None)
    if manager is None:
        return
    from collections import Counter

    expected: Counter = Counter(
        seq.lora_name
        for seq in engine._seqs.values()  # noqa: SLF001
        if seq.lora_name is not None and not seq.is_finished
    )
    refs = dict(manager._refs)  # noqa: SLF001
    for name, count in refs.items():
        if count < 1:
            out.append(
                f"lora registry: adapter {name!r} pinned {count}x "
                f"(non-positive refcount survived unpin)"
            )
    # exact equality only when this engine is the registry's sole user
    # (dp fleets and mid-rebuild transitions share one registry; there
    # the per-engine view can only lower-bound the fleet total)
    users = len(manager._pools) + len(manager._resync_cbs)  # noqa: SLF001
    if users <= 1:
        if refs != dict(expected):
            drift = {
                name: (refs.get(name, 0), expected.get(name, 0))
                for name in set(refs) | set(expected)
                if refs.get(name, 0) != expected.get(name, 0)
            }
            out.append(
                f"lora registry: pin counts (have, want-from-live-"
                f"requests) drifted: {drift} — an unpaired pin/unpin "
                f"lets eviction serve a live row the wrong weights"
            )
    else:
        under = {
            name: (refs.get(name, 0), count)
            for name, count in expected.items()
            if refs.get(name, 0) < count
        }
        if under:
            out.append(
                f"lora registry: live requests outnumber pins "
                f"(have, want) = {under}"
            )


# ------------------------------------------------------------ entry point


def check_engine(
    engine: "LLMEngine", raise_on_violation: bool = True
) -> list[str]:
    """Run every invariant over one engine; returns the violations."""
    out: list[str] = []
    scheduler = getattr(engine, "scheduler", None)
    alloc = getattr(scheduler, "allocator", None)
    if alloc is not None:
        check_allocator(alloc, out)
    check_arena(getattr(engine, "arena", None), out)
    check_tier(getattr(engine, "kv_tier", None), out)
    check_adapter_pool(engine, out)
    if out and raise_on_violation:
        step = getattr(engine, "step_counter", "?")
        raise SanitizerError(
            f"{ENV_VAR}=1: {len(out)} engine invariant violation(s) at "
            f"step {step} (replica "
            f"{getattr(engine, 'replica_index', 0)}):\n  - "
            + "\n  - ".join(out)
        )
    return out


def maybe_check(engine: "LLMEngine") -> None:
    """The step-boundary hook (``core.commit_step``): no-op unless
    ``TGIS_TPU_SANITIZE=1``."""
    if enabled():
        check_engine(engine)


# ------------------------------------------------------ lifecycle grammar

# The reviewed grammar lives with the schedule explorer
# (tools/dettest/lifecycle_grammar.py — the checked-in
# LIFECYCLE_MANIFEST); the installed package loads it by path from a
# source checkout and degrades to grammar-off in a bare wheel, exactly
# like the compile-lattice manifest is a repo artifact.  Statically the
# same manifest backs tpulint TPL511/TPL512.

#: set TGIS_TPU_GRAMMAR_OBSERVE to a file path to RECORD undeclared
#: edges instead of raising — the manifest-diff workflow
#: (docs/STATIC_ANALYSIS.md "Lifecycle grammar"): run the suite in
#: observe mode, review the observed edges, extend the manifest.
OBSERVE_ENV_VAR = "TGIS_TPU_GRAMMAR_OBSERVE"

# bound on per-recorder request tracking state; past it the oldest
# entry evicts and tracking degrades to entry-check-free (a forgotten
# request must not false-positive as "decode before admit")
_GRAMMAR_TRACK_CAP = 4096

_grammar_module = None  # tri-state: None=unloaded, False=absent, module
_observed: "Optional[set]" = None


def _load_grammar():  # noqa: ANN202
    """The lifecycle_grammar module, or None outside a source tree."""
    global _grammar_module
    if _grammar_module is None:
        import importlib.util
        from pathlib import Path

        path = (
            Path(__file__).resolve().parents[2]
            / "tools" / "dettest" / "lifecycle_grammar.py"
        )
        _grammar_module = False
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                "_tgis_tpu_lifecycle_grammar", path
            )
            if spec is not None and spec.loader is not None:
                module = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(module)
                _grammar_module = module
    return _grammar_module or None


def _observe(edge: str) -> bool:
    """Record ``edge`` to the observe file; True when observing."""
    global _observed
    path = os.environ.get(OBSERVE_ENV_VAR, "")
    if not path:
        return False
    if _observed is None:
        _observed = set()
    if edge not in _observed:
        _observed.add(edge)
        with open(path, "a") as f:
            f.write(edge + "\n")
    return True


class GrammarTracker:
    """Per-recorder DFA state: request id → last recorded kind.

    Fed by ``FlightRecorder.record`` for every per-request event while
    ``TGIS_TPU_SANITIZE=1``; raises :class:`SanitizerError` the moment
    an event arrives out of order (decode before admit, anything after
    the ledger close), naming the request and the violated edge.
    """

    def __init__(self, grammar) -> None:  # noqa: ANN001
        from collections import OrderedDict

        self._edges = grammar.request_edges()
        self._entry = grammar.request_entry_kinds()
        self._last: "OrderedDict[str, str]" = OrderedDict()
        self._evicted = False

    def feed(self, kind: str, request_id: str) -> None:
        prev = self._last.get(request_id)
        if prev is None:
            ok = kind in self._entry or (
                # tracking state for this request may have been evicted
                # mid-stream: accept any declared kind rather than
                # false-positive on a long-lived request
                self._evicted and kind in self._edges
            )
        else:
            ok = kind in self._edges.get(prev, frozenset())
        if not ok:
            edge = f"{prev if prev is not None else '<stream start>'} -> {kind}"
            if not _observe(f"request: {edge}"):
                raise SanitizerError(
                    f"{ENV_VAR}=1: flight-recorder lifecycle grammar "
                    f"violation for request {request_id!r}: {edge} is "
                    f"not a declared edge of the per-request event DFA "
                    f"(tools/dettest/lifecycle_grammar.py "
                    f"LIFECYCLE_MANIFEST)"
                )
        self._last[request_id] = kind
        self._last.move_to_end(request_id)
        while len(self._last) > _GRAMMAR_TRACK_CAP:
            self._last.popitem(last=False)
            self._evicted = True


def track_event(recorder, kind: str, request_id: str) -> None:  # noqa: ANN001
    """``FlightRecorder.record``'s per-request hook: validate the event
    against the request's DFA state on this recorder.  No-op unless
    ``TGIS_TPU_SANITIZE=1`` and the grammar manifest is loadable."""
    if not enabled():
        return
    grammar = _load_grammar()
    if grammar is None:
        return
    tracker = getattr(recorder, "_grammar_tracker", None)
    if tracker is None:
        tracker = recorder._grammar_tracker = GrammarTracker(grammar)  # noqa: SLF001
    tracker.feed(kind, request_id)


def check_lifecycle_edge(
    old: Optional[str], new: str, *, draining: bool = False
) -> None:
    """Validate one engine lifecycle transition (``supervisor.
    _set_lifecycle``'s hook).  ``draining`` flags the front door's
    drain state: ``recovering -> serving`` is legal in general but
    forbidden while draining (a SIGTERM landing mid-recovery wins).
    No-op unless ``TGIS_TPU_SANITIZE=1``."""
    if not enabled():
        return
    grammar = _load_grammar()
    if grammar is None:
        return
    if old is None:
        ok = new in grammar.engine_entry_states()
    else:
        ok = (old, new) in grammar.engine_edges()
    if ok and draining and (old, new) in grammar.forbidden_while_draining():
        ok = False
    if not ok:
        edge = f"{old if old is not None else '<boot>'} -> {new}"
        suffix = " while the front door is draining" if draining else ""
        if not _observe(f"lifecycle: {edge}{suffix}"):
            raise SanitizerError(
                f"{ENV_VAR}=1: engine lifecycle transition {edge}{suffix} "
                f"is not a declared edge of the lifecycle machine "
                f"(tools/dettest/lifecycle_grammar.py LIFECYCLE_MANIFEST)"
            )
