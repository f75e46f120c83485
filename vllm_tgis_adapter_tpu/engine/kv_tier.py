"""Host-RAM KV tier: a hash-addressed prefix-page store behind the device pool.

The device prefix cache (``kv_cache.BlockAllocator``) is bounded by HBM,
so fleet-scale system-prompt and RAG-corpus reuse — the dominant sharing
pattern under heavy multi-tenant traffic — evicts exactly when it
matters.  This module adds the next level of the memory hierarchy
(ROADMAP item 4; the same host-registry-feeding-device-slots move the
paged LoRA pool makes for adapters, engine/adapter_pool.py):

* **Content-hash-addressed page store.**  Every entry is ONE full KV
  page's host copy (``[L, H, block_size, D]`` per cache), keyed by the
  SAME token-chain digest ``match_prefix`` walks
  (``kv_cache.chain_digests``: sha256 over seed ‖ page₀ ‖ … ‖ pageₚ,
  LoRA-seeded), so device cache and host tier can never disagree about
  what a key means.  A byte-budgeted LRU (``--kv-host-cache-gb``) bounds
  host RAM; entries are validated on read (shape/dtype/nbytes) and a
  corrupt or short entry is dropped, never served.
* **Demotion (device → host).**  When a prompt's pages become final
  (prefix registration at prefill commit) or a preemption victim's
  computed pages are about to free (``core._swap_out_seq`` territory),
  the engine enqueues a fixed-shape jitted per-page gather
  (``runner.gather_kv_block`` — the device-side read is ordered before
  any later overwrite by dispatch order) and hands the device arrays
  here; the actual device→host copy runs in ``asyncio.to_thread`` under
  a transfer lock, mirroring the adapter pool's streaming discipline —
  never a sync copy on the event loop.
* **Promotion (host → device).**  A prefix-cache miss that the host
  tier can cover PARKS the request (``Scheduler.kv_gate``, exactly the
  adapter-pool parking shape: resident work fills the batch on both the
  bucketed and ragged planners) while the tier assembles the pages and
  ``device_put``s them off the loop; the engine core then scatters them
  into freshly allocated pages at a clean dispatch boundary
  (``runner.restore_kv_block``) and the request resumes prefill AFTER
  the restored span — the same continuation path a device prefix hit
  takes.
* **Cross-restart reuse.**  The store is plain host memory with no
  reference to the engine that fed it: a supervised rebuild
  (supervisor/supervisor.py) re-attaches the SURVIVING tier to the
  replacement engine, so a restarted replica re-serves warm prefixes
  without recompute; dp replicas share one tier (KV content is a pure
  function of tokens ‖ adapter ‖ model, so pages demoted by any replica
  serve all of them).

All store mutations happen on the event-loop thread (or single-threaded
in offline engines); worker threads only run the device↔host copies.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import json
import mmap
import os
import time
from collections import OrderedDict
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from vllm_tgis_adapter_tpu.logging import init_logger
from vllm_tgis_adapter_tpu.utils import spawn_task

logger = init_logger(__name__)

#: Entry-format version stamped into every serialized header ("v").
#: The disk format IS the kvnet wire format (docs/CROSS_HOST.md), so
#: the two evolve through this one number: readers accept any version
#: up to their own and treat NEWER versions as corrupt (never guess at
#: an unknown layout), which lets a rolling fleet upgrade writers one
#: host at a time.  Entries written before the field existed parse as
#: version 0 — the pre-versioning layout, which version 1 is payload-
#: compatible with.
ENTRY_VERSION = 1
#: Header "flags" bit: the entry's array tuple carries quant-scale
#: sidecars (ops/kv_quant.py — ``(k, v, k_scale, v_scale)``).  Purely
#: descriptive today (the "arrays" list already names every member);
#: UNKNOWN flag bits are ignored on read so future writers can mark
#: capabilities without breaking old readers.
ENTRY_FLAG_QUANT_SIDECAR = 0x1


def serialize_entry(arrays: tuple, meta: dict) -> bytes:
    """One self-describing entry blob: a JSON header line (version,
    flags, array shapes/dtypes, payload sha256, caller meta) followed
    by the raw concatenated array bytes.  This is both the on-disk
    layout (``DiskKVTier``) and the kvnet wire payload."""
    payload = b"".join(
        np.ascontiguousarray(a).tobytes() for a in arrays
    )
    header = dict(meta)
    header["v"] = ENTRY_VERSION
    header["flags"] = (
        ENTRY_FLAG_QUANT_SIDECAR if len(arrays) > 2 else 0
    )
    header["arrays"] = [
        {"shape": list(a.shape), "dtype": str(a.dtype)}
        for a in arrays
    ]
    header["sha256"] = hashlib.sha256(payload).hexdigest()
    return json.dumps(header).encode() + b"\n" + payload


def _validate_entry(meta: dict, payload: bytes) -> Optional[tuple]:
    """Shared read-side validation: version gate, payload checksum,
    array reconstruction.  ``None`` = corrupt or from-the-future —
    never served (both the mmap disk read and the network read funnel
    through here, so the two can never diverge on what "valid" means)."""
    try:
        if int(meta.get("v", 0)) > ENTRY_VERSION:
            # a newer writer's entry: the payload layout may have
            # changed in ways this reader cannot detect, so refuse it
            # exactly like a checksum mismatch
            return None
        # meta.get("flags", 0): known bits are descriptive only;
        # unknown bits are deliberately ignored (forward compat)
        if hashlib.sha256(payload).hexdigest() != meta.get("sha256"):
            return None
        arrays = []
        pos = 0
        for spec in meta["arrays"]:
            dt = np.dtype(spec["dtype"])
            count = int(np.prod(spec["shape"])) or 0
            arr = np.frombuffer(
                payload, dtype=dt, count=count, offset=pos
            ).reshape(spec["shape"]).copy()
            pos += count * dt.itemsize
            arrays.append(arr)
        return tuple(arrays)
    except Exception:  # noqa: BLE001 — any parse failure = corrupt
        return None


def _is_remote_marker(page) -> bool:  # noqa: ANN001
    """A ``("remote", digest)`` placeholder from ``_collect`` — the
    str check FIRST: a regular resolved page is a same-length tuple of
    numpy arrays, where ``== "remote"`` would be ambiguous."""
    return (
        isinstance(page, tuple) and len(page) == 2
        and isinstance(page[0], str) and page[0] == "remote"
    )


def parse_entry(blob: bytes) -> Optional[tuple]:
    """``(meta, arrays)`` from one serialized entry blob (the wire
    form a kvnet peer streams); ``None`` for corrupt / unknown-version
    blobs, never served."""
    try:
        nl = blob.index(b"\n")
        meta = json.loads(blob[:nl])
    except Exception:  # noqa: BLE001 — unparseable header = corrupt
        return None
    arrays = _validate_entry(meta, blob[nl + 1:])
    if arrays is None:
        return None
    return meta, arrays


class DiskKVTier:
    """Byte-budgeted local-disk tier BENEATH the host-RAM store
    (``--kv-disk-cache-gb``, docs/MEMORY.md "Disk tier").

    The lowest rung of the memory hierarchy: host-tier LRU victims —
    cold KV prefix pages and cold adapters spilled from the host
    registry — land here as one self-describing file per entry (a JSON
    header naming shapes/dtypes plus a sha256 of the payload, then the
    raw array bytes).  Reads go through ``mmap`` and are
    digest-validated exactly like the host tier validates shapes: a
    checksum mismatch UNLINKS the entry and reads as a miss, never
    served.  Files are content-addressed (the same token-chain digests
    the device cache and host tier key by), so the directory may
    survive restarts — a rebooted server re-serves warm prefixes
    straight from disk — and eviction is just an unlink of the LRU
    entry.

    All file I/O runs on worker threads under the host tier's transfer
    lock (store during demotion spill, load during promotion staging);
    the in-RAM index makes ``has``/peek probes loop-thread cheap.
    """

    PAGE_SUFFIX = ".kvpage"
    ADAPTER_SUFFIX = ".kvadapter"

    def __init__(
        self,
        budget_bytes: int,
        directory: Optional[str] = None,
        block_size: int = 16,
    ):
        import tempfile
        import threading

        self.budget_bytes = int(budget_bytes)
        self.block_size = block_size
        # KV page I/O arrives serialized by the host tier's asyncio
        # transfer lock, but ADAPTER spills/restores come from
        # LoRAManager's own worker threads — this thread lock makes
        # every index/bytes_used mutation safe regardless of which
        # path calls in (two concurrent _evict_to_budget walks would
        # otherwise double-pop the LRU head and corrupt accounting)
        self._lock = threading.Lock()
        self.dir = Path(
            directory
            or os.path.join(tempfile.gettempdir(), "tgis-tpu-kv-disk")
        )
        self.dir.mkdir(parents=True, exist_ok=True)
        # digest -> file size; LRU order, oldest first.  Adapters keyed
        # separately by name (their files carry the name in the header).
        self._index: "OrderedDict[bytes, int]" = OrderedDict()
        self._adapters: "OrderedDict[str, int]" = OrderedDict()
        self.bytes_used = 0
        self.stored_pages = 0
        self.loaded_pages = 0
        self.stored_adapters = 0
        self.loaded_adapters = 0
        self.evictions = 0
        self.dropped_corrupt = 0
        self._closed = False
        self._rescan()

    # --------------------------------------------------------------- index

    @staticmethod
    def _unlink_garbage(path: Path) -> None:
        try:
            path.unlink()
            logger.warning("kv disk tier: removed unadoptable file %s", path)
        except OSError:
            pass

    def _page_path(self, digest: bytes) -> Path:
        return self.dir / (digest.hex() + self.PAGE_SUFFIX)

    def _adapter_path(self, name: str) -> Path:
        return self.dir / (
            hashlib.sha256(name.encode()).hexdigest()
            + self.ADAPTER_SUFFIX
        )

    def _rescan(self) -> None:
        """Adopt surviving entries (cross-restart reuse): sizes from
        stat; integrity is verified lazily at load, like every read.
        Files that can never be adopted are UNLINKED — an orphaned
        ``.tmp`` from a crash mid-``_write`` or an unparseable name/
        header would otherwise sit outside ``bytes_used`` forever,
        uncountable and un-evictable, growing the directory past the
        budget across restarts."""
        for path in sorted(self.dir.glob("*.tmp")):
            try:
                path.unlink()
            except OSError:
                pass
        for path in sorted(self.dir.glob("*" + self.PAGE_SUFFIX)):
            try:
                digest = bytes.fromhex(path.name[: -len(self.PAGE_SUFFIX)])
                size = path.stat().st_size
            except (ValueError, OSError):
                self._unlink_garbage(path)
                continue
            self._index[digest] = size
            self.bytes_used += size
        for path in sorted(self.dir.glob("*" + self.ADAPTER_SUFFIX)):
            try:
                with open(path, "rb") as f:
                    meta = json.loads(f.readline())
                size = path.stat().st_size
            except (ValueError, OSError):
                self._unlink_garbage(path)
                continue
            name = meta.get("name")
            if name:
                self._adapters[name] = size
                self.bytes_used += size
            else:
                self._unlink_garbage(path)
        if self._index or self._adapters:
            logger.info(
                "kv disk tier: adopted %d page(s) + %d adapter(s) "
                "(%.1f MiB) surviving in %s",
                len(self._index), len(self._adapters),
                self.bytes_used / (1 << 20), self.dir,
            )
        self._evict_to_budget()
        self._observe_bytes()

    def has(self, digest: bytes) -> bool:
        return digest in self._index

    def has_adapter(self, name: str) -> bool:
        return name in self._adapters

    # --------------------------------------------------------------- store

    @staticmethod
    def _serialize(arrays: tuple, meta: dict) -> bytes:
        return serialize_entry(arrays, meta)

    def _write(self, path: Path, blob: bytes) -> None:
        tmp = path.with_suffix(path.suffix + ".tmp")
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)  # atomic: readers never see a torn entry

    def store_batch(self, items: list) -> None:
        """Persist ``[(digest, *arrays), ...]`` host-tier victims.
        Worker-thread half (file I/O under the transfer lock)."""
        if self._closed:
            return
        with self._lock:
            self._store_batch_locked(items)

    def _store_batch_locked(self, items: list) -> None:
        for digest, *arrays in items:
            if digest in self._index:
                continue
            blob = self._serialize(tuple(arrays), {"kind": "kv"})
            if len(blob) > self.budget_bytes:
                continue
            try:
                self._write(self._page_path(digest), blob)
            except OSError:
                logger.exception("kv disk tier: page write failed")
                continue
            self._index[digest] = len(blob)
            self.bytes_used += len(blob)
            self.stored_pages += 1
        self._evict_to_budget()
        self._observe_bytes()

    def store_adapter(self, name: str, weights, path_hint: str = "") -> None:  # noqa: ANN001
        """Spill one host-registry-evicted adapter
        (lora.LoRAAdapterWeights) to disk.  Worker-thread half."""
        if self._closed:
            return
        with self._lock:
            self._store_adapter_locked(name, weights, path_hint)

    def _store_adapter_locked(self, name: str, weights, path_hint: str) -> None:  # noqa: ANN001
        keys_a = sorted(weights.a)
        keys_b = sorted(weights.b)
        arrays = tuple(
            [weights.a[k] for k in keys_a] + [weights.b[k] for k in keys_b]
        )
        blob = self._serialize(arrays, {
            "kind": "adapter",
            "name": name,
            "rank": weights.rank,
            "scaling": weights.scaling,
            "target_modules": list(weights.target_modules),
            "keys_a": keys_a,
            "keys_b": keys_b,
            "path": path_hint,
        })
        if len(blob) > self.budget_bytes:
            return
        try:
            self._write(self._adapter_path(name), blob)
        except OSError:
            logger.exception("kv disk tier: adapter write failed")
            return
        old = self._adapters.pop(name, None)
        if old is not None:
            self.bytes_used -= old
        self._adapters[name] = len(blob)
        self.bytes_used += len(blob)
        self.stored_adapters += 1
        self._evict_to_budget()
        self._observe_bytes()

    # ---------------------------------------------------------------- load

    def _read_validated(self, path: Path) -> Optional[tuple]:
        """(meta, arrays) via an mmap'd read, payload checksum
        verified; a corrupt entry is unlinked and reads as a miss."""
        try:
            with open(path, "rb") as f:
                head = f.readline()
                meta = json.loads(head)
                offset = len(head)
                with mmap.mmap(
                    f.fileno(), 0, access=mmap.ACCESS_READ
                ) as mm:
                    payload = mm[offset:]
                    # shared validation with the wire read: version
                    # gate (newer-writer entries read as corrupt),
                    # checksum, array reconstruction
                    arrays = _validate_entry(meta, payload)
                    if arrays is None:
                        raise ValueError(
                            "corrupt or unknown-version entry"
                        )
            return meta, arrays
        except FileNotFoundError:
            return None
        except Exception:  # noqa: BLE001 — any parse failure = corrupt
            logger.warning(
                "kv disk tier: dropping corrupt entry %s instead of "
                "serving it", path.name,
            )
            self.dropped_corrupt += 1
            try:
                os.unlink(path)
            except OSError:
                pass
            return None

    def load(self, digest: bytes) -> Optional[tuple]:
        """One KV page's arrays, validated — worker-thread half (the
        promotion staging path).  A miss/corrupt read drops the index
        entry."""
        with self._lock:
            size = self._index.get(digest)
            if size is None:
                return None
            got = self._read_validated(self._page_path(digest))
            if got is None:
                self._index.pop(digest, None)
                self.bytes_used -= size
                self._observe_bytes()
                return None
            self._index.move_to_end(digest)  # LRU touch
            self.loaded_pages += 1
            return got[1]

    def load_adapter(self, name: str):  # noqa: ANN001 — LoRAAdapterWeights
        """Restore one spilled adapter's weights — worker-thread half."""
        with self._lock:
            size = self._adapters.get(name)
            if size is None:
                return None
            got = self._read_validated(self._adapter_path(name))
            if got is None:
                self._adapters.pop(name, None)
                self.bytes_used -= size
                self._observe_bytes()
                return None
            meta, arrays = got
            self._adapters.move_to_end(name)
            self.loaded_adapters += 1
        from vllm_tgis_adapter_tpu.engine.lora import LoRAAdapterWeights

        na = len(meta["keys_a"])
        return LoRAAdapterWeights(
            rank=int(meta["rank"]),
            scaling=float(meta["scaling"]),
            target_modules=tuple(meta["target_modules"]),
            a=dict(zip(meta["keys_a"], arrays[:na])),
            b=dict(zip(meta["keys_b"], arrays[na:])),
        ), meta.get("path", "")

    # ------------------------------------------------------------ eviction

    def _evict_to_budget(self) -> None:
        while self.bytes_used > self.budget_bytes and (
            self._index or self._adapters
        ):
            # evict whichever kind holds the older LRU head
            if self._index:
                digest, size = next(iter(self._index.items()))
                self._index.pop(digest)
                path = self._page_path(digest)
            else:
                name, size = next(iter(self._adapters.items()))
                self._adapters.pop(name)
                path = self._adapter_path(name)
            self.bytes_used -= size
            self.evictions += 1
            self._count_eviction("disk")
            try:
                os.unlink(path)
            except OSError:
                pass

    def close(self) -> None:
        self._closed = True

    # ------------------------------------------------------------- metrics

    def _observe_bytes(self) -> None:
        try:
            from vllm_tgis_adapter_tpu import metrics

            metrics.kv_host_tier_bytes.labels(tier="disk").set(
                self.bytes_used
            )
        except Exception:  # pragma: no cover — telemetry must not raise
            pass

    @staticmethod
    def _count_eviction(tier: str) -> None:
        try:
            from vllm_tgis_adapter_tpu import metrics

            metrics.kv_host_tier_evictions_total.labels(tier=tier).inc()
        except Exception:  # pragma: no cover — telemetry must not raise
            pass

    def debug_state(self) -> dict:
        return {
            "budget_bytes": self.budget_bytes,
            "bytes_used": self.bytes_used,
            "pages": len(self._index),
            "adapters": len(self._adapters),
            "stored_pages": self.stored_pages,
            "loaded_pages": self.loaded_pages,
            "stored_adapters": self.stored_adapters,
            "loaded_adapters": self.loaded_adapters,
            "evictions": self.evictions,
            "dropped_corrupt": self.dropped_corrupt,
            "directory": str(self.dir),
        }


class _Entry:
    """One full KV page's host copy.

    ``arrays`` is whatever ``runner.gather_kv_block`` produced for the
    page: ``(k, v)`` for plain caches, ``(k, v, k_scale, v_scale)`` when
    KV pages are quantized (ops/kv_quant.py — the per-head dequant
    scale column travels WITH the page, so promotions, checkpoints and
    role handoffs restore bit-exact content).  The store treats the
    tuple opaquely; validation pins every member's shape/dtype.
    """

    __slots__ = ("arrays", "nbytes", "stored_at")

    def __init__(self, *arrays: np.ndarray):
        self.arrays = tuple(arrays)
        self.nbytes = sum(int(a.nbytes) for a in self.arrays)
        self.stored_at = time.monotonic()

    # legacy accessors (tests corrupt entries through these)
    @property
    def k(self) -> np.ndarray:
        return self.arrays[0]

    @k.setter
    def k(self, value: np.ndarray) -> None:
        self.arrays = (value,) + self.arrays[1:]

    @property
    def v(self) -> np.ndarray:
        return self.arrays[1]

    @v.setter
    def v(self, value: np.ndarray) -> None:
        self.arrays = self.arrays[:1] + (value,) + self.arrays[2:]


@dataclasses.dataclass
class DecodeCheckpoint:
    """One mid-decode request's resumable host-side state.

    Created by ``LLMEngine.checkpoint_decode`` at supervisor quiesce
    time (docs/RECOVERY.md): the request's fully WRITTEN KV pages demote
    into the tier via the frontier-capped gathers, and this record —
    everything the device does not hold — is staged alongside, keyed by
    request id.  A resume (``LLMEngine.resume_request``) rebuilds a
    ``Sequence`` from it on the rebuilt replica or a healthy dp sibling;
    decode then continues token-identically because the sampler's PRNG
    folds the per-request position into ``fallback_seed`` (not a global
    step counter) and the seen-penalty matrix reseeds from the full
    prompt ‖ output chain, exactly like preemption-resume.

    The record is tiny (token ids + scalars — no tensors): the KV bytes
    live in the hash-addressed page store, shared with ordinary prefix
    reuse.  Schema documented in docs/KV_TIERING.md.
    """

    request_id: str
    prompt: Optional[str]
    prompt_token_ids: list
    output_token_ids: list  # emitted tokens — the client already holds these
    params: object  # SamplingParams (carries seed/penalties/stop/fsm spec)
    fallback_seed: int  # sampler key material — the token-identity anchor
    arrival_time: float
    deadline: Optional[float]
    tenant_id: Optional[str]
    lora_name: Optional[str]
    trace_id: Optional[str]
    # streaming bookkeeping: restored so DELTA streams never re-emit
    emitted_token_len: int
    emitted_text_len: int
    stop_scan_pos: int
    output_logprobs: Optional[list]
    prompt_logprobs: Optional[list]
    # request-timing restore: TTFT must not be re-observed on resume
    first_scheduled_time: Optional[float]
    first_token_time: Optional[float]
    last_token_time: Optional[float]
    time_in_queue: Optional[float]
    # the validation-read target: every one of these page digests must
    # be committed in the store before a resume is attempted
    digests: list
    pages: int
    # perf_counter stamp at capture (checkpoint_seconds observation)
    t0: float = 0.0
    # SLO/cost request class (telemetry/slo.py) — restored on resume so
    # the migrated request keeps billing under its original class
    request_class: str = "chat"
    # set by an explicit abort between staging and resume: the resume
    # paths skip a cancelled record even if they still hold a reference
    # to it (the client already received its final aborted frame)
    cancelled: bool = False


@dataclasses.dataclass
class PromotionTicket:
    """One parked request's in-flight host→device prefix restore.

    Created by the scheduler's kv gate (engine/core.py
    ``_kv_tier_gate``) with the target pages already allocated on the
    sequence; completed by the tier's assembly task; APPLIED by the
    engine core at a clean dispatch boundary (``_drain_promotions``) —
    the scatter rebinds ``runner.caches`` and must not race an in-flight
    dispatch, the same constraint swap-in has.
    """

    request_id: str
    digests: list
    start_tokens: int  # device-matched span already adopted
    end_tokens: int  # promotion target; may SHRINK at assembly (LRU race)
    pages: Optional[list] = None  # [(k_dev, v_dev)] once assembled
    ready: bool = False
    failed: bool = False
    cancelled: bool = False
    # pages fetched from a kvnet peer during assembly (engine core
    # records a remote_hit event and the remote-reuse metrics at apply)
    remote_pages: int = 0

    def cancel(self) -> None:
        self.cancelled = True


class HostKVTier:
    """Byte-budgeted LRU of hash-addressed KV pages in host RAM."""

    def __init__(self, budget_bytes: int, block_size: int):
        self.budget_bytes = int(budget_bytes)
        self.block_size = block_size
        # optional disk tier beneath this store (--kv-disk-cache-gb):
        # host LRU victims spill down, promotions walk disk→host→device
        self.disk: Optional[DiskKVTier] = None
        # optional networked tier beside/beneath the local rungs
        # (kvnet/, docs/CROSS_HOST.md): a fleet of peers whose digest
        # mirrors make `has` loop-thread cheap; fetches run async with
        # bounded retry and a fetch failure TRUNCATES the promotion
        # span (the shrunk-ticket contract) — a dead or slow peer
        # degrades to recompute, never a stall
        self.remote = None  # kvnet.client.RemoteKVTier
        # digest -> entry; LRU order, oldest first
        self._entries: "OrderedDict[bytes, _Entry]" = OrderedDict()
        self.bytes_used = 0
        # all pages of one engine config share a shape; pinned on first
        # insert so corrupt entries are detectable on read
        self._expected: Optional[tuple] = None
        # digests with a demotion copy in flight: dedups repeat gathers
        # of a hot prefix while its first copy still streams
        self._inflight: set[bytes] = set()
        # demotion backpressure: gathered device-side page copies live
        # OUTSIDE the KV pool's budget until the worker thread drains
        # them, so sustained eviction churn must not queue faster than
        # the serialized host copy drains — past this bound demotions
        # DROP (a dropped demotion is only a future cache miss)
        self.max_inflight_demotion_bytes = min(
            self.budget_bytes, 64 << 20
        )
        self._inflight_bytes = 0
        self.demotions_dropped = 0
        # serializes device↔host copies (adapter_pool's stream-lock
        # discipline): demotions and promotion assemblies never compete
        # for host-transfer bandwidth
        self._transfer_lock = asyncio.Lock()
        # strong refs to in-flight demote/promote tasks: the event loop
        # holds only WEAK task references, so an unreferenced transfer
        # task could be garbage-collected mid-flight (a lost promotion
        # would leave its request parked forever).  Mirrors
        # AdapterPool._streaming; close() cancels through this set.
        self._tasks: set = set()
        self._closed = False
        # staged DecodeCheckpoints (request_id → record): mid-decode
        # requests captured at supervisor quiesce, consumed at resume.
        # Records are token-id-sized, so no byte budget; they live in
        # the tier because the tier is exactly the state that SURVIVES
        # the dead engine (and is shared fleet-wide under dp, so a
        # healthy sibling can resume them before the rebuild).
        self._checkpoints: "OrderedDict[str, DecodeCheckpoint]" = (
            OrderedDict()
        )
        # lifetime stats (debug_state)
        self.demoted_pages = 0
        # disk-read pages hopped back UP into host RAM during a
        # promotion walk — kept apart from demoted_pages so operators
        # reading tier flow never see promotions inflate the demotion
        # counter
        self.recovered_pages = 0
        self.promoted_pages = 0
        self.promoted_tokens = 0
        self.evictions = 0
        self.dropped_corrupt = 0

    # ------------------------------------------------------------- lookups

    def attach_disk(self, disk: "DiskKVTier") -> None:
        """Hang the disk tier beneath this store (engine boot; the
        shared dp/rebuild-surviving tier carries it along)."""
        self.disk = disk

    def attach_remote(self, remote) -> None:  # noqa: ANN001 — RemoteKVTier
        """Hang the networked tier beside the local rungs
        (kvnet.manager at engine start; the shared dp/rebuild-surviving
        tier carries it along).  From here on, coverage probes and the
        promotion walk count FLEET-wide residency: a digest a healthy
        peer mirrors serves a park-and-promote exactly like a disk
        entry does."""
        self.remote = remote

    def _resident(self, digest: bytes) -> bool:
        """Committed in LOCAL tiers — host RAM or disk (either serves
        a promotion; disk entries hop through host on the way up)."""
        return digest in self._entries or (
            self.disk is not None and self.disk.has(digest)
        )

    def _covered(self, digest: bytes) -> bool:
        """Fetchable from ANY rung — local tiers or a healthy kvnet
        peer's mirror.  The coverage/dedup probe: a remote-mirrored
        page parks a request (promotion fetches it) and skips the
        duplicate demotion gather (one copy fleet-wide)."""
        return self._resident(digest) or (
            self.remote is not None and self.remote.has(digest)
        )

    def has(self, digest: bytes) -> bool:
        """Committed in the LOCAL rungs OR in-flight: the engine uses
        this to skip duplicate demotion gathers, so an in-flight copy
        counts.  Deliberately NOT `_covered`: a page only a peer
        mirrors must still demote here — this host can neither
        advertise it over INDEX nor gather it for a checkpoint handoff
        from a remote mirror (docs/CROSS_HOST.md)."""
        return self._resident(digest) or digest in self._inflight

    def local_digests(self) -> list:
        """Every digest committed in the LOCAL rungs (host RAM + disk)
        — the kvnet INDEX sync answer, so peers mirror exactly what
        this host can actually serve (loop-thread dict reads only)."""
        out = list(self._entries.keys())
        if self.disk is not None:
            out.extend(self.disk._index.keys())  # noqa: SLF001 — same module
        return out

    def peek_pages(self, digests: list) -> int:
        """Consecutive committed pages from ``digests[0]`` — the
        promotion-coverage probe (read-only, no LRU touch: mirrors
        ``BlockAllocator.peek_prefix``'s pure-walk contract)."""
        n = 0
        for digest in digests:
            if not self._covered(digest):
                break
            n += 1
        return n

    def peek_prefix_pages(
        self,
        token_ids: list,
        lora_name=None,  # noqa: ANN001 — Optional[str]
        start_page: int = 0,
        include_remote: bool = True,
    ) -> int:
        """Incremental chain walk: committed pages covering
        ``token_ids`` from ``start_page`` on, hashing only as far as
        entries exist.  The common cold-tier miss costs
        ``start_page + 1`` hashes instead of one per prompt page —
        this is the admission/placement hot-path probe; callers that
        need the digests themselves (ticket construction) re-derive
        exactly the covered span via ``kv_cache.chain_digests``.
        Capped one token short of the prompt, like ``match_prefix``.
        ``include_remote=False`` restricts the walk to the LOCAL rungs
        (placement scores local and peer coverage separately)."""
        from vllm_tgis_adapter_tpu.engine.kv_cache import BlockAllocator

        bs = self.block_size
        max_pages = (len(token_ids) - 1) // bs
        h = BlockAllocator._chain_seed(lora_name)  # noqa: SLF001
        probe = self._covered if include_remote else self._resident
        matched = 0
        for p in range(max_pages):
            h = BlockAllocator._chain_step(  # noqa: SLF001
                h, tuple(token_ids[p * bs: (p + 1) * bs])
            )
            if p < start_page:
                continue  # chain continuity only; not probed
            if not probe(h):
                break
            matched += 1
        return matched

    def _get_valid(self, digest: bytes) -> Optional[_Entry]:
        """Entry for ``digest`` with its integrity verified; a corrupt or
        short entry is DROPPED (never served) and reads as a miss."""
        entry = self._entries.get(digest)
        if entry is None:
            return None
        exp = self._expected
        ok = (
            exp is not None
            and len(entry.arrays) == len(exp)
            and all(
                getattr(a, "shape", None) == shape
                and getattr(a, "dtype", None) == dtype
                for a, (shape, dtype) in zip(entry.arrays, exp)
            )
            and entry.nbytes
            == sum(int(a.nbytes) for a in entry.arrays)
        )
        if not ok:
            logger.warning(
                "kv host tier: dropping corrupt entry (shape/dtype/size "
                "mismatch) instead of serving it"
            )
            self._entries.pop(digest, None)
            self.bytes_used -= entry.nbytes
            self.dropped_corrupt += 1
            self._observe_bytes()
            return None
        self._entries.move_to_end(digest)  # LRU touch
        return entry

    # ------------------------------------------------------------ demotion

    def submit(self, batch: list) -> None:
        """Accept ``[(digest, *page_arrays), ...]`` freshly gathered
        device pages — ``(k, v)`` per page, plus the scale columns when
        KV pages are quantized (``runner.gather_kv_block``'s tuple,
        stored verbatim).  The device→host copy (``np.asarray``) runs
        in a worker thread under the transfer lock; entries commit to
        the LRU back on the loop.  Offline engines (no running loop)
        copy inline."""
        if self._closed or not batch:
            return
        batch_bytes = sum(
            int(a.nbytes) for item in batch for a in item[1:]
        )
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        if (
            loop is not None
            and self._inflight_bytes + batch_bytes
            > self.max_inflight_demotion_bytes
        ):
            # backlogged: drop rather than accumulate device copies
            # outside the pool's budget while the transfer lock drains
            self.demotions_dropped += len(batch)
            return
        for digest, *_ in batch:
            self._inflight.add(digest)
        if loop is None:
            self._insert(self._to_host(batch))
            return
        self._inflight_bytes += batch_bytes
        spawn_task(
            self._demote_async(batch, batch_bytes),
            name="kv-tier-demote", retain=self._tasks, loop=loop,
        )

    async def _demote_async(self, batch: list, batch_bytes: int) -> None:
        try:
            async with self._transfer_lock:
                host = await asyncio.to_thread(self._to_host, batch)
        except Exception:
            logger.exception("kv host tier: demotion copy failed")
            for digest, *_ in batch:
                self._inflight.discard(digest)
            return
        finally:
            self._inflight_bytes -= batch_bytes
        self._insert(host)

    @staticmethod
    def _to_host(batch: list) -> list:
        """Worker-thread half: materialise the gathered device pages."""
        return [
            (item[0], *(np.asarray(a) for a in item[1:]))
            for item in batch
        ]

    def _insert(self, host_batch: list, recovered: bool = False) -> None:
        """Adopt host copies into the RAM store.  ``recovered`` marks
        disk-read pages hopping UP the hierarchy during a promotion —
        counted apart so reads never inflate ``demoted_pages``."""
        spill: list = []
        for digest, *arrays in host_batch:
            self._inflight.discard(digest)
            if self._closed or digest in self._entries:
                continue
            entry = _Entry(*arrays)
            if self._expected is None:
                self._expected = tuple(
                    (a.shape, a.dtype) for a in arrays
                )
            if entry.nbytes > self.budget_bytes:
                continue  # a single page over budget can never fit
            while (
                self.bytes_used + entry.nbytes > self.budget_bytes
                and self._entries
            ):
                vdigest, victim = self._entries.popitem(last=False)
                self.bytes_used -= victim.nbytes
                self.evictions += 1
                self._count_eviction()
                if self.disk is not None and not self.disk.has(vdigest):
                    # demotion cascades DOWN the hierarchy: the host
                    # LRU victim's next home is the disk tier, not
                    # oblivion (docs/MEMORY.md)
                    spill.append((vdigest, *victim.arrays))
            self._entries[digest] = entry
            self.bytes_used += entry.nbytes
            if recovered:
                self.recovered_pages += 1
            else:
                self.demoted_pages += 1
        self._observe_bytes()
        if spill:
            self._spill_to_disk(spill)

    def _spill_to_disk(self, spill: list) -> None:
        """Write host-tier victims to the disk tier — file I/O on a
        worker thread under the transfer lock (offline engines write
        inline); victims are already host numpy, so no device work."""
        if self.disk is None or self._closed:
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        if loop is None:
            self.disk.store_batch(spill)
            return
        spawn_task(
            self._spill_async(spill), name="kv-tier-spill-disk",
            retain=self._tasks, loop=loop,
        )

    async def _spill_async(self, spill: list) -> None:
        try:
            async with self._transfer_lock:
                await asyncio.to_thread(self.disk.store_batch, spill)
        except Exception:  # noqa: BLE001 — a lost spill is a future miss
            logger.exception("kv disk tier: spill failed")

    # ----------------------------------------------------------- promotion

    def start_promotion(self, ticket: PromotionTicket, put_fn: Callable) -> None:
        """Assemble the ticket's pages and ``device_put`` them off the
        loop; ``ticket.ready`` flips once the device arrays are staged
        (the engine core applies them at the next clean boundary).
        Offline engines assemble inline."""
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        if loop is None:
            staged, recovered = self._stage(self._collect(ticket), put_fn)
            if recovered:
                self._insert(recovered, recovered=True)
            self._finish_assembly(ticket, staged)
            return
        spawn_task(
            self._assemble(ticket, put_fn),
            name=f"kv-tier-promote-{ticket.request_id}",
            retain=self._tasks, loop=loop,
        )

    def _collect(self, ticket: PromotionTicket) -> list:
        """Longest still-valid prefix of the ticket's entries — host
        arrays where RAM has them, ``("disk", digest)`` markers where
        only the disk tier does (loaded by the worker-thread stage;
        loop-thread dict reads only here), and ``("remote", digest)``
        markers where only a kvnet peer mirrors the page (fetched
        async by ``_resolve_remote`` BEFORE the transfer lock)."""
        pages: list = []
        for digest in ticket.digests:
            entry = self._get_valid(digest)
            if entry is not None:
                pages.append(entry.arrays)
                continue
            if self.disk is not None and self.disk.has(digest):
                pages.append(("disk", digest))
                continue
            if self.remote is not None and self.remote.has(digest):
                pages.append(("remote", digest))
                continue
            break
        return pages

    def _stage(self, pages: list, put_fn: Callable) -> tuple:
        """Worker-thread half: host→device transfer of the assembled
        pages (the promotion's only bulk transfer; scale columns ride
        along for quantized pages).  Disk markers load-and-validate
        here — a corrupt disk entry TRUNCATES the span (the existing
        shrunk-ticket contract) — and the loaded host copies are
        returned so the loop can promote them INTO the host tier
        (disk → host → device, docs/MEMORY.md).

        The transfer is BATCHED per tuple position: one stacked
        ``put_fn`` per cache array instead of one per page per array —
        a 15-page promotion pays 2-4 transfers, not 30-60, which is
        what keeps warm-hit TTFT dominated by the restore itself
        rather than per-transfer dispatch overhead (the unified gate's
        warm/cold ratio rides on this)."""
        resolved: list = []
        recovered: list = []
        for page in pages:
            if isinstance(page, tuple) and len(page) == 2 and (
                isinstance(page[0], str)
            ):
                if page[0] != "disk":
                    # an unresolved remote marker (offline engine, or
                    # the fetch missed): the span shrinks here — the
                    # stage never blocks a worker thread on a peer
                    break
                arrays = (
                    self.disk.load(page[1])
                    if self.disk is not None
                    else None
                )
                if arrays is None:
                    break  # corrupt/evicted mid-flight: span shrinks
                recovered.append((page[1], *arrays))
                page = arrays
            resolved.append(page)
        if not resolved:
            return [], recovered
        cols = [
            put_fn(np.stack([page[j] for page in resolved]))
            for j in range(len(resolved[0]))
        ]
        staged = [
            tuple(col[i] for col in cols)
            for i in range(len(resolved))
        ]
        return staged, recovered

    async def _resolve_remote(self, pages: list) -> tuple:
        """Fetch the ``("remote", digest)`` markers from the networked
        tier BEFORE the transfer lock (peer latency must never hold
        local transfer bandwidth hostage).  Fetched pages are checksum-
        validated entry blobs; a miss, timeout or corrupt payload
        TRUNCATES the span at that page (the shrunk-ticket contract) —
        a dead or slow peer degrades to recompute, never a stall.
        Returns ``(resolved_pages, remote_page_count)``."""
        wanted = [p[1] for p in pages if _is_remote_marker(p)]
        if not wanted:
            return pages, 0
        fetched: dict = {}
        if self.remote is not None and not self._closed:
            try:
                fetched = await self.remote.fetch(wanted)
            except Exception:  # noqa: BLE001 — degradation, not failure
                logger.exception(
                    "kvnet: remote page fetch failed; promotion span "
                    "truncates to the locally covered prefix"
                )
        out: list = []
        recovered: list = []
        hits = 0
        for p in pages:
            if _is_remote_marker(p):
                arrays = fetched.get(p[1])
                if arrays is None:
                    break  # peer miss/corrupt mid-flight: span shrinks
                recovered.append((p[1], *arrays))
                out.append(arrays)
                hits += 1
            else:
                out.append(p)
        if recovered:
            # remote pages hop INTO host RAM like disk reads do: the
            # next warm request hits locally instead of re-fetching
            self._insert(recovered, recovered=True)
        return out, hits

    async def _assemble(self, ticket: PromotionTicket, put_fn: Callable) -> None:
        pages = self._collect(ticket)  # on loop: validated dict reads
        pages, ticket.remote_pages = await self._resolve_remote(pages)
        try:
            async with self._transfer_lock:
                staged, recovered = await asyncio.to_thread(
                    self._stage, pages, put_fn
                )
        except Exception:
            logger.exception(
                "kv host tier: promotion staging for %r failed",
                ticket.request_id,
            )
            ticket.failed = True
            ticket.ready = True
            return
        if recovered:
            # promote the disk-read pages one rung up: later warm
            # requests hit host RAM directly (back on the loop thread,
            # the only _entries mutator)
            self._insert(recovered, recovered=True)
        self._finish_assembly(ticket, staged)

    def _finish_assembly(self, ticket: PromotionTicket, staged: list) -> None:
        if not staged:
            # every entry evicted (or invalidated) between the gate's
            # peek and assembly: the request un-parks and recomputes
            ticket.failed = True
        else:
            ticket.pages = staged
            # the coverage may have SHRUNK if the LRU evicted tail
            # entries mid-flight; the apply scatters only what survived
            ticket.end_tokens = (
                ticket.start_tokens + len(staged) * self.block_size
            )
        ticket.ready = True

    def note_promoted(self, pages: int, tokens: int) -> None:
        """Apply-time accounting (the engine core is the one applier)."""
        self.promoted_pages += pages
        self.promoted_tokens += tokens

    # -------------------------------------------------- decode checkpoints

    def stage_checkpoint(self, ckpt: DecodeCheckpoint) -> None:
        """Stage one mid-decode request's resume record (quiesce-time
        triage).  Overwrites a same-id leftover — a retried recovery's
        fresh capture is always the authoritative one."""
        if self._closed:
            return
        self._checkpoints[ckpt.request_id] = ckpt

    def pop_checkpoint(
        self, request_id: str
    ) -> Optional[DecodeCheckpoint]:
        """Consume (resume) or discard (abort/disconnect/fallback) one
        staged record."""
        return self._checkpoints.pop(request_id, None)

    def pending_checkpoints(self) -> list:
        """Staged records not yet consumed — a recovery retry adopts
        these (the first attempt's captures survive its failure here,
        exactly like the KV pages themselves)."""
        return list(self._checkpoints.values())

    async def drain_transfers(self) -> None:
        """Barrier: await the transfer tasks in flight AT ENTRY.  The
        checkpoint validation read needs the quiesce-time gathers
        COMMITTED (a still-in-flight page reads as a miss and would
        fail a resume that is about to succeed); those were submitted
        before this call, so a single snapshot covers them.  Waiting
        for the set to EMPTY instead would never terminate on a shared
        dp tier whose healthy replicas keep streaming new transfers."""
        tasks = [t for t in list(self._tasks) if not t.done()]
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)

    def validate_checkpoint(self, ckpt: DecodeCheckpoint) -> bool:
        """The resume-eligibility read: every checkpointed page digest
        must be committed AND pass the per-entry integrity check
        (corrupt entries drop here, exactly as on the promotion path).
        A zero-page checkpoint (short decode — not one full page
        written yet) is trivially valid: resume recomputes from the
        prompt, still token-identically."""
        for digest in ckpt.digests[: ckpt.pages]:
            if self._get_valid(digest) is not None:
                continue
            if self.disk is not None and self.disk.has(digest):
                # disk-resident pages count: their payload checksum is
                # verified at load time, and a corrupt entry surfaces
                # as a shrunk promotion → the existing fallback rung
                continue
            if self.remote is not None and self.remote.has(digest):
                # peer-mirrored pages count the same way: the fetch
                # validates the entry checksum, and a fetch failure
                # shrinks the promotion span → recompute fallback
                continue
            return False
        return True

    # ------------------------------------------------------------ lifecycle

    def close(self) -> None:
        self._closed = True
        for task in list(self._tasks):
            task.cancel()
        self._entries.clear()
        self._checkpoints.clear()
        self.bytes_used = 0
        if self.disk is not None:
            self.disk.close()

    # ------------------------------------------------------------- metrics

    def _observe_bytes(self) -> None:
        try:
            from vllm_tgis_adapter_tpu import metrics

            # per-tier series (ISSUE 14 satellite): host and disk each
            # report their own bytes instead of silently summing
            metrics.kv_host_tier_bytes.labels(tier="host").set(
                self.bytes_used
            )
        except Exception:  # pragma: no cover — telemetry must not raise
            pass

    @staticmethod
    def _count_eviction() -> None:
        try:
            from vllm_tgis_adapter_tpu import metrics

            metrics.kv_host_tier_evictions_total.labels(
                tier="host"
            ).inc()
        except Exception:  # pragma: no cover — telemetry must not raise
            pass

    def debug_state(self) -> dict:
        """``kv_host_tier`` section of the /debug/state snapshot.

        The flat keys are the HOST tier's (the historical shape);
        ``tiers.host`` / ``tiers.disk`` split the hierarchy per rung so
        the two budgets never read as one silently-summed number
        (obs_check gates both sub-sections)."""
        host = {
            "budget_bytes": self.budget_bytes,
            "bytes_used": self.bytes_used,
            "pages": len(self._entries),
            "inflight_demotions": len(self._inflight),
            "demoted_pages": self.demoted_pages,
            "recovered_pages": self.recovered_pages,
            "demotions_dropped": self.demotions_dropped,
            "promoted_pages": self.promoted_pages,
            "promoted_tokens": self.promoted_tokens,
            "evictions": self.evictions,
            "dropped_corrupt": self.dropped_corrupt,
            "checkpoints": len(self._checkpoints),
        }
        return {
            **host,
            "tiers": {
                "host": dict(host),
                "disk": (
                    self.disk.debug_state()
                    if self.disk is not None
                    else None
                ),
                # networked rung (kvnet/): None until a manager
                # attaches one — the key itself is always present so
                # obs_check can gate the hierarchy shape
                "remote": (
                    self.remote.debug_state()
                    if self.remote is not None
                    else None
                ),
            },
        }
