"""``KvNetManager``: the control plane of the networked KV tier
(docs/CROSS_HOST.md).

Owns everything stateful about kvnet on one host:

* the ``KvTierService`` server (``--kvnet-listen``) and one
  ``PeerClient`` per ``--kvnet-peers`` entry, revived by a heartbeat
  loop that also syncs digest mirrors (INDEX) and prices RTT (PING);
* the remote-handoff state machine — ``StagedHandoffs`` holds
  checkpoints a prefill peer staged here until its COMMIT claims them
  (at-most-once: a commit racing a peer-death adoption can never
  double-promote);
* machine-loss resume — when a peer dies, its staged-but-uncommitted
  checkpoints are adopted onto a local decode-capable replica, and its
  mid-decode requests that were handed off TO us keep decoding with
  their outputs buffered (zero lost outputs: the chaos gate unions
  them with the survivor's streams);
* the output path — a pump per remotely resumed request forwards its
  ``RequestOutput`` frames back to the source host, which feeds its
  still-open client stream; a gone source flips the pump to
  buffer-only, a gone client stream answers with CANCEL.

Everything here runs on the event loop; the only cross-thread traffic
is the tier's own worker-thread staging, behind its existing locks.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Optional

from vllm_tgis_adapter_tpu import metrics
from vllm_tgis_adapter_tpu.kvnet import wire
from vllm_tgis_adapter_tpu.kvnet.client import (
    STATE_DEGRADED,
    STATE_HEALTHY,
    PeerClient,
    PeerError,
    RemoteKVTier,
)
from vllm_tgis_adapter_tpu.kvnet.service import KvTierService
from vllm_tgis_adapter_tpu.supervisor import failpoints
from vllm_tgis_adapter_tpu.utils import SERVER_STOP_TIMEOUT_S, spawn_task

logger = logging.getLogger(__name__)

#: heartbeat cadence — reconnect probes, RTT pings, peer-state gauges
HEARTBEAT_S = 0.5
#: mirror refresh: full INDEX sync every N beats (new demotions on a
#: peer become visible to placement/coverage within ~this window)
_INDEX_EVERY = 4


class StagedHandoffs:
    """Checkpoints a prefill peer staged on THIS host, keyed by request
    id, between its CKPT_PUT and its CKPT_COMMIT.

    The claim flag is the no-double-promote guarantee: ``claim`` and
    ``adopt_for_peer`` both run on the event loop and flip it
    atomically with the pop, so a COMMIT racing a peer-death sweep
    resolves to exactly one winner (the dettest KvNet scenario explores
    those schedules).
    """

    def __init__(self) -> None:
        # rid -> {"ckpt": DecodeCheckpoint, "source": node, "claimed": bool}
        self.records: dict = {}

    def stage(self, ckpt, source: str) -> None:  # noqa: ANN001
        self.records[ckpt.request_id] = {
            "ckpt": ckpt, "source": source, "claimed": False,
        }

    def claim(self, request_id: str):  # noqa: ANN201 — Optional[record]
        """At-most-once: the first claimer (COMMIT or adoption) gets
        the record; everyone after gets None."""
        rec = self.records.get(request_id)
        if rec is None or rec["claimed"]:
            return None
        rec["claimed"] = True
        self.records.pop(request_id, None)
        return rec

    def adopt_for_peer(self, source: str) -> list:
        """Claim every still-unclaimed record the dead ``source``
        staged here — the machine-loss resume sweep."""
        out = []
        for rid in [
            r for r, rec in self.records.items()
            if rec["source"] == source and not rec["claimed"]
        ]:
            rec = self.claim(rid)
            if rec is not None:
                out.append(rec)
        return out

    def discard(self, request_id: str) -> None:
        self.records.pop(request_id, None)

    def pending(self) -> int:
        return len(self.records)


class KvNetManager:
    """One per ``AsyncLLMEngine`` process when kvnet is configured."""

    def __init__(self, llm, config) -> None:  # noqa: ANN001
        self.llm = llm
        listen = getattr(config, "kvnet_listen", None)
        self.node_id = (
            getattr(config, "kvnet_node_id", None)
            or (listen or f"anon-{id(self) & 0xFFFF:x}")
        )
        self.timeout_s = float(
            getattr(config, "kvnet_timeout_s", 5.0) or 5.0
        )
        self.tier = llm.engine.kv_tier
        self.peers: list = [
            PeerClient(
                addr,
                node_id=self.node_id,
                timeout_s=self.timeout_s,
                on_push=self._on_push,
                on_peer_lost=self._on_peer_lost,
            )
            for addr in (getattr(config, "kvnet_peers", ()) or ())
        ]
        self.remote = RemoteKVTier(self.peers)
        self.service = (
            KvTierService(self, self.tier, listen) if listen else None
        )
        self.staged = StagedHandoffs()
        #: node -> live inbound ServerConn (the source's dialed socket;
        #: OUTPUT frames for its handed-off requests ride it back)
        self._inbound: dict = {}
        #: rid -> PeerClient decoding it remotely (source side)
        self.remote_out: dict = {}
        #: rid -> ServerConn|None feeding the source (target side)
        self._pump_conn: dict = {}
        #: rid -> [RequestOutput] buffered on the target — the
        #: zero-lost-output ledger for source-dead (adopted/orphaned)
        #: requests; drained into ``completed`` at finish
        self._out_buf: dict = {}
        self._pumps: dict = {}
        #: rid -> final buffered output list for finished requests whose
        #: source never took delivery (the chaos gate reads this)
        self.completed: dict = {}
        self._peer_state: dict = {}  # node -> last recorded up/down
        self._hb_task = None
        self._beats = 0
        self._stopping = False

    # ------------------------------------------------------------ lifecycle

    @property
    def listen_port(self) -> Optional[int]:
        return self.service.port if self.service is not None else None

    async def start(self) -> None:
        if self.service is not None:
            await self.service.start()
        # the shared tier now counts FLEET-wide coverage
        self.tier.attach_remote(self.remote)
        self._hb_task = spawn_task(
            self._heartbeat(), name=f"kvnet-heartbeat-{self.node_id}"
        )

    async def stop(self) -> None:
        """Outbound links first, then the listener (which closes the
        inbound ones before it waits, ``KvTierService.stop``): two
        hosts stopping together must not each wait on the other."""
        self._stopping = True
        cancelled = list(self._pumps.values())
        self._pumps.clear()
        if self._hb_task is not None:
            cancelled.append(self._hb_task)
            self._hb_task = None
        for task in cancelled:
            task.cancel()
        for peer in self.peers:
            await peer.close()
        if self.service is not None:
            await self.service.stop()
        if cancelled:
            # one loop turn, unless a task swallows its cancellation:
            # bounded like the rest
            await asyncio.wait(cancelled, timeout=SERVER_STOP_TIMEOUT_S)

    # ------------------------------------------------------------ telemetry

    def record(self, kind: str, request_id=None, **detail) -> None:  # noqa: ANN001, ANN003
        """Flight-recorder hook on the primary replica's recorder
        (peer_up/peer_down/remote_put are batch-scoped events)."""
        try:
            self.llm.engine.recorder.record(
                kind, request_id,
                step=self.llm.engine.step_counter, **detail,
            )
        except Exception:  # noqa: BLE001 — telemetry must never wound the data path
            logger.exception("kvnet: event record failed (%s)", kind)

    def _note_peer_state(self, node: Optional[str], up: bool) -> None:
        """Record peer_up/peer_down exactly on transitions."""
        if not node:
            return
        prev = self._peer_state.get(node)
        if prev is up:
            return
        self._peer_state[node] = up
        self.record("peer_up" if up else "peer_down", peer=node)

    def _observe_peers(self) -> None:
        states = [p.state for p in self.peers]
        metrics.kvnet_peers.labels(state="healthy").set(
            states.count(STATE_HEALTHY)
        )
        metrics.kvnet_peers.labels(state="degraded").set(
            states.count(STATE_DEGRADED)
        )
        metrics.kvnet_peers.labels(state="down").set(
            len(states)
            - states.count(STATE_HEALTHY)
            - states.count(STATE_DEGRADED)
        )

    # ------------------------------------------------------------ heartbeat

    async def _heartbeat(self) -> None:
        """Revive down peers, ping healthy ones, and refresh digest
        mirrors — the only periodic network activity kvnet generates."""
        while not self._stopping:
            self._beats += 1
            for peer in self.peers:
                try:
                    if not peer.connected:
                        if await peer.connect():
                            self._note_peer_state(peer.peer_node, True)
                            await self._sync_index(peer)
                    elif self._beats % _INDEX_EVERY == 0:
                        await self._sync_index(peer)
                    else:
                        await peer.request_retry(wire.OP_PING, {})
                except PeerError:
                    pass  # state ladder already updated by the client
                except Exception:  # noqa: BLE001 — heartbeat must survive anything
                    logger.exception(
                        "kvnet: heartbeat probe of %s failed", peer.addr
                    )
            self._observe_peers()
            await asyncio.sleep(HEARTBEAT_S)

    async def _sync_index(self, peer: PeerClient) -> None:
        header, _ = await peer.request_retry(wire.OP_INDEX, {})
        peer.mirror = {
            bytes.fromhex(h) for h in header.get("digests", [])
        }

    # --------------------------------------------- source side: handoff out

    async def handoff_to_peer(self, ckpt, tier) -> bool:  # noqa: ANN001
        """Stage + commit one DecodeCheckpoint onto a healthy peer.

        True = the peer accepted and now owns decode (its OUTPUT frames
        feed the local client stream).  False = no peer could take it —
        the caller continues down the local degradation ladder.  The
        window between STAGED and COMMIT is the machine-loss seam: a
        source death there leaves the record adoptable on the target.
        """
        peer = next(
            (p for p in self.peers if p.connected), None
        )
        if peer is None:
            logger.warning(
                "kvnet: handoff of %s has no connected peer "
                "(states: %s)", ckpt.request_id,
                [p.state for p in self.peers],
            )
            return False
        rid = ckpt.request_id
        try:
            # chaos site: a raise here is the partition-mid-handoff
            # scenario (tools/chaos_soak.py fault family)
            failpoints.fire("kvnet.handoff")
            items = await self._gather_pages(ckpt, tier)
            if items is None:
                missing = [
                    d.hex()[:12] for d in ckpt.digests
                    if tier._get_valid(d) is None  # noqa: SLF001
                    and not (tier.disk is not None and tier.disk.has(d))
                ]
                logger.warning(
                    "kvnet: handoff of %s aborted: %d/%d checkpoint "
                    "pages missing from the local tiers (LRU race): %s",
                    rid, len(missing), len(ckpt.digests), missing,
                )
                return False
            header = {"ckpt": wire.encode_checkpoint(ckpt)}
            payload = wire.pack_entries(items)
            await peer.request_retry(wire.OP_CKPT_PUT, header, payload)
            metrics.kvnet_transfer_bytes_total.labels(
                direction="out"
            ).inc(len(payload))
            peer.mirror.update(d for d, _ in items)
        except (PeerError, failpoints.FailpointError) as e:
            logger.warning(
                "kvnet: staging handoff of %s on %s failed: %s",
                rid, peer.addr, e,
            )
            metrics.kvnet_handoffs_total.labels(outcome="stage_failed").inc()
            return False  # nothing irrevocable yet: local ladder continues
        # the local record retires BEFORE the commit: exactly one of
        # {peer decode, adoption on the peer, client retry} serves this
        # request from here on — never a local resume racing a remote one
        tier.pop_checkpoint(rid)
        self.remote_out[rid] = peer
        try:
            header, _ = await peer.request_retry(
                wire.OP_CKPT_COMMIT, {"request_id": rid}
            )
            accepted = bool(header.get("accepted"))
        except PeerError:
            # commit outcome UNKNOWN (the peer may be decoding): never
            # locally resume — fail the stream retryable; a live peer's
            # orphan OUTPUT frames are answered with CANCEL
            self.remote_out.pop(rid, None)
            metrics.kvnet_handoffs_total.labels(outcome="commit_lost").inc()
            self._fail_stream(rid, "remote commit lost")
            return True
        if not accepted:
            self.remote_out.pop(rid, None)
            metrics.kvnet_handoffs_total.labels(outcome="rejected").inc()
            self._fail_stream(rid, "remote peer rejected the handoff")
            return True
        metrics.kvnet_handoffs_total.labels(outcome="remote").inc()
        self.llm.handoff_outcomes["remote"] = (
            self.llm.handoff_outcomes.get("remote", 0) + 1
        )
        self.record(
            "handoff_out", rid, outcome="remote",
            peer=peer.peer_node or peer.addr,
            output_tokens=len(ckpt.output_token_ids),
        )
        return True

    async def _gather_pages(self, ckpt, tier):  # noqa: ANN001, ANN201
        """``[(digest, arrays), ...]`` for every checkpoint page from
        the LOCAL rungs; None when any page is gone (LRU race — the
        caller falls back, exactly like local validation failing)."""
        items = []
        disk_wanted = []
        for digest in ckpt.digests:
            entry = tier._get_valid(digest)  # noqa: SLF001 — package-internal
            if entry is not None:
                items.append((digest, entry.arrays))
            elif tier.disk is not None and tier.disk.has(digest):
                disk_wanted.append(digest)
            else:
                return None
        if disk_wanted:
            disk = tier.disk

            def _load_all() -> list:
                return [
                    (d, arrays)
                    for d in disk_wanted
                    if (arrays := disk.load(d)) is not None
                ]

            loaded = await asyncio.to_thread(_load_all)
            if len(loaded) != len(disk_wanted):
                return None
            items.extend(loaded)
        return items

    def _fail_stream(self, request_id: str, reason: str) -> None:
        """Retryable floor on the source: the client sees 503 +
        Retry-After, and the prompt usually re-serves warm."""
        from vllm_tgis_adapter_tpu.frontdoor.errors import HandoffError

        queue = self.llm._queues.get(request_id)  # noqa: SLF001
        if queue is not None:
            queue.put_nowait(HandoffError(
                f"cross-host handoff failed ({reason}); partial "
                "output was discarded — retry shortly",
                retry_after_s=2.0,
            ))

    async def _on_push(
        self, peer: PeerClient, op: int, header: dict, payload: bytes
    ) -> None:
        """Unsolicited frames on an OUTBOUND connection — the decode
        peer streaming a handed-off request back to this source."""
        rid = header.get("request_id")
        if op == wire.OP_OUTPUT and rid is not None:
            queue = self.llm._queues.get(rid)  # noqa: SLF001
            if queue is None:
                # client stream gone (disconnect/abort): tell the peer
                # to stop decoding for it
                self.remote_out.pop(rid, None)
                await peer.push(
                    wire.OP_CANCEL, {"request_id": rid}
                )
                return
            out = wire.decode_request_output(header["out"])
            queue.put_nowait(out)
            if out.finished:
                self.remote_out.pop(rid, None)
        elif op == wire.OP_ERR and rid is not None:
            self.remote_out.pop(rid, None)
            self._fail_stream(
                rid, header.get("error", "remote decode failed")
            )

    def _on_peer_lost(self, peer: PeerClient) -> None:
        """Outbound connection loss: every request this host handed to
        that peer fails retryable NOW (the peer can no longer feed the
        stream), and the peer's mirror stops answering coverage."""
        self._note_peer_state(peer.peer_node, False)
        self._observe_peers()
        for rid, p in list(self.remote_out.items()):
            if p is peer:
                self.remote_out.pop(rid, None)
                metrics.kvnet_handoffs_total.labels(
                    outcome="peer_lost"
                ).inc()
                self._fail_stream(rid, "remote decode host lost")

    # --------------------------------------------- target side: handoff in

    def note_inbound(self, node: str, conn) -> None:  # noqa: ANN001
        self._inbound[node] = conn
        self._note_peer_state(node, True)

    def note_inbound_lost(self, node: str, conn) -> None:  # noqa: ANN001
        """An inbound peer connection dropped.  If that was the peer's
        LIVE connection (not an already-replaced one), treat it as the
        machine-loss signal: adopt its staged-uncommitted checkpoints
        and orphan its output pumps (they keep decoding, buffering)."""
        if self._inbound.get(node) is not conn:
            return  # superseded by a reconnect: not a death
        self._inbound.pop(node, None)
        self._note_peer_state(node, False)
        for rid, pconn in list(self._pump_conn.items()):
            if pconn is conn:
                self._pump_conn[rid] = None  # decode on; buffer only
        if self._stopping:
            return
        adopted = self.staged.adopt_for_peer(node)
        for rec in adopted:
            metrics.kvnet_handoffs_total.labels(outcome="adopted").inc()
            spawn_task(
                self._adopt(rec),
                name=f"kvnet-adopt-{rec['ckpt'].request_id}",
            )
        if adopted:
            logger.warning(
                "kvnet: peer %s died with %d staged handoff(s); "
                "adopting them onto local decode replicas",
                node, len(adopted),
            )

    async def _adopt(self, rec: dict) -> None:
        """Machine-loss resume: a dead source's staged checkpoint
        continues decoding HERE with no one to stream to (yet — a
        recovered source's late COMMIT re-attaches the stream)."""
        ok = await self._resume_remote(
            rec["ckpt"], rec["source"], conn=None
        )
        if not ok:
            logger.warning(
                "kvnet: adoption of %s from dead peer %s failed "
                "(pages or replicas unavailable); the client retry "
                "will recompute", rec["ckpt"].request_id, rec["source"],
            )

    def stage_remote(self, ckpt, source: str) -> None:  # noqa: ANN001
        """CKPT_PUT landed: pages are already in the local tier; the
        record waits for its COMMIT (or for the source to die)."""
        self.staged.stage(ckpt, source or "unknown")
        metrics.kvnet_handoffs_total.labels(outcome="staged").inc()

    async def commit_remote(self, request_id: str, conn) -> bool:  # noqa: ANN001
        """CKPT_COMMIT landed: claim-and-resume, or — when the adoption
        sweep won the race / already runs it — re-attach the source's
        stream to the running pump (flushing what it missed)."""
        rec = self.staged.claim(request_id)
        if rec is None:
            if request_id in self._pumps:
                # adopted while the source blinked: reconnect the
                # stream; the buffer replays every frame it missed
                self._pump_conn[request_id] = conn
                for out in list(self._out_buf.get(request_id, ())):
                    await conn.send(
                        wire.OP_OUTPUT,
                        {
                            "request_id": request_id,
                            "out": wire.encode_request_output(out),
                        },
                    )
                return True
            return False
        return await self._resume_remote(
            rec["ckpt"], rec["source"], conn
        )

    async def _resume_remote(self, ckpt, source: str, conn) -> bool:  # noqa: ANN001
        """Promote a remotely staged checkpoint onto a local
        decode-capable replica at the clean dispatch boundary — the
        cross-host twin of ``AsyncLLMEngine._resume_handoffs``."""
        from vllm_tgis_adapter_tpu.engine.async_llm import (
            _DECODE_CAPABLE,
        )

        rid = ckpt.request_id
        tier = self.tier
        await tier.drain_transfers()
        if not tier.validate_checkpoint(ckpt):
            metrics.kvnet_handoffs_total.labels(
                outcome="validation"
            ).inc()
            return False
        targets = [
            rep for rep in self.llm._replicas  # noqa: SLF001
            if rep.serving and rep.role in _DECODE_CAPABLE
        ]
        if not targets:
            metrics.kvnet_handoffs_total.labels(
                outcome="no_replica"
            ).inc()
            return False
        target = min(
            targets, key=lambda r: r.engine.scheduler.num_unfinished
        )
        # the pump IS the consumer: registered BEFORE resume so the
        # consumer-gone reap never fires between admission and pump
        queue: asyncio.Queue = asyncio.Queue()
        self.llm._queues[rid] = queue  # noqa: SLF001
        self.llm._owner[rid] = target  # noqa: SLF001
        try:
            async with target.lock:
                target.engine.recorder.record(
                    "remote_handoff_in", rid,
                    step=target.engine.step_counter,
                    trace_id=ckpt.trace_id, source=source,
                    output_tokens=len(ckpt.output_token_ids),
                )
                target.engine.resume_request(ckpt, path="handoff")
        except Exception:  # noqa: BLE001 — a bad resume degrades, never crashes the service
            logger.exception(
                "kvnet: remote resume of %s failed", rid
            )
            self.llm._queues.pop(rid, None)  # noqa: SLF001
            self.llm._owner.pop(rid, None)  # noqa: SLF001
            metrics.kvnet_handoffs_total.labels(outcome="resume").inc()
            return False
        target.last_beat = time.monotonic()
        target.new_work.set()
        metrics.kvnet_handoffs_total.labels(outcome="accepted").inc()
        self._pump_conn[rid] = conn
        self._out_buf[rid] = []
        self._pumps[rid] = spawn_task(
            self._pump(rid, queue), name=f"kvnet-pump-{rid}"
        )
        return True

    async def _pump(self, rid: str, queue: asyncio.Queue) -> None:
        """Forward one remote request's outputs to its source host;
        with the source gone, keep consuming (decode continues) and
        keep the buffer — machine-loss resume's zero-lost-output
        ledger."""
        try:
            while True:
                item = await queue.get()
                if isinstance(item, BaseException):
                    conn = self._pump_conn.get(rid)
                    if conn is not None:
                        await conn.send(
                            wire.OP_ERR,
                            {"request_id": rid, "error": str(item)},
                        )
                    break
                self._out_buf.setdefault(rid, []).append(item)
                conn = self._pump_conn.get(rid)
                if conn is not None:
                    ok = await conn.send(
                        wire.OP_OUTPUT,
                        {
                            "request_id": rid,
                            "out": wire.encode_request_output(item),
                        },
                    )
                    if not ok:
                        # source gone mid-stream: decode on, buffer only
                        self._pump_conn[rid] = None
                if item.finished:
                    break
        finally:
            self._pumps.pop(rid, None)
            self._pump_conn.pop(rid, None)
            self.completed[rid] = self._out_buf.pop(rid, [])
            self.llm._queues.pop(rid, None)  # noqa: SLF001
            self.llm._owner.pop(rid, None)  # noqa: SLF001

    def cancel_remote(self, request_id: Optional[str]) -> None:
        """CANCEL from the source (its client stream died): abort the
        local decode; the pump drains the final aborted frame."""
        if not request_id or request_id not in self._pumps:
            return
        spawn_task(
            self.llm.abort(request_id),
            name=f"kvnet-cancel-{request_id}",
        )

    # ------------------------------------------------------------ placement

    def peek_prefix_tokens(self, token_ids: list, lora_name=None) -> int:  # noqa: ANN001
        """Peer-covered prefix tokens for placement scoring (the
        covered-minus-local split happens in ``_place_replica``)."""
        tier = self.tier
        return tier.block_size * tier.peek_prefix_pages(
            token_ids, lora_name
        )

    def debug_state(self) -> dict:
        return {
            "node": self.node_id,
            "listen_port": self.listen_port,
            "staged": self.staged.pending(),
            "remote_out": len(self.remote_out),
            "pumps": len(self._pumps),
            "completed_orphans": len(self.completed),
            "peers": self.remote.debug_state(),
        }
