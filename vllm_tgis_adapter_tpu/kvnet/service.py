"""``KvTierService``: the asyncio TCP server half of the networked KV
tier (docs/CROSS_HOST.md).

One server per host (``--kvnet-listen``).  Each inbound connection is a
peer's ``PeerClient``; the service dispatches its frames against the
LOCAL tiers (HAS/GET/PUT/INDEX answer from host RAM + disk only — a
host never advertises pages it would itself have to fetch) and hands
checkpoint traffic (CKPT_PUT/CKPT_COMMIT/CANCEL) to the
``KvNetManager``, which owns the handoff state machine.

Blocking work (disk loads) runs on worker threads; everything else is
loop-thread dict reads, so a burst of peer traffic shares the loop
fairly with the step loop instead of stalling it.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Optional

from vllm_tgis_adapter_tpu import metrics
from vllm_tgis_adapter_tpu.kvnet import wire
from vllm_tgis_adapter_tpu.utils import close_server

logger = logging.getLogger(__name__)


class ServerConn:
    """One inbound peer connection: the writer, a write lock (whole
    frames, never interleaved bytes), and the peer's node id once its
    HELLO arrives.  Handoff OUTPUT pumps write through this object."""

    def __init__(self, writer: asyncio.StreamWriter) -> None:
        self.writer = writer
        self.wlock = asyncio.Lock()
        self.peer_node: Optional[str] = None
        self.closed = False

    async def send(
        self, op: int, header: dict, payload: bytes = b""
    ) -> bool:
        """Write one frame; False (and marks the conn closed) on any
        failure — the pump treats that as consumer-gone."""
        if self.closed:
            return False
        try:
            frame = wire.encode_frame(op, header, payload)
            async with self.wlock:
                self.writer.write(frame)
                await self.writer.drain()
            return True
        except Exception:  # noqa: BLE001 — peer gone mid-write
            self.closed = True
            return False


class KvTierService:
    """The RPC surface a host exposes: put/get/has/index by digest plus
    checkpoint stage/commit, over the ``wire`` framing."""

    def __init__(self, manager, tier, listen: str) -> None:  # noqa: ANN001
        self.manager = manager
        self.tier = tier  # engine.kv_tier.HostKVTier (the shared one)
        host, _, port = listen.rpartition(":")
        self.host = host or "127.0.0.1"
        self.port = int(port)
        self._server: Optional[asyncio.AbstractServer] = None
        self._conns: set = set()

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port
        )
        # port 0 → kernel-assigned (tests); surface the real one
        self.port = self._server.sockets[0].getsockname()[1]
        logger.info(
            "kvnet: KvTierService listening on %s:%d (node %s)",
            self.host, self.port, self.manager.node_id,
        )

    async def stop(self) -> None:
        """The listener, then the peer links, then a bounded wait
        (``utils.close_server``): a ``_handle`` parked in ``read_frame``
        on a live peer ends only when its link is closed, so a stop
        that waited on the listener first hung on every connected peer."""
        conns = list(self._conns)
        self._conns.clear()
        for conn in conns:
            conn.closed = True
        server, self._server = self._server, None
        if server is not None:
            await close_server(
                server, [conn.writer for conn in conns], "kvnet service"
            )

    # --------------------------------------------------------- connection

    async def _handle(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        conn = ServerConn(writer)
        self._conns.add(conn)
        try:
            while True:
                op, _flags, header, payload = await wire.read_frame(
                    reader
                )
                try:
                    await self._dispatch(conn, op, header, payload)
                except Exception as e:  # noqa: BLE001 — frame-scoped
                    logger.exception(
                        "kvnet: request failed (op=%d)", op
                    )
                    await conn.send(
                        wire.OP_ERR,
                        {"rid": header.get("rid"), "error": str(e)},
                    )
        except (asyncio.IncompleteReadError, ConnectionError):
            pass  # peer EOF/reset: the normal disconnect path
        except wire.ProtocolError as e:
            logger.warning(
                "kvnet: protocol violation from %s: %s",
                conn.peer_node or "unknown peer", e,
            )
        except (asyncio.CancelledError, GeneratorExit):
            raise
        except Exception:  # noqa: BLE001 — never kill the server loop
            logger.exception("kvnet: connection handler failed")
        finally:
            conn.closed = True
            self._conns.discard(conn)
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass
            if conn.peer_node is not None:
                # an inbound drop is a peer-death signal exactly like
                # an outbound one: the manager sweeps staged handoffs
                self.manager.note_inbound_lost(conn.peer_node, conn)

    async def _dispatch(
        self, conn: ServerConn, op: int, header: dict, payload: bytes
    ) -> None:
        rid = header.get("rid")
        if op == wire.OP_HELLO:
            conn.peer_node = str(header.get("node", ""))
            self.manager.note_inbound(conn.peer_node, conn)
            await conn.send(
                wire.OP_HELLO_R,
                {
                    "rid": rid,
                    "node": self.manager.node_id,
                    "version": wire.WIRE_VERSION,
                },
            )
        elif op == wire.OP_PING:
            await conn.send(wire.OP_PONG, {"rid": rid})
        elif op == wire.OP_HAS:
            hits = [
                self.tier._resident(bytes.fromhex(h))  # noqa: SLF001
                for h in header.get("digests", [])
            ]
            await conn.send(
                wire.OP_HAS_R, {"rid": rid, "hits": hits}
            )
        elif op == wire.OP_GET:
            await self._serve_get(conn, rid, header)
        elif op == wire.OP_PUT:
            entries = wire.unpack_entries(payload)
            if entries:
                self.tier._insert(  # noqa: SLF001 — package-internal
                    [(d, *arrays) for d, arrays in entries],
                    recovered=True,
                )
            metrics.kvnet_transfer_bytes_total.labels(
                direction="in"
            ).inc(len(payload))
            self.manager.record(
                "remote_put",
                peer=conn.peer_node, pages=len(entries),
            )
            await conn.send(
                wire.OP_PUT_R, {"rid": rid, "stored": len(entries)}
            )
        elif op == wire.OP_INDEX:
            digests = self.tier.local_digests()
            await conn.send(
                wire.OP_INDEX_R,
                {"rid": rid, "digests": [d.hex() for d in digests]},
            )
        elif op == wire.OP_CKPT_PUT:
            entries = wire.unpack_entries(payload)
            if entries:
                self.tier._insert(  # noqa: SLF001 — package-internal
                    [(d, *arrays) for d, arrays in entries],
                    recovered=True,
                )
            metrics.kvnet_transfer_bytes_total.labels(
                direction="in"
            ).inc(len(payload))
            ckpt = wire.decode_checkpoint(header["ckpt"])
            self.manager.stage_remote(ckpt, conn.peer_node)
            await conn.send(
                wire.OP_CKPT_STAGED,
                {"rid": rid, "request_id": ckpt.request_id},
            )
        elif op == wire.OP_CKPT_COMMIT:
            accepted = await self.manager.commit_remote(
                header["request_id"], conn
            )
            await conn.send(
                wire.OP_CKPT_COMMIT_R,
                {"rid": rid, "accepted": bool(accepted)},
            )
        elif op == wire.OP_CANCEL:
            self.manager.cancel_remote(header.get("request_id"))
        else:
            await conn.send(
                wire.OP_ERR,
                {"rid": rid, "error": f"unknown op {op}"},
            )

    async def _serve_get(
        self, conn: ServerConn, rid, header: dict  # noqa: ANN001
    ) -> None:
        """GET: host-RAM entries answer on the loop thread; disk-only
        digests load on a worker thread.  Served blobs re-checksum on
        the receiver, so a miss here is honest, never a guess."""
        wanted = [bytes.fromhex(h) for h in header.get("digests", [])]
        items: list = []
        disk_wanted: list = []
        for digest in wanted:
            entry = self.tier._get_valid(digest)  # noqa: SLF001
            if entry is not None:
                items.append((digest, entry.arrays))
            elif (
                self.tier.disk is not None
                and self.tier.disk.has(digest)
            ):
                disk_wanted.append(digest)
        if disk_wanted:
            disk = self.tier.disk

            def _load_all() -> list:
                out = []
                for digest in disk_wanted:
                    arrays = disk.load(digest)
                    if arrays is not None:
                        out.append((digest, arrays))
                return out

            items.extend(await asyncio.to_thread(_load_all))
        payload = wire.pack_entries(items)
        metrics.kvnet_transfer_bytes_total.labels(
            direction="out"
        ).inc(len(payload))
        await conn.send(
            wire.OP_GET_R,
            {"rid": rid, "hits": [d.hex() for d, _ in items]},
            payload,
        )
