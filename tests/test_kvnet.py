"""Networked KV tier (vllm_tgis_adapter_tpu/kvnet/, docs/CROSS_HOST.md).

Covers the wire codec (framing, version/flag gates, entry payloads,
checkpoint/output schemas), the staged-handoff bookkeeping (claim-once,
peer-death adoption), the config surface (prefill-only topologies are
legal exactly when kvnet peers exist), and the end-to-end guarantees:

- two in-process engines over loopback TCP: a remote prefix hit and a
  remote DecodeCheckpoint handoff are token-identical to the
  single-engine baseline;
- machine-loss resume: the prefill-side peer dies mid-decode and the
  survivor finishes the stream with zero lost outputs;
- two OS processes over localhost TCP: cross-process prefix hit and
  handoff, token-identical to single-process (tests/kvnet_harness.py).

Runs on the CPU backend (conftest virtual-device mesh).
"""

from __future__ import annotations

import asyncio
import json
import socket
import struct

import numpy as np
import pytest

from vllm_tgis_adapter_tpu.kvnet import wire


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ------------------------------------------------------------ wire codec


class _Reader:
    """Minimal asyncio-StreamReader stand-in over one bytes buffer."""

    def __init__(self, data: bytes) -> None:
        self._data = data
        self._pos = 0

    async def readexactly(self, n: int) -> bytes:
        if self._pos + n > len(self._data):
            raise asyncio.IncompleteReadError(b"", n)
        out = self._data[self._pos:self._pos + n]
        self._pos += n
        return out


def test_frame_roundtrip():
    frame = wire.encode_frame(
        wire.OP_GET, {"digests": ["ab" * 32]}, b"payload-bytes"
    )
    op, flags, header, payload = asyncio.run(
        wire.read_frame(_Reader(frame))
    )
    assert op == wire.OP_GET
    assert flags == 0
    assert header == {"digests": ["ab" * 32]}
    assert payload == b"payload-bytes"


def test_frame_rejects_bad_magic_and_newer_version():
    frame = bytearray(wire.encode_frame(wire.OP_PING, {}))
    frame[0:4] = b"XXXX"
    with pytest.raises(wire.ProtocolError, match="magic"):
        wire.decode_prefix(bytes(frame[:wire.PREFIX_LEN]))
    frame = bytearray(wire.encode_frame(wire.OP_PING, {}))
    frame[4] = wire.WIRE_VERSION + 1
    with pytest.raises(wire.ProtocolError, match="version"):
        wire.decode_prefix(bytes(frame[:wire.PREFIX_LEN]))


def test_frame_ignores_unknown_flags():
    # future writers may set flag bits this reader does not know;
    # the frame must still parse (mirror of the entry-header rule)
    frame = wire.encode_frame(wire.OP_PING, {"rid": 1}, flags=0x80)
    op, flags, header, _ = asyncio.run(wire.read_frame(_Reader(frame)))
    assert op == wire.OP_PING
    assert flags == 0x80
    assert header == {"rid": 1}


def test_frame_rejects_oversize():
    prefix = struct.pack(
        ">4sBBBBIQ", wire.MAGIC, wire.WIRE_VERSION, 0, wire.OP_PUT, 0,
        8, wire.MAX_PAYLOAD_BYTES + 1,
    )
    with pytest.raises(wire.ProtocolError, match="payload"):
        wire.decode_prefix(prefix)


def _pages(n=2, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (bytes([i] * 32),
         (rng.standard_normal((2, 4)).astype(np.float32),
          rng.standard_normal((2, 4)).astype(np.float32)))
        for i in range(n)
    ]


def test_entries_roundtrip():
    items = _pages(3)
    out = dict(wire.unpack_entries(wire.pack_entries(items)))
    assert set(out) == {d for d, _ in items}
    for digest, arrays in items:
        got = out[digest]
        assert len(got) == len(arrays)
        for a, b in zip(arrays, got):
            np.testing.assert_array_equal(a, b)


def test_entries_corrupt_blob_is_a_miss():
    items = _pages(2)
    payload = bytearray(wire.pack_entries(items))
    # flip one byte inside the FIRST entry's array payload: its
    # checksum fails and it reads as a miss; the second entry, behind
    # an intact length prefix, still decodes
    payload[len(payload) // 4] ^= 0xFF
    out = wire.unpack_entries(bytes(payload))
    assert len(out) == 1


def test_entry_version_gate_and_back_compat():
    from vllm_tgis_adapter_tpu.engine import kv_tier

    arrays = (np.ones((2, 2), np.float32),)
    blob = kv_tier.serialize_entry(arrays, {"kind": "kv"})
    header, payload = blob.split(b"\n", 1)
    meta = json.loads(header)
    # v0 reader compat: entries written before the version byte have
    # no "v"/"flags" keys and must still parse
    for key in ("v", "flags"):
        meta.pop(key)
    legacy = json.dumps(meta).encode() + b"\n" + payload
    assert kv_tier.parse_entry(legacy) is not None
    # from-the-future entries are refused like a checksum mismatch
    meta["v"] = kv_tier.ENTRY_VERSION + 1
    future = json.dumps(meta).encode() + b"\n" + payload
    assert kv_tier.parse_entry(future) is None
    # unknown flag bits are descriptive only — still served
    meta["v"] = kv_tier.ENTRY_VERSION
    meta["flags"] = 0x80
    flagged = json.dumps(meta).encode() + b"\n" + payload
    assert kv_tier.parse_entry(flagged) is not None


def test_sampling_params_codec():
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    params = SamplingParams(
        temperature=0.7, seed=41, max_tokens=9, ignore_eos=True,
        logprobs=2, output_kind=RequestOutputKind.DELTA,
    )
    out = wire.decode_params(wire.encode_params(params))
    assert out.temperature == params.temperature
    assert out.seed == params.seed
    assert out.max_tokens == params.max_tokens
    assert out.logprobs == params.logprobs
    assert out.output_kind is RequestOutputKind.DELTA


def test_checkpoint_codec():
    from vllm_tgis_adapter_tpu.engine.kv_tier import DecodeCheckpoint
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    ckpt = DecodeCheckpoint(
        request_id="r-1", prompt=None,
        prompt_token_ids=[3, 5, 7], output_token_ids=[11, 13],
        params=SamplingParams(temperature=0.5, seed=7, max_tokens=6),
        fallback_seed=1234, arrival_time=1.5, deadline=None,
        tenant_id="t0", lora_name=None, trace_id="tr",
        emitted_token_len=2, emitted_text_len=0, stop_scan_pos=0,
        output_logprobs=None, prompt_logprobs=None,
        first_scheduled_time=1.6, first_token_time=1.7,
        last_token_time=1.8, time_in_queue=0.1,
        digests=[b"\x01" * 32, b"\x02" * 32], pages=2,
    )
    out = wire.decode_checkpoint(wire.encode_checkpoint(ckpt))
    assert out.request_id == ckpt.request_id
    assert out.prompt_token_ids == ckpt.prompt_token_ids
    assert out.output_token_ids == ckpt.output_token_ids
    assert out.fallback_seed == ckpt.fallback_seed
    assert out.digests == ckpt.digests
    assert out.pages == ckpt.pages
    assert out.params.seed == 7
    assert out.request_class == "chat"
    assert out.cancelled is False


def test_request_output_codec():
    from vllm_tgis_adapter_tpu.engine.outputs import (
        CompletionOutput,
        RequestOutput,
    )

    out = RequestOutput(
        request_id="r-2", prompt=None, prompt_token_ids=[1, 2],
        outputs=[CompletionOutput(
            index=0, text="ab", token_ids=[5, 6], cumulative_logprob=None,
            logprobs=None, finish_reason="length",
        )],
        finished=True,
    )
    got = wire.decode_request_output(wire.encode_request_output(out))
    assert got.request_id == "r-2"
    assert got.finished is True
    assert got.outputs[0].token_ids == [5, 6]
    assert got.outputs[0].finish_reason == "length"


# ---------------------------------------------------- staged bookkeeping


def _mini_ckpt(rid: str):
    from vllm_tgis_adapter_tpu.engine.kv_tier import DecodeCheckpoint
    from vllm_tgis_adapter_tpu.engine.sampling_params import SamplingParams

    return DecodeCheckpoint(
        request_id=rid, prompt=None, prompt_token_ids=[1],
        output_token_ids=[], params=SamplingParams(max_tokens=2),
        fallback_seed=0, arrival_time=0.0, deadline=None,
        tenant_id=None, lora_name=None, trace_id=None,
        emitted_token_len=0, emitted_text_len=0, stop_scan_pos=0,
        output_logprobs=None, prompt_logprobs=None,
        first_scheduled_time=None, first_token_time=None,
        last_token_time=None, time_in_queue=None, digests=[], pages=0,
    )


def test_staged_handoffs_claim_once():
    from vllm_tgis_adapter_tpu.kvnet.manager import StagedHandoffs

    staged = StagedHandoffs()
    staged.stage(_mini_ckpt("r-1"), "peer-a")
    first = staged.claim("r-1")
    assert first is not None and first["ckpt"].request_id == "r-1"
    # a second claim — the duplicate-commit / commit-vs-adopt race —
    # must observe nothing: at-most-once promotion
    assert staged.claim("r-1") is None
    assert staged.pending() == 0


def test_staged_handoffs_adopt_for_peer():
    from vllm_tgis_adapter_tpu.kvnet.manager import StagedHandoffs

    staged = StagedHandoffs()
    staged.stage(_mini_ckpt("r-1"), "peer-a")
    staged.stage(_mini_ckpt("r-2"), "peer-a")
    staged.stage(_mini_ckpt("r-3"), "peer-b")
    assert staged.claim("r-1") is not None
    adopted = staged.adopt_for_peer("peer-a")
    # r-1 was already claimed; only r-2 is adoptable, r-3 belongs to a
    # live peer and must stay staged
    assert [rec["ckpt"].request_id for rec in adopted] == ["r-2"]
    assert staged.pending() == 1


# ------------------------------------------------------ service shutdown


class _StubManager:
    """What ``KvTierService`` needs of its manager for HELLO."""

    node_id = "S"

    def __init__(self) -> None:
        self.inbound: dict = {}

    def note_inbound(self, node, conn) -> None:
        self.inbound[node] = conn

    def note_inbound_lost(self, node, conn) -> None:
        self.inbound.pop(node, None)


def test_service_stop_does_not_wait_on_a_silent_peer():
    """A peer that said HELLO and then stays silent holds its handler in
    ``read_frame``; ``stop()`` must close that link itself and return
    (from Python 3.12 ``Server.wait_closed()`` waits for every accepted
    connection, so listener-first order never returned)."""
    from vllm_tgis_adapter_tpu.kvnet.service import KvTierService

    async def scenario():
        manager = _StubManager()
        service = KvTierService(manager, tier=None, listen="127.0.0.1:0")
        await service.start()
        before = asyncio.all_tasks()
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", service.port
        )
        writer.write(wire.encode_frame(
            wire.OP_HELLO, {"rid": 1, "node": "silent"}
        ))
        await writer.drain()
        op, _flags, header, _ = await asyncio.wait_for(
            wire.read_frame(reader), 5
        )
        assert op == wire.OP_HELLO_R and header["node"] == "S"
        assert "silent" in manager.inbound
        handlers = asyncio.all_tasks() - before
        assert len(handlers) == 1
        try:
            await asyncio.wait_for(service.stop(), 5)
            done, _ = await asyncio.wait(handlers, timeout=5)
            assert done == handlers
            assert "silent" not in manager.inbound
            # the peer sees EOF, not a hang of its own
            with pytest.raises(asyncio.IncompleteReadError):
                await asyncio.wait_for(wire.read_frame(reader), 5)
            # a second stop (engine.stop() after kvnet.stop()) is a no-op
            await asyncio.wait_for(service.stop(), 5)
        finally:
            writer.close()

    asyncio.run(scenario())


# -------------------------------------------------------- config surface


def test_prefill_only_topology_requires_peers(tiny_model_dir):
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )

    model_config = ModelConfig.from_pretrained(
        tiny_model_dir, dtype="float32"
    )

    def make(**overrides):
        kwargs = dict(
            model_config=model_config,
            cache_config=CacheConfig(
                block_size=16, num_blocks=64,
                cache_dtype=model_config.dtype,
            ),
            scheduler_config=SchedulerConfig(max_num_seqs=2),
            parallel_config=ParallelConfig(dp_replicas=1),
            lora_config=LoRAConfig(),
            kv_host_cache_gb=1.0,
            dp_replica_roles=("prefill",),
        )
        kwargs.update(overrides)
        return EngineConfig(**kwargs)

    # a lone prefill host is a dead end without peers...
    with pytest.raises(ValueError, match="decode-capable"):
        make()
    # ...but legal when decode capacity exists across the kvnet
    cfg = make(kvnet_peers=("127.0.0.1:19999",))
    assert cfg.resolved_replica_roles() == ("prefill",)
    # and symmetrically for a decode-only host
    with pytest.raises(ValueError, match="prefill-capable"):
        make(dp_replica_roles=("decode",))
    make(dp_replica_roles=("decode",),
         kvnet_peers=("127.0.0.1:19999",))


# ----------------------------------------- two engines, one process


PROMPT = [3 + i for i in range(48)]  # 3 full pages @ block_size 16


@pytest.fixture(scope="module")
def netpair(tiny_model_dir):
    """Engine A (prefill-only, node "A") and engine B (mixed, node "B")
    peered over loopback TCP, plus a plain single-engine baseline."""
    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )

    model_config = ModelConfig.from_pretrained(
        tiny_model_dir, dtype="float32"
    )

    def make(**overrides):
        kwargs = dict(
            model_config=model_config,
            cache_config=CacheConfig(
                block_size=16, num_blocks=96,
                cache_dtype=model_config.dtype,
                # demote at prefill commit so pages are INDEX-visible
                # without needing device-LRU pressure
                enable_prefix_caching=False,
            ),
            scheduler_config=SchedulerConfig(
                max_num_seqs=4, prefill_buckets=(32, 64)
            ),
            parallel_config=ParallelConfig(dp_replicas=1),
            lora_config=LoRAConfig(),
            kv_host_cache_gb=1.0,
        )
        kwargs.update(overrides)
        return AsyncLLMEngine.from_config(EngineConfig(**kwargs))

    async def build():
        port_a, port_b = _free_port(), _free_port()
        baseline = make()
        a = make(
            dp_replica_roles=("prefill",),
            kvnet_listen=f"127.0.0.1:{port_a}",
            kvnet_peers=(f"127.0.0.1:{port_b}",),
            kvnet_node_id="A",
        )
        b = make(
            kvnet_listen=f"127.0.0.1:{port_b}",
            kvnet_peers=(f"127.0.0.1:{port_a}",),
            kvnet_node_id="B",
        )
        await baseline.start()
        await a.start()
        await b.start()
        # first heartbeat round: both peer links healthy
        for _ in range(100):
            if (a.kvnet.peers[0].connected
                    and b.kvnet.peers[0].connected):
                break
            await asyncio.sleep(0.05)
        return baseline, a, b

    loop = asyncio.new_event_loop()
    baseline, a, b = loop.run_until_complete(build())
    yield loop, baseline, a, b

    async def teardown():
        await asyncio.gather(baseline.stop(), a.stop(), b.stop(),
                             return_exceptions=True)

    loop.run_until_complete(teardown())
    loop.close()


async def _stream(engine, rid, ids, *, max_tokens=10, temperature=0.0,
                  seed=None):
    from vllm_tgis_adapter_tpu.engine.sampling_params import (
        RequestOutputKind,
        SamplingParams,
    )

    toks: list[int] = []
    async for out in engine.generate(
        None,
        SamplingParams(
            temperature=temperature, seed=seed, max_tokens=max_tokens,
            ignore_eos=True, output_kind=RequestOutputKind.DELTA,
        ),
        request_id=rid,
        prompt_token_ids=list(ids),
    ):
        toks.extend(out.outputs[0].token_ids)
    return toks


def test_remote_handoff_and_prefix_hit_token_identical(netpair):
    """The acceptance path: B computes the baseline (and thereby owns
    the prefix pages); A — prefill-only, so EVERY request of its hands
    off — prefills the same prompt via a cross-engine remote prefix
    fetch from B, then hands the mid-decode checkpoint to B over TCP.
    Both streams must be token-identical to the baseline engine's."""
    from vllm_tgis_adapter_tpu import metrics

    loop, baseline, a, b = netpair

    async def scenario():
        base = await _stream(baseline, "base-1", PROMPT)
        mine = await _stream(b, "warm-1", PROMPT)
        assert mine == base
        # INDEX sync: A's mirror of B must learn B's demoted pages
        for _ in range(120):
            if a.kvnet.peers[0].mirror:
                break
            await asyncio.sleep(0.05)
        assert a.kvnet.peers[0].mirror, "INDEX sync never surfaced B's pages"
        hits_before = metrics.kvnet_remote_hits_total._value.get()  # noqa: SLF001
        remote = metrics.kvnet_handoffs_total.labels(outcome="remote")
        remote_before = remote._value.get()  # noqa: SLF001
        handed = await _stream(a, "hand-1", PROMPT)
        assert handed == base
        assert metrics.kvnet_remote_hits_total._value.get() > hits_before  # noqa: SLF001
        assert remote._value.get() == remote_before + 1  # noqa: SLF001
        # the handoff ran to completion and retired its source-side state
        assert not a.kvnet.remote_out
        assert b.kvnet.staged.pending() == 0
        return True

    assert loop.run_until_complete(scenario())


def test_machine_loss_resume_zero_lost_outputs(netpair):
    """Peer-death adoption: A hands a long decode to B, the consumer
    reads a few tokens, then A's kvnet dies abruptly.  B must notice
    the dead inbound link, orphan the stream, FINISH it locally, and
    bank the undelivered tail in ``completed`` — the zero-lost-outputs
    ledger.  Runs last in this module: it tears A's kvnet down."""
    loop, baseline, a, b = netpair
    prompt = [7 + i for i in range(40)]

    async def scenario():
        base = await _stream(baseline, "base-2", prompt, max_tokens=48)

        from vllm_tgis_adapter_tpu.engine.sampling_params import (
            RequestOutputKind,
            SamplingParams,
        )

        got: list[int] = []

        async def consume():
            try:
                async for out in a.generate(
                    None,
                    SamplingParams(
                        temperature=0.0, max_tokens=48, ignore_eos=True,
                        output_kind=RequestOutputKind.DELTA,
                    ),
                    request_id="lost-1",
                    prompt_token_ids=list(prompt),
                ):
                    got.extend(out.outputs[0].token_ids)
            except Exception:  # noqa: BLE001 — death mid-stream is the point
                pass

        # hold B's replica lock BEFORE the request: the cross-host
        # resume (kvnet/manager._resume_remote) registers the consumer
        # queue, then BLOCKS on this lock — so the kill below lands
        # deterministically before B has decoded a single token
        async with b._replicas[0].lock:  # noqa: SLF001
            task = asyncio.ensure_future(consume())
            for _ in range(5000):
                if "lost-1" in b._queues:  # noqa: SLF001
                    break
                await asyncio.sleep(0.005)
            assert "lost-1" in b._queues, (  # noqa: SLF001
                "handoff never reached B"
            )
            # A's "machine" drops off the network mid-handoff
            await a.kvnet.stop()
            await asyncio.sleep(0.2)
        # lock released: the resume proceeds on B, the pump finds the
        # dead inbound link, and the whole decode banks into the
        # zero-lost-outputs ledger.  Generous wait: the tail chunks
        # compile novel chained-decode shapes on a cold CPU backend.
        for _ in range(1200):
            if "lost-1" in b.kvnet.completed:
                break
            await asyncio.sleep(0.05)
        assert "lost-1" in b.kvnet.completed, b.kvnet.debug_state()
        tail: list[int] = []
        for out in b.kvnet.completed["lost-1"]:
            tail.extend(out.outputs[0].token_ids)
        # zero lost, zero duplicated: delivered head + banked tail is
        # exactly the baseline decode
        assert got + tail == base, (got, tail, base)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        return True

    assert loop.run_until_complete(scenario())


# -------------------------------------------- two processes, real TCP


def test_cross_process_prefix_hit_and_handoff(tiny_model_dir):
    """The ISSUE's acceptance gate, for real: two separate OS processes
    serve one workload over localhost TCP.  The child (mixed) computes
    the single-process baseline; the parent engine (prefill-only)
    serves the same prompt via a cross-PROCESS remote prefix fetch and
    hands its decode checkpoint across — token-identical both ways."""
    import queue
    import subprocess
    import sys
    import threading
    import time
    from pathlib import Path

    from vllm_tgis_adapter_tpu.engine.async_llm import AsyncLLMEngine
    from vllm_tgis_adapter_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        LoRAConfig,
        ModelConfig,
        ParallelConfig,
        SchedulerConfig,
    )

    port_a = _free_port()
    # the harness and the package of the tree under test, wherever it
    # is checked out
    repo_root = Path(__file__).resolve().parent.parent
    child = subprocess.Popen(
        [sys.executable, "tests/kvnet_harness.py", tiny_model_dir,
         "--listen", "127.0.0.1:0", "--peers", f"127.0.0.1:{port_a}",
         "--node-id", "B"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=repo_root,
    )
    # a reader thread, so that every wait on the child has a deadline
    lines: queue.Queue = queue.Queue()

    def pump_stdout():
        for line in child.stdout:
            lines.put(line)
        lines.put("")  # EOF

    threading.Thread(target=pump_stdout, daemon=True).start()

    def rpc(obj):
        child.stdin.write(json.dumps(obj) + "\n")
        child.stdin.flush()

    def read_event(kind, timeout_s=60.0):
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                line = lines.get(timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                raise AssertionError(
                    f"no {kind} from the harness in {timeout_s:.0f}s"
                ) from None
            if not line:
                raise AssertionError("harness died before " + kind)
            try:
                evt = json.loads(line)
            except ValueError:
                continue
            if evt.get("event") == kind:
                return evt

    try:
        ready = read_event("ready")
        port_b = ready["port"]
        assert port_b

        # single-process baseline, computed by the child itself
        rpc({"cmd": "run", "rid": "base-1", "prompt": PROMPT,
             "max_tokens": 10, "temperature": 0.0})
        base = read_event("done")
        assert base["status"] == "ok", base

        model_config = ModelConfig.from_pretrained(
            tiny_model_dir, dtype="float32"
        )
        engine = AsyncLLMEngine.from_config(EngineConfig(
            model_config=model_config,
            cache_config=CacheConfig(
                block_size=16, num_blocks=96,
                cache_dtype=model_config.dtype,
                enable_prefix_caching=False,
            ),
            scheduler_config=SchedulerConfig(
                max_num_seqs=4, prefill_buckets=(32, 64)
            ),
            parallel_config=ParallelConfig(dp_replicas=1),
            lora_config=LoRAConfig(),
            kv_host_cache_gb=1.0,
            dp_replica_roles=("prefill",),
            kvnet_listen=f"127.0.0.1:{port_a}",
            kvnet_peers=(f"127.0.0.1:{port_b}",),
            kvnet_node_id="A",
        ))

        async def scenario():
            await engine.start()
            peer = engine.kvnet.peers[0]
            for _ in range(200):
                if peer.connected and peer.mirror:
                    break
                await asyncio.sleep(0.05)
            assert peer.connected, "never connected to the child process"
            assert peer.mirror, "cross-process INDEX sync never arrived"
            toks = await _stream(engine, "hand-x", PROMPT)
            # remote fetch MUST have served prefix pages: the tier's
            # lifetime hit counter moved inside THIS process
            assert engine.engine.kv_tier.remote._hits > 0  # noqa: SLF001
            await engine.stop()
            return toks

        loop = asyncio.new_event_loop()
        try:
            toks = loop.run_until_complete(scenario())
        finally:
            loop.close()
        assert toks == base["tokens"], (toks, base["tokens"])

        rpc({"cmd": "stop"})
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=30)
