"""The load generator: one process, one thread, one asyncio loop, one
gRPC channel.  Sends ``GenerateStream`` requests on a schedule (open
loop) or from callers that each wait for their reply (closed loop), and
stamps every frame on the client's clock.

Requests are greedy with ``min_new_tokens = max_new_tokens``, and ask
for generated tokens with their logprobs, as ``chip_smoke.generate``
does, so every request runs the same sampler program."""

from __future__ import annotations

import asyncio
import math
import time

import grpc

from e2e import Record
from traffic import Planned

REQUEST_TIMEOUT_S = 1500


class Client:
    def __init__(self, grpc_port: int):
        from vllm_tgis_adapter_tpu.grpc.pb import generation_pb2 as pb2
        from vllm_tgis_adapter_tpu.grpc.pb.rpc import GenerationServiceStub

        self.pb2 = pb2
        self.channel = grpc.aio.insecure_channel(f"127.0.0.1:{grpc_port}")
        self.stub = GenerationServiceStub(self.channel)

    async def close(self) -> None:
        await self.channel.close()

    async def generate(self, prompt: str, record: Record) -> None:
        """One stream; fills ``record`` and checks everything one
        response can show: one input frame, then exactly the asked number
        of token frames, finite logprobs <= 0, the prompt's token count."""
        pb2 = self.pb2
        n = record.output_tokens
        request = pb2.SingleGenerationRequest(
            request=pb2.GenerationRequest(text=prompt),
            params=pb2.Parameters(
                method=pb2.DecodingMethod.GREEDY,
                stopping=pb2.StoppingCriteria(
                    max_new_tokens=n, min_new_tokens=n
                ),
                response=pb2.ResponseOptions(
                    generated_tokens=True, token_logprobs=True
                ),
            ),
        )
        record.sent = time.monotonic()
        try:
            first = True
            async for frame in self.stub.GenerateStream(
                request, timeout=REQUEST_TIMEOUT_S
            ):
                now = time.monotonic()
                if first:
                    first = False
                    if frame.generated_token_count or frame.tokens:
                        raise ValueError("first stream frame carries tokens")
                    if frame.input_token_count != record.prompt_tokens:
                        raise ValueError(
                            f"server counted {frame.input_token_count} "
                            f"prompt tokens, sent {record.prompt_tokens}"
                        )
                    continue
                if not frame.tokens:
                    raise ValueError("a later stream frame carries no token")
                for tok in frame.tokens:
                    if not (math.isfinite(tok.logprob) and tok.logprob <= 0):
                        raise ValueError(f"logprob {tok.logprob}")
                    record.tokens.append((tok.text, tok.logprob))
                    record.token_times.append(now)
                count = frame.generated_token_count
            if first or len(record.tokens) != n or count != n:
                raise ValueError(
                    f"asked for {n} tokens, got {len(record.tokens)}"
                )
            record.ok = True
        except asyncio.CancelledError:
            record.error = "cancelled"
            raise
        except (grpc.RpcError, ValueError) as e:
            record.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            record.done = time.monotonic()


class Control:
    """What the conductor decides while traffic flows: when the measured
    window is (absolute ``time.monotonic`` instants, unknown until the
    warm-up has settled) and when to cut what is left (``limit_at``)."""

    def __init__(self) -> None:
        self.window: tuple[float, float] | None = None
        self.limit_at: float | None = None
        self.records: list[Record] = []

    def mark_sample(self) -> None:
        start, end = self.window
        for r in self.records:
            r.in_sample = start <= r.due < end

    def in_flight(self) -> int:
        return sum(1 for r in self.records if r.done is None)

    def over(self) -> bool:
        """The window is past and every request due (open loop) or
        started (closed loop) inside it has ended, or the limit is hit."""
        now = time.monotonic()
        if self.limit_at is not None and now >= self.limit_at:
            return True
        if self.window is None or now < self.window[1]:
            return False
        start, end = self.window
        return all(r.done is not None for r in self.records
                   if start <= r.due < end)


async def run_open(client: Client, plan: list[Planned], prompts: list[str],
                   t0: float, control: Control | None = None) -> list[Record]:
    """Send ``plan`` on its schedule from ``t0`` (``time.monotonic``).
    Without ``control``: send all, wait for all.  With it: traffic flows
    until ``control.over()``."""
    free_run = control is None
    control = control or Control()
    records, tasks = control.records, []
    for planned, prompt in zip(plan, prompts):
        due = t0 + planned.due
        while (free_run or not control.over()) and (
            delay := due - time.monotonic()
        ) > 0:
            await asyncio.sleep(min(delay, 0.25))
        if not free_run and control.over():
            break
        record = Record(due, planned.prompt_tokens, planned.output_tokens)
        records.append(record)
        tasks.append(asyncio.create_task(client.generate(prompt, record)))
    else:
        if not free_run:
            raise RuntimeError("the schedule ran out before the window ended")
    await _finish(tasks, (lambda: False) if free_run else control.over)
    return records


async def run_closed(client: Client, sizes: list[tuple[int, int]],
                     prompts: list[str], callers: int,
                     control: Control) -> list[Record]:
    """``callers`` callers, each sending its next request when the last
    has ended, until ``control.over()``; a request is due when its caller
    is free.  The list is walked round and round."""
    records = control.records
    cursor = [0]

    async def caller() -> None:
        while not control.over():
            i = cursor[0] % len(sizes)
            cursor[0] += 1
            record = Record(time.monotonic(), *sizes[i])
            records.append(record)
            await client.generate(prompts[i], record)

    tasks = [asyncio.create_task(caller()) for _ in range(callers)]
    await _finish(tasks, control.over)
    return records


async def _finish(tasks: list[asyncio.Task], enough) -> None:
    """Wait for the tasks until ``enough()``; then cancel what is still in
    flight (a sample request cut so counts as failed)."""
    pending = {t for t in tasks if not t.done()}
    while pending and not enough():
        _, pending = await asyncio.wait(pending, timeout=0.25)
    for task in pending:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
