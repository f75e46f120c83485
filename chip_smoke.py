#!/usr/bin/env python3
"""Chip smoke: the served path, once, on the TPU, at granite-2b's widths.

    python chip_smoke.py              # one chip; what the driver runs
    python chip_smoke.py --chips 4    # tp=4 against tp=1, nothing else
    python chip_smoke.py --tiny       # CPU rehearsal, hidden-64 fixture

The parent process never imports JAX: it writes a seeded checkpoint,
then starts one chip-holding child at a time — first a kernel check
(the compiled ragged kernel against its XLA reference on the same
device), then the real server through its normal entry point
(``python -m vllm_tgis_adapter_tpu``), to which it talks over gRPC and
HTTP like any client.  Every failed phase raises; there is no fallback to
the CPU and nothing is skipped.  Each earlier line of output is one JSON
object of smoke output (NOT a benchmark result); the last line is
``{"ok": true, "device": {...}}`` with the device as the server's own
``/debug/state`` reports it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent
#: the checkpoint and nothing else; deleted at the end (git-ignored, and
#: outside chiprun_out/, which the chip tool copies back)
WORK_DIR = ROOT / ".chip_smoke_work"
LOG_DIR = ROOT / "chiprun_out"

# ibm-granite/granite-3.0-2b-instruct, config.json as published on the
# Hugging Face hub — written from memory of that file, without network
# access, hence printed under "assumed"
GRANITE_2B = {
    "architectures": ["GraniteForCausalLM"],
    "model_type": "granite",
    "vocab_size": 49155,
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "num_hidden_layers": 40,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "max_position_embeddings": 4096,
    "hidden_act": "silu",
    "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0,
    "tie_word_embeddings": True,
    "attention_bias": False,
    "mlp_bias": False,
    "embedding_multiplier": 12.0,
    "attention_multiplier": 0.015625,
    "residual_multiplier": 0.22,
    "logits_scaling": 8.0,
    "bos_token_id": 0,
    "eos_token_id": 0,
    "pad_token_id": 0,
    "torch_dtype": "bfloat16",
}
NEW_TOKENS = 32
#: max |Δ| of first-token logprobs, tp=4 against tp=1: both are bf16
#: matmuls, summed in a different order across four shards
TP_LOGPROB_TOL = 0.05


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ------------------------------------------------------------ checkpoint


def write_granite_checkpoint(path: Path, seed: int) -> None:
    """config.json + tokenizer + bf16 safetensors shards of seeded random
    weights, written four layers at a time from worker threads: no more
    than a few shards are ever in memory (the float32 one-file fixture
    writer would hold 10 GB)."""
    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file

    from tests.fixture_models import build_tokenizer

    cfg = GRANITE_2B
    path.mkdir(parents=True)
    (path / "config.json").write_text(json.dumps(cfg, indent=2))
    build_tokenizer(str(path), vocab_size=cfg["vocab_size"])

    d, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // heads
    layers = cfg["num_hidden_layers"]
    per_shard = 4
    groups = [
        list(range(lo, min(lo + per_shard, layers)))
        for lo in range(0, layers, per_shard)
    ]
    names = [
        f"model-{i + 1:05d}-of-{len(groups) + 1:05d}.safetensors"
        for i in range(len(groups) + 1)
    ]

    def rand(rng, shape):
        w = rng.standard_normal(shape, dtype=np.float32)
        w *= 0.02
        return w.astype(ml_dtypes.bfloat16)

    ones = np.ones(d, ml_dtypes.bfloat16)

    def write_shard(index: int) -> dict[str, str]:
        tensors = {}
        if index == len(groups):
            rng = np.random.default_rng([seed, layers])
            tensors["model.embed_tokens.weight"] = rand(
                rng, (cfg["vocab_size"], d)
            )
            tensors["model.norm.weight"] = ones
        else:
            for i in groups[index]:
                rng = np.random.default_rng([seed, i])
                p = f"model.layers.{i}"
                tensors |= {
                    f"{p}.input_layernorm.weight": ones,
                    f"{p}.post_attention_layernorm.weight": ones,
                    f"{p}.self_attn.q_proj.weight": rand(rng, (heads * dh, d)),
                    f"{p}.self_attn.k_proj.weight": rand(rng, (kv * dh, d)),
                    f"{p}.self_attn.v_proj.weight": rand(rng, (kv * dh, d)),
                    f"{p}.self_attn.o_proj.weight": rand(rng, (d, heads * dh)),
                    f"{p}.mlp.gate_proj.weight": rand(rng, (ffn, d)),
                    f"{p}.mlp.up_proj.weight": rand(rng, (ffn, d)),
                    f"{p}.mlp.down_proj.weight": rand(rng, (d, ffn)),
                }
        save_file(tensors, path / names[index])
        return {name: names[index] for name in tensors}

    weight_map: dict[str, str] = {}
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        for part in pool.map(write_shard, range(len(names))):
            weight_map |= part
    (path / "model.safetensors.index.json").write_text(
        json.dumps({"metadata": {}, "weight_map": weight_map})
    )


def write_tiny_checkpoint(path: Path, seed: int) -> None:
    """The hidden-64 llama fixture of the tests, with head counts that
    four devices divide."""
    from tests.fixture_models import (
        TINY_LLAMA_CONFIG,
        build_tokenizer,
        write_llama_safetensors,
    )

    cfg = dict(TINY_LLAMA_CONFIG, num_attention_heads=8,
               num_key_value_heads=4)
    path.mkdir(parents=True)
    build_tokenizer(str(path), vocab_size=cfg["vocab_size"])
    (path / "config.json").write_text(json.dumps(cfg, indent=2))
    write_llama_safetensors(
        str(path), vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], seed=seed,
    )


def make_prompts(model_dir: Path, lengths: list[int], seed: int) -> list[str]:
    """One prompt of exactly each length, in tokens of the checkpoint's
    own tokenizer.  Every atom starts at a pre-tokenizer boundary, so
    token counts add up."""
    import random

    from transformers import AutoTokenizer

    from tests.fixture_models import _CORPUS

    tok = AutoTokenizer.from_pretrained(str(model_dir))
    rng = random.Random(seed)
    words = sorted({" " + w for line in _CORPUS for w in line.split()})
    fillers = [t for t in tok.get_added_vocab() if t.startswith("<filler_")]
    atoms = {w: len(tok(w).input_ids) for w in words}
    atoms |= {f: 1 for f in rng.sample(fillers, min(len(fillers), 4096))}
    singles = [a for a, n in atoms.items() if n == 1]
    names = list(atoms)
    prompts = []
    for want in lengths:
        parts, have = [], 0
        while have < want:
            atom = rng.choice(names)
            if have + atoms[atom] > want:
                atom = rng.choice(singles)
            parts.append(atom)
            have += atoms[atom]
        text = "".join(parts)
        got = len(tok(text).input_ids)
        if got != want:
            raise RuntimeError(f"prompt built for {want} tokens has {got}")
        prompts.append(text)
    return prompts


# -------------------------------------------------- children (hold the chip)


def child_env(tiny: bool, chips: int) -> dict[str, str]:
    """The environment of a chip-holding child: the caller's, minus
    everything that could move it to another device or kernel.
    ``JAX_COMPILATION_CACHE_DIR`` passes through untouched."""
    env = dict(os.environ)
    for name in ("ATTENTION_BACKEND", "XLA_FLAGS", "JAX_PLATFORM_NAME"):
        env.pop(name, None)
    env["JAX_PLATFORMS"] = "cpu" if tiny else "tpu"
    if tiny and chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    return env


def run_phase(name: str, args: argparse.Namespace) -> None:
    """Run one ``--phase`` of this file in its own process, to its end."""
    cmd = [sys.executable, str(ROOT / "chip_smoke.py"), "--phase", name,
           "--chips", str(args.chips), "--seed", str(args.seed)]
    if args.tiny:
        cmd.append("--tiny")
    subprocess.run(cmd, cwd=ROOT, env=child_env(args.tiny, args.chips),
                   check=True, timeout=600)


def phase_kernel(args: argparse.Namespace) -> None:
    """On the chip: the Mosaic-compiled ragged kernel against the XLA
    reference on the same device, at the widths the server will run it
    (granite-2b heads, rows as wide as the pool keeps them), one mixed
    batch and one pure-decode batch.  Tolerance as in
    tests/test_tpu_kernels.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_tgis_adapter_tpu.ops import kv_quant
    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra

    device = jax.devices()[0]
    if not args.tiny and device.platform != "tpu":
        raise RuntimeError(f"kernel phase is on {device.platform}, not tpu")
    h, hkv, dh, block = (4, 2, 16, 16) if args.tiny else (32, 8, 64, 16)
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    width = kv_quant.cache_head_dim(dh)
    rng = np.random.default_rng(args.seed)
    num_slots = 256 * block

    def rows(*shape):
        x = np.zeros((*shape, width), np.float32)
        x[..., :dh] = rng.standard_normal((*shape, dh))
        return jnp.asarray(x, dtype)

    kc, vc = rows(hkv, num_slots), rows(hkv, num_slots)
    scale = dh**-0.5
    results = {}

    # mixed: a fresh 130-token prefill, a decode row deep in its context,
    # a 50-token chunk resuming at position 77
    max_blocks = 32
    cases = [(0, 130), (255, 1), (77, 50)]
    tables = np.zeros((4, max_blocks), np.int32)
    spans, positions, next_block, row = [], [], 0, 0
    for s, (ctx, n_new) in enumerate(cases):
        nb = -(-(ctx + n_new) // block)
        tables[s, :nb] = range(next_block, next_block + nb)
        next_block += nb
        spans.append((row, n_new, ctx))
        positions += range(ctx, ctx + n_new)
        row += n_new
    t, block_q = row, 128
    t_pad = -(-t // block_q) * block_q
    q = jnp.pad(rows(t, h), ((0, t_pad - t), (0, 0), (0, 0)))
    pos = np.zeros(t_pad, np.int32)
    pos[:t] = positions
    starts = np.full(5, t_pad, np.int32)
    starts[:3] = [s[0] for s in spans]
    starts[3] = t
    base = np.zeros(4, np.int32)
    base[:3] = [s[2] for s in spans]
    work, n_items = ra.build_work_schedule(
        spans, tables, block_size=block, block_q=block_q, t_pad=t_pad
    )
    got = ra._ragged_attention_pallas(
        q, kc, vc, jnp.asarray(starts), jnp.asarray(base),
        jnp.asarray(work), block, scale, block_q=block_q, window=0,
        alibi_slopes=None, interpret=args.tiny,
        n_items=jnp.asarray(n_items, jnp.int32),
    )
    ref = ra.ragged_attention_xla(
        q, kc, vc, jnp.asarray(pos), jnp.asarray(starts), jnp.asarray(t),
        jnp.asarray(tables), block, scale,
    )
    results["mixed"] = (np.asarray(got, np.float32)[:t],
                        np.asarray(ref, np.float32)[:t])

    # pure decode: 64 one-row spans, a quarter of them dead, over the
    # wave's compact schedule with its traced grid extent
    b, max_blocks = 64, 16
    ctx = rng.integers(1, max_blocks * block, b).astype(np.int32)
    tables = rng.permutation(256)[: b * 4].reshape(b, 4).astype(np.int32)
    tables = np.concatenate(
        [tables] * (max_blocks // 4), axis=1
    )  # pages reused across a row: reads only, any mapping is legal
    q = rows(b, h)
    pos_b = jnp.asarray(ctx - 1)
    starts_b = jnp.arange(b + 1, dtype=jnp.int32)
    live = np.arange(b) % 4 != 1
    work, n_items = ra.decode_work_schedule(
        pos_b, jnp.asarray(live), jnp.asarray(tables), block_size=block
    )
    run = ra.pages_per_item(block)
    if int(n_items) != int((-(-((ctx[live] - 1) // block + 1) // run)).sum()):
        raise RuntimeError(f"decode schedule: {int(n_items)} items")
    got = ra._ragged_attention_pallas(
        q, kc, vc, starts_b, pos_b, work, block, scale,
        block_q=ra.decode_block_q(b), window=0, interpret=args.tiny,
        n_items=n_items,
    )
    ref = ra.ragged_attention_xla(
        q, kc, vc, pos_b, starts_b, jnp.asarray(b), jnp.asarray(tables),
        block, scale,
    )
    results["decode"] = (np.asarray(got, np.float32)[live],
                         np.asarray(ref, np.float32)[live])
    if not np.isfinite(np.asarray(got, np.float32)).all():
        raise RuntimeError("ragged kernel (decode): dead rows not finite")

    for name, (got, ref) in results.items():
        if not np.isfinite(got).all():
            raise RuntimeError(f"ragged kernel ({name}): non-finite output")
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)
        if width > dh and np.abs(got[..., dh:]).max() != 0:
            raise RuntimeError(f"ragged kernel ({name}): padded lanes != 0")
    emit({
        "phase": "kernel", "platform": device.platform,
        "interpret": args.tiny, "heads": h, "kv_heads": hkv,
        "head_dim": dh, "pool_row_width": width,
        "max_abs_err": {
            name: float(np.abs(got - ref).max())
            for name, (got, ref) in results.items()
        },
    })


def phase_collectives(args: argparse.Namespace) -> None:
    """On the four chips: compile the runner's ragged step program,
    2 layers deep at the served widths, over a tp=4 mesh of the real
    devices, and read its text: the kernel is in it and so are the
    all-reduces that tensor parallelism needs."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from vllm_tgis_adapter_tpu.engine.config import ModelConfig
    from vllm_tgis_adapter_tpu.models.llama import LlamaForCausalLM
    from vllm_tgis_adapter_tpu.ops import ragged_attention as ra
    from vllm_tgis_adapter_tpu.ops.attention import _use_pallas
    from vllm_tgis_adapter_tpu.parallel import (
        build_mesh,
        cache_sharding,
        llama_param_specs,
    )

    devices = jax.devices()
    if len(devices) < args.chips:
        raise RuntimeError(f"{len(devices)} device(s), need {args.chips}")
    mcfg = ModelConfig.from_pretrained(
        str(WORK_DIR / "model"), dtype="float32" if args.tiny else "bfloat16"
    )
    import dataclasses

    mcfg = dataclasses.replace(mcfg, num_layers=2)
    mesh = build_mesh(tensor_parallel_size=args.chips,
                      devices=devices[: args.chips])
    model = LlamaForCausalLM(mcfg)
    model.mesh = mesh
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda leaf, spec: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        ),
        shapes, llama_param_specs(shapes, tp=args.chips),
    )
    block, t, seqs = 16, 128, 8
    caches = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=cache_sharding(mesh)
        ),
        jax.eval_shape(lambda: model.make_kv_caches(256 * block, mcfg.dtype)),
    )

    def spec(*shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=NamedSharding(mesh, P())
        )

    text = jax.jit(
        functools.partial(model.ragged_forward, block_size=block),
        donate_argnums=(1,),
    ).lower(
        params, caches, spec(t), spec(t), spec(t), spec(seqs + 1),
        spec(seqs), spec(), spec(seqs, mcfg.max_model_len // block),
        spec(seqs),
        work=spec(ra.work_rows(block), 64) if _use_pallas() else None,
    ).compile().as_text()
    found = {
        "all_reduce": text.count("all-reduce("),
        "tpu_custom_call": text.count("tpu_custom_call"),
    }
    emit({"phase": "collectives", "tp": args.chips,
          "platform": devices[0].platform, **found})
    if not found["all_reduce"]:
        raise RuntimeError("the tp step program holds no all-reduce")
    if not args.tiny and not found["tpu_custom_call"]:
        raise RuntimeError("the tp step program holds no Pallas kernel")


# ---------------------------------------------------------------- server


def http_json(url: str, body: dict | None = None, timeout: float = 600):
    request = urllib.request.Request(
        url,
        data=None if body is None else json.dumps(body).encode(),
        headers={"content-type": "application/json"},
    )
    with urllib.request.urlopen(request, timeout=timeout) as response:
        return json.loads(response.read())


class Server:
    """The real server, as the one chip-holding child."""

    def __init__(self, model_dir: Path, args: argparse.Namespace, tp: int):
        from tests.utils import get_random_port

        self.http_port, self.grpc_port = get_random_port(), get_random_port()
        LOG_DIR.mkdir(exist_ok=True)
        self.log_path = LOG_DIR / f"chip_smoke_server_tp{tp}.log"
        cmd = [
            sys.executable, "-m", "vllm_tgis_adapter_tpu",
            "--model", str(model_dir),
            "--dtype", "float32" if args.tiny else "bfloat16",
            "--max-model-len", "512" if args.tiny else "4096",
            "--port", str(self.http_port),
            "--grpc-port", str(self.grpc_port),
        ]
        if tp > 1:
            cmd += ["--tensor-parallel-size", str(tp)]
        self.started = time.monotonic()
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=child_env(args.tiny, args.chips),
            stdout=self.log, stderr=subprocess.STDOUT,
        )

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.http_port}{path}"

    def wait_healthy(self, timeout: float = 600) -> float:
        while time.monotonic() - self.started < timeout:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with code {self.proc.returncode} "
                    f"before it was healthy:\n{self.log_tail()}"
                )
            try:
                with urllib.request.urlopen(self.url("/health"), timeout=2):
                    return time.monotonic() - self.started
            except OSError:
                time.sleep(0.5)
        raise RuntimeError(f"server not healthy in {timeout}s:\n"
                           f"{self.log_tail()}")

    def log_tail(self, lines: int = 40) -> str:
        self.log.flush()
        return "\n".join(
            self.log_path.read_text(errors="replace").splitlines()[-lines:]
        )

    def drain(self) -> None:
        """SIGTERM, then the graceful-drain exit code 0."""
        self.proc.send_signal(signal.SIGTERM)
        code = self.proc.wait(timeout=120)
        if code != 0:
            raise RuntimeError(
                f"server exited {code} after SIGTERM, not 0:\n"
                f"{self.log_tail()}"
            )

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.log.close()


def generate(server: Server, prompt: str, *, stream: bool) -> dict:
    """One TGIS request for exactly NEW_TOKENS greedy tokens with their
    logprobs; checks everything one response can show."""
    from tests.utils import GrpcClient
    from vllm_tgis_adapter_tpu.grpc.pb import generation_pb2 as pb2

    params = pb2.Parameters(
        method=pb2.DecodingMethod.GREEDY,
        stopping=pb2.StoppingCriteria(
            max_new_tokens=NEW_TOKENS, min_new_tokens=NEW_TOKENS
        ),
        response=pb2.ResponseOptions(
            generated_tokens=True, token_logprobs=True
        ),
    )
    t0 = time.monotonic()
    ttft = None
    with GrpcClient("127.0.0.1", server.grpc_port) as client:
        if stream:
            request = pb2.SingleGenerationRequest(
                request=pb2.GenerationRequest(text=prompt), params=params
            )
            frames = []
            for frame in client.stub.GenerateStream(request, timeout=900):
                if ttft is None and frame.generated_token_count:
                    ttft = time.monotonic() - t0
                frames.append(frame)
            # N deltas after the one input-details frame
            if len(frames) != NEW_TOKENS + 1:
                raise RuntimeError(
                    f"stream framing: {len(frames)} frames for "
                    f"{NEW_TOKENS} tokens (want {NEW_TOKENS + 1})"
                )
            if frames[0].generated_token_count or frames[0].tokens:
                raise RuntimeError("first stream frame carries tokens")
            input_tokens = frames[0].input_token_count
            count = frames[-1].generated_token_count
            tokens = [tok for f in frames[1:] for tok in f.tokens]
            text = "".join(f.text for f in frames)
        else:
            response = client.make_request(
                prompt, params=params, timeout=900
            )
            input_tokens = response.input_token_count
            count = response.generated_token_count
            tokens = list(response.tokens)
            text = response.text
    if count != NEW_TOKENS or len(tokens) != NEW_TOKENS:
        raise RuntimeError(
            f"asked for {NEW_TOKENS} tokens, got count={count} with "
            f"{len(tokens)} token infos"
        )
    logprobs = [tok.logprob for tok in tokens]
    if not all(math.isfinite(lp) and lp <= 0 for lp in logprobs):
        raise RuntimeError(f"logprobs not finite and <= 0: {logprobs}")
    return {
        "kind": "GenerateStream" if stream else "Generate",
        "prompt_tokens": input_tokens,
        "new_tokens": count,
        "ttft_s": None if ttft is None else round(ttft, 3),
        "total_s": round(time.monotonic() - t0, 3),
        "tokens": [tok.text for tok in tokens],
        "first_logprob": logprobs[0],
        "text": text,
    }


def serve_requests(server: Server, prompts: list[str], lengths: list[int],
                   label: str) -> list[dict]:
    """Two requests alone (the same one twice), then six at once, half of
    all eight streamed."""
    alone = [generate(server, prompts[0], stream=False) for _ in range(2)]
    if alone[0]["tokens"] != alone[1]["tokens"]:
        raise RuntimeError(
            "the same greedy request, alone, twice: different tokens\n"
            f"{alone[0]['tokens']}\n{alone[1]['tokens']}"
        )
    with ThreadPoolExecutor(6) as pool:
        together = list(pool.map(
            lambda i: generate(server, prompts[i], stream=i < 4), range(6)
        ))
    results = alone + together
    for i, (result, want) in enumerate(
        zip(results, [lengths[0], lengths[0], *lengths[:6]])
    ):
        if result["prompt_tokens"] != want:
            raise RuntimeError(
                f"request {i}: server counted {result['prompt_tokens']} "
                f"prompt tokens, sent {want}"
            )
        emit({
            "smoke_request": i, "server": label,
            "note": "smoke output, not a benchmark result",
            "where": "alone" if i < 2 else "in the batch of six",
            **{k: result[k] for k in (
                "kind", "prompt_tokens", "new_tokens", "ttft_s", "total_s"
            )},
        })
    same = sum(a == b for a, b in zip(alone[0]["tokens"],
                                      together[0]["tokens"]))
    emit({"alone_vs_in_batch": {
        "server": label, "identical_tokens": same, "of": NEW_TOKENS,
        "note": "printed, not required: near-ties of random weights may "
                "flip under another batch shape",
    }})
    return results


def completions(server: Server, prompt: str) -> None:
    model = http_json(server.url("/v1/models"))["data"][0]["id"]
    body = http_json(server.url("/v1/completions"), {
        "model": model, "prompt": prompt, "max_tokens": NEW_TOKENS,
        "min_tokens": NEW_TOKENS, "temperature": 0.0,
    })
    usage = body["usage"]
    if usage["completion_tokens"] != NEW_TOKENS:
        raise RuntimeError(f"/v1/completions usage: {usage}")
    if not isinstance(body["choices"][0]["text"], str):
        raise RuntimeError(f"/v1/completions body: {body}")
    emit({"http_completions": usage})


def check_device(report: dict, args: argparse.Namespace, tp: int) -> None:
    """The server's own account of its device must be the chip's."""
    want = (
        {"platform": "cpu"} if args.tiny else
        {"platform": "tpu", "attention": "pallas",
         "pallas_mode": "compiled", "caches_donated": True}
    )
    wrong = {k: report.get(k) for k, v in want.items() if report.get(k) != v}
    if not args.tiny and report["kv_pool"]["sized_from"] != "memory_stats":
        wrong["kv_pool.sized_from"] = report["kv_pool"]["sized_from"]
    if report["engine_devices"] != tp:
        wrong["engine_devices"] = report["engine_devices"]
    if report["device_count"] < args.chips:
        wrong["device_count"] = report["device_count"]
    if wrong:
        raise RuntimeError(f"server is not where it should be: {wrong} "
                           f"(wanted {want}, tp={tp})")
    in_use = [m.get("bytes_in_use") for m in report["memory"]]
    if tp > 1 and all(b is not None for b in in_use):
        # weights and pool both shard: no device may hold it all
        shares = [b / sum(in_use) for b in in_use]
        if not all(1 / 5 <= s <= 1 / 3 for s in shares):
            raise RuntimeError(
                f"tp={tp} memory is not spread evenly: shares {shares}"
            )


def run_server(model_dir: Path, args: argparse.Namespace, tp: int,
               prompts: list[str], lengths: list[int]) -> tuple[list, dict]:
    label = f"tp{tp}"
    server = Server(model_dir, args, tp)
    try:
        healthy_s = server.wait_healthy()
        results = serve_requests(server, prompts, lengths, label)
        completions(server, prompts[1])
        state = http_json(server.url("/debug/state"))
        report, tracker = state["device"], state["compile_tracker"]
        emit({
            "phase": "server", "server": label, "device_report": report,
            "healthy_after_s": round(healthy_s, 1),
            "weights_load_s": report["weights_load_s"],
            "compile": {
                "programs": tracker["compiled_shapes"],
                "seconds": tracker["compile_seconds"],
            },
            "kv_pool_pages": report["kv_pool"]["num_blocks"],
            "hbm_bytes_in_use": [
                m.get("bytes_in_use") for m in report["memory"]
            ],
            "first_request_s": results[0]["total_s"],
            "note": "smoke output, not a benchmark result",
        })
        check_device(report, args, tp)
        server.drain()
    finally:
        server.kill()
    return results, report


# ------------------------------------------------------------------ main


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4: only tp=4 and its tp=1 comparison")
    parser.add_argument("--tiny", action="store_true",
                        help="CPU rehearsal with the hidden-64 fixture")
    parser.add_argument("--phase", choices=("kernel", "collectives"),
                        help=argparse.SUPPRESS)  # children of this script
    args = parser.parse_args()
    if args.phase:
        {"kernel": phase_kernel, "collectives": phase_collectives}[
            args.phase
        ](args)
        return

    # protoc runs HERE, once, not in two children at the same moment
    try:
        import vllm_tgis_adapter_tpu.grpc.pb  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"chip_smoke: cannot import the gRPC messages "
                         f"(is protoc on PATH?): {e}") from e

    emit({"assumed": {
        "model": "ibm-granite/granite-3.0-2b-instruct",
        "config.json": GRANITE_2B,
        "why": "written without network access; weights are random "
               "from --seed, no width, depth or vocabulary is cut",
    } if not args.tiny else {"model": "tests/fixture_models tiny llama"}})

    if args.chips == 1:
        # first, and before the 5 GB checkpoint: where there is no chip
        # this is where the script ends
        run_phase("kernel", args)

    shutil.rmtree(WORK_DIR, ignore_errors=True)
    model_dir = WORK_DIR / "model"
    try:
        t0 = time.monotonic()
        if args.tiny:
            write_tiny_checkpoint(model_dir, args.seed)
            lengths = [100, 400, 150, 200, 300, 350]
        else:
            write_granite_checkpoint(model_dir, args.seed)
            lengths = [100, 900, 260, 420, 580, 740]
        prompts = make_prompts(model_dir, lengths, args.seed)
        emit({"phase": "checkpoint", "seconds": round(
            time.monotonic() - t0, 1
        ), "bytes": sum(f.stat().st_size for f in model_dir.iterdir())})

        if args.chips == 1:
            _, report = run_server(model_dir, args, 1, prompts, lengths)
        else:
            run_phase("collectives", args)
            sharded, report = run_server(
                model_dir, args, args.chips, prompts, lengths
            )
            single, _ = run_server(model_dir, args, 1, prompts, lengths)
            deltas = [
                abs(a["first_logprob"] - b["first_logprob"])
                for a, b in zip(sharded, single)
            ]
            same = sum(
                x == y for a, b in zip(sharded, single)
                for x, y in zip(a["tokens"], b["tokens"])
            )
            emit({"tp_vs_single": {
                "max_first_logprob_delta": max(deltas),
                "tolerance": TP_LOGPROB_TOL,
                "identical_greedy_tokens": same,
                "of": NEW_TOKENS * len(sharded),
            }})
            if max(deltas) > TP_LOGPROB_TOL:
                raise RuntimeError(
                    f"tp={args.chips} first-token logprobs differ from "
                    f"tp=1 by {max(deltas)} > {TP_LOGPROB_TOL}"
                )
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)

    if "jax" in sys.modules:
        raise RuntimeError("the parent imported jax: it could hold the chip")
    emit({"ok": True, "device": {
        "platform": report["platform"], "kind": report["device_kind"],
        "count": report["device_count"],
    }})


if __name__ == "__main__":
    main()
