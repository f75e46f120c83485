#!/usr/bin/env python3
"""What one grid step of the ragged attention kernel costs.

Times ``_ragged_attention_pallas`` (``ops/ragged_attention.py``) alone,
with no model around it, over the schedule of a synthetic deep stream:
``build_work_schedule``'s for prompt chunks deep in their prompts, or,
with ``--chunk 1``, ``decode_work_schedule``'s for a wave of one-token
rows.  Twice: as scheduled (every item's tile is computed) and with
every item's ``live`` field (row 6 of the schedule) rewritten to 0 (a
dead item: no body at all, so what is left is the grid step's own cost,
its page DMAs and the pipeline's turn).  It prints microseconds an item
(one KV head's grid step) for both, what the tile's two matmuls come to
in TFLOP/s over the time the body adds to a dead step, and which axis
the tile's statistics run along (``tile_stats``).

    chiprun -- python3 tools/probe_ragged_tile.py
    chiprun -- python3 tools/probe_ragged_tile.py \\
        --shape 16,64,128,4096,128 --items 4096 --chunk 1024 --depth 6144
    chiprun -- python3 tools/probe_ragged_tile.py --chunk 1 --depth 8192 \\
        --shape 16,8,128,4096,128 --shape 4,8,64,0,16 --shape 5,8,128,0,16

A ``--shape`` is ``G,block_q,head_dim,window,block_size``: query heads a
KV head, rows a query block, head dim (lane-padded to a multiple of 128
as the pool's rows are), the band (0: none) and tokens a page.  The
defaults are the layouts of the three configurations that run the
kernel: Command A+'s window and full layers, granite's, falcon's.  It
reads no model and no checkpoint.  It measures on a TPU and nowhere
else: on any other platform it prints the platform and exits 1
(``tests/test_probe_ragged_tile.py`` rehearses ``probe`` in interpret
mode, at a tiny size).  To compare two trees, unpack the other one
beside this and run its copy of this file in the same call: the probe
reads the kernel of the tree it lies in.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from vllm_tgis_adapter_tpu.ops import ragged_attention as ragged  # noqa: E402

#: (G, block_q, head dim, window, block size): Command A+ window layers,
#: its full layers, granite-3.0-2b, Falcon-H1-34B
DEFAULT_SHAPES = (
    "16,64,128,4096,128",
    "16,64,128,0,128",
    "4,128,64,0,16",
    "5,128,128,0,16",
)

#: what every item's ``live`` field is rewritten to under each label
#: (None: left as scheduled); the two values the kernel knows
MARKS = (("computed", None), ("dead", 0))


def build_case(g, block_q, head_dim, window, block_size, *, kv_heads,
               items, chunk, depth):
    """A stream of equal spans, each ``chunk`` rows at position
    ``depth`` of its own sequence, as many as give about ``items`` work
    items; every sequence reads the same pages (the table's entries are
    what a DMA costs, not which).  Spans of one row are a wave: row s is
    sequence s, under the schedule the wave builds for itself."""
    rng = np.random.default_rng(0)
    lanes = -(-head_dim // 128) * 128
    max_blocks = -(-(depth + chunk) // block_size)
    table = rng.permutation(max_blocks).astype(np.int32)
    t_one = -(-chunk // block_q) * block_q if chunk > 1 else 1

    def schedule(n_spans):
        tables = np.tile(table, (n_spans, 1))
        if chunk == 1:
            work, n_items = ragged.decode_work_schedule(
                jnp.full(n_spans, depth, jnp.int32),
                jnp.ones(n_spans, bool), jnp.asarray(tables),
                block_size=block_size, block_q=block_q, window=window)
            return np.array(work), int(n_items)
        spans = [(s * t_one, chunk, depth) for s in range(n_spans)]
        return ragged.build_work_schedule(
            spans, tables, block_size=block_size,
            block_q=block_q, t_pad=n_spans * t_one, window=window)

    _, per_span = schedule(1)
    n_spans = max(1, min(items, ragged.MAX_WORK_WIDTH) // per_span)
    if chunk == 1:  # whole query blocks of rows
        n_spans = max(block_q, n_spans // block_q * block_q)
    work, n_items = schedule(n_spans)
    t = n_spans * t_one
    dtype = jnp.bfloat16
    q = jnp.asarray(
        rng.standard_normal((t, kv_heads * g, lanes), np.float32), dtype)
    pool = [
        jnp.asarray(rng.standard_normal(
            (1, kv_heads, max_blocks * block_size, lanes), np.float32),
            dtype)
        for _ in range(2)
    ]
    starts = np.full(n_spans + 1, t, np.int32)
    starts[:n_spans] = np.arange(n_spans) * t_one
    base = np.full(n_spans, depth, np.int32)
    return q, pool, jnp.asarray(starts), jnp.asarray(base), work, n_items


def time_call(fn, args, repeats):
    jax.block_until_ready(fn(*args))  # compile, and one warm run
    seconds = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        seconds.append(time.perf_counter() - t0)
    return statistics.median(seconds), min(seconds)


def probe(shape: str, *, items: int = 4096, kv_heads: int = 8,
          chunk: int = 1024, depth: int = 6144, repeats: int = 5) -> dict:
    g, block_q, head_dim, window, block_size = map(int, shape.split(","))
    q, (k, v), starts, base, work, n_items = build_case(
        g, block_q, head_dim, window, block_size, kv_heads=kv_heads,
        items=items, chunk=chunk, depth=depth)
    fn = jax.jit(lambda q, k, v, st, pb, wk, n: (
        ragged._ragged_attention_pallas(
            q, k, v, st, pb, wk, block_size, head_dim ** -0.5,
            block_q=block_q, window=window, n_items=n, layer=0,
            interpret=ragged._pallas_interpret())))
    rows = g * block_q
    out = {
        "platform": jax.devices()[0].platform,
        "shape": shape, "rows_a_tile": rows,
        "stats": ragged.tile_stats(g, block_q),
        "items": int(n_items), "kv_heads": kv_heads,
        # q @ k^T and p @ v over one tile of KV_TILE cached tokens
        "tile_mflop": round(
            4e-6 * rows * ragged.KV_TILE * q.shape[-1], 1),
    }
    steps = n_items * kv_heads
    for label, mark in MARKS:
        marked = work.copy()
        if mark is not None:
            marked[6, :n_items] = mark
        median, least = time_call(
            fn, (q, k, v, starts, base, jnp.asarray(marked),
                 jnp.asarray(n_items, jnp.int32)), repeats)
        out[f"{label}_us_item"] = round(1e6 * median / steps, 3)
        out[f"{label}_us_item_min"] = round(1e6 * least / steps, 3)
    body_us = out["computed_us_item"] - out["dead_us_item"]
    if body_us > 0:
        out["body_tflops"] = round(out["tile_mflop"] / body_us, 1)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append",
                    help="G,block_q,head_dim,window,block_size; repeatable")
    ap.add_argument("--items", type=int, default=4096,
                    help="work items a KV head to aim for (at most "
                    f"{ragged.MAX_WORK_WIDTH})")
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=1024,
                    help="rows of each prompt chunk")
    ap.add_argument("--depth", type=int, default=6144,
                    help="position of each chunk's first row")
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    print(json.dumps({"platform": device.platform,
                      "device_kind": device.device_kind}), flush=True)
    if device.platform != "tpu":
        print("probe_ragged_tile: no TPU here, and interpret mode times "
              "nothing: run it through chiprun", file=sys.stderr)
        return 1
    for shape in args.shape or DEFAULT_SHAPES:
        print(json.dumps(probe(
            shape, items=args.items, kv_heads=args.kv_heads,
            chunk=args.chunk, depth=args.depth, repeats=args.repeats)),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
