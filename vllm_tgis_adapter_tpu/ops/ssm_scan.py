"""Mamba-2 state-space scan over the engine's two step shapes.

The recurrence, per sequence, layer and head (``h`` is ``[N, P]``: state
size by head size; ``A`` is the head's negative decay rate, ``dt`` the
token's step size after softplus, ``B`` and ``C`` the token's ``[N]``
input and output projections of the head's group):

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t
    y_t = C_t . h_t                      (the ``D x_t`` skip is the caller's)

The state lives in a slab ``[layers, slots, H, N, P]`` float32 beside the
KV page pool (docs/STATE.md): one slot a running sequence.  ``N`` is the
second-minor axis and ``P`` the lane axis, so that inside the kernel the
token's ``x`` (``[H, P]``) broadcasts along sublanes, ``C . h`` is a
reduction over sublanes that leaves a lane-dense ``[H, P]``, and only the
small ``B`` and ``C`` rows are turned into columns.

Three forms, all held to :func:`ssm_recurrence` (token by token):

* :func:`ssm_decode_update`: one token for each of R rows, the fused
  decode wave's step and the mixed step's one-token spans.  On the TPU a
  Pallas kernel over live (row, head block) items that reads each row's
  slot through scalar-prefetched indices and writes the slab in place
  (aliased input to output): a gather and a scatter by slot in XLA would
  move every row's 4 MB a layer three times.  Elsewhere the same
  arithmetic in ``jax.numpy``.
* :func:`ssm_chunk`: up to ``Q`` tokens of ONE sequence in the matmul
  form of the recurrence (Mamba-2's "state space duality": the causal
  ``[Q, Q]`` decay-weighted ``C B^T`` product within the chunk, the state
  carried between chunks).
* :func:`ssm_scan_spans`: the multi-token spans of a flat mixed stream,
  each cut from its own first token into pieces of at most ``Q`` tokens;
  a loop over the live pieces reads the piece's slot, runs
  :func:`ssm_chunk`, and writes the slot back, so a span of any length
  starts from its slot's state and leaves its final state there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_tgis_adapter_tpu.ops import attention as attn_ops

#: the Pallas kernel's name in HLO and in the device trace
#: (benchmark/layer_metrics/ssm_update_kernel_pct.json matches it)
DECODE_KERNEL_NAME = "ssm_decode_update"
#: heads of one grid step: [8, N, P] f32 is 1 MB at N 256, P 128, so the
#: double-buffered in and out blocks stay at 4 MB of VMEM
HEAD_BLOCK = 8
_HIGHEST = jax.lax.Precision.HIGHEST


def ssm_recurrence(
    h0: jax.Array,  # [H, N, P] f32
    x: jax.Array,  # [T, H, P]
    dt: jax.Array,  # [T, H] after softplus
    a: jax.Array,  # [H] negative
    b: jax.Array,  # [T, G, N]
    c: jax.Array,  # [T, G, N]
) -> tuple[jax.Array, jax.Array]:
    """The recurrence token by token for one sequence: ``(y [T, H, P],
    h_T)`` in float32.  What every other form here is checked against."""
    heads = x.shape[1]
    rep = heads // b.shape[1]

    def step(h, tok):
        xt, dtt, bt, ct = tok
        bt = jnp.repeat(bt, rep, axis=0)  # [H, N]
        ct = jnp.repeat(ct, rep, axis=0)
        h = (
            jnp.exp(dtt * a)[:, None, None] * h
            + (dtt[:, None] * bt)[:, :, None] * xt[:, None, :]
        )
        return h, jnp.sum(ct[:, :, None] * h, axis=1)

    f32 = jnp.float32
    h, y = jax.lax.scan(
        step, h0.astype(f32),
        (x.astype(f32), dt.astype(f32), b.astype(f32), c.astype(f32)),
    )
    return y, h


# ------------------------------------------------------------ one token


def _decode_update_xla(state, layer, slots, live, fresh, x, dt, a, b, c):
    rep = x.shape[1] // b.shape[1]
    f32 = jnp.float32
    safe = jnp.clip(slots, 0, state.shape[1] - 1)
    h = state[layer, safe]  # [R, H, N, P]
    h = jnp.where(fresh[:, None, None, None], 0.0, h)
    bh = jnp.repeat(b.astype(f32), rep, axis=1)  # [R, H, N]
    ch = jnp.repeat(c.astype(f32), rep, axis=1)
    dt = dt.astype(f32)
    h = (
        jnp.exp(dt * a)[:, :, None, None] * h
        + (dt[:, :, None] * bh)[..., None] * x.astype(f32)[:, :, None, :]
    )
    y = jnp.sum(ch[..., None] * h, axis=2)
    # a dead row's slot is dropped: out of range on purpose
    where = jnp.where(live, slots, state.shape[1])
    state = state.at[layer, where].set(h, mode="drop")
    return jnp.where(live[:, None, None], y, 0.0), state


def _decode_kernel(rows_ref, slots_ref, fresh_ref,  # scalar prefetch
                   xdt_ref, decay_ref, b_ref, c_ref, h_ref, y_in_ref,
                   y_ref, h_out_ref):
    del rows_ref, slots_ref, y_in_ref  # index maps and aliasing only
    i = pl.program_id(0)
    lanes = xdt_ref.shape[-1]
    n = b_ref.shape[-1]
    # a [1, N] row as an [N, P] column block: broadcast down the sublanes,
    # then one 2-D transpose (N and P are whole tiles)
    b_col = jnp.broadcast_to(b_ref[0, 0], (lanes, n)).T  # [N, P]
    c_col = jnp.broadcast_to(c_ref[0, 0], (lanes, n)).T
    xdt = xdt_ref[0]  # [hb, P] = dt * x
    decay = decay_ref[0]  # [hb, P] = exp(dt A), the same along P
    h = h_ref[0, 0]  # [hb, N, P]
    h = jnp.where(fresh_ref[i] > 0, 0.0, h)
    h = decay[:, None, :] * h + b_col[None] * xdt[:, None, :]
    h_out_ref[0, 0] = h
    y_ref[0] = jnp.sum(c_col[None] * h, axis=1)


def _decode_update_pallas(state, layer, slots, live, fresh, x, dt, a, b, c,
                          *, interpret):
    rows, heads, p = x.shape
    groups, n = b.shape[1], b.shape[2]
    hb = min(HEAD_BLOCK, heads // groups)
    if heads % hb or (heads // groups) % hb:
        raise ValueError(
            f"{heads} heads in {groups} groups do not split into blocks "
            f"of {hb}"
        )
    per_group = heads // groups // hb  # head blocks of one group
    f32 = jnp.float32
    dt = dt.astype(f32)
    xdt = x.astype(f32) * dt[:, :, None]
    decay = jnp.broadcast_to(jnp.exp(dt * a)[:, :, None], xdt.shape)
    # live rows first; the grid ends at the last of them
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)
    row_slots = jnp.clip(slots[order], 0, state.shape[1] - 1).astype(jnp.int32)
    row_fresh = fresh[order].astype(jnp.int32)
    b4 = b.astype(f32)[:, :, None, :]  # [R, G, 1, N]
    c4 = c.astype(f32)[:, :, None, :]

    def by_row(i, j, rows_, slots_, fresh_):
        return (rows_[i], j, 0)

    def by_group(i, j, rows_, slots_, fresh_):
        return (rows_[i], j // per_group, 0, 0)

    def by_slot(i, j, rows_, slots_, fresh_):
        return (layer, slots_[i], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_live, heads // hb),
        in_specs=[
            pl.BlockSpec((1, hb, p), by_row),
            pl.BlockSpec((1, hb, p), by_row),
            pl.BlockSpec((1, 1, 1, n), by_group),
            pl.BlockSpec((1, 1, 1, n), by_group),
            pl.BlockSpec((1, 1, hb, n, p), by_slot),
            pl.BlockSpec((1, hb, p), by_row),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, p), by_row),
            pl.BlockSpec((1, 1, hb, n, p), by_slot),
        ],
    )
    y, state = pl.pallas_call(
        _decode_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, heads, p), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands count the three scalar-prefetch arrays: the slab is
        # updated in place, and y starts as zeros so a dead row reads 0
        input_output_aliases={7: 1, 8: 0},
        interpret=interpret,
        name=DECODE_KERNEL_NAME,
    )(order, row_slots, row_fresh, xdt, decay, b4, c4, state,
      jnp.zeros((rows, heads, p), f32))
    return y, state


def ssm_decode_update(
    state: jax.Array,  # [L, S, H, N, P] f32, the whole slab
    layer: int,  # static
    slots: jax.Array,  # [R] i32 slot of each row
    live: jax.Array,  # [R] bool: rows whose state this step updates
    fresh: jax.Array,  # [R] bool: rows that start from a zero state
    x: jax.Array,  # [R, H, P]
    dt: jax.Array,  # [R, H] after softplus
    a: jax.Array,  # [H] f32, negative
    b: jax.Array,  # [R, G, N]
    c: jax.Array,  # [R, G, N]
) -> tuple[jax.Array, jax.Array]:
    """One token a row: ``(y [R, H, P] f32, the slab)``; a dead row's
    slot is untouched and its ``y`` is zero."""
    if state.dtype != jnp.float32:
        raise ValueError(f"the state slab is float32, not {state.dtype}")
    args = (state, layer, slots.astype(jnp.int32), live, fresh, x, dt,
            a.astype(jnp.float32), b, c)
    # asked of ops/attention.py each time: one place decides the kernels
    if not attn_ops._use_pallas():
        return _decode_update_xla(*args)
    return _decode_update_pallas(
        *args, interpret=attn_ops._pallas_interpret())


# ------------------------------------------------------------ spans


def ssm_chunk(
    h0: jax.Array,  # [H, N, P] f32
    x: jax.Array,  # [Q, H, P]
    dt: jax.Array,  # [Q, H] f32 after softplus; 0 on rows past the piece
    a: jax.Array,  # [H] f32
    b: jax.Array,  # [Q, G, N]
    c: jax.Array,  # [Q, G, N]
) -> tuple[jax.Array, jax.Array]:
    """Up to Q tokens of one sequence in matmul form: ``(y [Q, H, P]
    f32, h after the last token)``.  A row with ``dt`` 0 neither decays
    nor feeds the state (its own ``y`` is of no use to anyone).

    The products that read or write the float32 state run at the
    highest matmul precision (on the TPU a float32 product is otherwise
    rounded to bfloat16 operands); the causal ``[Q, Q]`` product within
    the chunk takes the activations as they come."""
    q, heads, _ = x.shape
    groups = b.shape[1]
    rep = heads // groups
    f32 = jnp.float32
    dt = dt.astype(f32)
    cs = jnp.cumsum(dt * a, axis=0)  # [Q, H], <= 0 and falling
    # decay from after token s to token t, for s <= t
    seg = cs.T[:, :, None] - cs.T[:, None, :]  # [H, t, s]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = jnp.einsum("tgn,sgn->gts", c, b, preferred_element_type=f32)
    m = jnp.repeat(cb, rep, axis=0) * decay * dt.T[:, None, :]  # [H, t, s]
    y = jnp.einsum("hts,shp->thp", m, x.astype(f32))
    # what the state carried in gives every row
    ch = jnp.repeat(c.astype(f32), rep, axis=1)  # [Q, H, N]
    y = y + jnp.exp(cs)[:, :, None] * jnp.einsum(
        "thn,hnp->thp", ch, h0, precision=_HIGHEST)
    # the state after the last row
    to_end = jnp.exp(cs[-1][None, :] - cs) * dt  # [Q, H]
    bh = jnp.repeat(b.astype(f32), rep, axis=1) * to_end[:, :, None]
    h = jnp.exp(cs[-1])[:, None, None] * h0 + jnp.einsum(
        "shn,shp->hnp", bh, x.astype(f32), precision=_HIGHEST)
    return y, h


def span_pieces(seq_starts: jax.Array, slots: jax.Array, chunk: int,
                max_pieces: int):
    """The multi-token spans of a flat stream cut into pieces of at most
    ``chunk`` tokens, each span from its own first token: ``(seq [M],
    lo [M] first flat row, n [M] rows, first [M] bool, count)`` with the
    ``count`` live pieces first, in stream order.  One-token spans are
    the decode update's; a sequence without a slot (``slots`` < 0) is
    padding, the stream's padded tail among it."""
    lens = jnp.where(slots >= 0, seq_starts[1:] - seq_starts[:-1], 0)
    per_seq = jnp.where(lens >= 2, -(-lens // chunk), 0)
    ends = jnp.cumsum(per_seq)
    idx = jnp.arange(max_pieces, dtype=jnp.int32)
    seq = jnp.searchsorted(ends, idx, side="right").astype(jnp.int32)
    seq = jnp.minimum(seq, lens.shape[0] - 1)
    k = idx - (ends[seq] - per_seq[seq])  # piece of its span
    lo = seq_starts[seq] + k * chunk
    n = jnp.clip(seq_starts[seq + 1] - lo, 0, chunk)
    return seq, lo.astype(jnp.int32), n.astype(jnp.int32), k == 0, ends[-1]


def ssm_scan_spans(
    state: jax.Array,  # [L, S, H, N, P] f32
    layer: int,  # static
    pieces: tuple,  # span_pieces(...)
    slots: jax.Array,  # [S] i32 slot of each sequence of the stream
    fresh: jax.Array,  # [S] bool: sequences that start from a zero state
    x: jax.Array,  # [T, H, P]
    dt: jax.Array,  # [T, H] f32 after softplus
    a: jax.Array,  # [H] f32
    b: jax.Array,  # [T, G, N]
    c: jax.Array,  # [T, G, N]
    *,
    chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """The chunked scan of every multi-token span of the stream: ``(y
    [T, H, P] f32, zero outside those spans; the slab)``."""
    seq, lo, n, first, count = pieces
    t = x.shape[0]
    f32 = jnp.float32

    def padded(v):  # a piece's window never runs off the stream
        return jnp.pad(v, ((0, chunk),) + ((0, 0),) * (v.ndim - 1))

    xp, dtp, bp, cp = padded(x), padded(dt.astype(f32)), padded(b), padded(c)
    a = a.astype(f32)
    in_piece = jnp.arange(chunk, dtype=jnp.int32)

    def body(i, carry):
        state, y = carry
        s, start = seq[i], lo[i]
        slot = jnp.clip(slots[s], 0, state.shape[1] - 1)
        valid = in_piece < n[i]

        def window(v):
            return jax.lax.dynamic_slice_in_dim(v, start, chunk, axis=0)

        h0 = jax.lax.dynamic_slice(
            state, (layer, slot, 0, 0, 0), (1, 1) + state.shape[2:])[0, 0]
        h0 = jnp.where(first[i] & fresh[s], 0.0, h0)
        y_piece, h = ssm_chunk(
            h0, window(xp), jnp.where(valid[:, None], window(dtp), 0.0), a,
            window(bp), window(cp),
        )
        state = jax.lax.dynamic_update_slice(
            state, h[None, None], (layer, slot, 0, 0, 0))
        y = jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(valid[:, None, None], y_piece, window(y)),
            start, axis=0)
        return state, y

    y0 = jnp.zeros((t + chunk,) + x.shape[1:], f32)
    state, y = jax.lax.fori_loop(0, count, body, (state, y0))
    return y[:t], state
