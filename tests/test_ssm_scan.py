"""ops/ssm_scan.py: the one-token update (the Pallas kernel in interpret
mode and its ``jax.numpy`` twin) and the chunked scan over spans, each
against the token-by-token recurrence."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_tgis_adapter_tpu.ops import ssm_scan

LAYERS, SLOTS = 2, 6


def draw(rng, t, heads, groups, n, p):
    f32 = jnp.float32
    return (
        jnp.asarray(rng.normal(size=(t, heads, p)), f32),
        jax.nn.softplus(jnp.asarray(rng.normal(size=(t, heads)), f32) - 2.0),
        jnp.asarray(rng.normal(size=(t, groups, n)), f32),
        jnp.asarray(rng.normal(size=(t, groups, n)), f32),
    )


def setup(seed, heads, n, p):
    rng = np.random.default_rng(seed)
    a = -jnp.exp(jnp.asarray(rng.normal(size=(heads,)), jnp.float32))
    state = jnp.asarray(
        rng.normal(size=(LAYERS, SLOTS, heads, n, p)), jnp.float32)
    return rng, a, state


SHAPES = [
    # heads, groups, state, head dim
    (4, 2, 16, 16),
    (8, 1, 32, 8),
    (16, 2, 8, 128),  # two head blocks a group, whole lanes
]


@pytest.mark.parametrize("heads,groups,n,p", SHAPES)
def test_chunk_is_the_recurrence(heads, groups, n, p):
    rng, a, state = setup(1, heads, n, p)
    x, dt, b, c = draw(rng, 24, heads, groups, n, p)
    want_y, want_h = ssm_scan.ssm_recurrence(state[0, 0], x, dt, a, b, c)
    got_y, got_h = ssm_scan.ssm_chunk(state[0, 0], x, dt, a, b, c)
    np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(got_h, want_h, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("pallas", [False, True],
                         ids=["jax.numpy", "pallas-interpret"])
@pytest.mark.parametrize("heads,groups,n,p", SHAPES)
def test_decode_update_is_one_step_of_the_recurrence(heads, groups, n, p,
                                                     pallas):
    """Live rows move one token on from their slot's state (or from
    zeros, when fresh) and write it back in place; dead rows touch
    nothing and read zero; the other layer is untouched."""
    rng, a, state = setup(2, heads, n, p)
    x, dt, b, c = draw(rng, 5, heads, groups, n, p)
    slots = jnp.asarray([2, 4, -1, 0, 1], jnp.int32)
    live = jnp.asarray([True, True, False, True, False])
    fresh = jnp.asarray([False, True, False, False, False])
    args = (state, 1, slots, live, fresh, x, dt, a, b, c)
    y, new = (
        ssm_scan._decode_update_pallas(*args, interpret=True) if pallas
        else ssm_scan._decode_update_xla(*args))
    for row in range(5):
        slot = int(slots[row])
        if not live[row]:
            assert float(jnp.abs(y[row]).max()) == 0.0
            continue
        h0 = (jnp.zeros_like(state[1, slot]) if fresh[row]
              else state[1, slot])
        want_y, want_h = ssm_scan.ssm_recurrence(
            h0, x[row:row + 1], dt[row:row + 1], a, b[row:row + 1],
            c[row:row + 1])
        np.testing.assert_allclose(y[row], want_y[0], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(new[1, slot], want_h, atol=1e-6,
                                   rtol=1e-6)
    untouched = [s for s in range(SLOTS) if s not in (2, 4, 0)]
    np.testing.assert_array_equal(new[1, untouched], state[1, untouched])
    np.testing.assert_array_equal(new[0], state[0])


@pytest.mark.parametrize("lens", [
    [5, 1, 19, 1, 2], [32], [7, 9], [1, 1, 1], [8, 8, 16],
], ids=str)
@pytest.mark.parametrize("chunk", [8, 16])
def test_spans_of_a_flat_stream_each_from_their_own_slot(lens, chunk):
    """Spans of several sequences in one stream, lengths no multiple of
    the chunk: every multi-token span starts from its slot's state (zeros
    when fresh) and leaves its final state there; one-token spans and the
    padded tail are left alone."""
    heads, groups, n, p = 4, 2, 16, 16
    rng, a, state = setup(3, heads, n, p)
    total, max_seqs = 32, 8
    starts = np.full(max_seqs + 1, total, np.int32)
    starts[:len(lens) + 1] = np.concatenate([[0], np.cumsum(lens)])
    slots = np.full(max_seqs, -1, np.int32)
    slots[:len(lens)] = [3, 0, 5, 1, 2][:len(lens)]
    fresh = np.zeros(max_seqs, bool)
    fresh[[0, 4]] = True
    x, dt, b, c = draw(rng, total, heads, groups, n, p)
    pieces = ssm_scan.span_pieces(
        jnp.asarray(starts), jnp.asarray(slots), chunk,
        total // chunk + max_seqs)
    assert int(pieces[4]) == sum(-(-n_ // chunk) for n_ in lens if n_ > 1)
    y, new = jax.jit(
        lambda st: ssm_scan.ssm_scan_spans(
            st, 1, pieces, jnp.asarray(slots), jnp.asarray(fresh), x, dt, a,
            b, c, chunk=chunk))(state)
    for i, length in enumerate(lens):
        lo, slot = int(starts[i]), int(slots[i])
        if length == 1:
            assert float(jnp.abs(y[lo]).max()) == 0.0
            np.testing.assert_array_equal(new[1, slot], state[1, slot])
            continue
        h0 = (jnp.zeros_like(state[1, slot]) if fresh[i]
              else state[1, slot])
        want_y, want_h = ssm_scan.ssm_recurrence(
            h0, x[lo:lo + length], dt[lo:lo + length], a,
            b[lo:lo + length], c[lo:lo + length])
        np.testing.assert_allclose(y[lo:lo + length], want_y, atol=2e-4,
                                   rtol=1e-4)
        np.testing.assert_allclose(new[1, slot], want_h, atol=2e-4,
                                   rtol=1e-4)
    assert float(jnp.abs(y[sum(lens):]).max(initial=0.0)) == 0.0
    np.testing.assert_array_equal(new[0], state[0])


def test_the_slab_is_float32():
    rng, a, state = setup(4, 4, 16, 16)
    x, dt, b, c = draw(rng, 2, 4, 2, 16, 16)
    with pytest.raises(ValueError, match="float32"):
        ssm_scan.ssm_decode_update(
            state.astype(jnp.bfloat16), 0, jnp.zeros(2, jnp.int32),
            jnp.ones(2, bool), jnp.zeros(2, bool), x, dt, a, b, c)
