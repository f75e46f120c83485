"""Batched, jit-compiled TPU sampler.

TPU-native replacement for the sampling stack the reference adapter
configures on vLLM (``SamplingParams`` consumption at grpc_server.py:606-622
and the custom logits processors in tgis_utils/logits_processors.py).  The
reference stack applies per-request logits processors row-by-row in eager
torch; on TPU everything must be one fused, statically-shaped program, so
every per-request knob is an array over the batch row axis and every
processor is a masked vectorised transform:

* temperature / top-k / top-p / typical-p filtering,
* repetition penalty over prompt+generated tokens (seen-token matrix),
* TGIS exponential-decay EOS length penalty,
* min-tokens EOS suppression,
* per-request seeded PRNG (base key folded with the step counter),
* greedy and sampled rows coexisting in one batch,
* chosen-token logprob + rank + top-N logprobs for token info
  (n+1 semantics handled by the server layer),
* optional structured-output token bitmask hook.

All functions are pure; the engine jits :func:`sample` once per batch-size
bucket.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = float("-inf")
# top-N token info is capped by validation at 10 (+1 for the chosen token);
# a fixed device-side width keeps the jitted shape static
TOPN_WIDTH = 16


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SamplingTensors:
    """Per-row sampling knobs for one (padded) running batch."""

    temperature: jax.Array  # [B] f32; 0.0 == greedy row
    top_k: jax.Array  # [B] i32; 0 or negative == disabled
    top_p: jax.Array  # [B] f32 in (0, 1]
    typical_p: jax.Array  # [B] f32 in (0, 1]; 1.0 == disabled
    repetition_penalty: jax.Array  # [B] f32; 1.0 == disabled
    len_penalty_start: jax.Array  # [B] i32; -1 == disabled
    len_penalty_decay: jax.Array  # [B] f32 (>= 1.0)
    min_tokens: jax.Array  # [B] i32
    eos_token_id: jax.Array  # [B] i32
    gen_len: jax.Array  # [B] i32 tokens generated so far
    base_key: jax.Array  # [B] uint32 per-request PRNG seed material

    @staticmethod
    def from_params(params_list, eos_token_id: int, gen_lens,
                    fallback_seeds) -> "SamplingTensors":
        """Host-side packing of a list of SamplingParams into arrays.

        ``fallback_seeds`` supplies one engine-drawn uint32 per row for
        requests without an explicit seed (kept stable per request so a
        request's stream is reproducible across steps).
        """
        n = len(params_list)
        temperature = np.ones(n, np.float32)
        top_k = np.zeros(n, np.int32)
        top_p = np.ones(n, np.float32)
        typical_p = np.ones(n, np.float32)
        rep = np.ones(n, np.float32)
        lp_start = np.full(n, -1, np.int32)
        lp_decay = np.ones(n, np.float32)
        min_tokens = np.zeros(n, np.int32)
        keys = np.asarray(fallback_seeds, np.uint32).copy()
        for i, p in enumerate(params_list):
            if p is None:
                temperature[i] = 0.0
                continue
            temperature[i] = p.temperature
            top_k[i] = 0 if p.top_k in (-1, None) else p.top_k
            top_p[i] = p.top_p
            typical_p[i] = p.typical_p
            rep[i] = p.repetition_penalty
            if p.length_penalty is not None:
                lp_start[i] = p.length_penalty[0]
                lp_decay[i] = p.length_penalty[1]
            min_tokens[i] = p.min_tokens
            if p.seed is not None:
                keys[i] = np.uint32(p.seed & 0xFFFFFFFF) ^ np.uint32(p.seed >> 32)
        # HOST numpy leaves: callers decide when (and packed how) these
        # cross to the device — runner.execute_decode packs them into two
        # arrays per dispatch, execute_prefill tree-maps _put.  Returning
        # device arrays here would force a device round trip per field
        # on every decode dispatch.
        return SamplingTensors(
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            typical_p=typical_p,
            repetition_penalty=rep,
            len_penalty_start=lp_start,
            len_penalty_decay=lp_decay,
            min_tokens=min_tokens,
            eos_token_id=np.full(n, eos_token_id, np.int32),
            gen_len=np.asarray(gen_lens, np.int32),
            base_key=keys,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SamplerOutput:
    tokens: jax.Array  # [B] i32 chosen token
    logprob: jax.Array  # [B] f32 logprob of chosen token
    rank: jax.Array  # [B] i32 1-based rank of chosen token
    topn_ids: jax.Array  # [B, TOPN_WIDTH] i32
    topn_logprobs: jax.Array  # [B, TOPN_WIDTH] f32


def apply_penalties(
    logits: jax.Array,  # [B, V] f32
    seen: jax.Array,  # [B, V] bool — prompt+generated token presence
    t: SamplingTensors,
) -> jax.Array:
    """Repetition penalty, exp-decay EOS length penalty, min-tokens mask."""
    b, v = logits.shape

    # repetition penalty (HF/TGIS convention: divide positive logits,
    # multiply negative ones, only for tokens already seen)
    rep = t.repetition_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rep, logits * rep)
    logits = jnp.where(seen, penalized, logits)

    # exponential-decay EOS length penalty: past the start index the EOS
    # logit is boosted by |eos_logit| * (decay^tokens_past - 1)
    cols = jnp.arange(v, dtype=jnp.int32)[None, :]
    is_eos = cols == t.eos_token_id[:, None]
    tokens_past = (t.gen_len - t.len_penalty_start).astype(jnp.float32)
    active = (t.len_penalty_start >= 0) & (tokens_past > 0)
    boost = jnp.abs(logits) * (
        jnp.power(t.len_penalty_decay[:, None], tokens_past[:, None]) - 1.0
    )
    logits = jnp.where(active[:, None] & is_eos, logits + boost, logits)

    # min-tokens: forbid EOS until the row has produced min_tokens
    suppress = (t.gen_len < t.min_tokens)[:, None] & is_eos
    return jnp.where(suppress, NEG_INF, logits)


def _filter_top_k_top_p_typical(
    scaled: jax.Array,  # [B, V] temperature-scaled logits
    t: SamplingTensors,
) -> jax.Array:
    """Mask logits outside the top-k / nucleus / typical sets.

    Each family's full-vocab sort is gated by its own lax.cond, so a
    batch only pays for the filters some row actually enables."""
    b, v = scaled.shape
    probs = jax.nn.softmax(scaled, axis=-1)

    # ---- top-k + top-p share one descending sort of the probabilities
    def topk_topp_mask():
        order = jnp.argsort(-probs, axis=-1)  # [B, V] desc
        sorted_probs = jnp.take_along_axis(probs, order, axis=-1)
        positions = jnp.arange(v, dtype=jnp.int32)[None, :]

        k = jnp.where(t.top_k <= 0, v, t.top_k)[:, None]
        keep_sorted = positions < k

        cumulative = jnp.cumsum(sorted_probs, axis=-1)
        # keep tokens until the cumulative mass *before* them reaches
        # top_p
        exclusive = cumulative - sorted_probs
        keep_sorted &= exclusive < t.top_p[:, None]
        # never drop the best token
        keep_sorted = keep_sorted.at[:, 0].set(True)

        return jnp.zeros((b, v), bool).at[
            jnp.arange(b)[:, None], order
        ].set(keep_sorted)

    keep = jax.lax.cond(
        jnp.any(t.top_k > 0) | jnp.any(t.top_p < 1.0),
        topk_topp_mask, lambda: jnp.ones((b, v), bool),
    )

    # ---- typical-p: rank tokens by |surprisal - entropy| ascending, keep
    # the smallest set with cumulative prob >= typical_p.  Its own sort
    # is gated separately — top-k/top-p batches are common, typical-p
    # rare, and the lax.cond skips the second full-vocab sort entirely
    # when no row uses it
    def typical_mask(keep):
        logp = jax.nn.log_softmax(scaled, axis=-1)
        entropy = -jnp.sum(jnp.where(probs > 0, probs * logp, 0.0),
                           axis=-1, keepdims=True)
        shifted = jnp.abs(-logp - entropy)
        t_order = jnp.argsort(shifted, axis=-1)
        t_sorted_probs = jnp.take_along_axis(probs, t_order, axis=-1)
        t_cum = jnp.cumsum(t_sorted_probs, axis=-1)
        t_keep_sorted = (t_cum - t_sorted_probs) < t.typical_p[:, None]
        t_keep_sorted = t_keep_sorted.at[:, 0].set(True)
        t_keep = jnp.zeros((b, v), bool).at[
            jnp.arange(b)[:, None], t_order
        ].set(t_keep_sorted)
        typical_active = (t.typical_p < 1.0)[:, None]
        return keep & jnp.where(typical_active, t_keep, True)

    keep = jax.lax.cond(
        jnp.any(t.typical_p < 1.0), typical_mask, lambda k: k, keep
    )

    return jnp.where(keep, scaled, NEG_INF)


@partial(jax.jit, donate_argnums=(), static_argnames=("want_topn",))
def sample(
    logits: jax.Array,  # [B, V] f32 raw model logits for the last position
    seen: jax.Array,  # [B, V] bool
    t: SamplingTensors,
    allowed_mask: jax.Array | None = None,  # [B, V] bool structured-output mask
    *,
    want_topn: bool = True,  # static: False skips the per-step top-k
    #     entirely and emits zero-width topn arrays (no request in the
    #     batch asked for top-N logprobs — the common case)
) -> SamplerOutput:
    b, v = logits.shape
    logits = logits.astype(jnp.float32)
    if allowed_mask is not None:
        logits = jnp.where(allowed_mask, logits, NEG_INF)
        # an FSM dead-end state permits only EOS; min-tokens suppression
        # would then leave an all -inf row, so the constraint wins and the
        # row's min_tokens is lifted for this step
        eos_col = jnp.take_along_axis(
            allowed_mask, t.eos_token_id[:, None], axis=-1
        )[:, 0]
        non_eos_allowed = jnp.sum(allowed_mask, axis=-1) - eos_col.astype(
            jnp.int32
        )
        t = dataclasses.replace(
            t, min_tokens=jnp.where(non_eos_allowed > 0, t.min_tokens, 0)
        )
    # decode waves run sample() every fused step, so the [B, V] heavy
    # ops are gated at RUNTIME on whether any row actually uses them
    # (lax.cond executes one branch on TPU): an all-default batch skips
    # the penalty rewrite and — the big one — the two full-vocab sorts
    # of the top-k/top-p/typical filter.  One compiled program still
    # serves every batch composition (no retrace; the predicate is data).
    needs_penalties = (
        jnp.any(t.repetition_penalty != 1.0)
        | jnp.any(t.len_penalty_start >= 0)
        | jnp.any(t.min_tokens > 0)
    )
    logits = jax.lax.cond(
        needs_penalties,
        lambda lg: apply_penalties(lg, seen, t),
        lambda lg: lg,
        logits,
    )

    # token-info distribution: post-penalty, pre-filter (matches the TGIS
    # token detail semantics of "logprob the model assigned")
    greedy = t.temperature <= 0.0
    safe_temp = jnp.where(greedy, 1.0, t.temperature)[:, None]
    scaled = logits / safe_temp
    logp = jax.nn.log_softmax(scaled, axis=-1)

    needs_filter = jnp.any(~greedy) & (
        jnp.any(t.top_k > 0)
        | jnp.any(t.top_p < 1.0)
        | jnp.any(t.typical_p < 1.0)
    )
    filtered = jax.lax.cond(
        needs_filter,
        lambda s: _filter_top_k_top_p_typical(s, t),
        lambda s: s,
        scaled,
    )
    # fold the per-request position (NOT a global step counter) into the
    # key: a seeded request replays the same draw stream no matter how it
    # is batched or scheduled
    keys = jax.vmap(
        lambda s, g: jax.random.fold_in(jax.random.PRNGKey(s), g)
    )(t.base_key, t.gen_len)
    sampled = jax.vmap(jax.random.categorical)(keys, filtered)
    argmax = jnp.argmax(logits, axis=-1)
    tokens = jnp.where(greedy, argmax, sampled).astype(jnp.int32)

    chosen_logp = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    rank = 1 + jnp.sum(logp > chosen_logp[:, None], axis=-1).astype(jnp.int32)
    if want_topn:
        topn_logprobs, topn_ids = jax.lax.top_k(logp, min(TOPN_WIDTH, v))
    else:
        topn_logprobs = jnp.zeros((b, 0), jnp.float32)
        topn_ids = jnp.zeros((b, 0), jnp.int32)
    return SamplerOutput(
        tokens=tokens,
        logprob=chosen_logp,
        rank=rank,
        topn_ids=topn_ids.astype(jnp.int32),
        topn_logprobs=topn_logprobs,
    )


@jax.jit
def pack_output(out: SamplerOutput) -> jax.Array:
    """Merge a SamplerOutput into ONE int32 buffer (floats bitcast).

    Each device->host buffer is its own transfer at the runtime layer, so the
    five result arrays come back in a single fetch.  Layout along the
    last axis: [tokens, rank, topn_ids (W), logprob, topn_logprobs (W)]
    -> [..., 3+2W]; unpacked by _HostSamplerOutput.from_packed."""
    return jnp.concatenate(
        [out.tokens[..., None], out.rank[..., None], out.topn_ids,
         jax.lax.bitcast_convert_type(out.logprob, jnp.int32)[..., None],
         jax.lax.bitcast_convert_type(out.topn_logprobs, jnp.int32)],
        axis=-1,
    )


def packed_width(vocab_size: int, want_topn: bool) -> int:
    """Last-axis width of ``pack_output`` for ``sample(..., want_topn)``
    over ``vocab_size`` logits: 3+2W."""
    return 3 + 2 * (min(TOPN_WIDTH, vocab_size) if want_topn else 0)


@jax.jit
def pack_prompt_logprob_parts(
    parts: tuple[jax.Array, jax.Array, jax.Array, jax.Array],
) -> jax.Array:
    """Same single-fetch packing for prompt_logprob_info's row table:
    [logprob, rank, topn_ids (W), topn_logprobs (W)] -> [T, 2+2W] i32."""
    lp, rank, tn_ids, tn_lp = parts
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(lp, jnp.int32)[..., None],
         rank[..., None], tn_ids,
         jax.lax.bitcast_convert_type(tn_lp, jnp.int32)],
        axis=-1,
    )


@jax.jit
def update_seen(seen: jax.Array, rows: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mark newly generated tokens in the seen-token presence matrix.

    Padding rows carry -1; JAX scatter only drops *positive* out-of-bounds
    indices (negatives wrap to the end), so remap them first.
    """
    safe_rows = jnp.where(rows < 0, seen.shape[0], rows)
    return seen.at[safe_rows, tokens].set(True, mode="drop")


@jax.jit
def note_sampled(
    seen: jax.Array,  # [max_seqs, V]
    last_tokens: jax.Array,  # [max_seqs] newest sampled token a row
    rows: jax.Array,  # [K] batch rows; -1 entries are dropped
    tokens: jax.Array,  # [K]
) -> tuple[jax.Array, jax.Array]:
    """``update_seen`` and, in the same dispatch, each row's newest
    sampled token (runner.last_tokens: what a step planned while this
    one runs reads its input tokens from)."""
    safe_rows = jnp.where(rows < 0, seen.shape[0], rows)
    return (
        seen.at[safe_rows, tokens].set(True, mode="drop"),
        last_tokens.at[safe_rows].set(tokens, mode="drop"),
    )


@jax.jit
def set_seen_row(seen: jax.Array, row: jax.Array, token_ids: jax.Array) -> jax.Array:
    """Reset one batch row of the seen matrix from (padded) prompt tokens."""
    v = seen.shape[1]
    clipped = jnp.where(token_ids < 0, v, token_ids)  # drop -1 pads
    row_vec = jnp.zeros((v,), bool).at[clipped].set(True, mode="drop")
    return seen.at[row].set(row_vec)


@jax.jit
def set_seen_rows(
    seen: jax.Array,  # [max_seqs, V]
    rows: jax.Array,  # [K] batch rows; -1 entries are dropped
    token_ids: jax.Array,  # [K, P] padded prompt tokens (-1 pads)
) -> jax.Array:
    """Batched ``set_seen_row``: seed K rows in ONE dispatch (packed
    prefill seeds every packed prompt's row; K sequential calls would
    copy the full seen matrix K times)."""
    k = rows.shape[0]
    v = seen.shape[1]
    clipped = jnp.where(token_ids < 0, v, token_ids)  # [K, P]
    row_vecs = jnp.zeros((k, v), bool).at[
        jnp.arange(k)[:, None], clipped
    ].set(True, mode="drop")
    safe_rows = jnp.where(rows < 0, seen.shape[0], rows)
    return seen.at[safe_rows].set(row_vecs, mode="drop")


@jax.jit
def prompt_logprob_info(
    logits: jax.Array,  # [T, V] prefill (chunk) logits
    targets: jax.Array,  # [T] token each row predicts (-1 pads)
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-position prompt logprob/rank/top-N (TGIS input token details).

    Row i describes the prediction of ``targets[i]`` — the token at the
    NEXT global position.  Targets cross chunk boundaries (the host
    supplies the next chunk's first token for a chunk's last row), which
    is what makes chunked prompt-logprobs exact; negative pads clamp and
    the caller slices the valid row count.
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    safe = jnp.clip(targets, 0, logp.shape[-1] - 1)
    chosen = jnp.take_along_axis(logp, safe[:, None], axis=-1)[:, 0]
    rank = 1 + jnp.sum(logp > chosen[:, None], axis=-1).astype(jnp.int32)
    topn_lp, topn_ids = jax.lax.top_k(logp, min(TOPN_WIDTH, logp.shape[-1]))
    return chosen, rank, topn_ids.astype(jnp.int32), topn_lp


@partial(jax.jit, static_argnums=(1,))
def prompt_seen_matrix(
    token_rows: jax.Array,  # [B, T] padded prompt tokens (-1 pads)
    vocab_size: int,
) -> jax.Array:
    """Build the initial seen matrix from (padded) prompt token ids."""
    b, _ = token_rows.shape
    seen = jnp.zeros((b, vocab_size), bool)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], token_rows.shape)
    clipped = jnp.where(token_rows < 0, vocab_size, token_rows)  # drop pads
    return seen.at[rows, clipped].set(True, mode="drop")
