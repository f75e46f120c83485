"""Draft-model speculative decoding (propose γ → verify as a ragged span).

The reference exposes ``--speculative-model`` / ``--num-speculative-tokens``
and delegates the mechanism to its engine
(/root/reference/src/vllm_tgis_adapter/tgis_utils/args.py:164-168,221-231);
this is the TPU-native mechanism itself, composed with the ragged paged
attention data path (docs/ATTENTION.md "Speculative decoding"):

* **propose**: a ``lax.scan`` over γ draft-model decode steps — one device
  dispatch proposes γ tokens per batch row (writing the draft's own paged
  K/V as it goes) and returns the draft's per-position sampling
  distribution q, which rejection-sampling verification needs;
* **verify**: a spec-eligible running row contributes a (γ+1)-token SPAN
  ``[last_token, d₁ … d_γ]`` to the SAME flat ragged stream that carries
  fresh prefill chunks and plain decode rows — the per-sequence span
  descriptors from the Ragged Paged Attention formulation handle a short
  multi-token span natively, and the kernel's causal masking within the
  span yields exactly the verify logits.  One dispatch
  (``runner._ragged_verify_fn``) serves the whole mixed batch; acceptance
  runs on device via ``_rejection_core`` below;
* rejected positions leave stale K/V in both caches, which is safe: the
  next dispatch re-inputs the corrected token at that position and
  overwrites the slot before anything reads it (device work is strictly
  serialized).

Greedy equivalence: the accepted prefix plus the bonus token reproduces
exactly the non-speculative greedy chain — each accepted dᵢ equals the
target argmax given the identical prefix.  Sampled rows (temperature>0,
top-k/top-p, unseeded) verify by REJECTION SAMPLING — accept dᵢ with
prob min(1, p(dᵢ)/q(dᵢ)), resample the residual norm(max(p−q,0)) on
reject — which emits tokens distributed exactly as the target's sampling
distribution (Leviathan et al. 2023).  LoRA rows verify through the
adapted target (per-row ``lora_idx`` rides the stream) while the draft
proposes from base weights.  Rows with state-evolving knobs (repetition
penalty, typical-p, length-penalty/min-tokens, FSM) and SEEDED sampled
rows ride the plain one-token decode span in the same dispatch —
speculation is per-ROW on the ragged path, not per-batch.

Draft/target contract: same tokenizer and vocab size (validated at
boot); the draft shares the target's block tables and slot geometry, so
its cache is simply a second (smaller) set of paged arrays.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from vllm_tgis_adapter_tpu.logging import init_logger

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.runner import ModelRunner

logger = init_logger(__name__)

_LOG_EVERY = 50  # dispatches between acceptance-rate log lines

# PRNG stream salts: the draft's proposal draws, the acceptance uniforms
# and the residual/bonus draws must be mutually independent streams per
# (request, position) or acceptance correlates with the proposal
_SALT_DRAFT = 1
_SALT_ACCEPT = 2
_SALT_EMIT = 3


def _spec_dist(
    logits: jax.Array,  # [N, V] raw model logits
    temps: jax.Array,  # [N] f32; 0 == greedy row
    top_k: jax.Array,  # [N] i32; <=0 disabled
    top_p: jax.Array,  # [N] f32
) -> jax.Array:
    """Per-row sampling distribution: temperature scale + top-k/top-p
    filter, softmax; greedy rows become exact one-hots so the rejection
    test degenerates to an argmax match for them."""
    import types

    from vllm_tgis_adapter_tpu.engine.sampler import (
        _filter_top_k_top_p_typical,
    )

    greedy = temps <= 0.0
    safe = jnp.where(greedy, 1.0, temps)[:, None]
    scaled = logits.astype(jnp.float32) / safe
    knobs = types.SimpleNamespace(
        top_k=top_k, top_p=top_p, typical_p=jnp.ones_like(top_p)
    )
    probs = jax.nn.softmax(_filter_top_k_top_p_typical(scaled, knobs), -1)
    onehot = jax.nn.one_hot(
        jnp.argmax(logits, axis=-1), logits.shape[-1], dtype=probs.dtype
    )
    return jnp.where(greedy[:, None], onehot, probs)


def _rejection_core(
    logits: jax.Array,  # [B, K, V] target logits over the window
    q_probs: jax.Array,  # [gamma, B, V] draft sampling distributions
    window: jax.Array,  # [B, K] last token + gamma draft proposals
    temps: jax.Array,  # [B]
    top_k: jax.Array,  # [B]
    top_p: jax.Array,  # [B]
    base_key: jax.Array,  # [B] uint32
    gen0: jax.Array,  # [B] tokens generated so far (PRNG position base)
) -> tuple[jax.Array, jax.Array]:
    """Pure rejection-sampling acceptance + emission (Leviathan et al.).

    Accept draft token d_j with prob min(1, p(d_j)/q(d_j)); at the first
    rejection sample from the residual norm(max(p−q, 0)); on full
    acceptance sample the bonus token from p directly.  Greedy rows have
    one-hot p/q, so acceptance degenerates to the argmax match test and
    emission to the target argmax — bit-identical to a greedy verify.
    Returns (emitted [B, K], accepted [B] in 0..gamma).  Factored out of
    the verify program so the distribution-preservation property is
    testable without a model (tests/test_speculative.py).
    """
    b, kw, v = logits.shape
    gamma = kw - 1
    rep = lambda x: jnp.repeat(x, kw, axis=0)  # noqa: E731
    p_probs = _spec_dist(
        logits.reshape(b * kw, v), rep(temps), rep(top_k), rep(top_p)
    ).reshape(b, kw, v)

    d = window[:, 1:]  # [B, gamma] draft proposals
    q_t = jnp.moveaxis(q_probs, 0, 1)  # [B, gamma, V]
    p_d = jnp.take_along_axis(
        p_probs[:, :gamma], d[..., None], axis=-1
    )[..., 0]
    q_d = jnp.take_along_axis(q_t, d[..., None], axis=-1)[..., 0]
    ratio = p_d / jnp.maximum(q_d, 1e-20)

    def u_one(s, p):
        kk = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(s), p), _SALT_ACCEPT
        )
        return jax.random.uniform(kk)

    u = jax.vmap(
        lambda s, g: jax.vmap(lambda j: u_one(s, g + j))(jnp.arange(gamma))
    )(base_key, gen0)  # [B, gamma]
    accept = u < ratio
    accepted = jnp.sum(
        jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
    )  # [B] in 0..gamma

    # emission at the first non-accepted position: residual distribution
    # (or p itself for the bonus token)
    pos_e = jnp.minimum(accepted, gamma)
    p_e = jnp.take_along_axis(p_probs, pos_e[:, None, None], axis=1)[:, 0]
    q_e = jnp.take_along_axis(
        q_t, jnp.minimum(accepted, gamma - 1)[:, None, None], axis=1
    )[:, 0]
    q_e = jnp.where((accepted >= gamma)[:, None], 0.0, q_e)
    resid = jnp.maximum(p_e - q_e, 0.0)
    mass = jnp.sum(resid, axis=-1, keepdims=True)
    dist = jnp.where(mass > 0, resid / jnp.maximum(mass, 1e-30), p_e)
    keys_e = jax.vmap(
        lambda s, p: jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(s), p), _SALT_EMIT
        )
    )(base_key, gen0 + accepted)
    tok_sampled = jax.vmap(jax.random.categorical)(
        keys_e, jnp.log(dist + 1e-30)
    )
    tok_e = jnp.where(
        temps <= 0.0, jnp.argmax(dist, axis=-1), tok_sampled
    ).astype(jnp.int32)

    cols = jnp.arange(kw)[None, :]
    emitted = jnp.where(
        cols < accepted[:, None],
        jnp.pad(d, ((0, 0), (0, 1))),
        tok_e[:, None],
    )  # [B, K]; col j<a: draft token, col a: resampled/bonus
    return emitted, accepted


def _pack_spec_results(emitted, accepted, lp, rank, topn_ids, topn_lp):
    """Merge the verify outputs into ONE int32 buffer so the whole spec
    result comes back in a single device fetch: the standard
    sampler.pack_output layout ([B, K, 3+2W]) plus a trailing broadcast
    `accepted` column -> [B, K, 4+2W].  Unpacked by
    _HostSamplerOutput.from_packed on [..., :-1].  Called from INSIDE
    the jitted ragged_verify program (runner._build_ragged_verify_fn)."""
    from vllm_tgis_adapter_tpu.engine import sampler as sampler_mod

    packed = sampler_mod.pack_output(sampler_mod.SamplerOutput(
        tokens=emitted, logprob=lp, rank=rank,
        topn_ids=topn_ids, topn_logprobs=topn_lp,
    ))
    acc = jnp.broadcast_to(
        accepted.astype(jnp.int32)[:, None, None], (*emitted.shape, 1)
    )
    return jnp.concatenate([packed, acc], axis=-1)


def spec_eligible(params) -> bool:  # noqa: ANN001
    """Row eligibility for speculative verify spans.

    Greedy rows verify by argmax match; unseeded sampled rows (any
    temperature, top-k/top-p) verify by rejection sampling — accept
    draft token d with prob min(1, p(d)/q(d)), resample the residual on
    reject — which preserves the target distribution exactly (Leviathan
    et al.; the mechanism the reference consumes from vLLM's spec
    decode).  Excluded (these rows ride a plain one-token decode span in
    the SAME ragged dispatch — eligibility is per row, not per batch):

    * knobs whose state evolves WITHIN a speculation window (repetition
      penalty's seen matrix, typical-p's entropy set, length-penalty/
      min-tokens EOS shaping, FSM masks);
    * SEEDED sampled requests: the sampler guarantees a seeded request
      replays the same draw stream no matter how it is batched
      (engine/sampler.py), and the spec path's salted draft/accept/emit
      streams differ from the fused sampler's — a seeded row must always
      take the one deterministic path.
    """
    return (
        params.repetition_penalty == 1.0
        and params.typical_p == 1.0
        and params.length_penalty is None
        and params.min_tokens == 0
        and params.structured_outputs is None
        and (params.temperature == 0.0 or params.seed is None)
    )


@dataclasses.dataclass
class SpecStats:
    proposed: int = 0
    accepted: int = 0
    dispatches: int = 0

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.proposed if self.proposed else 0.0


class SpeculativeDecoder:
    """Owns the draft model's device state + the propose program.

    Verification itself lives in the runner's jitted ``ragged_verify``
    entry point (the verify span IS part of the ragged dispatch); this
    class contributes the draft side: cache mirroring/catch-up, the
    γ-step propose scan, and acceptance accounting.
    """

    def __init__(
        self,
        runner: "ModelRunner",
        draft_model,  # noqa: ANN001
        draft_params,  # noqa: ANN001
        num_speculative_tokens: int,
    ):
        if num_speculative_tokens < 1:
            raise ValueError("--num-speculative-tokens must be >= 1")
        self.runner = runner
        self.gamma = num_speculative_tokens
        self.draft_model = draft_model
        self.stats = SpecStats()
        # time-decayed per-dispatch acceptance (30s half-life): the
        # responsive signal the γ auto-tuner consumes, exported as
        # spec_acceptance_rate_ewma next to the lifetime rate
        from vllm_tgis_adapter_tpu.telemetry.ewma import DecayedEwma

        self.acceptance_ewma = DecayedEwma(half_life_s=30.0)

        tcfg = runner.config.model_config
        dcfg = draft_model.config
        if dcfg.vocab_size != tcfg.vocab_size:
            raise ValueError(
                f"draft vocab_size {dcfg.vocab_size} != target "
                f"{tcfg.vocab_size}; speculative decoding requires a "
                "shared tokenizer"
            )

        mesh = runner.mesh
        draft_model.mesh = mesh
        cache_cfg = runner.config.cache_config
        cache_dtype = cache_cfg.cache_dtype
        if mesh is not None:
            from vllm_tgis_adapter_tpu.parallel import (
                cache_sharding,
                shard_llama_params,
                validate_tp_divisibility,
            )

            validate_tp_divisibility(dcfg, mesh.shape["tp"])
            draft_params = shard_llama_params(mesh, draft_params)
            sh = cache_sharding(mesh)
            out_sh = sh
            if cache_cfg.kv_quantization != "none":
                from jax.sharding import (
                    NamedSharding,
                    PartitionSpec as _P,
                )

                from vllm_tgis_adapter_tpu.ops.kv_quant import (
                    QuantizedKVCache,
                )

                out_sh = QuantizedKVCache(
                    sh,
                    NamedSharding(mesh, _P(None, "tp", None)),
                    cache_cfg.block_size,
                )
            self.draft_caches = jax.jit(
                lambda: draft_model.make_kv_caches(
                    runner.num_slots, cache_dtype,
                    quantization=cache_cfg.kv_quantization,
                    block_size=cache_cfg.block_size,
                ),
                out_shardings=(out_sh, out_sh),
            )()
        else:
            # the draft's paged cache follows the target's quantization
            # (greedy acceptance compares against TARGET logits, so a
            # quantized draft never perturbs emitted tokens)
            self.draft_caches = draft_model.make_kv_caches(
                runner.num_slots, cache_dtype,
                quantization=cache_cfg.kv_quantization,
                block_size=cache_cfg.block_size,
            )
        self.draft_params = draft_params

        donate = (1,)
        self._draft_prefill_fn = jax.jit(
            draft_model.prefill, donate_argnums=donate
        )
        self._draft_chunk_fn = jax.jit(
            functools.partial(
                draft_model.prefill_chunk, block_size=runner.block_size
            ),
            donate_argnums=donate,
        )
        self._propose_fn = self._build_propose_fn()

    # ------------------------------------------------------------- prefill

    def draft_prefill(self, prep) -> None:  # noqa: ANN001
        """Mirror the target's (legacy solo) prefill chunk into the draft
        cache.  The ragged path never mirrors at prefill — verify-time
        catch-up (``catch_up``) replays whatever the draft is missing."""
        put = self.runner._put
        common = (
            self.draft_params,
            self.draft_caches,
            put(prep.token_ids),
            put(prep.positions),
            put(prep.slot_mapping),
            put(np.asarray(prep.t, np.int32)),
        )
        # logits for row 0 only — the draft's prefill output is unused,
        # only its KV writes matter
        idx = put(np.asarray([0], np.int32))
        if prep.start_pos == 0:
            _, self.draft_caches = self._draft_prefill_fn(*common, idx)
        else:
            _, self.draft_caches = self._draft_chunk_fn(
                *common, put(prep.block_table), idx
            )

    def catch_up(self, catchups: list[dict]) -> None:
        """Replay lagging rows' missing context through the draft (rows
        that decoded as plain spans, fresh prompts the ragged path
        prefilled target-only, prefix-cache / host-tier adopted spans
        the draft never saw).  Chunk widths ride the prefill-bucket pad
        ladder, so catch-up adds no compile shapes."""
        put = self.runner._put
        for cu in catchups:
            _, self.draft_caches = self._draft_chunk_fn(
                self.draft_params,
                self.draft_caches,
                put(cu["token_ids"]),
                put(cu["positions"]),
                put(cu["slot_mapping"]),
                put(np.asarray(cu["t"], np.int32)),
                put(cu["block_table"]),
                put(np.asarray([0], np.int32)),
            )

    # -------------------------------------------------------------- propose

    def _build_propose_fn(self):
        """One propose program for greedy AND sampled rows: the draft
        SAMPLES from its (temperature/top-k/top-p transformed)
        distribution — greedy rows degenerate to argmax through the
        one-hot ``_spec_dist`` — and returns that distribution per
        proposed position, which rejection-sampling verification needs
        to form the residual.  Inactive rows (non-spec spans sharing the
        dispatch) carry ``limits = -1`` so their writes drop."""
        draft = self.draft_model
        block_size = self.runner.block_size

        def propose(
            params, caches, tokens0, positions0, limits, block_tables,
            context_lens0, temps, top_k, top_p, base_key, gen0, gamma: int,
        ):
            max_blocks = block_tables.shape[1]

            def step(carry, k):
                caches, tok = carry
                pos = positions0 + k
                active = pos <= limits
                blk = jnp.take_along_axis(
                    block_tables,
                    jnp.clip(pos // block_size, 0, max_blocks - 1)[:, None],
                    axis=1,
                )[:, 0]
                slot = jnp.where(
                    active, blk * block_size + pos % block_size, -1
                )
                logits, caches = draft.decode(
                    params, caches, tok, pos, slot, block_tables,
                    context_lens0 + k, block_size,
                )
                probs = _spec_dist(logits, temps, top_k, top_p)
                keys = jax.vmap(
                    lambda s, p: jax.random.fold_in(
                        jax.random.fold_in(jax.random.PRNGKey(s), p),
                        _SALT_DRAFT,
                    )
                )(base_key, gen0 + k)
                sampled = jax.vmap(jax.random.categorical)(
                    keys, jnp.log(probs + 1e-30)
                )
                nxt = jnp.where(
                    temps <= 0.0, jnp.argmax(logits, axis=-1), sampled
                ).astype(jnp.int32)
                return (caches, nxt), (nxt, probs)

            # gamma+1 steps: the extra step feeds d_gamma back so ITS K/V
            # lands in the draft cache too — on a fully-accepted window
            # the next dispatch's context covers d_gamma's position, which
            # would otherwise be a permanent hole (its logits are unused)
            (caches, _), (drafted, qprobs) = jax.lax.scan(
                step, (caches, tokens0), jnp.arange(gamma + 1)
            )
            return caches, drafted[:gamma], qprobs[:gamma]  # [γ,B],[γ,B,V]

        donate = (1,)
        return jax.jit(propose, static_argnums=(12,), donate_argnums=donate)

    def propose(self, prep) -> tuple:  # noqa: ANN001
        """Run draft catch-up + the γ-step propose scan over a prepared
        ragged verify dispatch (runner.PreparedRagged spec fields).
        Returns device-resident ``(drafted [γ, S], q_probs [γ, S, V])``
        — enqueue-only, no host synchronisation."""
        self.catch_up(prep.draft_catchups)
        put = self.runner._put
        t = prep.tensors
        self.draft_caches, drafted, q_probs = self._propose_fn(
            self.draft_params,
            self.draft_caches,
            put(prep.spec_tokens0),
            put(prep.spec_positions0),
            put(prep.spec_limits),
            put(prep.block_tables),
            put(prep.spec_context0),
            put(np.asarray(t.temperature, np.float32)),
            put(np.asarray(t.top_k, np.int32)),
            put(np.asarray(t.top_p, np.float32)),
            put(np.asarray(t.base_key, np.uint32)),
            put(np.asarray(t.gen_len, np.int32)),
            self.gamma,
        )
        return drafted, q_probs

    # ----------------------------------------------------------- accounting

    def note_batch(self, proposed: int, accepted: int) -> None:
        """Fold one verify dispatch's acceptance into the stats + the
        spec metrics (called from the commit path with host counts)."""
        self.stats.proposed += proposed
        self.stats.accepted += accepted
        self.stats.dispatches += 1
        if proposed:
            self.acceptance_ewma.update(accepted / proposed)
        try:
            from vllm_tgis_adapter_tpu import metrics

            metrics.spec_proposed_tokens_total.inc(proposed)
            metrics.spec_accepted_tokens_total.inc(accepted)
        except Exception:  # pragma: no cover - metrics are best-effort
            pass
        if self.stats.dispatches % _LOG_EVERY == 0:
            logger.info(
                "speculative decoding: %.1f%% acceptance over %d proposed "
                "tokens (%d dispatches)",
                100 * self.stats.acceptance_rate, self.stats.proposed,
                self.stats.dispatches,
            )
