"""Decoder-family models in pure JAX over the paged KV cache.

Covers llama / mistral / granite / qwen2 / mixtral (the reference stack's
flagship models, BASELINE.json) as one parameterised skeleton: RMSNorm →
GQA attention with rotary embeddings → SwiGLU MLP, pre-norm residuals,
optional granite scaling multipliers and qwen-style attention biases —
plus the OPT lineage (BASELINE.json: opt-125m) through static config
branches: learned positional embeddings (HF offset-by-2 table),
pre-LayerNorm with biases, plain fc1/ReLU/fc2 MLP, biased
out-projection.  Every branch is plain Python on frozen config, so each
architecture still traces to one straight-line XLA program.

Design notes (TPU-first, SURVEY.md §7):
* params are a plain pytree (list of per-layer dicts) — no framework
  module system between the weights and ``jnp.einsum``, so sharding
  annotations (parallel/sharding.py) attach directly to leaves;
* projection weights are stored ``[in, out]`` so the hot path is plain
  ``x @ w`` on the MXU in bf16; logits are computed in float32 for sampler
  numerics;
* the forward functions are pure: ``(params, caches, inputs) -> (logits,
  caches)`` and are jit-compiled by the model runner with donated caches.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from vllm_tgis_adapter_tpu.ops import attention as attn_ops
from vllm_tgis_adapter_tpu.ops import kv_quant

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float
) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    out = x * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(dtype)


def alibi_slopes(n_heads: int) -> list[float]:
    """Standard ALiBi head slopes (HF build_alibi_tensor convention):
    powers of 2^(-8/n) for power-of-two head counts, with the
    closest-power-of-two + interleave rule otherwise."""
    import math

    def pow2_slopes(n: int) -> list[float]:
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    if math.log2(n_heads).is_integer():
        return pow2_slopes(n_heads)
    closest = 2 ** math.floor(math.log2(n_heads))
    extra = pow2_slopes(2 * closest)[0::2][: n_heads - closest]
    return pow2_slopes(closest) + extra


_ACTIVATIONS = {
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    # HF "gelu" is the exact erf form; jax.nn.gelu defaults to the tanh
    # approximation, which is HF's distinct "gelu_new"
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_new": lambda x: jax.nn.gelu(x, approximate=True),
}


def rotary_cos_sin(
    positions: jax.Array,
    head_dim: int,
    theta: float,
    inv_freq_divisors=None,  # per-dim divisors (rope_scaling, config.py)
    mscale: float = 1.0,  # longrope attention factor on cos/sin
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for the HF llama rotate-half convention."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if inv_freq_divisors is not None:
        inv_freq = inv_freq / jnp.asarray(inv_freq_divisors, jnp.float32)
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [T, Dh]
    return jnp.cos(emb) * mscale, jnp.sin(emb) * mscale


def apply_rotary(
    x: jax.Array,  # [T, H, Dh]
    cos: jax.Array,  # [T, Dh]
    sin: jax.Array,
) -> jax.Array:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    xf = x.astype(jnp.float32)
    rf = rotated.astype(jnp.float32)
    out = xf * cos[:, None, :] + rf * sin[:, None, :]
    return out.astype(x.dtype)


def linear(layer: dict, name: str, x: jax.Array) -> jax.Array:
    """``x @ layer[name]``, transparently consuming weight-only int8
    leaves (engine/weights.py quantize_params_int8): the int8 → x.dtype
    cast rides into the matmul (MXU bf16 in, f32 accumulate; int8 values
    are exact in bf16) and the per-out-channel scale is one fused
    elementwise multiply on the output."""
    q = layer.get(name + "_q8")
    if q is None:
        return x @ layer[name]
    y = x @ q.astype(x.dtype)
    return (
        y.astype(jnp.float32) * layer[name + "_scale"]
    ).astype(x.dtype)


def _rank_lattice_for(lora, target: str) -> tuple[int, ...]:
    """The pow2 rank-bucket lattice, derived STATICALLY from the
    stacks' padded width (engine/lora.py rank_lattice) — identical in
    every trace, so heterogeneous-rank dispatch adds zero compile
    shapes per adapter swap."""
    from vllm_tgis_adapter_tpu.engine.lora import rank_lattice

    return rank_lattice(lora.a[target].shape[-1])


def _lora_delta_single(lora, layer: int, slot, target: str, x: jax.Array):
    """LoRA delta for one sequence (scalar adapter slot): x @ A @ B · s.

    With gathered stacks (``lora.ranks`` carried — docs/LORA.md
    "Gathered matmul") the matmul runs at the slot's rank BUCKET via a
    ``lax.switch`` over the static lattice: the A/B contractions touch
    only the first ``rb`` rank lanes instead of padding every request
    to ``--max-lora-rank``.  Zero-padded lanes contribute exactly 0, so
    the result is the padded path's."""
    scale = lora.scaling[slot]
    xf = x.astype(jnp.float32)
    if getattr(lora, "ranks", None) is None:
        a_l = lora.a[target][layer][slot]  # [din, r]
        b_l = lora.b[target][layer][slot]  # [r, dout]
        t = xf @ a_l
        return (scale * (t @ b_l)).astype(x.dtype)
    lattice = _rank_lattice_for(lora, target)

    def branch(rb):
        def run(xx):
            a_l = lora.a[target][layer][slot][:, :rb]  # [din, rb]
            b_l = lora.b[target][layer][slot][:rb, :]  # [rb, dout]
            return (xx @ a_l) @ b_l

        return run

    which = jnp.searchsorted(
        jnp.asarray(lattice, jnp.int32), lora.ranks[slot]
    )
    d = jax.lax.switch(which, [branch(rb) for rb in lattice], xf)
    return (scale * d).astype(x.dtype)


def _lora_delta_batched(lora, layer: int, idx, target: str, x: jax.Array):
    """Per-row adapter slots (mixed ragged / decode batch): gathered
    batched A·B GEMMs.

    Gathered heterogeneous-rank path (``lora.ranks`` carried): the
    batch's shards are gathered from the arena-resident stacks sliced
    to the LARGEST rank bucket present and contracted once at that
    width (a ``lax.cond`` per lattice value picks the one static
    shape).  A chat batch over rank-8 tenant adapters pays rank-8
    FLOPs even on a ``--max-lora-rank 256`` server; a mixed batch pays
    its widest member's bucket — never more than the padded path.
    Slot 0 (no adapter) has rank bucket 0 and scaling 0, so a
    no-adapter batch contracts nothing and adapter-free rows
    contribute zero — same as the padded path's zero slot."""
    xf = x.astype(jnp.float32)
    if getattr(lora, "ranks", None) is None:
        a_sel = jnp.take(lora.a[target][layer], idx, axis=0)  # [B, din, r]
        b_sel = jnp.take(lora.b[target][layer], idx, axis=0)  # [B, r, dout]
        t = jnp.einsum("bd,bdr->br", xf, a_sel)
        d = jnp.einsum("br,bro->bo", t, b_sel)
        return (jnp.take(lora.scaling, idx)[:, None] * d).astype(x.dtype)
    lattice = _rank_lattice_for(lora, target)
    row_rb = jnp.take(lora.ranks, idx)  # [B]
    a_layer = lora.a[target][layer]  # [S, din, rmax]
    b_layer = lora.b[target][layer]  # [S, rmax, dout]
    # ONE contraction at the batch's LARGEST present bucket: the stacks
    # are zero past each adapter's true rank, so any row computes
    # bit-identically at any width >= its own bucket (the extra terms
    # are exact zeros), and max(present) <= sum(present) always — a
    # per-present-bucket loop would recompute every row at every
    # present width and cost MORE than the padded path on mixed
    # batches.  maxrb lands exactly on a lattice value (or 0 for a
    # no-adapter batch, which leaves the delta zero), so exactly one
    # branch fires and no masking is needed.
    maxrb = jnp.max(row_rb)
    out = jnp.zeros((x.shape[0], b_layer.shape[-1]), jnp.float32)
    for rb in lattice:
        def bucket(acc, rb=rb):
            a_sel = jnp.take(a_layer[:, :, :rb], idx, axis=0)
            b_sel = jnp.take(b_layer[:, :rb, :], idx, axis=0)
            t = jnp.einsum("bd,bdr->br", xf, a_sel)
            return jnp.einsum("br,bro->bo", t, b_sel)

        out = jax.lax.cond(maxrb == rb, bucket, lambda acc: acc, out)
    return (jnp.take(lora.scaling, idx)[:, None] * out).astype(x.dtype)


def _clears_moe_mask(fn):
    """Reset the trace-local MoE validity mask when the entry point
    returns: the attribute is only meaningful inside the trace that set
    it, and a leaked tracer would poison any later direct _moe_mlp call
    (advisor: stale-state hazard of the side-channel mask)."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        try:
            return fn(self, *args, **kwargs)
        finally:
            self._moe_valid_mask = None

    return wrapper


class LlamaForCausalLM:
    def __init__(self, config: "ModelConfig"):
        self.config = config
        # TP mesh for shard_map-wrapped Pallas attention (ops/attention.py);
        # assigned by the runner at boot, None on a single device
        self.mesh = None
        # sequence-parallel prefill style under an sp>1 mesh: "ring"
        # (ppermute K/V rotation) or "ulysses" (head/seq all-to-all);
        # stamped by the runner from ParallelConfig
        self.sp_mode = "ring"
        # pipeline parallelism: a stage model sees only its layer slice;
        # this offset maps local layer index -> global (qwen2's
        # max_window_layers gate needs the global index)
        self.layer_offset = 0
        # bloom lineage: per-head position-bias slopes, a pure function
        # of the head count (never a checkpoint tensor)
        self.alibi = (
            jnp.asarray(alibi_slopes(config.num_heads), jnp.float32)
            if config.position_embedding == "alibi"
            else None
        )

    # ---------------------------------------------------------------- params

    def init_params(self, rng: jax.Array) -> dict:
        """Random init (tests/bench fixtures; real weights via engine/weights.py)."""
        cfg = self.config
        d, dh = cfg.hidden_size, cfg.head_dim
        h, hkv, f = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size
        keys = iter(jax.random.split(rng, 4 + cfg.num_layers))

        def dense(key, shape):
            scale = 1.0 / (shape[0] ** 0.5)
            return (
                jax.random.normal(key, shape, dtype=jnp.float32) * scale
            ).astype(cfg.dtype)

        params: dict = {
            "embed": dense(next(keys), (cfg.vocab_size, d)),
            "final_norm": jnp.ones((d,), dtype=cfg.dtype),
            "layers": [],
        }
        if cfg.norm_type == "layernorm":
            params["final_norm_bias"] = jnp.zeros((d,), dtype=cfg.dtype)
        if cfg.position_embedding == "learned":
            params["pos_embed"] = dense(
                next(keys), (cfg.num_position_embeddings, d)
            )
        if cfg.embed_norm:
            params["embed_norm"] = jnp.ones((d,), dtype=cfg.dtype)
            params["embed_norm_bias"] = jnp.zeros((d,), dtype=cfg.dtype)
        if not cfg.tie_word_embeddings:
            params["lm_head"] = dense(next(keys), (d, cfg.vocab_size))
        for _ in range(cfg.num_layers):
            lk = iter(jax.random.split(next(keys), 9))
            layer = {
                "input_norm": jnp.ones((d,), dtype=cfg.dtype),
                "post_attn_norm": jnp.ones((d,), dtype=cfg.dtype),
                "wq": dense(next(lk), (d, h * dh)),
                "wk": dense(next(lk), (d, hkv * dh)),
                "wv": dense(next(lk), (d, hkv * dh)),
                "wo": dense(next(lk), (h * dh, d)),
            }
            if cfg.norm_type == "layernorm":
                layer["input_norm_bias"] = jnp.zeros((d,), dtype=cfg.dtype)
                layer["post_attn_norm_bias"] = jnp.zeros(
                    (d,), dtype=cfg.dtype
                )
            if cfg.attention_out_bias:
                layer["bo"] = jnp.zeros((d,), dtype=cfg.dtype)
            if cfg.num_experts > 0:
                e = cfg.num_experts

                def stacked(key, shape, fan_in):
                    return (
                        jax.random.normal(key, shape, dtype=jnp.float32)
                        / (fan_in**0.5)
                    ).astype(cfg.dtype)

                layer["router"] = dense(next(lk), (d, e)).astype(jnp.float32)
                layer["experts_gate"] = stacked(next(lk), (e, d, f), d)
                layer["experts_up"] = stacked(next(lk), (e, d, f), d)
                layer["experts_down"] = stacked(next(lk), (e, f, d), f)
            elif cfg.gated_mlp:
                layer["w_gate"] = dense(next(lk), (d, f))
                layer["w_up"] = dense(next(lk), (d, f))
                layer["w_down"] = dense(next(lk), (f, d))
            else:
                layer["w_up"] = dense(next(lk), (d, f))
                layer["w_down"] = dense(next(lk), (f, d))
                if cfg.mlp_bias:
                    layer["b_up"] = jnp.zeros((f,), dtype=cfg.dtype)
                    layer["b_down"] = jnp.zeros((d,), dtype=cfg.dtype)
            if cfg.attention_bias:
                layer["bq"] = jnp.zeros((h * dh,), dtype=cfg.dtype)
                layer["bk"] = jnp.zeros((hkv * dh,), dtype=cfg.dtype)
                layer["bv"] = jnp.zeros((hkv * dh,), dtype=cfg.dtype)
            params["layers"].append(layer)
        return params

    def make_kv_caches(
        self,
        num_slots: int,
        dtype,
        quantization: str = "none",
        block_size: int = 16,
        kv_scale_floors=None,
    ) -> tuple:
        # head-leading layout: a KV page is a contiguous (block_size, Dh)
        # tile per head — the shape the Pallas decode kernel DMAs directly
        # (ops/pallas_attention.py module docstring).  With
        # --kv-quantization the caches become QuantizedKVCache pytrees
        # (int8/fp8 data + per-page-per-head scale sidecar,
        # ops/kv_quant.py); "none" returns the plain arrays unchanged.
        # ``kv_scale_floors`` ((k_floor, v_floor), each [L, Hkv] f32)
        # attaches calibrated page-scale floors from checkpoints that
        # ship k_scale/v_scale tensors (engine/weights.py).
        cfg = self.config
        # rows are cache_head_dim wide: head_dim, or whole lanes where
        # the device would not keep a narrower head axis minor
        shape = (cfg.num_layers, cfg.num_kv_heads, num_slots,
                 kv_quant.cache_head_dim(cfg.head_dim))
        k_floor, v_floor = (
            kv_scale_floors
            if kv_scale_floors is not None
            else (None, None)
        )
        return (
            kv_quant.make_kv_cache(
                shape, dtype, quantization, block_size,
                scale_floor=k_floor,
            ),
            kv_quant.make_kv_cache(
                shape, dtype, quantization, block_size,
                scale_floor=v_floor,
            ),
        )

    # --------------------------------------------------------------- forward

    def _attention_scale(self) -> float:
        cfg = self.config
        if cfg.attention_multiplier is not None:
            return cfg.attention_multiplier
        return cfg.head_dim**-0.5

    def _norm(self, container: dict, x: jax.Array, name: str) -> jax.Array:
        cfg = self.config
        if cfg.norm_type == "layernorm":
            return layer_norm(
                x, container[name], container[f"{name}_bias"],
                cfg.rms_norm_eps,
            )
        return rms_norm(x, container[name], cfg.rms_norm_eps)

    def _window_for_layer(self, i: int) -> int:
        """Layer ``i``'s sliding window, 0 = full attention, as the
        config's per-layer data has it (``ModelConfig.window_of_layer``:
        every layer banded, or qwen2's first ``max_window_layers`` full).
        ``i`` is local to this stage's layer slice; layer_offset
        globalises it."""
        return self.config.window_of_layer(i + self.layer_offset)

    def _rope_tables(self, positions: jax.Array):
        """cos/sin for rotary models; None when positions enter at embed."""
        cfg = self.config
        if cfg.position_embedding != "rope":
            return None
        rd = cfg.rotary_dim or cfg.head_dim
        return rotary_cos_sin(
            positions, rd, cfg.rope_theta,
            inv_freq_divisors=cfg.rope_inv_freq_divisors,
            mscale=cfg.rope_mscale,
        )

    def _apply_pos_qk(
        self, q: jax.Array, k: jax.Array, tables
    ) -> tuple[jax.Array, jax.Array]:
        if tables is None:
            return q, k
        cos, sin = tables
        rd = self.config.rotary_dim
        if not rd or rd == self.config.head_dim:
            return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        # gpt_neox partial rotary: rotate the first rotary_dim dims of
        # each head, pass the rest through unchanged
        q = jnp.concatenate(
            [apply_rotary(q[..., :rd], cos, sin), q[..., rd:]], axis=-1
        )
        k = jnp.concatenate(
            [apply_rotary(k[..., :rd], cos, sin), k[..., rd:]], axis=-1
        )
        return q, k

    def _decoder_block(
        self, layer: dict, x: jax.Array, attend, dl, rope
    ) -> jax.Array:
        """One transformer block around an ``attend(q, k, v) -> [T, H, Dh]``
        closure (the caller owns the KV-cache scatter and the attention
        variant: dense prefill / chunked / paged decode)."""
        cfg = self.config
        h = self._norm(layer, x, "input_norm")
        q, k, v = self._qkv(layer, h, dl)
        q, k = self._apply_pos_qk(q, k, rope)
        o = attend(q, k, v)
        o_flat = o.reshape(x.shape[0], -1)
        o = linear(layer, "wo", o_flat)
        if "bo" in layer:
            o = o + layer["bo"]
        if dl is not None:
            o = o + dl("o_proj", o_flat)
        if cfg.parallel_residual:
            # gpt_neox: x + attn(ln1 x) + mlp(ln2 x) — the MLP reads a
            # second norm of the block INPUT, not of the attn residual
            h = self._norm(layer, x, "post_attn_norm")
            return x + cfg.residual_multiplier * (
                o + self._mlp(layer, h, dl)
            )
        x = x + cfg.residual_multiplier * o
        h = self._norm(layer, x, "post_attn_norm")
        return x + cfg.residual_multiplier * self._mlp(layer, h, dl)

    def _qkv(self, layer: dict, x: jax.Array, dl=None) -> tuple[jax.Array, ...]:
        cfg = self.config
        t = x.shape[0]
        q = linear(layer, "wq", x)
        k = linear(layer, "wk", x)
        v = linear(layer, "wv", x)
        if dl is not None:  # LoRA deltas share the projection input
            q = q + dl("q_proj", x)
            k = k + dl("k_proj", x)
            v = v + dl("v_proj", x)
        if "bq" in layer:
            q = q + layer["bq"]
            k = k + layer["bk"]
            v = v + layer["bv"]
        q = q.reshape(t, cfg.num_heads, cfg.head_dim)
        k = k.reshape(t, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(t, cfg.num_kv_heads, cfg.head_dim)
        if "q_norm" in layer:
            # qwen3: per-head-dim RMSNorm on q/k after projection,
            # BEFORE rotary (HF Qwen3Attention order)
            q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
        return q, k, v

    def _mlp(self, layer: dict, x: jax.Array, dl=None) -> jax.Array:
        if "router" in layer:
            return self._moe_mlp(layer, x)
        act = _ACTIVATIONS[self.config.hidden_act]
        if not self.config.gated_mlp:
            # fc1 → act → fc2 (OPT lineage), biases optional
            h = linear(layer, "w_up", x)
            if "b_up" in layer:
                h = h + layer["b_up"]
            if dl is not None:
                h = h + dl("up_proj", x)
            h = act(h)
            out = linear(layer, "w_down", h)
            if "b_down" in layer:
                out = out + layer["b_down"]
            if dl is not None:
                out = out + dl("down_proj", h)
            return out
        gate = linear(layer, "w_gate", x)
        up = linear(layer, "w_up", x)
        if dl is not None:
            gate = gate + dl("gate_proj", x)
            up = up + dl("up_proj", x)
        h = act(gate) * up
        out = linear(layer, "w_down", h)
        if dl is not None:
            out = out + dl("down_proj", h)
        return out

    def _moe_mlp(self, layer: dict, x: jax.Array) -> jax.Array:
        """Mixtral-style sparse MoE block.

        Router picks top-k experts per token (softmax over router logits,
        renormalised over the selected k, HF mixtral convention), then
        dispatches per ``config.moe_dispatch``:

        * ``dense`` (default): every expert runs on every token as one
          stacked einsum and non-selected contributions are zeroed by
          the routing weights — exact, no data-dependent shapes, E/k ×
          the ideal sparse FLOPs (fine for tiny fixtures/tests);
        * ``capacity``: static per-expert buffers, FLOPs scale with k
          (serving-grade; see _moe_capacity_mlp).
        """
        cfg = self.config
        k = cfg.num_experts_per_tok
        num_experts = layer["router"].shape[1]

        logits = x.astype(jnp.float32) @ layer["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
        top_p, top_idx = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        if cfg.moe_dispatch == "capacity":
            return self._moe_capacity_mlp(layer, x, top_idx, top_p)

        weights = jnp.sum(
            jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32)
            * top_p[..., None],
            axis=1,
        )  # [T, E] — zero for unselected experts

        act = _ACTIVATIONS[cfg.hidden_act]
        gate = jnp.einsum("td,edf->tef", x, layer["experts_gate"])
        up = jnp.einsum("td,edf->tef", x, layer["experts_up"])
        h = act(gate) * up
        out = jnp.einsum("tef,efd->ted", h, layer["experts_down"])
        return jnp.sum(
            out * weights[..., None].astype(out.dtype), axis=1
        ).astype(x.dtype)

    def _moe_capacity_mlp(
        self,
        layer: dict,
        x: jax.Array,  # [T, d]
        top_idx: jax.Array,  # [T, k] selected expert ids
        top_p: jax.Array,  # [T, k] renormalised routing weights
    ) -> jax.Array:
        """Capacity-bucketed top-k dispatch: FLOPs scale with k, not E.

        Every (token, expert) assignment is scattered into a static
        ``[E, C, d]`` buffer where ``C = ceil(T*k/E * capacity_factor)``
        (all shapes static per compile bucket — jit-stable).  Each expert
        runs its FFN over its C rows only; outputs gather back and sum
        with the routing weights.  Assignments beyond an expert's
        capacity are DROPPED (contribute zero) — the standard MoE
        serving trade-off; raise --moe-capacity-factor to trade FLOPs
        for fidelity (factor >= E/k can never drop).

        Expert parallelism: the expert stacks are sharded on the expert
        axis when tp divides E (parallel/sharding.py); the scatter from
        replicated tokens into the E-sharded buffer and the gather back
        become XLA collectives over the tp axis — the all-to-all
        dispatch/combine of a classic EP MoE, derived by the SPMD
        partitioner instead of hand-written.
        """
        cfg = self.config
        t, d = x.shape
        k = cfg.num_experts_per_tok
        num_experts = layer["router"].shape[1]
        capacity = max(
            1,
            int(-(-t * k * cfg.moe_capacity_factor // num_experts)),
        )
        capacity = min(capacity, t)  # an expert can't exceed all tokens

        flat_e = top_idx.reshape(-1)  # [T*k]
        flat_w = top_p.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

        # padding rows (bucket tail, slot -1 — mask set at the prefill/
        # decode entry points) must not eat expert capacity: zero them out
        # of the position ranking and never dispatch them, so real tokens
        # get the full buffer and the drop metrics count real work only
        valid = getattr(self, "_moe_valid_mask", None)
        flat_valid = None if valid is None else jnp.repeat(valid, k)

        # position of each assignment within its expert's buffer: rank
        # among same-expert assignments in flat order (cumsum of the
        # one-hot assignment matrix)
        onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
        if flat_valid is not None:
            onehot = onehot * flat_valid[:, None].astype(onehot.dtype)
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - 1, flat_e[:, None], axis=1
        )[:, 0]  # [T*k]
        keep = pos < capacity
        if flat_valid is not None:
            keep = keep & flat_valid

        if cfg.moe_record_drops:
            # surface the silent-drop count to Prometheus (metrics.py).
            # Only wired on single-device engines: a host callback inside
            # an SPMD program would run per-shard and stall collectives.
            import functools

            from jax.experimental import io_callback

            from vllm_tgis_adapter_tpu import metrics as _metrics

            if flat_valid is None:
                dropped = jnp.sum(jnp.logical_not(keep))
                total = jnp.asarray(t * k, jnp.int32)
            else:
                dropped = jnp.sum(jnp.logical_not(keep) & flat_valid)
                total = jnp.sum(flat_valid).astype(jnp.int32)
            io_callback(
                functools.partial(
                    _metrics.record_moe_dispatch, capacity=capacity
                ),
                None,
                dropped,
                total,
                ordered=False,
            )

        # scatter tokens into per-expert buffers; dropped assignments
        # remap to expert index E and are discarded by mode='drop'
        safe_e = jnp.where(keep, flat_e, num_experts)
        safe_pos = jnp.where(keep, pos, 0)
        buf = jnp.zeros((num_experts, capacity, d), x.dtype)
        buf = buf.at[safe_e, safe_pos].set(x[flat_tok], mode="drop")

        act = _ACTIVATIONS[cfg.hidden_act]
        gate = jnp.einsum("ecd,edf->ecf", buf, layer["experts_gate"])
        up = jnp.einsum("ecd,edf->ecf", buf, layer["experts_up"])
        h = act(gate) * up
        out_e = jnp.einsum("ecf,efd->ecd", h, layer["experts_down"])

        # combine: gather each assignment's expert output, weight it,
        # and segment-sum back over the token axis
        clamped_e = jnp.clip(flat_e, 0, num_experts - 1)
        y = out_e[clamped_e, safe_pos]  # [T*k, d]
        y = jnp.where(
            keep[:, None], y * flat_w[:, None].astype(y.dtype), 0.0
        )
        combined = jnp.zeros((t, d), y.dtype).at[flat_tok].add(y)
        return combined.astype(x.dtype)

    def _embed(
        self, params: dict, token_ids: jax.Array, positions: jax.Array
    ) -> jax.Array:
        cfg = self.config
        x = jnp.take(params["embed"], token_ids, axis=0)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if cfg.position_embedding == "learned":
            # clip keeps padding rows (positions past the table) in
            # bounds; their outputs are discarded by the caller
            idx = jnp.clip(
                positions + cfg.learned_pos_offset,
                0,
                params["pos_embed"].shape[0] - 1,
            )
            x = x + jnp.take(params["pos_embed"], idx, axis=0)
        if cfg.embed_norm:
            x = layer_norm(
                x, params["embed_norm"], params["embed_norm_bias"],
                cfg.rms_norm_eps,
            )
        return x

    def _logits(self, params: dict, x: jax.Array) -> jax.Array:
        cfg = self.config
        x = self._norm(params, x, "final_norm")
        if cfg.tie_word_embeddings:
            logits = x.astype(jnp.float32) @ params["embed"].T.astype(jnp.float32)
        else:
            logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits

    @_clears_moe_mask
    def prefill(
        self,
        params: dict,
        caches: tuple[jax.Array, jax.Array],  # ([L,Hkv,S,Dh], [L,Hkv,S,Dh])
        token_ids: jax.Array,  # [T] padded to a bucket length
        positions: jax.Array,  # [T]
        slot_mapping: jax.Array,  # [T] flat cache slot per token; -1 pads
        valid_len: jax.Array,  # scalar: number of real tokens
        logits_indices: jax.Array | None = None,  # [R] rows to compute logits for
        lora=None,  # LoRAStacks (engine/lora.py) or None
        lora_slot: jax.Array | None = None,  # scalar adapter slot
        *,
        hidden: jax.Array | None = None,  # [T, d] from the previous pp stage
        first_stage: bool = True,  # embed input tokens here
        last_stage: bool = True,  # apply final norm + lm_head here
    ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
        """Full-prompt forward.

        Pipeline parallelism: a non-first stage takes ``hidden`` instead
        of embedding ``token_ids``; a non-last stage returns the raw
        hidden states for the next stage instead of logits.

        This is the LEGACY solo entry point: the serving data path is
        ``ragged_forward`` below; solo prefill survives for pp>1 / sp>1
        engines and prompt-logprob heads (docs/ATTENTION.md).

        Returns logits only at ``logits_indices`` (default: every position).
        Restricting to the sampled row avoids materialising a ``[T, vocab]``
        float32 logits array for long prompts — the lm_head matmul then runs
        on a single row instead of the whole bucket.
        """
        cfg = self.config
        k_cache, v_cache = caches
        scale = self._attention_scale()
        tables = self._rope_tables(positions)
        # trace-local row-validity mask (padding rows carry slot -1):
        # capacity MoE dispatch excludes padding so it cannot eat expert
        # capacity or skew the drop metrics (_moe_capacity_mlp)
        self._moe_valid_mask = slot_mapping >= 0
        # negative (padding) slots must not wrap: remap past the end, then
        # scatter mode='drop' discards them (JAX drops only positive OOB)
        safe_slots = jnp.where(slot_mapping < 0, k_cache.shape[2], slot_mapping)

        def attend(i, q, k, v):
            nonlocal k_cache, v_cache
            k_cache = kv_quant.scatter_layer(
                k_cache, i, safe_slots, k, self.mesh
            )
            v_cache = kv_quant.scatter_layer(
                v_cache, i, safe_slots, v, self.mesh
            )
            # dense attend over the chunk's own (full-precision) K/V:
            # quantization only perturbs later PAGED reads of this cache
            return attn_ops.prefill_attention(
                q, k, v, scale, valid_len, mesh=self.mesh,
                window=self._window_for_layer(i),
                alibi_slopes=self.alibi,
                sp_mode=self.sp_mode,
            )

        x = (
            self._embed(params, token_ids, positions)
            if first_stage
            else hidden
        )
        for i, layer in enumerate(params["layers"]):
            dl = None
            if lora is not None:
                dl = (
                    lambda target, xx, i=i: _lora_delta_single(
                        lora, i, lora_slot, target, xx
                    )
                )
            x = self._decoder_block(
                layer, x, lambda q, k, v, i=i: attend(i, q, k, v), dl,
                tables,
            )

        if not last_stage:
            return x, (k_cache, v_cache)
        if logits_indices is not None:
            x = x[logits_indices]
        return self._logits(params, x), (k_cache, v_cache)

    @_clears_moe_mask
    def prefill_chunk(
        self,
        params: dict,
        caches: tuple[jax.Array, jax.Array],
        token_ids: jax.Array,  # [T] one chunk, padded to a bucket
        positions: jax.Array,  # [T] GLOBAL positions (start_pos + i)
        slot_mapping: jax.Array,  # [T] cache slot per chunk token; -1 pads
        valid_len: jax.Array,  # scalar: real tokens in this chunk
        block_table: jax.Array,  # [max_blocks] this sequence's page table
        logits_indices: jax.Array | None = None,
        lora=None,
        lora_slot: jax.Array | None = None,
        *,
        block_size: int,
        hidden: jax.Array | None = None,
        first_stage: bool = True,
        last_stage: bool = True,
    ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
        """A non-first prefill chunk: queries attend to the chunk AND all
        earlier context already resident in the paged cache.

        The chunk's K/V are scattered into the cache first, then the
        chunk's queries attend over [0, start+T) through
        ``ops.attention.chunked_prefill_attention`` — a dedicated Pallas
        kernel on TPU (each context page read once per kv-head × query
        block), the gather-based decode formulation elsewhere.
        """
        cfg = self.config
        k_cache, v_cache = caches
        scale = self._attention_scale()
        tables = self._rope_tables(positions)
        self._moe_valid_mask = slot_mapping >= 0  # see prefill
        safe_slots = jnp.where(slot_mapping < 0, k_cache.shape[2], slot_mapping)

        # the chunk's first global position; padding rows (beyond
        # valid_len) produce garbage the caller discards
        start = positions[0]

        def attend(i, q, k, v):
            nonlocal k_cache, v_cache
            k_cache = kv_quant.scatter_layer(
                k_cache, i, safe_slots, k, self.mesh
            )
            v_cache = kv_quant.scatter_layer(
                v_cache, i, safe_slots, v, self.mesh
            )
            return attn_ops.chunked_prefill_attention(
                kv_quant.pad_to_cache_width(q, k_cache),
                kv_quant.layer_data(k_cache, i),
                kv_quant.layer_data(v_cache, i), block_table, start,
                valid_len, block_size, scale, mesh=self.mesh,
                window=self._window_for_layer(i),
                alibi_slopes=self.alibi,
                kv_scales=kv_quant.layer_scales(k_cache, v_cache, i),
            )[..., : q.shape[-1]]

        x = (
            self._embed(params, token_ids, positions)
            if first_stage
            else hidden
        )
        for i, layer in enumerate(params["layers"]):
            dl = None
            if lora is not None:
                dl = (
                    lambda target, xx, i=i: _lora_delta_single(
                        lora, i, lora_slot, target, xx
                    )
                )
            x = self._decoder_block(
                layer, x, lambda q, k, v, i=i: attend(i, q, k, v), dl,
                tables,
            )

        if not last_stage:
            return x, (k_cache, v_cache)
        if logits_indices is not None:
            x = x[logits_indices]
        return self._logits(params, x), (k_cache, v_cache)

    @_clears_moe_mask
    def ragged_forward(
        self,
        params: dict,
        caches: tuple[jax.Array, jax.Array],
        token_ids: jax.Array,  # [T] flat mixed stream, padded to a bucket
        positions: jax.Array,  # [T] GLOBAL position per row
        slot_mapping: jax.Array,  # [T] cache slot per row; -1 pads
        seq_starts: jax.Array,  # [S+1] flat span start per sequence
        pos_base: jax.Array,  # [S] global position of each span's first row
        total_tokens: jax.Array,  # scalar: real rows in the stream
        block_tables: jax.Array,  # [S, max_blocks]
        logits_indices: jax.Array,  # [R] rows to compute logits for
        lora=None,  # LoRAStacks or None
        lora_idx: jax.Array | None = None,  # [T] adapter slot per ROW
        *,
        block_size: int,
        work: jax.Array | None = None,  # Pallas work schedule (TPU only)
        n_items: jax.Array | None = None,  # scalar: real items of ``work``
    ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
        """One forward over a mixed ragged prefill+decode token stream.

        The ragged backend's unified step (ops/ragged_attention.py):
        each sequence owns a contiguous span of the flat token axis — a
        whole prompt, a prefill chunk, or a single decode token — and
        every row attends causally to its sequence's paged context.
        This single entry point replaces the solo-prefill, packed-
        prefill, chunked-prefill AND single-step-decode programs of the
        bucketed path, so the compile lattice collapses to the flat
        token buckets.
        """
        k_cache, v_cache = caches
        scale = self._attention_scale()
        tables = self._rope_tables(positions)
        self._moe_valid_mask = slot_mapping >= 0  # see prefill
        safe_slots = jnp.where(slot_mapping < 0, k_cache.shape[2], slot_mapping)

        def attend(i, q, k, v):
            nonlocal k_cache, v_cache
            k_cache = kv_quant.scatter_layer(
                k_cache, i, safe_slots, k, self.mesh
            )
            v_cache = kv_quant.scatter_layer(
                v_cache, i, safe_slots, v, self.mesh
            )
            from vllm_tgis_adapter_tpu.ops.ragged_attention import (
                ragged_paged_attention,
            )

            return ragged_paged_attention(
                kv_quant.pad_to_cache_width(q, k_cache),
                *kv_quant.pool_data(k_cache, v_cache), positions,
                seq_starts, pos_base, total_tokens, block_tables,
                block_size, scale, layer=i,
                work=work, n_items=n_items, mesh=self.mesh,
                window=self._window_for_layer(i),
                alibi_slopes=self.alibi,
                kv_scales=kv_quant.pool_scales(k_cache, v_cache),
            )[..., : q.shape[-1]]

        x = self._embed(params, token_ids, positions)
        for i, layer in enumerate(params["layers"]):
            dl = None
            if lora is not None:
                dl = (
                    lambda target, xx, i=i: _lora_delta_batched(
                        lora, i, lora_idx, target, xx
                    )
                )
            x = self._decoder_block(
                layer, x, lambda q, k, v, i=i: attend(i, q, k, v), dl,
                tables,
            )
        x = x[logits_indices]
        return self._logits(params, x), (k_cache, v_cache)

    @_clears_moe_mask
    def decode(
        self,
        params: dict,
        caches: tuple[jax.Array, jax.Array],
        token_ids: jax.Array,  # [B]
        positions: jax.Array,  # [B]
        slot_mapping: jax.Array,  # [B] where this step's K/V lands; -1 = inactive
        block_tables: jax.Array,  # [B, max_blocks]
        context_lens: jax.Array,  # [B] length INCLUDING the current token
        block_size: int,
        lora=None,  # LoRAStacks or None
        lora_idx: jax.Array | None = None,  # [B] adapter slot per row
        hidden: jax.Array | None = None,  # [B, d] from the previous pp stage
        first_stage: bool = True,
        last_stage: bool = True,
    ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
        """One decode step for the whole (padded) running batch.

        Attention routes through the unified ragged kernel — each batch
        row is a one-token span, so the fused decode wave and the mixed
        ragged step run the SAME kernel (the bucketed
        folded → perhead → xla variant chain is retired;
        docs/ATTENTION.md).
        """
        cfg = self.config
        k_cache, v_cache = caches
        scale = self._attention_scale()
        tables = self._rope_tables(positions)
        self._moe_valid_mask = slot_mapping >= 0  # see prefill
        # see prefill: negative pad slots must not wrap to the last page
        safe_slots = jnp.where(slot_mapping < 0, k_cache.shape[2], slot_mapping)
        from vllm_tgis_adapter_tpu.ops import ragged_attention as ragged

        b = token_ids.shape[0]
        # one-token spans: row i is sequence i at position
        # context_lens[i] - 1 (dead rows carry context 1/slot -1 and
        # their output is discarded by the sampler mask, same as the
        # padded-batch decode contract)
        pos_base = jnp.maximum(context_lens, 1) - 1
        seq_starts = jnp.arange(b + 1, dtype=jnp.int32)
        # the kernel's schedule is the same for every layer: built once
        # a step, over the live rows' live pages alone (a row with slot
        # -1 writes nothing this step and nothing reads its output)
        work = n_items = None
        if attn_ops._use_pallas():
            work, n_items = ragged.decode_work_schedule(
                pos_base, slot_mapping >= 0, block_tables,
                block_size=block_size,
            )

        def attend(i, q, k, v):
            nonlocal k_cache, v_cache
            k_cache = kv_quant.scatter_layer(
                k_cache, i, safe_slots, k, self.mesh
            )
            v_cache = kv_quant.scatter_layer(
                v_cache, i, safe_slots, v, self.mesh
            )
            return ragged.ragged_paged_attention(
                kv_quant.pad_to_cache_width(q, k_cache),
                *kv_quant.pool_data(k_cache, v_cache),
                pos_base, seq_starts, pos_base,
                jnp.asarray(b, jnp.int32),
                block_tables, block_size, scale, layer=i,
                work=work, n_items=n_items,
                block_q=ragged.decode_block_q(b), mesh=self.mesh,
                window=self._window_for_layer(i),
                alibi_slopes=self.alibi,
                kv_scales=kv_quant.pool_scales(k_cache, v_cache),
            )[..., : q.shape[-1]]

        x = (
            self._embed(params, token_ids, positions)
            if first_stage
            else hidden
        )
        for i, layer in enumerate(params["layers"]):
            dl = None
            if lora is not None:
                dl = (
                    lambda target, xx, i=i: _lora_delta_batched(
                        lora, i, lora_idx, target, xx
                    )
                )
            x = self._decoder_block(
                layer, x, lambda q, k, v, i=i: attend(i, q, k, v), dl,
                tables,
            )

        if not last_stage:
            return x, (k_cache, v_cache)
        return self._logits(params, x), (k_cache, v_cache)
