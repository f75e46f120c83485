"""DeepSeek-V2: latent attention (MLA) in every layer, then a dense
SwiGLU MLP in the leading layers and group-routed experts beside shared
ones in the rest.

    u   = RMSNorm(x)
    c_q = RMSNorm(q_a u);  per head [q_nope | q_pe] = q_b c_q
    [c_kv | k_pe] = kv_a u;  c_kv = RMSNorm(c_kv);  RoPE on q_pe, k_pe
    q_lat = q_nope W_k                      (W_k, W_v: kv_b a head)
    o_lat = softmax((q_lat . c_kv + q_pe . k_pe) scale) . c_kv
    x'  = x + o_proj(concat_heads(o_lat W_v))
    x'' = x' + sum_k w_k expert_k(v) + shared(v),   v = RMSNorm(x')

(``models/reference/deepseek_v2.py`` writes it out in full, in the
non-absorbed form.)  A class of its own beside ``LlamaForCausalLM`` and
``FalconH1ForCausalLM``, reusing llama.py's free functions.  What is
cached is ONE row a token a layer, ``[c_kv after its norm | k_pe after
RoPE]``, in a pool of latent rows (``make_kv_caches``;
docs/LATENT_ATTENTION.md) addressed by the same block tables, and read
by ops/mla_attention.py over the same work schedule as the ragged
kernel.  The expert layer routes over every expert the router scores
and computes the pairs that fall on the experts this server holds
(ops/moe.py): what the absent experts would add is left out.

The runner's ragged path calls ``ragged_forward`` (the mixed step) and
``decode`` (one step of the fused wave).  Beside the pool, ``caches``
carries two running counts (local pairs, held experts hit, summed over
expert layers and wrapping at 2**32) that the step programs hand back
with their sampled tokens (engine/runner.py).

Weights and activations are the model dtype; the softmax, the router's
scores and every accumulation are float32.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from vllm_tgis_adapter_tpu.models.llama import (
    apply_rotary,
    linear,
    rms_norm,
    rotary_cos_sin,
)
from vllm_tgis_adapter_tpu.ops import attention as attn_ops
from vllm_tgis_adapter_tpu.ops import mla_attention as mla_ops
from vllm_tgis_adapter_tpu.ops import moe as moe_ops
from vllm_tgis_adapter_tpu.ops import ragged_attention as ragged

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

_LEGACY = (
    "deepseek_v2 is served on the ragged path only: the legacy "
    "solo-prefill programs (prompt logprobs, pp/sp engines) know no "
    "latent pool (docs/LATENT_ATTENTION.md)"
)


class DeepseekV2ForCausalLM:
    #: the runner lays a mixed step's work schedule out for this query
    #: block, and hands back the running expert counts a step
    ragged_block_q = mla_ops.MIXED_BLOCK_Q
    has_expert_counts = True

    def __init__(self, config: "ModelConfig"):
        self.config = config
        # stamped by the runner (tp > 1 is refused at boot for this
        # model, so the mesh stays None on the served path)
        self.mesh = None
        self.sp_mode = "ring"

    def max_step_prefill_tokens(self, max_num_seqs: int,
                                block_size: int) -> int:
        """The planner's chunk budget for this model (engine/core.py):
        what keeps a mixed step's work schedule inside the kernel's
        scalar memory at any context."""
        return mla_ops.max_step_prefill_tokens(
            self.config.max_model_len, max_num_seqs, block_size)

    # ---------------------------------------------------------------- params

    def init_params(self, rng: jax.Array) -> dict:
        """Random init for tests; real weights via engine/weights.py."""
        cfg, mla, ex = self.config, self.config.mla, self.config.experts
        d, h = cfg.hidden_size, cfg.num_heads
        nope, rope_dim = mla.qk_nope_head_dim, mla.qk_rope_head_dim
        f = ex.intermediate_size
        keys = iter(jax.random.split(rng, 2 + cfg.num_layers))

        def dense(key, shape, fan_in=None):
            scale = 1.0 / ((fan_in or shape[-2]) ** 0.5)
            return (
                jax.random.normal(key, shape, dtype=jnp.float32) * scale
            ).astype(cfg.dtype)

        params: dict = {
            "embed": dense(next(keys), (cfg.vocab_size, d), 1.0),
            "final_norm": jnp.ones((d,), cfg.dtype),
            "lm_head": dense(next(keys), (d, cfg.vocab_size)),
            "layers": [],
        }
        for i in range(cfg.num_layers):
            lk = iter(jax.random.split(next(keys), 16))
            layer = {
                "input_norm": jnp.ones((d,), cfg.dtype),
                "post_norm": jnp.ones((d,), cfg.dtype),
                "q_a": dense(next(lk), (d, mla.q_lora_rank)),
                "q_a_norm": jnp.ones((mla.q_lora_rank,), cfg.dtype),
                "q_b": dense(next(lk),
                             (mla.q_lora_rank, h * (nope + rope_dim))),
                "kv_a": dense(next(lk), (d, mla.latent_dim)),
                "kv_a_norm": jnp.ones((mla.kv_lora_rank,), cfg.dtype),
                "w_kc": dense(next(lk), (h, nope, mla.kv_lora_rank),
                              mla.kv_lora_rank),
                "w_vc": dense(next(lk), (h, mla.kv_lora_rank,
                                         mla.v_head_dim)),
                "wo": dense(next(lk), (h * mla.v_head_dim, d)),
            }
            if i < ex.first_dense_layers:
                ffn = cfg.intermediate_size
                layer |= {
                    "w_gate": dense(next(lk), (d, ffn)),
                    "w_up": dense(next(lk), (d, ffn)),
                    "w_down": dense(next(lk), (ffn, d)),
                }
            else:
                shared = ex.num_shared * f
                layer |= {
                    "router": dense(next(lk), (d, ex.num_routed)),
                    "experts_gate": dense(next(lk), (ex.num_held, d, f)),
                    "experts_up": dense(next(lk), (ex.num_held, d, f)),
                    "experts_down": dense(next(lk), (ex.num_held, f, d)),
                    "w_gate": dense(next(lk), (d, shared)),
                    "w_up": dense(next(lk), (d, shared)),
                    "w_down": dense(next(lk), (shared, d)),
                }
            params["layers"].append(layer)
        return params

    def make_kv_caches(self, num_slots: int, dtype, quantization: str = "none",
                       block_size: int = 16, kv_scale_floors=None) -> tuple:
        """``(pool [L, num_slots, row_width], counts [2] u32)``: the
        pool of latent rows, ``[c_kv | k_pe | zeros]`` a token a layer
        (``LatentAttentionConfig.row_width`` lanes), and the running
        expert counts that travel with it."""
        if quantization != "none":
            raise ValueError(
                "the latent pool is not quantized (boot refuses "
                "--kv-quantization for this model, engine/config.py)"
            )
        cfg = self.config
        return (
            jnp.zeros((cfg.num_layers, num_slots, cfg.mla.row_width), dtype),
            jnp.zeros((2,), jnp.uint32),
        )

    # ----------------------------------------------------------------- parts

    def _rope(self, positions: jax.Array):
        cfg = self.config
        return rotary_cos_sin(
            positions, cfg.mla.qk_rope_head_dim, cfg.rope_theta,
            cfg.rope_inv_freq_divisors, cfg.rope_mscale,
        )

    def _latent_qkv(self, layer: dict, u: jax.Array, rope):
        """``(q [T, H, row_width], row [T, row_width])``: every head's
        absorbed query ``[q_lat | q_pe | zeros]`` and the token's row of
        the pool ``[c_kv | k_pe | zeros]``."""
        cfg, mla = self.config, self.config.mla
        t = u.shape[0]
        nope, rope_dim = mla.qk_nope_head_dim, mla.qk_rope_head_dim
        pad = mla.row_width - mla.rope_offset - rope_dim
        cos, sin = rope
        c_q = rms_norm(linear(layer, "q_a", u), layer["q_a_norm"],
                       cfg.rms_norm_eps)
        q = linear(layer, "q_b", c_q).reshape(t, cfg.num_heads,
                                              nope + rope_dim)
        q_pe = apply_rotary(q[..., nope:], cos, sin)
        with jax.named_scope("mla_q_absorb"):
            q_lat = jnp.einsum("thn,hnc->thc", q[..., :nope], layer["w_kc"])
        kv = linear(layer, "kv_a", u)
        c_kv = rms_norm(kv[:, :mla.kv_lora_rank], layer["kv_a_norm"],
                        cfg.rms_norm_eps)
        k_pe = apply_rotary(kv[:, None, mla.kv_lora_rank:], cos, sin)[:, 0]
        gap = mla.rope_offset - mla.kv_lora_rank
        q_row = jnp.pad(
            jnp.concatenate([q_lat, jnp.zeros((t, cfg.num_heads, gap),
                                              u.dtype), q_pe], axis=-1),
            ((0, 0), (0, 0), (0, pad)))
        row = jnp.pad(
            jnp.concatenate([c_kv, jnp.zeros((t, gap), u.dtype), k_pe],
                            axis=-1),
            ((0, 0), (0, pad)))
        return q_row, row

    def _attention_out(self, layer: dict, o_lat: jax.Array) -> jax.Array:
        """``o_proj`` over the heads' ``o_lat W_v``."""
        with jax.named_scope("mla_v_absorb"):
            o = jnp.einsum("thc,hcv->thv", o_lat, layer["w_vc"])
        return linear(layer, "wo", o.reshape(o.shape[0], -1))

    @staticmethod
    def _swiglu(layer: dict, v: jax.Array) -> jax.Array:
        act = jax.nn.silu(linear(layer, "w_gate", v)) * linear(
            layer, "w_up", v)
        return linear(layer, "w_down", act)

    def _experts(self, layer: dict, v: jax.Array, valid: jax.Array):
        """The held experts' share and the shared experts, with the
        layer's ``(local pairs, held experts hit)``."""
        ex = self.config.experts
        with jax.named_scope("moe_route"):
            logits = jnp.dot(
                v.astype(jnp.float32), layer["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            experts, weights = moe_ops.group_limited_route(
                logits, n_group=ex.n_group, topk_group=ex.topk_group,
                top_k=ex.top_k, scaling=ex.routed_scaling_factor,
            )
        with jax.named_scope("moe_experts"):
            routed, counts = moe_ops.held_experts_mlp(
                v, experts, weights, valid, layer["experts_gate"],
                layer["experts_up"], layer["experts_down"], ex.first_held,
            )
        with jax.named_scope("moe_shared"):
            shared = self._swiglu(layer, v)
        return routed + shared, counts

    def _block(self, layer: dict, x: jax.Array, attend, rope, valid):
        """One layer around an ``attend(q_row, row) -> o_lat [T, H,
        kv_lora_rank]`` closure (the caller owns the pool's write and
        the attention call).  Returns the layer's output and its expert
        counts (zeros for a dense layer)."""
        cfg = self.config
        u = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        with jax.named_scope("mla_attention"):
            o_lat = attend(*self._latent_qkv(layer, u, rope))
            x = x + self._attention_out(layer, o_lat)
        v = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps)
        if "router" not in layer:
            return x + self._swiglu(layer, v), jnp.zeros((2,), jnp.int32)
        y, counts = self._experts(layer, v, valid)
        return x + y, counts

    def _logits(self, params: dict, x: jax.Array) -> jax.Array:
        x = rms_norm(x, params["final_norm"], self.config.rms_norm_eps)
        return jnp.dot(x, params["lm_head"],
                       preferred_element_type=jnp.float32)

    def _layers(self, params: dict, caches: tuple, token_ids, slot_mapping,
                positions, attend):
        """Every layer over one stream: writes each token's row into the
        pool, calls ``attend(i, pool, q_row)``, and adds up the expert
        counts."""
        pool, counts = caches
        rope = self._rope(positions)
        valid = slot_mapping >= 0
        safe_slots = jnp.where(valid, slot_mapping, pool.shape[1])
        step_counts = jnp.zeros((2,), jnp.int32)

        def attend_layer(i, q_row, row):
            nonlocal pool
            pool = pool.at[i, safe_slots].set(
                row.astype(pool.dtype), mode="drop")
            return attend(i, pool, q_row.astype(pool.dtype)).astype(
                q_row.dtype)

        x = jnp.take(params["embed"], token_ids, axis=0)
        for i, layer in enumerate(params["layers"]):
            x, c = self._block(
                layer, x,
                lambda q_row, row, i=i: attend_layer(i, q_row, row),
                rope, valid,
            )
            step_counts = step_counts + c
        return x, (pool, counts + step_counts.astype(jnp.uint32))

    # ------------------------------------------------------------ mixed step

    def ragged_forward(
        self,
        params: dict,
        caches: tuple,  # (pool, counts)
        token_ids: jax.Array,  # [T] flat mixed stream, padded to a bucket
        positions: jax.Array,  # [T] GLOBAL position per row
        slot_mapping: jax.Array,  # [T] pool slot per row; -1 pads
        seq_starts: jax.Array,  # [S+1] flat span start per sequence
        pos_base: jax.Array,  # [S] global position of each span's first row
        total_tokens: jax.Array,  # scalar: real rows in the stream
        block_tables: jax.Array,  # [S, max_blocks]
        logits_indices: jax.Array,  # [R] rows to compute logits for
        lora=None,
        lora_idx: jax.Array | None = None,
        *,
        block_size: int,
        work: jax.Array | None = None,
        n_items: jax.Array | None = None,
    ) -> tuple[jax.Array, tuple]:
        """One forward over a mixed ragged prefill+decode token stream
        (models/llama.py ``ragged_forward`` has the stream's contract)."""
        if lora is not None:
            raise NotImplementedError(
                "deepseek_v2 has no LoRA targets yet (engine/config.py "
                "refuses --enable-lora for it)")
        mla = self.config.mla

        def attend(i, pool, q_row):
            return mla_ops.mla_ragged_attention(
                q_row, pool, positions, seq_starts, pos_base, total_tokens,
                block_tables, block_size, mla.softmax_scale, layer=i,
                value_dim=mla.kv_lora_rank, work=work, n_items=n_items,
                block_q=self.ragged_block_q,
            )

        x, caches = self._layers(
            params, caches, token_ids, slot_mapping, positions, attend)
        return self._logits(params, x[logits_indices]), caches

    # ------------------------------------------------------------ decode wave

    def decode(
        self,
        params: dict,
        caches: tuple,  # (pool, counts)
        token_ids: jax.Array,  # [B]
        positions: jax.Array,  # [B]
        slot_mapping: jax.Array,  # [B] pool slot of this step; -1 = inactive
        block_tables: jax.Array,  # [B, max_blocks]
        context_lens: jax.Array,  # [B] length INCLUDING the current token
        block_size: int,
        lora=None,
        lora_idx: jax.Array | None = None,
    ) -> tuple[jax.Array, tuple]:
        """One decode step for the whole (padded) running batch: each
        row a one-token span through the latent kernel.  An inactive row
        (slot -1) writes nothing and is routed nowhere."""
        if lora is not None:
            raise NotImplementedError(
                "deepseek_v2 has no LoRA targets yet (engine/config.py "
                "refuses --enable-lora for it)")
        mla = self.config.mla
        b = token_ids.shape[0]
        pos_base = jnp.maximum(context_lens, 1) - 1
        seq_starts = jnp.arange(b + 1, dtype=jnp.int32)
        work = n_items = None
        if attn_ops._use_pallas():
            work, n_items = ragged.decode_work_schedule(
                pos_base, slot_mapping >= 0, block_tables,
                block_size=block_size, block_q=mla_ops.DECODE_BLOCK_Q,
            )

        def attend(i, pool, q_row):
            return mla_ops.mla_ragged_attention(
                q_row, pool, pos_base, seq_starts, pos_base,
                jnp.asarray(b, jnp.int32), block_tables, block_size,
                mla.softmax_scale, layer=i, value_dim=mla.kv_lora_rank,
                work=work, n_items=n_items,
                block_q=mla_ops.DECODE_BLOCK_Q,
            )

        x, caches = self._layers(
            params, caches, token_ids, slot_mapping, positions, attend)
        return self._logits(params, x), caches

    # ----------------------------------------------------------- legacy path

    def prefill(self, *args, **kwargs):
        raise NotImplementedError(_LEGACY)

    def prefill_chunk(self, *args, **kwargs):
        raise NotImplementedError(_LEGACY)
