"""Command A+ (``cohere2_moe``): window and full attention layers in one
stack, a parallel block under one weight-only LayerNorm, sigmoid-routed
experts of which the server holds a share beside averaged shared ones,
and a tied head.

    u  = LayerNorm(x)                     (weight only, mean-subtracting)
    q, k, v = Wq u, Wk u, Wv u            (128 query heads over 8 KV heads)
    a window layer: RoPE on q and k, row t sees s with 0 <= t - s < window
    a full layer:   no positional encoding, causal over everything
    a  = Wo softmax(q k^T / sqrt(head_dim)) v
    x' = x + a + sum_k w_k expert_k(u) + mean_j shared_j(u)

(``models/reference/cohere2_moe.py`` writes it out in full.)  A class of
its own beside ``DeepseekV2ForCausalLM``, reusing llama.py's free
functions and ops/moe.py's held-experts layer.

**Two groups of layers, two pools.**  ``caches`` is ``(k_full, v_full,
k_window, v_window, counts)``: the full layers' pages in one
``[L_full, Hkv, slots, Dh]`` pool pair, the window layers' in another of
its own size, each addressed by its own block table and written at its
own slots (engine/kv_cache.py keeps a sequence's pages in two groups and
frees the window group's behind the band; docs/ATTENTION.md).  A step
carries one work schedule a layer kind: the window layers' leaves out
the runs of pages below the band (ops/ragged_attention.py), and the
kernel's band mask cuts into the first run that is left.

The window layers' rotary pairs are interleaved in the checkpoint
(``rope_gptj``); the loader regroups the columns of ``q_proj`` and
``k_proj`` to halves once (engine/weights.py), so ``apply_rotary``
rotates halves here: a score is a dot product over the head, which a
permutation shared by q and k leaves as it was.  The four shared
experts run as ONE SwiGLU of four times the width whose output is
divided by four.

Weights and activations are the model dtype; the norm, the softmax, the
router's scores and every accumulation are float32.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from vllm_tgis_adapter_tpu.models.llama import (
    apply_rotary,
    linear,
    rotary_cos_sin,
)
from vllm_tgis_adapter_tpu.ops import attention as attn_ops
from vllm_tgis_adapter_tpu.ops import kv_quant
from vllm_tgis_adapter_tpu.ops import moe as moe_ops
from vllm_tgis_adapter_tpu.ops import ragged_attention as ragged

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

_LEGACY = (
    "cohere2_moe is served on the ragged path only: the legacy "
    "solo-prefill programs (prompt logprobs, pp/sp engines) know one "
    "block table (docs/ATTENTION.md)"
)

#: rows of the kernel's query tile, ``q heads a KV head x block_q``: at
#: 1,024 its float32 score tile against one 128-token run is 512 KB and
#: the tile's accumulators and temporaries stay well inside the
#: kernel's 16 MB of VMEM (the llama lineage's default, 128 rows at 4
#: heads a KV head, is half that)
QUERY_TILE_ROWS = 1024

#: the kernels' names in a device trace, for this model only
WINDOW_KERNEL = "window_ragged_attention"
FULL_KERNEL = "full_ragged_attention"


def layer_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Mean-subtracting LayerNorm with a weight and no bias, float32."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return out.astype(x.dtype)


def ragged_block_q(q_per_kv: int) -> int:
    """The mixed step's query block for ``q_per_kv`` query heads a KV
    head: the most rows, a power of two from 8 to 128, that keep the
    kernel's tile within ``QUERY_TILE_ROWS``."""
    rows = max(8, min(128, QUERY_TILE_ROWS // q_per_kv))
    return 1 << (rows.bit_length() - 1)


class Cohere2MoeForCausalLM:
    has_expert_counts = True

    def __init__(self, config: "ModelConfig"):
        self.config = config
        # stamped by the runner (tp > 1 is refused at boot for this
        # model, so the mesh stays None on the served path)
        self.mesh = None
        self.sp_mode = "ring"
        #: the runner lays a mixed step's schedules out for this block
        self.ragged_block_q = ragged_block_q(config.q_per_kv)
        # layer -> (is a window layer, its index in its group's pool)
        self._pool_index = {}
        for n, i in enumerate(config.window_layers):
            self._pool_index[i] = (True, n)
        for n, i in enumerate(config.full_layers):
            self._pool_index[i] = (False, n)

    def max_step_prefill_tokens(self, max_num_seqs: int,
                                block_size: int) -> int:
        """The planner's chunk budget for this model (engine/core.py):
        what keeps the full layers' schedule of a mixed step inside the
        kernel's scalar memory at any context (the window layers' is
        never wider).  The running rows' one-token spans have a
        schedule of their own (``ragged_forward``), so what they take
        of this one is the query blocks they push the chunk along by,
        not a run of items each."""
        shifted = -(-max_num_seqs // self.ragged_block_q) + 1
        return ragged.max_step_prefill_tokens(
            self.config.max_model_len, shifted, block_size,
            self.ragged_block_q)

    # ---------------------------------------------------------------- params

    def init_params(self, rng: jax.Array) -> dict:
        """Random init for tests; real weights via engine/weights.py."""
        cfg, ex = self.config, self.config.experts
        d, h, kv, dh = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                        cfg.head_dim)
        f = ex.intermediate_size
        keys = iter(jax.random.split(rng, 1 + cfg.num_layers))

        def dense(key, shape, fan_in=None):
            scale = 1.0 / ((fan_in or shape[-2]) ** 0.5)
            return (
                jax.random.normal(key, shape, dtype=jnp.float32) * scale
            ).astype(cfg.dtype)

        params: dict = {
            "embed": dense(next(keys), (cfg.vocab_size, d), 1.0),
            "final_norm": jnp.ones((d,), cfg.dtype),
            "layers": [],
        }
        shared = ex.num_shared * f
        for _ in range(cfg.num_layers):
            lk = iter(jax.random.split(next(keys), 12))
            params["layers"].append({
                "input_norm": jnp.ones((d,), cfg.dtype),
                "wq": dense(next(lk), (d, h * dh)),
                "wk": dense(next(lk), (d, kv * dh)),
                "wv": dense(next(lk), (d, kv * dh)),
                "wo": dense(next(lk), (h * dh, d)),
                "router": dense(next(lk), (d, ex.num_routed)),
                "experts_gate": dense(next(lk), (ex.num_held, d, f)),
                "experts_up": dense(next(lk), (ex.num_held, d, f)),
                "experts_down": dense(next(lk), (ex.num_held, f, d)),
                "w_gate": dense(next(lk), (d, shared)),
                "w_up": dense(next(lk), (d, shared)),
                "w_down": dense(next(lk), (shared, d)),
            })
        return params

    def make_kv_caches(self, num_slots: int, dtype, quantization: str = "none",
                       block_size: int = 16, kv_scale_floors=None, *,
                       window_slots: int) -> tuple:
        """``(k_full, v_full, k_window, v_window, counts)``: the full
        layers' pool pair ``[L_full, Hkv, num_slots, Dh]``, the window
        layers' ``[L_window, Hkv, window_slots, Dh]``, and the running
        expert counts ``[2] u32`` that travel with them."""
        if quantization != "none":
            raise ValueError(
                "the two groups' pools are not quantized (boot refuses "
                "--kv-quantization for this model, engine/config.py)"
            )
        cfg = self.config

        def pool(layers: int, slots: int):
            # rows as wide as the llama lineage's pools keep them
            shape = (layers, cfg.num_kv_heads, slots,
                     kv_quant.cache_head_dim(cfg.head_dim))
            return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

        return (
            *pool(len(cfg.full_layers), num_slots),
            *pool(len(cfg.window_layers), window_slots),
            jnp.zeros((2,), jnp.uint32),
        )

    # ----------------------------------------------------------------- parts

    def _experts(self, layer: dict, u: jax.Array, valid: jax.Array):
        """The held experts' share plus the shared experts' mean, with
        the layer's ``(local pairs, held experts hit)``."""
        ex = self.config.experts
        with jax.named_scope("moe_route"):
            logits = jnp.dot(
                u.astype(jnp.float32), layer["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            experts, weights = moe_ops.sigmoid_route(logits, top_k=ex.top_k)
        with jax.named_scope("moe_experts"):
            routed, counts = moe_ops.held_experts_mlp(
                u, experts, weights, valid, layer["experts_gate"],
                layer["experts_up"], layer["experts_down"], ex.first_held,
            )
        with jax.named_scope("moe_shared"):
            act = jax.nn.silu(linear(layer, "w_gate", u)) * linear(
                layer, "w_up", u)
            # one SwiGLU as wide as the shared experts together: its
            # output is their sum, and they are averaged
            shared = linear(layer, "w_down", act) * (1.0 / ex.num_shared)
        return routed + shared.astype(routed.dtype), counts

    def _logits(self, params: dict, x: jax.Array) -> jax.Array:
        cfg = self.config
        x = layer_norm(x, params["final_norm"], cfg.rms_norm_eps)
        # the head is the embedding (tie_word_embeddings)
        logits = jax.lax.dot_general(
            x, params["embed"], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return logits / cfg.logits_scaling

    def _layers(self, params: dict, caches: tuple, token_ids, positions,
                slots, window_slots, attend):
        """Every layer over one stream: writes each token's keys and
        values into its layer's pool at its group's slot, calls
        ``attend(i, windowed, pool_layer, k_pool, v_pool, q)``, and adds
        up the expert counts."""
        cfg = self.config
        k_full, v_full, k_win, v_win, counts = caches
        t = token_ids.shape[0]
        cos, sin = rotary_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        valid = slots >= 0
        # negative pad slots must not wrap to the last page
        safe_full = jnp.where(valid, slots, k_full.shape[2])
        safe_win = jnp.where(window_slots >= 0, window_slots,
                             k_win.shape[2])
        step_counts = jnp.zeros((2,), jnp.int32)
        x = jnp.take(params["embed"], token_ids, axis=0)
        for i, layer in enumerate(params["layers"]):
            windowed, n = self._pool_index[i]
            u = layer_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            q = linear(layer, "wq", u).reshape(t, cfg.num_heads, cfg.head_dim)
            k = linear(layer, "wk", u).reshape(
                t, cfg.num_kv_heads, cfg.head_dim)
            v = linear(layer, "wv", u).reshape(
                t, cfg.num_kv_heads, cfg.head_dim)
            if cfg.layer_rotates(i):
                q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
            if windowed:
                k_win = kv_quant.scatter_layer(k_win, n, safe_win, k)
                v_win = kv_quant.scatter_layer(v_win, n, safe_win, v)
                pools = (k_win, v_win)
            else:
                k_full = kv_quant.scatter_layer(k_full, n, safe_full, k)
                v_full = kv_quant.scatter_layer(v_full, n, safe_full, v)
                pools = (k_full, v_full)
            a = attend(
                i, windowed, n, *pools,
                kv_quant.pad_to_cache_width(q, pools[0]),
            )[..., : cfg.head_dim]
            a = linear(layer, "wo", a.astype(x.dtype).reshape(t, -1))
            m, c = self._experts(layer, u, valid)
            x = x + a + m
            step_counts = step_counts + c
        return x, (k_full, v_full, k_win, v_win,
                   counts + step_counts.astype(jnp.uint32))

    # ------------------------------------------------------------ mixed step

    def ragged_forward(
        self,
        params: dict,
        caches: tuple,  # (k_full, v_full, k_window, v_window, counts)
        token_ids: jax.Array,  # [T] flat mixed stream, padded to a bucket
        positions: jax.Array,  # [T] GLOBAL position per row
        slot_mapping: jax.Array,  # [T] full-group slot per row; -1 pads
        seq_starts: jax.Array,  # [S+1] flat span start per sequence
        pos_base: jax.Array,  # [S] global position of each span's first row
        total_tokens: jax.Array,  # scalar: real rows in the stream
        block_tables: jax.Array,  # [S, max_blocks] the full group's
        logits_indices: jax.Array,  # [R] rows to compute logits for
        lora=None,
        lora_idx: jax.Array | None = None,
        *,
        block_size: int,
        window_slots: jax.Array,  # [T] window-group slot per row
        window_tables: jax.Array,  # [S, max_blocks] the window group's
        work: jax.Array | None = None,  # the full layers' schedule
        n_items: jax.Array | None = None,
        window_work: jax.Array | None = None,  # the window layers'
        window_n_items: jax.Array | None = None,
        unit_rows: jax.Array | None = None,  # scalar: the stream's
        #     first ``unit_rows`` spans are one token each (the running
        #     rows) and ``work`` / ``window_work`` leave them out
    ) -> tuple[jax.Array, tuple]:
        """One forward over a mixed ragged prefill+decode token stream
        (models/llama.py ``ragged_forward`` has the stream's contract).

        With the compiled kernel a layer's attention is two calls: the
        prompt chunks' under the host-built schedule at this model's
        query block, and the running rows' one-token spans under a
        schedule built here as the wave builds its own, at the wave's
        query block of 8.  In the chunks' tile of ``QUERY_TILE_ROWS`` a
        running row has 16 live rows of 1,024 for every run of its
        context, which cost a step 7-12 ms a deep row (PERF.md section
        6, PR 49)."""
        if lora is not None:
            raise NotImplementedError(
                "cohere2_moe has no LoRA targets yet (engine/config.py "
                "refuses --enable-lora for it)")
        cfg = self.config
        scale = cfg.head_dim ** -0.5
        t = token_ids.shape[0]
        rows = None
        if unit_rows is not None:
            # a unit span s is flat row s: the wave's layout, over the
            # head of this stream
            rows = min(seq_starts.shape[0] - 1, t)
            live = jnp.arange(rows, dtype=jnp.int32) < unit_rows
            row_schedules = {
                False: ragged.decode_work_schedule(
                    pos_base[:rows], live, block_tables[:rows],
                    block_size=block_size),
                True: ragged.decode_work_schedule(
                    pos_base[:rows], live, window_tables[:rows],
                    block_size=block_size, window=cfg.group_window),
            }
            is_unit = (jnp.arange(t, dtype=jnp.int32) < unit_rows)[
                :, None, None]

        def attend(i, windowed, n, k_pool, v_pool, q):
            def call(q, work, n_items, block_q):
                return ragged.ragged_paged_attention(
                    q, k_pool, v_pool, positions[: q.shape[0]], seq_starts,
                    pos_base, total_tokens,
                    window_tables if windowed else block_tables,
                    block_size, scale, layer=n, work=work, n_items=n_items,
                    window=cfg.window_of_layer(i), block_q=block_q,
                    name=WINDOW_KERNEL if windowed else FULL_KERNEL,
                )

            out = call(q, window_work if windowed else work,
                       window_n_items if windowed else n_items,
                       self.ragged_block_q)
            if rows is None:
                return out
            units = call(q[:rows], *row_schedules[windowed],
                         ragged.decode_block_q(rows))
            return jnp.where(
                is_unit, jnp.pad(units, ((0, t - rows), (0, 0), (0, 0))),
                out)

        x, caches = self._layers(
            params, caches, token_ids, positions, slot_mapping,
            window_slots, attend)
        return self._logits(params, x[logits_indices]), caches

    # ------------------------------------------------------------ decode wave

    def decode(
        self,
        params: dict,
        caches: tuple,  # (k_full, v_full, k_window, v_window, counts)
        token_ids: jax.Array,  # [B]
        positions: jax.Array,  # [B]
        slot_mapping: jax.Array,  # [B] full-group slot; -1 = inactive
        block_tables: jax.Array,  # [B, max_blocks] the full group's
        context_lens: jax.Array,  # [B] length INCLUDING the current token
        block_size: int,
        lora=None,
        lora_idx: jax.Array | None = None,
        *,
        window_slots: jax.Array,  # [B] window-group slot of this step
        window_tables: jax.Array,  # [B, max_blocks] the window group's
    ) -> tuple[jax.Array, tuple]:
        """One decode step for the whole (padded) running batch: each
        row a one-token span, one in-trace schedule a layer kind.  An
        inactive row (slot -1) writes nothing and is routed nowhere."""
        if lora is not None:
            raise NotImplementedError(
                "cohere2_moe has no LoRA targets yet (engine/config.py "
                "refuses --enable-lora for it)")
        cfg = self.config
        scale = cfg.head_dim ** -0.5
        b = token_ids.shape[0]
        pos_base = jnp.maximum(context_lens, 1) - 1
        seq_starts = jnp.arange(b + 1, dtype=jnp.int32)
        active = slot_mapping >= 0
        schedules = {False: (None, None), True: (None, None)}
        if attn_ops._use_pallas():
            schedules = {
                False: ragged.decode_work_schedule(
                    pos_base, active, block_tables, block_size=block_size),
                True: ragged.decode_work_schedule(
                    pos_base, active, window_tables, block_size=block_size,
                    window=cfg.group_window),
            }

        def attend(i, windowed, n, k_pool, v_pool, q):
            work, n_items = schedules[windowed]
            return ragged.ragged_paged_attention(
                q, k_pool, v_pool, pos_base, seq_starts, pos_base,
                jnp.asarray(b, jnp.int32),
                window_tables if windowed else block_tables,
                block_size, scale, layer=n, work=work, n_items=n_items,
                window=cfg.window_of_layer(i),
                block_q=ragged.decode_block_q(b),
                name=WINDOW_KERNEL if windowed else FULL_KERNEL,
            )

        x, caches = self._layers(
            params, caches, token_ids, positions, slot_mapping,
            window_slots, attend)
        return self._logits(params, x), caches

    # ----------------------------------------------------------- legacy path

    def prefill(self, *args, **kwargs):
        raise NotImplementedError(_LEGACY)

    def prefill_chunk(self, *args, **kwargs):
        raise NotImplementedError(_LEGACY)
