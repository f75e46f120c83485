"""Falcon-H1: a Mamba-2 mixer and grouped-query attention side by side in
every layer, on the same normed input, then a SwiGLU MLP.

    u  = RMSNorm(x)
    a  = Wo(attention(Wq(u a_in), Wk(u a_in) k_mult, Wv(u a_in))) a_out
    s  = out_proj(mixer(in_proj(u s_in) * mup_vector)) s_out
    x' = x + a + s
    x''= x' + down(silu(gate(v) g_mult) up(v)) d_mult,   v = RMSNorm(x')

(``models/reference/falcon_h1.py`` writes it out in full.)  A class of
its own beside ``LlamaForCausalLM``: the attention half calls the same
ragged paged-attention kernel over the same page pool, through the same
free functions (``rms_norm``, ``rotary_cos_sin``, ``apply_rotary``,
``linear``); the mixer half keeps per-sequence state that is NOT KV
pages: the scan state ``[H, N, P]`` float32 and the last ``d_conv - 1``
inputs of the causal convolution, one slot a running sequence in a slab
that travels with the caches (``caches = (k_cache, v_cache, (ssm,
conv))``, donated through the step programs like the pool;
docs/STATE.md).

The runner's ragged path calls two entry points, with the slot of every
sequence as ``state_slots``:

* ``ragged_forward``: the mixed step.  A span that starts at position 0
  starts from a zero state; any other span starts from its slot's state
  and leaves its final state there, so a prompt cut into chunks of any
  length hands its state from chunk to chunk.  One-token spans (the
  decode rows of a mixed step) go through ``ssm_decode_update``, longer
  ones through the chunked scan (ops/ssm_scan.py).
* ``decode``: one token a row, the fused wave's step.

Weights and activations are the model dtype; the scan's accumulation
and the state are float32.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from vllm_tgis_adapter_tpu.models.llama import (
    _lora_delta_batched,
    apply_rotary,
    linear,
    rms_norm,
    rotary_cos_sin,
)
from vllm_tgis_adapter_tpu.ops import attention as attn_ops
from vllm_tgis_adapter_tpu.ops import kv_quant
from vllm_tgis_adapter_tpu.ops import ragged_attention as ragged
from vllm_tgis_adapter_tpu.ops import ssm_scan

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

_LEGACY = (
    "falcon_h1 is served on the ragged path only: the legacy solo-prefill "
    "programs (prompt logprobs, pp/sp engines) do not carry recurrent "
    "state (docs/STATE.md)"
)


class FalconH1ForCausalLM:
    #: the runner allocates a state slab for this model and passes the
    #: rows' slots to its step entry points
    has_recurrent_state = True

    def __init__(self, config: "ModelConfig"):
        self.config = config
        # stamped by the runner (tp > 1 is refused at boot for this
        # model, so the mesh stays None on the served path)
        self.mesh = None
        self.sp_mode = "ring"
        mx = config.mixer
        bc = mx.n_groups * mx.d_state
        widths = (mx.d_ssm, mx.d_ssm, bc, bc, mx.num_heads)
        #: ``ssm_multipliers`` laid over in_proj's columns (z, x, B, C, dt)
        self.mup_vector = jnp.concatenate([
            jnp.full((w,), v, jnp.float32)
            for w, v in zip(widths, mx.ssm_multipliers)
        ])

    # ---------------------------------------------------------------- params

    def init_params(self, rng: jax.Array) -> dict:
        """Random init for tests; real weights via engine/weights.py.
        ``A_log``, ``dt_bias`` and ``D`` as Mamba-2 publishes them."""
        cfg, mx = self.config, self.config.mixer
        d, dh, f = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
        h, hkv = cfg.num_heads, cfg.num_kv_heads
        keys = iter(jax.random.split(rng, 3 + cfg.num_layers))

        def dense(key, shape, fan_in=None):
            scale = 1.0 / ((fan_in or shape[0]) ** 0.5)
            return (
                jax.random.normal(key, shape, dtype=jnp.float32) * scale
            ).astype(cfg.dtype)

        params: dict = {
            "embed": dense(next(keys), (cfg.vocab_size, d), 1.0),
            "final_norm": jnp.ones((d,), cfg.dtype),
            "lm_head": dense(next(keys), (d, cfg.vocab_size)),
            "layers": [],
        }
        for _ in range(cfg.num_layers):
            lk = iter(jax.random.split(next(keys), 13))
            dt = jnp.exp(jax.random.uniform(
                next(lk), (mx.num_heads,), jnp.float32,
                jnp.log(1e-3), jnp.log(1e-1)))
            params["layers"].append({
                "input_norm": jnp.ones((d,), cfg.dtype),
                "pre_ff_norm": jnp.ones((d,), cfg.dtype),
                "wq": dense(next(lk), (d, h * dh)),
                "wk": dense(next(lk), (d, hkv * dh)),
                "wv": dense(next(lk), (d, hkv * dh)),
                "wo": dense(next(lk), (h * dh, d)),
                "in_proj": dense(next(lk), (d, mx.in_proj_dim)),
                "conv_w": dense(next(lk), (mx.d_conv, mx.conv_dim)),
                "conv_b": dense(next(lk), (mx.conv_dim,), 4.0),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.dtype),
                "a_log": jnp.log(jax.random.uniform(
                    next(lk), (mx.num_heads,), jnp.float32, 1.0, 16.0)
                ).astype(cfg.dtype),
                "d": jnp.ones((mx.num_heads,), cfg.dtype),
                "mixer_norm": jnp.ones((mx.d_ssm,), cfg.dtype),
                "out_proj": dense(next(lk), (mx.d_ssm, d)),
                "w_gate": dense(next(lk), (d, f)),
                "w_up": dense(next(lk), (d, f)),
                "w_down": dense(next(lk), (f, d)),
            })
        return params

    def make_kv_caches(self, num_slots: int, dtype, quantization: str = "none",
                       block_size: int = 16, kv_scale_floors=None) -> tuple:
        """The paged K and V pools, laid out as the llama lineage's."""
        cfg = self.config
        shape = (cfg.num_layers, cfg.num_kv_heads, num_slots,
                 kv_quant.cache_head_dim(cfg.head_dim))
        k_floor, v_floor = kv_scale_floors or (None, None)
        return (
            kv_quant.make_kv_cache(shape, dtype, quantization, block_size,
                                   scale_floor=k_floor),
            kv_quant.make_kv_cache(shape, dtype, quantization, block_size,
                                   scale_floor=v_floor),
        )

    def make_state(self, num_rows: int) -> tuple[jax.Array, jax.Array]:
        """The state slab, one slot a running sequence: ``(ssm [L, rows,
        H, N, P] float32, conv [L, rows, d_conv - 1, conv_dim])``.  Its
        bytes are ``MixerConfig.state_bytes_per_row`` a row."""
        cfg, mx = self.config, self.config.mixer
        return (
            jnp.zeros((cfg.num_layers, num_rows, mx.num_heads, mx.d_state,
                       mx.head_dim), jnp.float32),
            jnp.zeros((cfg.num_layers, num_rows, mx.d_conv - 1, mx.conv_dim),
                      cfg.dtype),
        )

    # ----------------------------------------------------------------- parts

    def _qkv(self, layer: dict, u: jax.Array, rope, dl):
        cfg, mx = self.config, self.config.mixer
        t = u.shape[0]
        ua = u * mx.attention_in_multiplier
        q, k, v = (linear(layer, w, ua) for w in ("wq", "wk", "wv"))
        if dl is not None:
            q = q + dl("q_proj", ua)
            k = k + dl("k_proj", ua)
            v = v + dl("v_proj", ua)
        k = k * mx.key_multiplier
        q = q.reshape(t, cfg.num_heads, cfg.head_dim)
        k = k.reshape(t, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(t, cfg.num_kv_heads, cfg.head_dim)
        cos, sin = rope
        return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin), v

    def _in_proj(self, layer: dict, u: jax.Array):
        """``[z | xBC | dt]`` of the mixer, multipliers applied."""
        mx = self.config.mixer
        proj = linear(layer, "in_proj", u * mx.ssm_in_multiplier)
        proj = proj * self.mup_vector.astype(proj.dtype)
        return (proj[:, :mx.d_ssm],
                proj[:, mx.d_ssm:mx.d_ssm + mx.conv_dim],
                proj[:, mx.d_ssm + mx.conv_dim:])

    def _scan_inputs(self, layer: dict, xbc: jax.Array, dt: jax.Array):
        """The convolved channels as the scan takes them."""
        mx = self.config.mixer
        t = xbc.shape[0]
        bc = mx.n_groups * mx.d_state
        x = xbc[:, :mx.d_ssm].reshape(t, mx.num_heads, mx.head_dim)
        b = xbc[:, mx.d_ssm:mx.d_ssm + bc].reshape(t, mx.n_groups, mx.d_state)
        c = xbc[:, mx.d_ssm + bc:].reshape(t, mx.n_groups, mx.d_state)
        dt = jax.nn.softplus(
            dt.astype(jnp.float32) + layer["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(layer["a_log"].astype(jnp.float32))
        return x, dt, a, b, c

    def _mixer_out(self, layer: dict, y, x, z) -> jax.Array:
        """Skip, gate, grouped norm and ``out_proj`` on the scan's ``y``
        (float32 ``[T, H, P]``)."""
        cfg, mx = self.config, self.config.mixer
        t = y.shape[0]
        with jax.named_scope("ssm_gate_norm"):
            y = y + layer["d"].astype(jnp.float32)[:, None] * x.astype(
                jnp.float32)
            y = y.reshape(t, mx.d_ssm) * jax.nn.silu(z.astype(jnp.float32))
            if mx.rms_norm:
                y = y.reshape(t, mx.n_groups, mx.d_ssm // mx.n_groups)
                y = y * jax.lax.rsqrt(
                    jnp.mean(jnp.square(y), -1, keepdims=True)
                    + cfg.rms_norm_eps)
                y = y.reshape(t, mx.d_ssm) * layer["mixer_norm"].astype(
                    jnp.float32)
            y = y.astype(z.dtype)
        with jax.named_scope("ssm_out_proj"):
            return linear(layer, "out_proj", y) * mx.ssm_out_multiplier

    def _mlp(self, layer: dict, v: jax.Array, dl) -> jax.Array:
        gate_mult, down_mult = self.config.mixer.mlp_multipliers
        gate, up = linear(layer, "w_gate", v), linear(layer, "w_up", v)
        if dl is not None:
            gate = gate + dl("gate_proj", v)
            up = up + dl("up_proj", v)
        act = jax.nn.silu(gate * gate_mult) * up
        down = linear(layer, "w_down", act)
        if dl is not None:
            down = down + dl("down_proj", act)
        return down * down_mult

    def _block(self, layer: dict, x: jax.Array, attend, mix, rope, dl):
        """One layer around ``attend(q, k, v) -> [T, H, Dh]`` and
        ``mix(u) -> [T, d]`` closures (the caller owns the cache scatter,
        the attention call and the state)."""
        cfg, mx = self.config, self.config.mixer
        u = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        with jax.named_scope("attention"):
            o = attend(*self._qkv(layer, u, rope, dl)).reshape(x.shape[0], -1)
            a = linear(layer, "wo", o)
            if dl is not None:
                a = a + dl("o_proj", o)
            a = a * mx.attention_out_multiplier
        with jax.named_scope("ssm_mixer"):
            s = mix(u)
        x = x + a + s
        v = rms_norm(x, layer["pre_ff_norm"], cfg.rms_norm_eps)
        return x + self._mlp(layer, v, dl)

    def _logits(self, params: dict, x: jax.Array) -> jax.Array:
        cfg = self.config
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.dot(x, params["lm_head"],
                         preferred_element_type=jnp.float32)
        return logits * cfg.mixer.lm_head_multiplier

    def _embed(self, params: dict, token_ids: jax.Array) -> jax.Array:
        x = jnp.take(params["embed"], token_ids, axis=0)
        return x * self.config.embedding_multiplier

    @staticmethod
    def _lora_for(lora, lora_idx, i: int):
        if lora is None:
            return None
        return lambda target, xx: _lora_delta_batched(
            lora, i, lora_idx, target, xx)

    # ------------------------------------------------------------ mixed step

    def ragged_forward(
        self,
        params: dict,
        caches: tuple,  # (k_cache, v_cache, (ssm, conv))
        token_ids: jax.Array,  # [T] flat mixed stream, padded to a bucket
        positions: jax.Array,  # [T] GLOBAL position per row
        slot_mapping: jax.Array,  # [T] KV cache slot per row; -1 pads
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
        state_slots: jax.Array,  # [S] state slot per sequence; -1 pads
    ) -> tuple[jax.Array, tuple]:
        """One forward over a mixed ragged prefill+decode token stream
        (models/llama.py ``ragged_forward`` has the stream's contract);
        ``state_slots`` names the slab slot of every sequence."""
        cfg, mx = self.config, self.config.mixer
        k_cache, v_cache, (ssm, conv) = caches
        scale = cfg.head_dim ** -0.5
        rope = rotary_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        safe_slots = jnp.where(slot_mapping < 0, k_cache.shape[2],
                               slot_mapping)
        t = token_ids.shape[0]
        n_seqs = state_slots.shape[0]
        taps = mx.d_conv

        # what every layer's mixer needs of the stream's shape, once a step
        live = state_slots >= 0
        starts = seq_starts[:-1]
        lens = jnp.where(live, seq_starts[1:] - starts, 0)
        fresh = live & (pos_base == 0)
        single = lens == 1
        rows = jnp.arange(t, dtype=jnp.int32)
        seq_of = jnp.clip(
            jnp.searchsorted(seq_starts[1:], rows, side="right"),
            0, n_seqs - 1)
        offset = rows - starts[seq_of]  # row's place within its span
        chunk = min(mx.chunk_size, max(8, t))
        pieces = ssm_scan.span_pieces(
            seq_starts, state_slots, chunk, t // chunk + n_seqs)
        first_rows = jnp.clip(starts, 0, t - 1)
        store_slots = jnp.where(live, state_slots, ssm.shape[1])
        tail_slots = jnp.clip(state_slots, 0, ssm.shape[1] - 1)

        def conv_ragged(i, layer, xbc):
            """Causal depthwise convolution over the flat stream: a
            row's left context is its own span, then its slot's tail
            (zeros for a span that starts its sequence)."""
            nonlocal conv
            f32 = jnp.float32
            w = layer["conv_w"].astype(f32)  # tap j meets taps-1-j back
            xf = xbc.astype(f32)
            out = layer["conv_b"].astype(f32) + w[taps - 1] * xf
            for k in range(1, taps):
                back = jnp.pad(xf, ((k, 0), (0, 0)))[:t]
                out = out + w[taps - 1 - k] * jnp.where(
                    (offset >= k)[:, None], back, 0.0)
            tail = jnp.where(fresh[:, None, None], 0.0,
                             conv[i, tail_slots].astype(f32))  # [S, taps-1, C]
            for o in range(taps - 1):  # the span's first rows reach into it
                reach = sum(
                    w[taps - 1 - k] * tail[:, taps - 1 + o - k]
                    for k in range(o + 1, taps))
                rows_o = jnp.where(lens > o, starts + o, t)
                out = out.at[rows_o].add(reach, mode="drop")
            new_tail = []
            for j in range(taps - 1):  # the span's last rows, or what stays
                rel = lens - (taps - 1) + j
                from_span = xf[jnp.clip(starts + rel, 0, t - 1)]
                kept = jnp.take_along_axis(
                    tail, jnp.clip(rel + taps - 1, 0, taps - 2)[:, None, None],
                    axis=1)[:, 0]
                new_tail.append(
                    jnp.where((rel >= 0)[:, None], from_span, kept))
            conv = conv.at[i, store_slots].set(
                jnp.stack(new_tail, axis=1).astype(conv.dtype), mode="drop")
            return jax.nn.silu(out).astype(xbc.dtype)

        def mix(i, layer, u):
            nonlocal ssm
            with jax.named_scope("ssm_in_proj"):
                z, xbc, dt = self._in_proj(layer, u)
            with jax.named_scope("ssm_conv"):
                xbc = conv_ragged(i, layer, xbc)
            x, dt, a, b, c = self._scan_inputs(layer, xbc, dt)
            with jax.named_scope("ssm_decode_update"):
                y_one, ssm = ssm_scan.ssm_decode_update(
                    ssm, i, state_slots, single, fresh, x[first_rows],
                    dt[first_rows], a, b[first_rows], c[first_rows])
            with jax.named_scope("ssm_chunked_scan"):
                y, ssm = ssm_scan.ssm_scan_spans(
                    ssm, i, pieces, state_slots, fresh, x, dt, a, b, c,
                    chunk=chunk)
            y = y.at[jnp.where(single, starts, t)].add(y_one, mode="drop")
            return self._mixer_out(layer, y, x, z)

        def attend(i, q, k, v):
            nonlocal k_cache, v_cache
            k_cache = kv_quant.scatter_layer(
                k_cache, i, safe_slots, k, self.mesh)
            v_cache = kv_quant.scatter_layer(
                v_cache, i, safe_slots, v, self.mesh)
            return ragged.ragged_paged_attention(
                kv_quant.pad_to_cache_width(q, k_cache),
                *kv_quant.pool_data(k_cache, v_cache), positions,
                seq_starts, pos_base, total_tokens, block_tables,
                block_size, scale, layer=i,
                work=work, n_items=n_items, mesh=self.mesh,
                kv_scales=kv_quant.pool_scales(k_cache, v_cache),
            )[..., : q.shape[-1]]

        x = self._embed(params, token_ids)
        for i, layer in enumerate(params["layers"]):
            x = self._block(
                layer, x,
                lambda q, k, v, i=i: attend(i, q, k, v),
                lambda u, i=i, layer=layer: mix(i, layer, u),
                rope, self._lora_for(lora, lora_idx, i),
            )
        x = x[logits_indices]
        return self._logits(params, x), (k_cache, v_cache, (ssm, conv))

    # ------------------------------------------------------------ decode wave

    def decode(
        self,
        params: dict,
        caches: tuple,  # (k_cache, v_cache, (ssm, conv))
        token_ids: jax.Array,  # [B]
        positions: jax.Array,  # [B]
        slot_mapping: jax.Array,  # [B] KV slot of this step; -1 = inactive
        block_tables: jax.Array,  # [B, max_blocks]
        context_lens: jax.Array,  # [B] length INCLUDING the current token
        block_size: int,
        lora=None,
        lora_idx: jax.Array | None = None,
        *,
        state_slots: jax.Array,  # [B] state slot per row
    ) -> tuple[jax.Array, tuple]:
        """One decode step for the whole (padded) running batch: each
        row a one-token span through the ragged kernel, and one token
        through its slot's state.  An inactive row (KV slot -1) writes
        neither."""
        cfg, mx = self.config, self.config.mixer
        k_cache, v_cache, (ssm, conv) = caches
        scale = cfg.head_dim ** -0.5
        rope = rotary_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        active = (slot_mapping >= 0) & (state_slots >= 0)
        safe_slots = jnp.where(slot_mapping < 0, k_cache.shape[2],
                               slot_mapping)
        b = token_ids.shape[0]
        taps = mx.d_conv
        pos_base = jnp.maximum(context_lens, 1) - 1
        seq_starts = jnp.arange(b + 1, dtype=jnp.int32)
        work = n_items = None
        if attn_ops._use_pallas():
            work, n_items = ragged.decode_work_schedule(
                pos_base, slot_mapping >= 0, block_tables,
                block_size=block_size,
            )
        never = jnp.zeros((b,), bool)  # a wave's row is past position 0
        store_slots = jnp.where(active, state_slots, ssm.shape[1])
        tail_slots = jnp.clip(state_slots, 0, ssm.shape[1] - 1)

        def mix(i, layer, u):
            nonlocal ssm, conv
            f32 = jnp.float32
            with jax.named_scope("ssm_in_proj"):
                z, xbc, dt = self._in_proj(layer, u)
            with jax.named_scope("ssm_conv"):
                window = jnp.concatenate(
                    [conv[i, tail_slots], xbc[:, None, :]], axis=1)
                conv = conv.at[i, store_slots].set(
                    window[:, 1:], mode="drop")
                out = layer["conv_b"].astype(f32) + jnp.sum(
                    layer["conv_w"].astype(f32)[None] * window.astype(f32),
                    axis=1)
                xbc = jax.nn.silu(out).astype(xbc.dtype)
            x, dt, a, bb, c = self._scan_inputs(layer, xbc, dt)
            with jax.named_scope("ssm_decode_update"):
                y, ssm = ssm_scan.ssm_decode_update(
                    ssm, i, state_slots, active, never, x, dt, a, bb, c)
            return self._mixer_out(layer, y, x, z)

        def attend(i, q, k, v):
            nonlocal k_cache, v_cache
            k_cache = kv_quant.scatter_layer(
                k_cache, i, safe_slots, k, self.mesh)
            v_cache = kv_quant.scatter_layer(
                v_cache, i, safe_slots, v, self.mesh)
            return ragged.ragged_paged_attention(
                kv_quant.pad_to_cache_width(q, k_cache),
                *kv_quant.pool_data(k_cache, v_cache),
                pos_base, seq_starts, pos_base,
                jnp.asarray(b, jnp.int32),
                block_tables, block_size, scale, layer=i,
                work=work, n_items=n_items,
                block_q=ragged.decode_block_q(b), mesh=self.mesh,
                kv_scales=kv_quant.pool_scales(k_cache, v_cache),
            )[..., : q.shape[-1]]

        x = self._embed(params, token_ids)
        for i, layer in enumerate(params["layers"]):
            x = self._block(
                layer, x,
                lambda q, k, v, i=i: attend(i, q, k, v),
                lambda u, i=i, layer=layer: mix(i, layer, u),
                rope, self._lora_for(lora, lora_idx, i),
            )
        return self._logits(params, x), (k_cache, v_cache, (ssm, conv))

    # ----------------------------------------------------------- legacy path

    def prefill(self, *args, **kwargs):
        raise NotImplementedError(_LEGACY)

    def prefill_chunk(self, *args, **kwargs):
        raise NotImplementedError(_LEGACY)
