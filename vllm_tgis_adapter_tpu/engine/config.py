"""Engine configuration objects.

The analog of the model/cache/scheduler/parallel config surface the adapter
consumes from vLLM (reference: grpc_server.py:195-199 reads
``model_config.max_model_len``; args flow in via __main__.py:118-122).  All
fields here are plain data so configs can be hashed into jit static args.
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path
from typing import Any, Optional

import jax.numpy as jnp

from vllm_tgis_adapter_tpu.logging import init_logger

_logger = init_logger(__name__)

_DTYPE_MAP = {
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float32": jnp.float32,
    "float8_e4m3": jnp.float8_e4m3fn,
}


def resolve_dtype(name: str, default: str = "bfloat16") -> Any:
    """``"bfloat16"``-style name → jnp scalar type (jit-static)."""
    if name in ("auto", None):
        name = default
    if name not in _DTYPE_MAP:
        raise ValueError(f"unsupported dtype: {name}")
    return _DTYPE_MAP[name]


@dataclasses.dataclass(frozen=True)
class MixerConfig:
    """A Mamba-2 mixer beside the attention of every layer (``falcon_h1``;
    models/falcon_h1.py), with the family's muP multipliers: what the
    llama lineage has no field for.  Its presence on a ModelConfig is
    what "a model with recurrent state" means to the engine: a state
    slab beside the KV pool (engine/kv_cache.py, docs/STATE.md)."""

    d_ssm: int  # the mixer's inner width = num_heads * head_dim
    num_heads: int
    head_dim: int
    d_state: int
    n_groups: int  # B and C are shared by num_heads / n_groups heads
    d_conv: int  # taps of the causal depthwise convolution
    chunk_size: int
    rms_norm: bool  # gated RMSNorm (after the gate) on the mixer's output
    # muP scalars, all read from config.json
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: tuple[float, ...]  # over z, x, B, C, dt of in_proj
    mlp_multipliers: tuple[float, float]  # gate, down
    lm_head_multiplier: float

    @property
    def conv_dim(self) -> int:
        """Channels through the convolution: x, then B and C."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_proj_dim(self) -> int:
        """Columns of ``in_proj``: gate z, the conv channels, dt."""
        return self.d_ssm + self.conv_dim + self.num_heads

    @property
    def scan_state_bytes(self) -> int:
        """The float32 scan state of one sequence in one layer."""
        return self.num_heads * self.d_state * self.head_dim * 4

    def state_bytes_per_row(self, num_layers: int, act_itemsize: int) -> int:
        """Bytes one slot of the state slab holds over all layers: the
        scan state and the convolution's tail in the activations'
        dtype."""
        conv = (self.d_conv - 1) * self.conv_dim * act_itemsize
        return num_layers * (self.scan_state_bytes + conv)


def held_experts_of_checkpoint(
    model_path: str, num_held: int
) -> tuple[int, Optional[int]]:
    """``(first held expert, experts the router scores)`` of a
    ``deepseek_v2`` checkpoint: the indices ``e`` of the
    ``mlp.experts.{e}.*`` tensors it holds, which are the experts'
    published indices and must be ``num_held`` contiguous ones, and the
    rows of its router ``mlp.gate.weight``.  A directory with no
    safetensors (a config read on its own) holds experts 0 on, all the
    router scores."""
    import re

    from safetensors import safe_open

    held: set[int] = set()
    routed = None
    pattern = re.compile(r"model\.layers\.(\d+)\.mlp\.experts\.(\d+)\.")
    for file in sorted(Path(model_path).glob("*.safetensors")):
        with safe_open(file, framework="np") as f:
            for name in f.keys():  # noqa: SIM118
                m = pattern.match(name)
                if m:
                    held.add(int(m.group(2)))
                elif routed is None and name.endswith(".mlp.gate.weight"):
                    routed = f.get_slice(name).get_shape()[0]
    if not held or routed is None:
        return 0, None
    first = min(held)
    if sorted(held) != list(range(first, first + num_held)):
        raise ValueError(
            f"deepseek_v2 checkpoint holds experts {sorted(held)[:4]}... "
            f"({len(held)} of them), but config.json's n_routed_experts "
            f"says {num_held} are held: a server's share is that many "
            "contiguous experts under their published indices"
        )
    if first + num_held > routed:
        raise ValueError(
            f"deepseek_v2 checkpoint holds experts up to "
            f"{first + num_held - 1}, but its router scores only {routed}"
        )
    return first, routed


#: lanes of one row of the latent pool: the 512-value latent, then the
#: 64 rotary key columns padded to a lane tile (docs/LATENT_ATTENTION.md)
LATENT_LANE = 128


@dataclasses.dataclass(frozen=True)
class LatentAttentionConfig:
    """Multi-head latent attention (``deepseek_v2``; models/deepseek_v2.py):
    keys and values of every head are projections of ONE cached row a
    token a layer, ``[c_kv after its norm | k_pe after RoPE]``.  Its
    presence on a ModelConfig is what "a latent cache" means to the
    engine: a pool whose row is that latent and not per-head keys and
    values (engine/kv_cache.py, docs/LATENT_ATTENTION.md)."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    # (nope + rope)^-0.5, times the YaRN attention factor squared
    softmax_scale: float

    @property
    def latent_dim(self) -> int:
        """Values a cached row holds: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def rope_offset(self) -> int:
        """First lane of the rotary key within a row: the latent, padded
        to a lane tile."""
        return -(-self.kv_lora_rank // LATENT_LANE) * LATENT_LANE

    @property
    def row_width(self) -> int:
        """Lanes of a row of the pool: each half padded to a lane tile,
        which is what the device's tiled memory stores anyway."""
        return self.rope_offset + (
            -(-self.qk_rope_head_dim // LATENT_LANE) * LATENT_LANE)


@dataclasses.dataclass(frozen=True)
class HeldExpertsConfig:
    """Group-routed sparse experts of which this server holds a
    contiguous share (``deepseek_v2``): the router scores all
    ``num_routed`` experts, the layer computes the pairs that fall on the
    ``num_held`` experts from ``first_held`` on, and what the absent
    experts would add is left out (docs/LATENT_ATTENTION.md).  Which
    experts are held is read from the checkpoint's tensor names, the
    router's width from its tensor (``from_pretrained``)."""

    num_routed: int  # the router's outputs (all experts of a layer)
    first_held: int
    num_held: int
    n_group: int
    topk_group: int
    top_k: int
    routed_scaling_factor: float
    num_shared: int
    intermediate_size: int  # one expert's width
    first_dense_layers: int  # leading layers with a dense MLP instead

    @property
    def held(self) -> tuple[int, int]:
        """``[first, last)`` of the held experts' published indices."""
        return self.first_held, self.first_held + self.num_held


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters, read from a HF-style ``config.json``."""

    model: str
    model_type: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_model_len: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    eos_token_id: int = 2
    bos_token_id: int = 1
    # granite-style output scaling (1.0 = disabled)
    logits_scaling: float = 1.0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    # int4 quantized checkpoint (AWQ/AutoGPTQ wire formats): tensors are
    # stored packed (qweight/qzeros/scales[/g_idx]) and dequantized
    # group-wise at load into the model dtype (engine/quantized.py)
    checkpoint_quant: Optional[str] = None  # None | "awq" | "gptq"
    checkpoint_quant_group_size: int = 128
    checkpoint_quant_desc_act: bool = False  # gptq act-order (g_idx)
    # mixtral-style MoE (num_experts == 0 means dense)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    # MoE dispatch mode: "dense" runs every expert on every token (exact,
    # E/k x FLOP overhead — fine for tiny fixtures); "capacity" routes
    # each (token, expert) assignment into a static per-expert buffer of
    # ceil(T*k/E * capacity_factor) rows — FLOPs scale with k, and
    # assignments past an expert's capacity are dropped (their routing
    # weight contributes zero), the standard MoE serving trade-off
    moe_dispatch: str = "dense"  # "dense" | "capacity"
    moe_capacity_factor: float = 1.25
    # surface capacity-dispatch drop counts to Prometheus via a host
    # io_callback — set by the engine on single-device runs only
    # (engine/core.py from_config); off under SPMD meshes
    moe_record_drops: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    # architecture family knobs beyond the llama lineage (OPT et al.);
    # all are static Python branches in models/llama.py, so each
    # combination still compiles to one straight-line XLA program
    position_embedding: str = "rope"  # "rope" | "learned" | "alibi"
    norm_type: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    # gemma lineage: HF computes (1 + w) * x̂ in RMSNorm; the weight
    # loader folds the offset into the stored weights once at load
    # (engine/weights.py), so the runtime norm stays the plain w * x̂
    norm_weight_offset: float = 0.0
    # rope_scaling (llama3 / longrope / linear), precomputed at config
    # time into per-dim DIVISORS of the base inverse frequencies plus a
    # cos/sin attention factor (models/llama.py rotary_cos_sin); unknown
    # scaling types fail at config load rather than silently running
    # plain RoPE (see _rope_scaling_factors)
    rope_inv_freq_divisors: Optional[tuple] = None  # len head_dim // 2
    rope_mscale: float = 1.0
    # qwen3: per-head-dim RMSNorm on q and k after projection, before
    # rotary (weights q_norm/k_norm of size head_dim per layer)
    qk_norm: bool = False
    hidden_act: str = "silu"  # "silu" | "relu" | "gelu" | "gelu_new"
    gated_mlp: bool = True  # SwiGLU gate/up/down vs plain fc1/act/fc2
    attention_out_bias: bool = False
    # learned-position table: row count and the OPT-style lookup offset
    num_position_embeddings: int = 0
    learned_pos_offset: int = 0
    # gpt_neox-style partial rotary (0 = rotate the full head_dim) and
    # parallel attention+MLP residual (x + attn(ln1 x) + mlp(ln2 x))
    rotary_dim: int = 0
    parallel_residual: bool = False
    # bloom-style LayerNorm directly after the embedding lookup
    embed_norm: bool = False
    # mistral-style sliding-window attention: each token attends to at
    # most the previous ``sliding_window`` tokens (0 = full attention).
    # Enforced as a band mask in the attention ops; KV pages that fall
    # entirely below the band are freed as decode advances when the
    # rolling-eviction gates hold (engine/scheduler.py rolling_window)
    sliding_window: int = 0
    # qwen2 semantics: the first ``max_window_layers`` layers use FULL
    # attention, the band applies from that layer on (0 = all layers)
    max_window_layers: int = 0
    # per-layer attention kind as data (cohere2_moe's ``layer_types``):
    # each layer's window (0 = full attention) and whether it rotates
    # its queries and keys.  None = the llama lineage's two patterns,
    # which ``window_of_layer`` fills from the two integers above: every
    # layer banded, or the first ``max_window_layers`` full
    layer_windows: Optional[tuple] = None  # int a layer
    layer_rotary: Optional[tuple] = None  # bool a layer
    # falcon_h1: a Mamba-2 mixer beside attention in every layer, and
    # with it per-sequence state that is not KV pages
    mixer: Optional[MixerConfig] = None
    # deepseek_v2: latent attention over a pool of latent rows, and
    # group-routed experts of which a share is held
    mla: Optional[LatentAttentionConfig] = None
    experts: Optional[HeldExpertsConfig] = None

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def has_recurrent_state(self) -> bool:
        return self.mixer is not None

    @property
    def has_latent_cache(self) -> bool:
        return self.mla is not None

    def window_of_layer(self, i: int) -> int:
        """Layer ``i``'s attention window, 0 = full (``i`` is the
        layer's index in the whole model, whatever slice a stage
        holds)."""
        if self.layer_windows is not None:
            return self.layer_windows[i]
        if self.sliding_window and i < self.max_window_layers:
            return 0
        return self.sliding_window

    def layer_rotates(self, i: int) -> bool:
        """Whether layer ``i`` applies rotary embeddings to q and k."""
        return self.layer_rotary is None or bool(self.layer_rotary[i])

    @property
    def window_layers(self) -> tuple:
        """Indices of the layers with a window, in order."""
        return tuple(i for i in range(self.num_layers)
                     if self.window_of_layer(i) > 0)

    @property
    def full_layers(self) -> tuple:
        """Indices of the full-attention layers, in order."""
        return tuple(i for i in range(self.num_layers)
                     if self.window_of_layer(i) == 0)

    @property
    def has_window_group(self) -> bool:
        """Window layers AND full layers in one stack: the cache keeps
        the two kinds' pages in two groups, each with its own pool and
        block table (engine/kv_cache.py, docs/ATTENTION.md).  A model
        with one kind of layer has one group."""
        return (self.layer_windows is not None
                and bool(self.window_layers) and bool(self.full_layers))

    @property
    def default_block_size(self) -> int:
        """The page size where ``--block-size`` names none: 16 tokens,
        the granule of the prefix cache and of a pool's last-page waste;
        for a model with a window group one run of the attention
        kernel's work item (ops/ragged_attention.py ``KV_TILE``).  Such
        a model has no prefix cache to share pages with (boot refuses
        it), its rows are long (a context past the window is why it has
        two groups), and a run of 128 tokens is then one DMA a pool
        where pages of 16 make it eight: on a v5e the median time per
        token at 10-15 rows was 163 ms at pages of 16 and 84 ms at 128
        (PERF.md section 4, PR 49)."""
        return 128 if self.has_window_group else 16

    @property
    def group_window(self) -> int:
        """The window group's band: its layers' widest window."""
        return max((self.window_of_layer(i) for i in self.window_layers),
                   default=0)

    @staticmethod
    def _rope_scaling_factors(
        scaling: dict, *, theta: float, dim: int, max_len: int, hf: dict
    ) -> tuple[tuple, float]:
        """HF ``rope_scaling`` → (per-dim inv_freq divisors, mscale).

        Mirrors transformers' modeling_rope_utils exactly:

        * ``linear``: every frequency divided by ``factor``;
        * ``llama3`` (llama-3.1+): long wavelengths divided by
          ``factor``, short ones untouched, smooth ramp between;
        * ``longrope`` (phi-3 long-context): per-dim short/long factor
          arrays — chosen STATICALLY by whether the serving context
          (max_model_len) exceeds the pretrained window, matching the
          compile-once model — plus the sqrt(1 + ln f / ln L) attention
          factor on cos/sin;
        * ``yarn`` (yarn-llama, Qwen-long, deepseek lineage): NTK-by-parts
          — interpolate low frequencies by ``factor``, extrapolate high
          ones, linear ramp between the beta_fast/beta_slow correction
          dims — plus the 0.1·mscale·ln(factor)+1 attention factor;
        * ``dynamic`` (dynamic NTK): base stretched by
          ``(factor·L/max_pos − factor + 1)^(dim/(dim−2))``.  HF rescales
          per forward from the live seq_len; the compile-once engine
          evaluates it STATICALLY at L = max(max_model_len, max_pos) —
          identical to HF whenever max_model_len stays within the
          pretrained window (HF's init-time value), and the serving-length
          frequencies otherwise (same static convention as longrope).

        Anything else raises: running plain RoPE under an unsupported
        scaling would silently produce wrong logits.
        """
        import math

        import numpy as np

        rtype = scaling.get("rope_type") or scaling.get("type")
        if rtype in (None, "default"):
            return None, 1.0
        half = dim // 2
        if rtype == "linear":
            return (float(scaling["factor"]),) * half, 1.0
        inv_freq = 1.0 / (theta ** (np.arange(0, dim, 2) / dim))
        if rtype == "llama3":
            factor = scaling["factor"]
            lo_f = scaling["low_freq_factor"]
            hi_f = scaling["high_freq_factor"]
            old = scaling["original_max_position_embeddings"]
            wavelen = 2 * np.pi / inv_freq
            scaled = np.where(
                wavelen > old / lo_f, inv_freq / factor, inv_freq
            )
            smooth = (old / wavelen - lo_f) / (hi_f - lo_f)
            smoothed = (1 - smooth) * inv_freq / factor + smooth * inv_freq
            medium = ~(wavelen < old / hi_f) & ~(wavelen > old / lo_f)
            scaled = np.where(medium, smoothed, scaled)
            return tuple((inv_freq / scaled).tolist()), 1.0
        if rtype in ("longrope", "su"):
            # "su" is phi-3's original alias for what transformers later
            # standardised as "longrope" — identical semantics
            orig = (
                hf.get("original_max_position_embeddings")
                or scaling.get("original_max_position_embeddings")
                or hf.get("max_position_embeddings")
            )
            max_pos = hf.get("max_position_embeddings", orig)
            factor = max_pos / orig if orig else scaling.get("factor", 1.0)
            mscale = scaling.get("attention_factor")
            if mscale is None:
                mscale = (
                    1.0
                    if factor <= 1.0
                    else math.sqrt(1 + math.log(factor) / math.log(orig))
                )
            ext = (
                scaling["long_factor"]
                if (max_len or max_pos) > orig
                else scaling["short_factor"]
            )
            if len(ext) != half:
                raise ValueError(
                    f"longrope factor length {len(ext)} != head_dim/2 "
                    f"({half})"
                )
            return tuple(float(x) for x in ext), float(mscale)
        if rtype == "yarn":
            factor = scaling["factor"]
            orig = (
                scaling.get("original_max_position_embeddings")
                or hf.get("max_position_embeddings", 2048)
            )
            attn_factor = scaling.get("attention_factor")
            msc, msc_all = scaling.get("mscale"), scaling.get("mscale_all_dim")

            def get_mscale(scale: float, m: float = 1.0) -> float:
                return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

            if attn_factor is None:
                attn_factor = (
                    get_mscale(factor, msc) / get_mscale(factor, msc_all)
                    if msc and msc_all
                    else get_mscale(factor)
                )
            beta_fast = scaling.get("beta_fast") or 32
            beta_slow = scaling.get("beta_slow") or 1

            def correction_dim(rotations: float) -> float:
                return (
                    dim * math.log(orig / (rotations * 2 * math.pi))
                ) / (2 * math.log(theta))

            low, high = correction_dim(beta_fast), correction_dim(beta_slow)
            if scaling.get("truncate", True):
                low, high = math.floor(low), math.ceil(high)
            low, high = max(low, 0), min(high, dim - 1)
            if low == high:
                high += 0.001  # avoid the 0/0 ramp singularity
            ramp = np.clip(
                (np.arange(half, dtype=np.float32) - low) / (high - low),
                0.0, 1.0,
            )
            extrap_w = 1.0 - ramp  # 1 → keep base freq, 0 → interpolate
            scaled = (
                inv_freq / factor * (1 - extrap_w) + inv_freq * extrap_w
            )
            return tuple((inv_freq / scaled).tolist()), float(attn_factor)
        if rtype == "dynamic":
            factor = scaling["factor"]
            max_pos = hf.get("max_position_embeddings", 2048)
            seq_len = max(max_len or max_pos, max_pos)
            new_theta = theta * (
                (factor * seq_len / max_pos) - (factor - 1)
            ) ** (dim / (dim - 2))
            scaled = 1.0 / (new_theta ** (np.arange(0, dim, 2) / dim))
            return tuple((inv_freq / scaled).tolist()), 1.0
        raise ValueError(
            f"rope_scaling type {rtype!r} is not supported (supported: "
            "linear, llama3, longrope/su, yarn, dynamic); refusing to "
            "run plain RoPE on a scaled checkpoint"
        )

    @staticmethod
    def from_hf_config(
        model: str,
        hf: dict,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        """Map a HF transformers config dict onto ModelConfig, including
        the int4 quantized-checkpoint metadata (AWQ/GPTQ)."""
        cfg = ModelConfig._from_hf_config_impl(
            model, hf, max_model_len=max_model_len, dtype=dtype
        )
        qc = hf.get("quantization_config")
        if qc:
            method = (qc.get("quant_method") or "").lower()
            if method not in ("awq", "gptq"):
                raise ValueError(
                    f"quantization_config quant_method {method!r} is not "
                    "supported (supported: awq, gptq)"
                )
            bits = qc.get("bits", qc.get("w_bit", 4))
            if bits != 4:
                raise ValueError(
                    f"{method} checkpoints with bits={bits} are not "
                    "supported (int4 only)"
                )
            group = qc.get("group_size", qc.get("q_group_size", 128))
            cfg = dataclasses.replace(
                cfg,
                checkpoint_quant=method,
                checkpoint_quant_group_size=int(group),
                checkpoint_quant_desc_act=bool(qc.get("desc_act", False)),
            )
        return cfg

    @staticmethod
    def _from_hf_config_impl(
        model: str,
        hf: dict,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        """Map a HF transformers config dict onto ModelConfig.

        Supports the llama lineage (llama/mistral/granite/mixtral/qwen2):
        same decoder skeleton, differing in GQA ratios, biases, and the
        granite scaling multipliers.
        """
        model_type = hf.get("model_type", "llama")
        # non-llama-lineage families have their own HF field spellings —
        # dispatch BEFORE reading any llama-keyed fields
        if model_type == "opt":
            return ModelConfig._from_opt_config(
                model, hf, max_model_len=max_model_len, dtype=dtype
            )
        if model_type == "gpt_neox":
            return ModelConfig._from_gpt_neox_config(
                model, hf, max_model_len=max_model_len, dtype=dtype
            )
        if model_type == "bloom":
            return ModelConfig._from_bloom_config(
                model, hf, max_model_len=max_model_len, dtype=dtype
            )
        if model_type == "gpt2":
            return ModelConfig._from_gpt2_config(
                model, hf, max_model_len=max_model_len, dtype=dtype
            )
        if model_type == "falcon_h1":
            return ModelConfig._from_falcon_h1_config(
                model, hf, max_model_len=max_model_len, dtype=dtype
            )
        if model_type == "deepseek_v2":
            return ModelConfig._from_deepseek_v2_config(
                model, hf, max_model_len=max_model_len, dtype=dtype
            )
        if model_type == "cohere2_moe":
            return ModelConfig._from_cohere2_moe_config(
                model, hf, max_model_len=max_model_len, dtype=dtype
            )
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        derived_len = hf.get("max_position_embeddings", 2048)
        eos = hf.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        # mistral v0.1 ships sliding_window=4096; v0.3 sets it null.
        # qwen2 carries the field but gates it off by default, and when
        # on keeps its first max_window_layers layers on full attention.
        sliding_window = hf.get("sliding_window") or 0
        max_window_layers = 0
        if model_type in ("qwen2", "qwen3"):
            if not hf.get("use_sliding_window", False):
                sliding_window = 0
            else:
                max_window_layers = hf.get("max_window_layers", 0)
        if sliding_window:
            _logger.info(
                "sliding-window attention enabled (window=%d tokens)",
                sliding_window,
            )
        hidden_act = hf.get("hidden_act") or "silu"
        embedding_multiplier = hf.get("embedding_multiplier", 1.0)
        norm_weight_offset = 0.0
        tie = hf.get("tie_word_embeddings", False)
        rope_divisors, rope_mscale = None, 1.0
        if hf.get("rope_scaling"):
            rope_divisors, rope_mscale = ModelConfig._rope_scaling_factors(
                hf["rope_scaling"],
                theta=hf.get("rope_theta", 10000.0),
                dim=hf.get("head_dim", hidden // heads),
                max_len=max_model_len or derived_len,
                hf=hf,
            )
        if model_type == "gemma":
            # gemma: GeGLU MLP (HF spells the activation under
            # hidden_activation, default gelu_pytorch_tanh == our
            # gelu_new), sqrt(d)-scaled embeddings, (1+w) RMSNorm,
            # tied head
            act = (
                hf.get("hidden_activation")
                or hf.get("hidden_act")
                or "gelu_pytorch_tanh"
            )
            hidden_act = {"gelu_pytorch_tanh": "gelu_new"}.get(act, act)
            embedding_multiplier = float(hidden) ** 0.5
            norm_weight_offset = 1.0
            tie = hf.get("tie_word_embeddings", True)
        return ModelConfig(
            model=model,
            model_type=model_type,
            vocab_size=hf["vocab_size"],
            hidden_size=hidden,
            intermediate_size=hf.get("intermediate_size", 4 * hidden),
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=hf.get("num_key_value_heads", heads),
            head_dim=hf.get("head_dim", hidden // heads),
            max_model_len=max_model_len or derived_len,
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=tie,
            dtype=resolve_dtype(dtype),
            eos_token_id=eos,
            bos_token_id=hf.get("bos_token_id", 1) or 1,
            logits_scaling=hf.get("logits_scaling", 1.0),
            embedding_multiplier=embedding_multiplier,
            hidden_act=hidden_act,
            norm_weight_offset=norm_weight_offset,
            rope_inv_freq_divisors=rope_divisors,
            rope_mscale=rope_mscale,
            qk_norm=model_type == "qwen3",
            residual_multiplier=hf.get("residual_multiplier", 1.0),
            attention_multiplier=hf.get("attention_multiplier"),
            num_experts=hf.get("num_local_experts", 0),
            num_experts_per_tok=hf.get("num_experts_per_tok", 0),
            attention_bias=hf.get("attention_bias", False),
            mlp_bias=hf.get("mlp_bias", False),
            sliding_window=sliding_window,
            max_window_layers=max_window_layers,
        )

    @staticmethod
    def _from_falcon_h1_config(
        model: str,
        hf: dict,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        """Falcon-H1: attention and a Mamba-2 mixer side by side in
        every layer, then a SwiGLU MLP (models/falcon_h1.py)."""
        unsupported = {
            "mamba_norm_before_gate": "the gated norm is applied after "
            "the gate only",
            "mamba_proj_bias": "in_proj and out_proj are loaded without "
            "biases",
            "attention_bias": "the attention projections are loaded "
            "without biases",
            "mlp_bias": "the MLP is loaded without biases",
            "projectors_bias": "the MLP is loaded without biases",
            "rope_scaling": "the rotary tables are unscaled",
        }
        for key, why in unsupported.items():
            if hf.get(key):
                raise ValueError(
                    f"falcon_h1 config sets {key}={hf[key]!r}, which is "
                    f"not supported yet: {why}"
                )
        if not hf.get("mamba_use_mlp", True):
            raise ValueError(
                "falcon_h1 config sets mamba_use_mlp=false, which is not "
                "supported yet: every layer runs its MLP"
            )
        if hf.get("mamba_conv_bias") is False:
            raise ValueError(
                "falcon_h1 config sets mamba_conv_bias=false, which is "
                "not supported yet: the convolution is loaded with its "
                "bias"
            )
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        m_heads = hf["mamba_n_heads"]
        m_head_dim = hf["mamba_d_head"]
        d_ssm = hf.get("mamba_d_ssm") or hf.get("mamba_expand", 2) * hidden
        if m_heads * m_head_dim != d_ssm:
            raise ValueError(
                f"falcon_h1 config: mamba_n_heads {m_heads} x mamba_d_head "
                f"{m_head_dim} is not mamba_d_ssm {d_ssm}"
            )
        groups = hf.get("mamba_n_groups", 1)
        if m_heads % groups:
            raise ValueError(
                f"falcon_h1 config: mamba_n_groups {groups} does not "
                f"divide mamba_n_heads {m_heads}"
            )
        eos = hf.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        return ModelConfig(
            model=model,
            model_type="falcon_h1",
            vocab_size=hf["vocab_size"],
            hidden_size=hidden,
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=hf.get("num_key_value_heads", heads),
            head_dim=hf.get("head_dim") or hidden // heads,
            max_model_len=(
                max_model_len or hf.get("max_position_embeddings", 2048)
            ),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            dtype=resolve_dtype(dtype),
            eos_token_id=eos,
            bos_token_id=hf.get("bos_token_id", 1) or 1,
            embedding_multiplier=hf.get("embedding_multiplier", 1.0),
            hidden_act=ModelConfig._validated_hidden_act(
                hf.get("hidden_act") or "silu", "falcon_h1"
            ),
            mixer=MixerConfig(
                d_ssm=d_ssm,
                num_heads=m_heads,
                head_dim=m_head_dim,
                d_state=hf["mamba_d_state"],
                n_groups=groups,
                d_conv=hf.get("mamba_d_conv", 4),
                chunk_size=hf.get("mamba_chunk_size", 128),
                rms_norm=hf.get("mamba_rms_norm", True),
                attention_in_multiplier=hf.get("attention_in_multiplier", 1.0),
                attention_out_multiplier=hf.get(
                    "attention_out_multiplier", 1.0),
                key_multiplier=hf.get("key_multiplier", 1.0),
                ssm_in_multiplier=hf.get("ssm_in_multiplier", 1.0),
                ssm_out_multiplier=hf.get("ssm_out_multiplier", 1.0),
                ssm_multipliers=tuple(
                    hf.get("ssm_multipliers") or (1.0,) * 5),
                mlp_multipliers=tuple(
                    hf.get("mlp_multipliers") or (1.0, 1.0)),
                lm_head_multiplier=hf.get("lm_head_multiplier", 1.0),
            ),
        )

    @staticmethod
    def _from_deepseek_v2_config(
        model: str,
        hf: dict,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        """DeepSeek-V2: latent attention in every layer, then a dense
        SwiGLU MLP in the first ``first_k_dense_replace`` layers and
        group-routed experts beside shared ones in the rest
        (models/deepseek_v2.py)."""
        import math

        unsupported = (
            ("attention_bias", hf.get("attention_bias"),
             "the latent projections are loaded without biases"),
            ("scoring_func", hf.get("scoring_func", "softmax") != "softmax",
             "the router's scores are a softmax"),
            ("topk_method",
             hf.get("topk_method", "group_limited_greedy")
             != "group_limited_greedy",
             "routing is group-limited greedy"),
            ("norm_topk_prob", hf.get("norm_topk_prob"),
             "the chosen experts' weights are not renormalised"),
            ("moe_layer_freq", hf.get("moe_layer_freq", 1) != 1,
             "every layer after the dense ones is an expert layer"),
            ("q_lora_rank", not hf.get("q_lora_rank"),
             "the query goes through its low-rank projection"),
            ("n_routed_experts", not hf.get("n_routed_experts"),
             "the layers after the dense ones are expert layers"),
        )
        for key, bad, why in unsupported:
            if bad:
                raise ValueError(
                    f"deepseek_v2 config sets {key}={hf.get(key)!r}, which "
                    f"is not supported yet: {why}"
                )
        nope, rope_dim = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
        theta = float(hf.get("rope_theta", 10000.0))
        scaling = hf.get("rope_scaling")
        divisors, rope_mscale, score_mscale = None, 1.0, 1.0
        if scaling:
            divisors, rope_mscale = ModelConfig._rope_scaling_factors(
                scaling, theta=theta, dim=rope_dim,
                max_len=max_model_len or 0, hf=hf,
            )
            m_all, factor = scaling.get("mscale_all_dim", 0), scaling["factor"]
            if m_all and factor > 1:
                score_mscale = 0.1 * m_all * math.log(factor) + 1.0
        num_held = hf["n_routed_experts"]
        first_held, num_routed = held_experts_of_checkpoint(model, num_held)
        groups = hf.get("n_group") or 1
        if num_routed is None:
            # no checkpoint beside the config: nothing can run from it,
            # and the router's width is the tensor's to say
            num_routed = num_held
        elif num_routed % groups:
            raise ValueError(
                f"deepseek_v2 config: n_group {groups} does not divide the "
                f"router's {num_routed} experts"
            )
        eos = hf.get("eos_token_id", 100001)
        if isinstance(eos, list):
            eos = eos[0]
        heads = hf["num_attention_heads"]
        return ModelConfig(
            model=model,
            model_type="deepseek_v2",
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            # one cached row serves every head
            num_kv_heads=1,
            head_dim=nope + rope_dim,
            max_model_len=(
                max_model_len or hf.get("max_position_embeddings", 4096)
            ),
            rope_theta=theta,
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            dtype=resolve_dtype(dtype),
            eos_token_id=eos,
            bos_token_id=hf.get("bos_token_id", 100000) or 1,
            rope_inv_freq_divisors=divisors,
            rope_mscale=rope_mscale,
            hidden_act=ModelConfig._validated_hidden_act(
                hf.get("hidden_act") or "silu", "deepseek_v2"
            ),
            mla=LatentAttentionConfig(
                q_lora_rank=hf["q_lora_rank"],
                kv_lora_rank=hf["kv_lora_rank"],
                qk_nope_head_dim=nope,
                qk_rope_head_dim=rope_dim,
                v_head_dim=hf["v_head_dim"],
                softmax_scale=(nope + rope_dim) ** -0.5 * score_mscale ** 2,
            ),
            experts=HeldExpertsConfig(
                num_routed=num_routed,
                first_held=first_held,
                num_held=num_held,
                n_group=groups,
                topk_group=hf.get("topk_group") or groups,
                top_k=hf["num_experts_per_tok"],
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor", 1.0)),
                num_shared=hf.get("n_shared_experts") or 0,
                intermediate_size=hf["moe_intermediate_size"],
                first_dense_layers=hf.get("first_k_dense_replace", 0),
            ),
        )

    @staticmethod
    def _from_cohere2_moe_config(
        model: str,
        hf: dict,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        """Command A+ (``cohere2_moe``): window and full attention layers
        in one stack (``layer_types``), a parallel block under one
        weight-only LayerNorm, sigmoid-routed experts of which a share is
        held beside averaged shared ones, a tied head
        (models/cohere2_moe.py)."""
        kinds = hf.get("layer_types")
        layers = hf["num_hidden_layers"]
        if not kinds:
            # the published pattern: layer_switch - 1 window layers,
            # then one full
            switch = hf.get("layer_switch") or 4
            kinds = ["full_attention" if (i + 1) % switch == 0
                     else "sliding_attention" for i in range(layers)]
        kinds = list(kinds)[:layers]
        window = hf.get("sliding_window") or 0
        unsupported = (
            ("attention_bias", hf.get("attention_bias"),
             "the projections are loaded without biases"),
            ("use_qk_norm", hf.get("use_qk_norm"),
             "queries and keys are not normed"),
            ("use_parallel_block", not hf.get("use_parallel_block", True),
             "one norm feeds attention and the experts"),
            ("first_k_dense_replace", hf.get("first_k_dense_replace"),
             "every layer is an expert layer"),
            ("expert_selection_fn",
             hf.get("expert_selection_fn", "sigmoid") != "sigmoid",
             "the router's scores are sigmoids"),
            ("norm_topk_prob", not hf.get("norm_topk_prob", True),
             "the chosen experts' weights are renormalised"),
            ("shared_expert_combination_strategy",
             hf.get("shared_expert_combination_strategy", "average")
             != "average", "the shared experts are averaged"),
            ("position_embedding_type",
             hf.get("position_embedding_type", "rope_gptj") != "rope_gptj",
             "window layers rotate interleaved pairs"),
            ("rotary_pct", hf.get("rotary_pct", 1) != 1,
             "window layers rotate the whole head"),
            ("tie_word_embeddings", not hf.get("tie_word_embeddings", True),
             "the head is the embedding"),
            ("layer_types", len(kinds) != layers or any(
                k not in ("sliding_attention", "full_attention")
                for k in kinds),
             "a layer is sliding_attention or full_attention"),
            ("sliding_window", "sliding_attention" in kinds and window <= 0,
             "a sliding layer needs its window"),
        )
        for key, bad, why in unsupported:
            if bad:
                raise ValueError(
                    f"cohere2_moe config sets {key}={hf.get(key)!r}, which "
                    f"is not supported yet: {why}"
                )
        num_held = hf["num_experts"]
        first_held, num_routed = held_experts_of_checkpoint(model, num_held)
        if num_routed is None:
            num_routed = num_held
        eos = hf.get("eos_token_id", 255001)
        if isinstance(eos, list):
            eos = eos[0]
        theta = float(
            (hf.get("rope_parameters") or {}).get("rope_theta")
            or hf.get("rope_theta", 10000.0))
        return ModelConfig(
            model=model,
            model_type="cohere2_moe",
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=layers,
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim")
            or hf["hidden_size"] // hf["num_attention_heads"],
            max_model_len=(
                max_model_len or hf.get("max_position_embeddings", 8192)
            ),
            rope_theta=theta,
            rms_norm_eps=hf.get("layer_norm_eps", 1e-5),
            tie_word_embeddings=True,
            dtype=resolve_dtype(dtype),
            eos_token_id=eos,
            bos_token_id=hf.get("bos_token_id", 5) or 1,
            logits_scaling=1.0 / float(hf.get("logit_scale", 1.0) or 1.0),
            hidden_act=ModelConfig._validated_hidden_act(
                hf.get("hidden_act") or "silu", "cohere2_moe"
            ),
            norm_type="layernorm",
            parallel_residual=True,
            sliding_window=window,
            layer_windows=tuple(
                window if k == "sliding_attention" else 0 for k in kinds),
            layer_rotary=tuple(k == "sliding_attention" for k in kinds),
            experts=HeldExpertsConfig(
                num_routed=num_routed,
                first_held=first_held,
                num_held=num_held,
                n_group=1,
                topk_group=1,
                top_k=hf["num_experts_per_tok"],
                routed_scaling_factor=1.0,
                num_shared=hf.get("num_shared_experts") or 0,
                intermediate_size=hf["intermediate_size"],
                first_dense_layers=0,
            ),
        )

    @staticmethod
    def _from_opt_config(
        model: str,
        hf: dict,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        """OPT decoder (BASELINE.json config: opt-125m single Generate).

        Same paged-KV skeleton, different block chemistry: learned
        positional embeddings with the HF offset-by-2 table, pre-LayerNorm
        with biases, plain fc1/ReLU/fc2 MLP, biased out-projection, MHA.
        """
        if not hf.get("do_layer_norm_before", True):
            raise ValueError(
                "post-norm OPT variants (do_layer_norm_before=false, e.g. "
                "opt-350m) are not supported"
            )
        hidden = hf["hidden_size"]
        proj = hf.get("word_embed_proj_dim", hidden)
        if proj != hidden:
            raise ValueError(
                f"OPT word_embed_proj_dim={proj} != hidden_size={hidden} "
                "(projected-embedding variants are not supported)"
            )
        heads = hf["num_attention_heads"]
        derived_len = hf.get("max_position_embeddings", 2048)
        if max_model_len and max_model_len > derived_len:
            # positions past the learned table would silently clip to its
            # last row (models/llama.py _embed) — wrong hidden states, so
            # reject like the other unsupported-variant checks above
            raise ValueError(
                f"max_model_len={max_model_len} exceeds OPT's learned-"
                f"position table ({derived_len} positions)"
            )
        bias = hf.get("enable_bias", True)
        eos = hf.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        return ModelConfig(
            model=model,
            model_type="opt",
            vocab_size=hf["vocab_size"],
            hidden_size=hidden,
            intermediate_size=hf["ffn_dim"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=heads,
            head_dim=hidden // heads,
            max_model_len=max_model_len or derived_len,
            # layernorm epsilon rides the rms_norm_eps field (HF
            # OPTConfig has no eps knob; torch LayerNorm default)
            rms_norm_eps=1e-5,
            tie_word_embeddings=hf.get("tie_word_embeddings", True),
            dtype=resolve_dtype(dtype),
            eos_token_id=eos,
            bos_token_id=hf.get("bos_token_id", 2) or 2,
            attention_bias=bias,
            attention_out_bias=bias,
            mlp_bias=bias,
            position_embedding="learned",
            norm_type="layernorm",
            hidden_act=ModelConfig._validated_hidden_act(
                hf.get("activation_function", "relu"), "opt"
            ),
            gated_mlp=False,
            # HF OPTLearnedPositionalEmbedding: table rows = max_pos + 2,
            # lookup index = position + 2
            num_position_embeddings=derived_len + 2,
            learned_pos_offset=2,
        )

    @staticmethod
    def _validated_hidden_act(act: str, model_type: str) -> str:
        """Fail at config time, not with a KeyError mid-trace on the
        first forward pass (HF has many ACT2FN names we don't map)."""
        from vllm_tgis_adapter_tpu.models.llama import _ACTIVATIONS

        if act not in _ACTIVATIONS:
            raise ValueError(
                f"{model_type}: hidden_act {act!r} is not supported; "
                f"supported: {sorted(_ACTIVATIONS)}"
            )
        return act

    @staticmethod
    def _from_gpt_neox_config(
        model: str,
        hf: dict,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        """GPT-NeoX / Pythia family: partial rotary (rotary_pct of each
        head), parallel attention+MLP residual, pre-LayerNorm with
        biases, fused-QKV checkpoints (de-interleaved by the loader),
        plain fc1/GELU/fc2, untied embed_out lm_head, MHA."""
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        head_dim = hidden // heads
        rotary_pct = hf.get("rotary_pct", 0.25)
        rotary_dim = int(head_dim * rotary_pct)
        if rotary_dim % 2:
            raise ValueError(
                f"rotary_pct={rotary_pct} gives odd rotary_dim="
                f"{rotary_dim} (head_dim {head_dim}); rotate-half needs "
                "an even dimension"
            )
        eos = hf.get("eos_token_id", 0)
        if isinstance(eos, list):
            eos = eos[0]
        return ModelConfig(
            model=model,
            model_type="gpt_neox",
            vocab_size=hf["vocab_size"],
            hidden_size=hidden,
            intermediate_size=hf.get("intermediate_size", 4 * hidden),
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=heads,
            head_dim=head_dim,
            max_model_len=max_model_len
            or hf.get("max_position_embeddings", 2048),
            # legacy configs spell it rotary_emb_base; newer transformers
            # serialise rope_theta
            rope_theta=hf.get(
                "rotary_emb_base", hf.get("rope_theta", 10000.0)
            ),
            # layernorm epsilon rides the rms_norm_eps field
            rms_norm_eps=hf.get("layer_norm_eps", 1e-5),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            dtype=resolve_dtype(dtype),
            eos_token_id=eos,
            bos_token_id=hf.get("bos_token_id", 0) or 0,
            attention_bias=hf.get("attention_bias", True),
            attention_out_bias=hf.get("attention_bias", True),
            mlp_bias=True,
            norm_type="layernorm",
            hidden_act=ModelConfig._validated_hidden_act(
                hf.get("hidden_act", "gelu"), "gpt_neox"
            ),
            gated_mlp=False,
            rotary_dim=rotary_dim if rotary_dim != head_dim else 0,
            parallel_residual=hf.get("use_parallel_residual", True),
        )

    @staticmethod
    def _from_gpt2_config(
        model: str,
        hf: dict,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        """GPT-2 family: learned positions (no offset), pre-LayerNorm
        with biases, fused Conv1D c_attn (plain column thirds, split by
        the loader), fc/GELU(tanh)/proj MLP, tied head, MHA.

        Note: the official checkpoints' vocab_size of 50257 is odd, so
        tensor parallelism rejects them at boot (validate_tp_divisibility
        — vocab padding is not implemented); gpt2-scale models fit one
        chip anyway.
        """
        if hf.get("scale_attn_by_inverse_layer_idx", False):
            raise ValueError(
                "gpt2: scale_attn_by_inverse_layer_idx=true variants are "
                "not supported"
            )
        if not hf.get("scale_attn_weights", True):
            # HF skips the 1/sqrt(head_dim) scaling for these; the shared
            # kernel always applies it, so loading would be silently wrong
            raise ValueError(
                "gpt2: scale_attn_weights=false variants are not supported"
            )
        hidden = hf["n_embd"]
        heads = hf["n_head"]
        derived_len = hf.get("n_positions", hf.get("n_ctx", 1024))
        if max_model_len and max_model_len > derived_len:
            raise ValueError(
                f"max_model_len={max_model_len} exceeds GPT-2's learned-"
                f"position table ({derived_len} positions)"
            )
        eos = hf.get("eos_token_id", 50256)
        if isinstance(eos, list):
            eos = eos[0]
        return ModelConfig(
            model=model,
            model_type="gpt2",
            vocab_size=hf["vocab_size"],
            hidden_size=hidden,
            intermediate_size=hf.get("n_inner") or 4 * hidden,
            num_layers=hf["n_layer"],
            num_heads=heads,
            num_kv_heads=heads,
            head_dim=hidden // heads,
            max_model_len=max_model_len or derived_len,
            rms_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            tie_word_embeddings=True,
            dtype=resolve_dtype(dtype),
            eos_token_id=eos,
            bos_token_id=hf.get("bos_token_id", 50256) or 50256,
            attention_bias=True,
            attention_out_bias=True,
            mlp_bias=True,
            norm_type="layernorm",
            hidden_act=ModelConfig._validated_hidden_act(
                hf.get("activation_function", "gelu_new"), "gpt2"
            ),
            gated_mlp=False,
            position_embedding="learned",
            num_position_embeddings=derived_len,
            learned_pos_offset=0,
        )

    @staticmethod
    def _from_bloom_config(
        model: str,
        hf: dict,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        """BLOOM family (the original TGIS flagship): ALiBi positional
        biases (no position params at all), a LayerNorm directly on the
        embeddings, pre-LN with biases, fused per-head query_key_value
        checkpoints, plain fc1/GELU(tanh)/fc2, tied head, MHA."""
        if hf.get("apply_residual_connection_post_layernorm", False):
            raise ValueError(
                "bloom: apply_residual_connection_post_layernorm=true "
                "variants are not supported"
            )
        hidden = hf["hidden_size"]
        heads = hf["n_head"]
        eos = hf.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        return ModelConfig(
            model=model,
            model_type="bloom",
            vocab_size=hf["vocab_size"],
            hidden_size=hidden,
            intermediate_size=4 * hidden,
            num_layers=hf["n_layer"],
            num_heads=heads,
            num_kv_heads=heads,
            head_dim=hidden // heads,
            # ALiBi has no positional table to outgrow; 2048 is BLOOM's
            # training length and a sane serving default
            max_model_len=max_model_len or hf.get("seq_length", 2048),
            rms_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
            tie_word_embeddings=hf.get("tie_word_embeddings", True),
            dtype=resolve_dtype(dtype),
            eos_token_id=eos,
            bos_token_id=hf.get("bos_token_id", 1) or 1,
            attention_bias=True,
            attention_out_bias=True,
            mlp_bias=True,
            norm_type="layernorm",
            # HF BloomGelu is the tanh approximation
            hidden_act="gelu_new",
            gated_mlp=False,
            position_embedding="alibi",
            embed_norm=True,
        )

    @staticmethod
    def from_pretrained(
        model_path: str,
        *,
        max_model_len: int | None = None,
        dtype: str = "auto",
    ) -> "ModelConfig":
        config_file = Path(model_path) / "config.json"
        if not config_file.exists():
            raise ValueError(
                f"model path {model_path!r} has no config.json; only local "
                "model paths are supported (use `model-util download-weights` "
                "to fetch from the HF hub)"
            )
        with open(config_file) as f:
            hf = json.load(f)
        return ModelConfig.from_hf_config(
            model_path, hf, max_model_len=max_model_len, dtype=dtype
        )


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Paged KV-cache geometry."""

    block_size: int = 16
    # <= 0 requests auto-sizing against the HBM budget at engine boot
    # (kv_cache.resolve_num_blocks); a positive value is used as-is
    num_blocks: int = 512
    # pages of the WINDOW group's pool, for a model whose window layers
    # and full layers keep their pages in two groups (num_blocks is then
    # the full group's); <= 0 derives it from num_blocks at engine boot
    # (kv_cache.window_blocks_for).  Unused by a model with one group
    num_window_blocks: int = 0
    cache_dtype: Any = jnp.bfloat16
    # content-addressed reuse of full prompt pages across requests
    # (engine/kv_cache.py BlockAllocator prefix caching)
    enable_prefix_caching: bool = False
    # --kv-quantization {none,int8,fp8}: KV pages stored quantized with
    # per-page-per-head scales, dequantized at the page read
    # (ops/kv_quant.py, docs/QUANTIZATION.md).  "none" (default) is
    # byte-identical to the unquantized engine; int8/fp8 roughly double
    # KV-page capacity at equal HBM.  Subsumes the raw-cast
    # --kv-cache-dtype fp8/int8 spellings (tgis_utils/args.py).
    kv_quantization: str = "none"

    def kv_dtype_label(self) -> str:
        """Metrics label for kv_page_capacity_blocks{dtype=...}."""
        if self.kv_quantization != "none":
            return self.kv_quantization
        import numpy as _np

        try:
            return str(jnp.dtype(self.cache_dtype).name)
        except Exception:  # pragma: no cover — exotic dtype objects
            return str(_np.dtype(self.cache_dtype))


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    max_num_seqs: int = 64
    max_num_batched_tokens: int = 2048
    # prompt lengths are padded up to one of these buckets to bound the
    # number of distinct compiled prefill shapes
    prefill_buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048, 4096)
    # the CEILING of the decode steps fused into one device dispatch (a
    # device loop over the step axis): a wave samples up to this many
    # tokens per sequence before control returns to the host.  In the
    # overlapped step loop a wave fuses only as many steps as hide the
    # host's turn, ceil(host turn / token step) from what the loop
    # measures (telemetry/steptime.py wave_depth), so that a client sees
    # a token a step and not eight at once; in the serial order
    # (enable_chained_decode off, LLMEngine.step) every fused step saves
    # a turn the device would wait for and the ceiling is the depth.
    # Stop/EOS detection happens on the host afterwards, so up to K-1
    # speculatively decoded tokens per finished sequence are discarded.
    num_decode_steps: int = 8
    # plan-ahead overlap (async scheduling): while one dispatch runs on
    # the device, its successor — wave or mixed step — is planned from a
    # projection of it and enqueued, its input tokens read on the device
    # (engine/async_llm.py _run_loop).  False is the strictly serial
    # loop — plan / dispatch / wait / commit in sequence, waves at the
    # full num_decode_steps — the control the tests compare the
    # overlapped loop against, a kill-switch for bisecting overlap bugs,
    # and the deliberately host-bound configuration the bottleneck
    # doctor's host_bound regime is validated against
    # (docs/OBSERVABILITY.md "Validating the doctor"): with
    # num_decode_steps=1 every token pays the full un-overlapped host
    # round-trip.
    enable_chained_decode: bool = True

    def __post_init__(self) -> None:
        if self.num_decode_steps < 1:
            raise ValueError(
                f"num_decode_steps must be >= 1 "
                f"(got {self.num_decode_steps}); 1 disables multi-step decode"
            )


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    data_parallel_size: int = 1
    # --dp-replicas: like data_parallel_size (N independent engine
    # replicas behind the front door's placement router), but tolerant
    # of a host with fewer than N*pp*sp*tp devices — replicas then SHARE
    # the visible device set (each still owns its own scheduler, KV
    # pool, step loop, and flight recorder).  That shared mode is the
    # single-host dev story (CPU tests, chaos tests);
    # on real multi-chip hosts with enough devices both flags partition
    # identical disjoint slices.  Mutually exclusive with
    # data_parallel_size > 1 (one replica-count knob at a time).
    dp_replicas: int = 1
    # ring-attention sequence parallelism for long-context prefill: the
    # sequence axis of prefill activations/attention is sharded over the
    # mesh's sp axis (ops/ring_attention.py); the paged KV cache stays
    # head-sharded on tp and replicated over sp, so decode runs replicated
    # across sp shards — sp buys prefill memory/compute scale-out
    sequence_parallel_size: int = 1
    # sp>1 attention style: "ring" (ppermute K/V rotation — bandwidth
    # pipelined under compute) or "ulysses" (head/seq all-to-all — the
    # single-device flash kernel runs unchanged on the gathered slice;
    # needs sp to divide the per-tp-shard head counts)
    sequence_parallel_mode: str = "ring"


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    enabled: bool = False
    # device slots concurrently resident per replica pool (slot 0 =
    # base model is extra); also the legacy registry capacity when the
    # pool is disabled
    max_loras: int = 4
    max_lora_rank: int = 64
    # paged adapter pool (engine/adapter_pool.py): host registry up to
    # max_cpu_loras adapters, device residency streamed on demand.
    # False = pre-pool behavior (sync_lora full-stack rebuild slow path)
    pool: bool = True
    # host-RAM registry capacity in pool mode (>= max_loras); 0 =
    # auto (max(64, 4 * max_loras))
    max_cpu_loras: int = 0
    # concurrent host→device adapter streams per pool
    prefetch_concurrency: int = 2
    # heterogeneous-rank gathered matmul (docs/LORA.md "Gathered
    # matmul"): stacks carry a per-slot rank-bucket operand and each
    # row's delta computes at its TRUE pow2 rank bucket instead of
    # padding to max_lora_rank.  False (--no-lora-gathered) restores
    # the padded matmuls bit-for-bit.
    gathered: bool = True

    def resolved_max_cpu_loras(self) -> int:
        if self.max_cpu_loras > 0:
            return max(self.max_cpu_loras, self.max_loras)
        return max(64, 4 * self.max_loras)


@dataclasses.dataclass(frozen=True)
class SpeculativeConfig:
    """Draft-model speculative decoding (engine/speculative.py)."""

    draft_model: str  # local path of the draft checkpoint
    num_speculative_tokens: int
    draft_model_config: ModelConfig

    @staticmethod
    def from_args(args: Any, target: ModelConfig) -> "Optional[SpeculativeConfig]":
        path = getattr(args, "speculative_model", None)
        if not path:
            return None
        n = getattr(args, "num_speculative_tokens", None)
        if n is None:
            n = 5
        if n < 1:
            raise ValueError(
                f"--num-speculative-tokens must be >= 1 (got {n}); drop "
                "--speculative-model to disable speculation"
            )
        draft = ModelConfig.from_pretrained(
            path,
            max_model_len=target.max_model_len,
            dtype=args.dtype,
        )
        return SpeculativeConfig(
            draft_model=path,
            num_speculative_tokens=n,
            draft_model_config=draft,
        )


@dataclasses.dataclass(frozen=True)
class FrontdoorConfig:
    """Admission control / fair queuing / drain knobs (frontdoor/).

    All-zero defaults reproduce the pre-frontdoor behavior exactly
    except for ordering: requests beyond the engine's small admission
    window park in the weighted fair queue instead of the scheduler's
    deque, and are released in per-tenant virtual-time order.
    """

    enabled: bool = True
    # > 0 bounds parked + engine-waiting requests; past it new arrivals
    # shed with RESOURCE_EXHAUSTED/429 + Retry-After.  0 = unbounded.
    max_waiting_requests: int = 0
    # > 0 sheds a request when the ESTIMATED queue-drain time (observed
    # token throughput EWMA, seeded from KV-pool token capacity)
    # already exceeds this many seconds.  0 disables.
    admission_deadline_s: float = 0.0
    # > 0 early-aborts requests still pre-prefill this long after
    # arrival (tightened by any request-level deadline).  0 disables.
    queue_ttl_s: float = 0.0
    # SIGTERM drain: seconds in-flight generations get to finish before
    # the process exits anyway.
    drain_grace_s: float = 30.0
    # ("tenant", weight) pairs for weighted fair queuing; unlisted
    # tenants weigh 1.0
    tenant_weights: tuple[tuple[str, float], ...] = ()
    # per-tenant token bucket: sustained tokens/s and burst capacity
    # (0 burst defaults to 10s of sustained rate).  0 rate disables.
    tenant_rate_tokens_per_s: float = 0.0
    tenant_burst_tokens: float = 0.0
    # header / gRPC metadata key carrying the tenant id; requests
    # without it fall back to the adapter id, then "default"
    tenant_header: str = "x-tenant-id"

    @staticmethod
    def parse_tenant_weights(spec: Optional[str]) -> tuple[tuple[str, float], ...]:
        """``"teamA=4,teamB=1"`` → (("teamA", 4.0), ("teamB", 1.0))."""
        if not spec:
            return ()
        out = []
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            name, sep, weight = part.partition("=")
            if not sep or not name:
                raise ValueError(
                    f"--tenant-weights entry {part!r} is not name=weight"
                )
            w = float(weight)
            if w <= 0:
                raise ValueError(
                    f"--tenant-weights weight for {name!r} must be > 0"
                )
            out.append((name.strip(), w))
        return tuple(out)

    @staticmethod
    def from_args(args: Any) -> "FrontdoorConfig":
        return FrontdoorConfig(
            enabled=not getattr(args, "disable_frontdoor", False),
            max_waiting_requests=int(
                getattr(args, "max_waiting_requests", 0) or 0
            ),
            admission_deadline_s=float(
                getattr(args, "admission_deadline", 0.0) or 0.0
            ),
            queue_ttl_s=float(getattr(args, "queue_ttl", 0.0) or 0.0),
            drain_grace_s=float(
                getattr(args, "drain_grace", 30.0) or 0.0
            ),
            tenant_weights=FrontdoorConfig.parse_tenant_weights(
                getattr(args, "tenant_weights", None)
            ),
            tenant_rate_tokens_per_s=float(
                getattr(args, "tenant_rate_limit", 0.0) or 0.0
            ),
            tenant_burst_tokens=float(
                getattr(args, "tenant_burst", 0.0) or 0.0
            ),
            # lowercased once here: HTTP header parsing and gRPC
            # invocation metadata both produce lowercase keys, and
            # every consumer of this field must match them
            tenant_header=(
                getattr(args, "tenant_header", "x-tenant-id")
                or "x-tenant-id"
            ).lower(),
        )


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    model_config: ModelConfig
    cache_config: CacheConfig
    scheduler_config: SchedulerConfig
    parallel_config: ParallelConfig
    lora_config: LoRAConfig
    tokenizer: str | None = None
    # checkpoint revision: picks the HF-cache snapshot when --model is a
    # hub id (tgis_utils/hub.get_model_path) and rides through to
    # AutoTokenizer.from_pretrained (reference passes it into vLLM's
    # engine args, src/vllm_tgis_adapter/tgis_utils/args.py)
    revision: str | None = None
    # allow custom tokenizer/config code shipped inside the (local)
    # model directory — passed through to AutoTokenizer.from_pretrained
    trust_remote_code: bool = False
    seed: int = 0
    max_logprobs: int = 20
    hbm_memory_utilization: float = 0.90
    # --swap-space GiB of HOST memory for preempted sequences' KV: > 0
    # swaps a decode-phase preemption victim's pages to host and restores
    # them on re-admission instead of recompute-prefill (engine/core.py
    # _swap_out_seq; reference maps the flag into vLLM's CPU swap).
    # 0 keeps the recompute-only path.
    swap_space_gib: float = 0.0
    # --kv-host-cache-gb GiB of host RAM for the tiered KV store
    # (engine/kv_tier.py, docs/KV_TIERING.md): a hash-addressed
    # prefix-page cache behind the device pool — registered prompt pages
    # demote device→host, prefix misses the tier can cover park for an
    # async promotion, preemption swap-out lands in the same store, and
    # the store survives supervised engine restarts.  0 (the library
    # default) is byte-identical to the pre-tier engine; the served
    # binary defaults it ON (tgis_utils/args.py, --no-kv-host-cache to
    # disable).
    kv_host_cache_gb: float = 0.0
    # --kv-disk-cache-gb GiB of local disk beneath the host tier
    # (engine/kv_tier.py DiskKVTier, docs/MEMORY.md): host-tier LRU
    # victims — cold KV prefix pages AND cold adapters spilled from the
    # host registry — land in mmap-read, checksum-validated files;
    # promotion walks disk → host → device through the existing
    # park/promote gates.  0 (default) disables; requires the host tier.
    kv_disk_cache_gb: float = 0.0
    # directory for the disk tier's entries; None = a stable path under
    # the system tempdir.  Entries are content-addressed and validated
    # on read, so the directory may survive restarts (cross-restart
    # reuse) or be shared by successive server generations.
    kv_disk_cache_dir: str | None = None
    # --kvnet-listen host:port of the networked KV tier's RPC service
    # (kvnet/, docs/CROSS_HOST.md): cross-host prefix sharing, remote
    # DecodeCheckpoint handoffs, and machine-loss resume over the
    # disk-entry wire format.  None (default) keeps kvnet entirely off
    # — zero behavior change.  Port 0 binds an ephemeral port (tests).
    kvnet_listen: str | None = None
    # --kvnet-peers host:port addresses of the other hosts in the
    # fleet; each becomes a heartbeat-revived PeerClient whose digest
    # mirror extends prefix coverage fleet-wide
    kvnet_peers: tuple[str, ...] = ()
    # --kvnet-node-id stable identity in peer HELLOs (adoption sweeps
    # key staged handoffs by it); None derives one from the listen addr
    kvnet_node_id: str | None = None
    # --kvnet-timeout per-request deadline against a peer; bounded
    # retry with backoff inside it, then degradation to local tiers
    kvnet_timeout_s: float = 5.0
    # unified paged HBM arena (engine/arena.py, docs/MEMORY.md): KV
    # pages and adapter shards draw from ONE block budget with unified
    # LRU + pinning — adapter residency charges true-rank pages, KV
    # pressure evicts cold adapters (back to the host registry), adapter
    # pressure evicts cold cached KV pages (demoting into the host
    # tier).  False restores separately-budgeted pools.
    unified_arena: bool = True
    quantization: str | None = None
    otlp_traces_endpoint: str | None = None
    # telemetry signal layer (telemetry/, docs/OBSERVABILITY.md):
    # per-class SLO objectives (JSON object or path; None = defaults)
    # and the cost-ledger JSONL sink — optional, zero-cost when off
    slo_config: str | None = None
    ledger_log: str | None = None
    disable_log_requests: bool = True
    disable_log_stats: bool = False
    # stall watchdog (watchdog.py): a step loop with unfinished work
    # that stops beating for this long gets a full diagnostic dump
    # (scheduler queues, KV stats, flight-recorder tail).  0 disables.
    watchdog_deadline_s: float = 120.0
    # --watchdog-action: what a declared stall triggers beyond the
    # diagnostic snapshot — 'snapshot' (PR-3 behavior: diagnose only) or
    # 'restart' (hand the stalled replica to the engine supervisor; the
    # snapshot is still written FIRST)
    watchdog_action: str = "snapshot"
    # --dump-dir: directory for watchdog stall snapshots (JSON, one file
    # per stall); None keeps dumps in the log/termination-log only
    dump_dir: str | None = None
    # engine supervision (supervisor/): > 0 enables supervised restart
    # after engine death — quiesce, replay pre-prefill work, fail
    # mid-decode retryable, rebuild with a fresh KV pool — allowing at
    # most this many restarts inside engine_restart_window_s before the
    # crash-loop circuit breaker escalates to clean process death.
    # 0 keeps the pre-PR5 crash-fast semantics (the library default;
    # the served binary defaults to 3 via --max-engine-restarts).
    max_engine_restarts: int = 0
    engine_restart_window_s: float = 300.0
    # base of the exponential backoff between restart attempts
    # (base * 2^(attempts_in_window - 1), capped at 30s)
    engine_restart_backoff_s: float = 0.5
    # mid-decode checkpoint/resume at supervised restart
    # (docs/RECOVERY.md): when supervision AND the host KV tier are both
    # on, a mid-decode request checkpoints into the tier at quiesce and
    # resumes token-identically instead of failing EngineRestartError.
    # --no-decode-resume is the escape hatch back to the fail-retryable
    # floor; the flag is inert without --max-engine-restarts > 0 and
    # --kv-host-cache-gb > 0.
    decode_resume: bool = True
    speculative: "Optional[SpeculativeConfig]" = None
    # front door (frontdoor/): admission control, per-tenant fair
    # queuing, load shedding, graceful drain
    frontdoor: FrontdoorConfig = dataclasses.field(
        default_factory=FrontdoorConfig
    )
    # prefill/decode disaggregation (docs/SCALING.md "Disaggregated
    # roles"): the role every replica serves when --dp-replica-roles is
    # not given.  'mixed' (default) is the pre-disaggregation behavior;
    # 'prefill' replicas run ragged full-bucket prefill only and hand
    # finished prompts to decode-capable replicas through the host KV
    # tier (demote at prefill commit, stage a DecodeCheckpoint, resume
    # at decode admission — the PR-10 machinery verbatim); 'decode'
    # replicas admit handoffs through the kv gate and run decode.
    replica_role: str = "mixed"
    # per-replica role list ("prefill,decode,decode"), length must equal
    # the replica count; overrides replica_role.  () = uniform.
    dp_replica_roles: tuple[str, ...] = ()
    # --attention-backend: the serving data path (docs/ATTENTION.md).
    # "ragged" (the default AND only backend) runs the unified
    # ragged-paged-attention path (ops/ragged_attention.py): mixed
    # prefill+decode token streams — speculative verify spans included
    # — in one dispatch, one flat-length bucket, no per-prompt padding.
    # "bucketed" (the pre-consolidation solo/packed prefill buckets +
    # per-batch-width decode ladder) is RETIRED and fails boot with a
    # pointer here; pp>1 / sp>1 engines and prompt-logprob requests
    # transparently use the legacy solo-prefill/fused-decode planner.
    attention_backend: str = "ragged"

    def __post_init__(self) -> None:
        if self.attention_backend == "bucketed":
            raise ValueError(
                "--attention-backend=bucketed was retired: the ragged "
                "paged-attention path (the default) is the only serving "
                "data path — measured 3.5-4x bucketed tok/s at padding "
                "waste 0.000 (docs/ATTENTION.md).  Drop the flag; pp>1 "
                "/ sp>1 engines and prompt-logprob requests "
                "transparently use the legacy solo-prefill planner."
            )
        if self.attention_backend != "ragged":
            raise ValueError(
                f"--attention-backend must be 'ragged' "
                f"(got {self.attention_backend!r}; 'bucketed' is "
                "retired — docs/ATTENTION.md)"
            )
        if (
            self.speculative is not None
            and self.parallel_config.sequence_parallel_size > 1
        ):
            # truthful flags (VERDICT r2/r3): speculation rides the
            # ragged verify span, and sp>1 engines plan through the
            # legacy solo/fused path (the ragged kernel reads the
            # replicated paged cache, not the sp ring)
            raise ValueError(
                "--speculative-model does not compose with "
                "--sequence-parallel-size > 1 yet (speculative verify "
                "rides the ragged span path; sp engines use the legacy "
                "planner — docs/ATTENTION.md); drop one of the flags"
            )
        if self.parallel_config.dp_replicas < 1:
            raise ValueError(
                f"--dp-replicas must be >= 1 "
                f"(got {self.parallel_config.dp_replicas})"
            )
        if (
            self.parallel_config.dp_replicas > 1
            and self.parallel_config.data_parallel_size > 1
        ):
            raise ValueError(
                "--dp-replicas and --data-parallel-size are two spellings "
                "of the replica count (strict disjoint-device vs "
                "shared-device-tolerant); set exactly one of them > 1"
            )
        self._validate_replica_roles()
        self._validate_recurrent_state()
        self._validate_latent_cache()
        self._validate_window_group()
        if self.kv_disk_cache_gb > 0 and self.kv_host_cache_gb <= 0:
            raise ValueError(
                "--kv-disk-cache-gb requires the host KV tier "
                "(--kv-host-cache-gb > 0): the disk tier sits BENEATH "
                "host RAM — demotions cascade host→disk and promotions "
                "walk disk→host→device (docs/MEMORY.md); raise the host "
                "budget or drop the disk flag"
            )
        if self.watchdog_action not in ("snapshot", "restart"):
            raise ValueError(
                f"--watchdog-action must be 'snapshot' or 'restart' "
                f"(got {self.watchdog_action!r})"
            )
        if self.quantization not in (None, "int8", "awq", "gptq"):
            # truthful flags (VERDICT r2/r3): only the schemes that are
            # actually implemented may pass boot.  Reference maps these
            # names into vLLM's quantization engine
            # (tgis_utils/args.py --quantize); here int8 weight-only is
            # native (engine/weights.py quantize_params_int8) and
            # awq/gptq int4 checkpoints dequantize at load
            # (engine/quantized.py)
            raise ValueError(
                f"quantization scheme {self.quantization!r} is not "
                "implemented; supported: 'int8' (native weight-only, "
                "per-channel), 'awq'/'gptq' (int4 checkpoint, "
                "dequant-on-load)"
            )
        kvq = self.cache_config.kv_quantization
        if kvq not in ("none", "int8", "fp8"):
            raise ValueError(
                f"--kv-quantization must be one of none/int8/fp8 "
                f"(got {kvq!r}); see docs/QUANTIZATION.md"
            )
        if kvq != "none":
            # truthful flags: refuse every combo the quantized page
            # lifecycle does not implement, at boot — not as a trace
            # failure three layers down (docs/QUANTIZATION.md "Flags")
            if kvq == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
                raise ValueError(
                    "--kv-quantization fp8 needs jax.numpy."
                    "float8_e4m3fn, which this JAX build lacks; use "
                    "int8 or upgrade JAX"
                )
            if self.parallel_config.pipeline_parallel_size > 1:
                raise ValueError(
                    "--kv-quantization does not compose with "
                    "--pipeline-parallel-size > 1 yet (the staged "
                    "runner has no quantized-cache plumbing); drop one "
                    "flag"
                )
            if self.parallel_config.sequence_parallel_size > 1:
                raise ValueError(
                    "--kv-quantization does not compose with "
                    "--sequence-parallel-size > 1 yet (ring/ulysses "
                    "attention reads dense K/V, not quantized pages); "
                    "drop one flag"
                )
            if self.swap_space_gib > 0:
                raise ValueError(
                    "--kv-quantization does not compose with "
                    "--swap-space (the per-sequence swap copy predates "
                    "the scale sidecar); use the host KV tier "
                    "(--kv-host-cache-gb), which carries quantized "
                    "pages natively"
                )
            if "float8" in str(self.cache_config.cache_dtype):
                raise ValueError(
                    "--kv-cache-dtype fp8 and --kv-quantization both "
                    "set: the raw-cast dtype path is subsumed by "
                    "--kv-quantization fp8 — drop --kv-cache-dtype"
                )
        ckpt_quant = self.model_config.checkpoint_quant
        if self.quantization in ("awq", "gptq") and (
            self.quantization != ckpt_quant
        ):
            raise ValueError(
                f"--quantization {self.quantization} but the checkpoint's "
                f"quantization_config says "
                f"{ckpt_quant or 'no quantization'}; the checkpoint "
                "format is authoritative — drop the flag or fix the model"
            )
        # sliding-window / ALiBi compose with sp>1: the ring carries the
        # band mask and position bias in global coordinates across hops,
        # ulysses head-slices the slopes (ops/ring_attention.py,
        # ops/ulysses_attention.py; parity on the virtual mesh in
        # tests/test_ring_attention.py)
        pp = self.parallel_config.pipeline_parallel_size
        if pp <= 1:
            return
        # v1 pipeline-parallel scope (engine/pipeline.py): composes with
        # tp / chunked prefill / prefix caching / guided decoding; the
        # features below need per-stage plumbing that doesn't exist yet,
        # so they fail at config time rather than running wrong
        if self.speculative is not None:
            raise ValueError(
                "--speculative-model is not supported with "
                "--pipeline-parallel-size > 1 yet"
            )
        if self.parallel_config.sequence_parallel_size > 1:
            raise ValueError(
                "--sequence-parallel-size does not compose with "
                "--pipeline-parallel-size yet"
            )
        # dp × pp composes: the async fleet builds one PIPELINE per dp
        # replica over a disjoint pp×tp device slice
        # (engine/async_llm.py from_config)

    def _validate_recurrent_state(self) -> None:
        """Truthful flags for a model with recurrent state (a Mamba-2
        mixer: ``ModelConfig.mixer``, docs/STATE.md): a sequence's state
        lives in ONE slot of the slab and is a function of every token
        before it, so whatever moves, shares, shards or skips KV pages
        without carrying that state is refused at boot, with what is
        missing.  The host KV tier (and decode resume, which rests on
        it) turns itself off in engine/core.py with a warning, as it
        does for rolling-window eviction."""
        if not self.model_config.has_recurrent_state:
            return
        model = f"model_type {self.model_config.model_type!r}"
        pcfg = self.parallel_config
        refused = [
            (self.cache_config.enable_prefix_caching,
             "--enable-prefix-caching",
             "a cached page run would need the scan state at its end "
             "snapshotted beside it (engine/kv_cache.py keeps none)"),
            (self.swap_space_gib > 0, "--swap-space",
             "the swap copy holds KV pages only; a preempted row is "
             "recomputed from position 0 instead"),
            (self.speculative is not None, "--speculative-model",
             "a rejected draft token cannot be rolled back out of the "
             "scan state (the verify span keeps no per-token states)"),
            (bool(self.kvnet_listen or self.kvnet_peers),
             "--kvnet-listen / --kvnet-peers",
             "the kvnet wire format and DecodeCheckpoint carry KV pages "
             "and no state slot (kvnet/, engine/kv_tier.py)"),
            (self.roles_active(), "--dp-replica-roles",
             "a prefill-to-decode handoff carries KV pages and no state "
             "slot (engine/kv_tier.py DecodeCheckpoint)"),
            (pcfg.tensor_parallel_size > 1, "--tensor-parallel-size > 1",
             "the mixer's projections, its convolution and the state "
             "slab have no shard rules (parallel/)"),
            (pcfg.pipeline_parallel_size > 1, "--pipeline-parallel-size > 1",
             "the staged runner allocates no state slab "
             "(engine/pipeline.py)"),
            (pcfg.sequence_parallel_size > 1, "--sequence-parallel-size > 1",
             "the legacy solo-prefill planner sp engines use does not "
             "carry state between chunks"),
            (pcfg.data_parallel_size > 1 or pcfg.dp_replicas > 1,
             "--data-parallel-size / --dp-replicas > 1",
             "a request that moves between replicas (resume, handoff) "
             "would leave its state behind"),
        ]
        for on, flag, missing in refused:
            if on:
                raise ValueError(
                    f"{flag} does not compose with {model} yet: the "
                    f"model keeps recurrent state beside its KV pages, "
                    f"and {missing}; drop the flag (docs/STATE.md)"
                )

    def _validate_latent_cache(self) -> None:
        """Truthful flags for a model with a latent cache and held
        experts (``ModelConfig.mla`` / ``.experts``,
        docs/LATENT_ATTENTION.md): the pool's row is one latent a token
        a layer, so whatever quantizes, shares, moves or shards per-head
        KV pages is refused at boot, with what is missing.  The host KV
        tier (and decode resume, which rests on it) turns itself off in
        engine/core.py with a warning, as it does for a model with
        recurrent state: it is on by default at the command line."""
        if not self.model_config.has_latent_cache:
            return
        model = f"model_type {self.model_config.model_type!r}"
        pcfg = self.parallel_config
        refused = [
            (self.cache_config.kv_quantization != "none",
             "--kv-quantization",
             "the page-scale sidecar is laid out per KV head and the "
             "latent kernel reads unscaled rows (ops/kv_quant.py, "
             "ops/mla_attention.py)"),
            (self.cache_config.enable_prefix_caching,
             "--enable-prefix-caching",
             "page sharing and copy-on-write move per-head K and V "
             "pages (engine/runner.py copy programs know no latent "
             "pool)"),
            (self.swap_space_gib > 0, "--swap-space",
             "the swap copy gathers per-head K and V pages; a preempted "
             "row is recomputed from position 0 instead"),
            (self.speculative is not None, "--speculative-model",
             "the draft model's pages share the pool's budget and the "
             "verify program assumes per-head K and V caches "
             "(engine/speculative.py)"),
            (bool(self.kvnet_listen or self.kvnet_peers),
             "--kvnet-listen / --kvnet-peers",
             "the kvnet wire format and DecodeCheckpoint carry per-head "
             "K and V pages (kvnet/, engine/kv_tier.py)"),
            (self.roles_active(), "--dp-replica-roles",
             "a prefill-to-decode handoff carries per-head K and V "
             "pages (engine/kv_tier.py DecodeCheckpoint)"),
            (self.lora_config.enabled, "--enable-lora",
             "the adapter stacks target q/k/v/o and the dense MLP's "
             "projections; the latent projections and the expert "
             "stacks have no LoRA targets (engine/lora.py)"),
            (pcfg.tensor_parallel_size > 1, "--tensor-parallel-size > 1",
             "the latent projections, the expert stacks and the latent "
             "pool have no shard rules, and an expert layer has no "
             "exchange across chips (parallel/)"),
            (pcfg.pipeline_parallel_size > 1, "--pipeline-parallel-size > 1",
             "the staged runner allocates per-head K and V pools "
             "(engine/pipeline.py)"),
            (pcfg.sequence_parallel_size > 1, "--sequence-parallel-size > 1",
             "the legacy solo-prefill planner sp engines use knows no "
             "latent pool"),
            (pcfg.data_parallel_size > 1 or pcfg.dp_replicas > 1,
             "--data-parallel-size / --dp-replicas > 1",
             "each replica would need a device slice of its own, and "
             "slices are cut for tensor-parallel meshes (parallel/)"),
        ]
        for on, flag, missing in refused:
            if on:
                raise ValueError(
                    f"{flag} does not compose with {model} yet: the "
                    f"model keeps a latent cache and a share of its "
                    f"experts, and {missing}; drop the flag "
                    f"(docs/LATENT_ATTENTION.md)"
                )

    def _validate_window_group(self) -> None:
        """Truthful flags for a model whose window layers and full
        layers keep their pages in two groups
        (``ModelConfig.has_window_group``, docs/ATTENTION.md): a
        sequence has a block table in each, and the window group's pages
        behind the band are freed as it advances, so whatever shares,
        moves, shards or quantizes ONE table's pages is refused at boot,
        with what is missing; none falls back to keeping every page.
        The host KV tier (and decode resume) turns itself off in
        engine/core.py with a warning, as for rolling-window eviction."""
        if not self.model_config.has_window_group:
            return
        model = f"model_type {self.model_config.model_type!r}"
        pcfg = self.parallel_config
        refused = [
            (self.cache_config.kv_quantization != "none",
             "--kv-quantization",
             "the page-scale sidecars are made for one pool, not one a "
             "group (ops/kv_quant.py)"),
            (self.cache_config.enable_prefix_caching,
             "--enable-prefix-caching",
             "a cached page run would have to be kept and matched in "
             "both groups, and the window group frees its pages behind "
             "the band (engine/kv_cache.py registers one table)"),
            (self.swap_space_gib > 0, "--swap-space",
             "the swap copy gathers one table's slots; a preempted row "
             "is recomputed from position 0 instead"),
            (self.speculative is not None, "--speculative-model",
             "the draft model's pages share one table's slot geometry "
             "(engine/speculative.py)"),
            (bool(self.kvnet_listen or self.kvnet_peers),
             "--kvnet-listen / --kvnet-peers",
             "the kvnet wire format and DecodeCheckpoint carry one "
             "table's pages (kvnet/, engine/kv_tier.py)"),
            (self.roles_active(), "--dp-replica-roles",
             "a prefill-to-decode handoff carries one table's pages "
             "(engine/kv_tier.py DecodeCheckpoint)"),
            (self.lora_config.enabled, "--enable-lora",
             "the expert stacks have no LoRA targets (engine/lora.py)"),
            (pcfg.tensor_parallel_size > 1, "--tensor-parallel-size > 1",
             "the expert stacks and the second pool have no shard rules "
             "(parallel/)"),
            (pcfg.pipeline_parallel_size > 1, "--pipeline-parallel-size > 1",
             "the staged runner allocates one pool a stage and knows no "
             "window group (engine/pipeline.py)"),
            (pcfg.sequence_parallel_size > 1, "--sequence-parallel-size > 1",
             "the legacy solo-prefill planner sp engines use knows one "
             "table"),
            (pcfg.data_parallel_size > 1 or pcfg.dp_replicas > 1,
             "--data-parallel-size / --dp-replicas > 1",
             "a request that moves between replicas (resume, handoff) "
             "carries one table's pages"),
        ]
        for on, flag, missing in refused:
            if on:
                raise ValueError(
                    f"{flag} does not compose with {model} yet: the "
                    f"model keeps its window layers' and its full "
                    f"layers' pages in two groups, and {missing}; drop "
                    f"the flag (docs/ATTENTION.md)"
                )

    def resolved_replica_roles(self) -> tuple[str, ...]:
        """One role per replica: ``dp_replica_roles`` when given, else
        ``replica_role`` repeated over the replica count."""
        dp = max(
            self.parallel_config.data_parallel_size,
            self.parallel_config.dp_replicas,
        )
        if self.dp_replica_roles:
            return tuple(self.dp_replica_roles)
        return (self.replica_role,) * dp

    def roles_active(self) -> bool:
        """True when any replica serves a dedicated (non-mixed) role."""
        return any(r != "mixed" for r in self.resolved_replica_roles())

    def _validate_replica_roles(self) -> None:
        """Boot-time refusals for --replica-role / --dp-replica-roles:
        a role config that could never serve (no decode-capable or no
        prefill-capable replica) or whose handoff substrate is missing
        (KV tier off, decode-resume off, pp > 1) fails HERE, loudly,
        not at the first handoff."""
        valid = ("prefill", "decode", "mixed")
        if self.replica_role not in valid:
            raise ValueError(
                f"--replica-role must be one of {valid} "
                f"(got {self.replica_role!r})"
            )
        for role in self.dp_replica_roles:
            if role not in valid:
                raise ValueError(
                    f"--dp-replica-roles entry {role!r} is not one of "
                    f"{valid}"
                )
        dp = max(
            self.parallel_config.data_parallel_size,
            self.parallel_config.dp_replicas,
        )
        if self.dp_replica_roles and len(self.dp_replica_roles) != dp:
            raise ValueError(
                f"--dp-replica-roles names {len(self.dp_replica_roles)} "
                f"replica(s) but the fleet has {dp}; give exactly one "
                "role per replica"
            )
        roles = self.resolved_replica_roles()
        if all(r == "mixed" for r in roles):
            return  # pre-disaggregation behavior; nothing to demand
        # a host with kvnet peers can satisfy either role REMOTELY:
        # an all-prefill host hands checkpoints to decode-capable
        # peers over the networked tier (docs/CROSS_HOST.md), and an
        # all-decode host adopts staged checkpoints from prefill
        # peers — so the single-host capability demands only apply
        # when this process is the whole fleet
        if not self.kvnet_peers:
            if not any(r in ("decode", "mixed") for r in roles):
                raise ValueError(
                    f"replica roles {roles} have no decode-capable "
                    "replica (decode or mixed): prefill replicas would "
                    "stage handoffs nothing can ever consume"
                )
            if not any(r in ("prefill", "mixed") for r in roles):
                raise ValueError(
                    f"replica roles {roles} have no prefill-capable "
                    "replica (prefill or mixed): fresh requests would "
                    "have nowhere to run their prompt"
                )
        if self.kv_host_cache_gb <= 0:
            raise ValueError(
                "prefill/decode replica roles require the host KV tier "
                "(--kv-host-cache-gb > 0): the prefill→decode handoff "
                "moves KV pages through it (docs/SCALING.md)"
            )
        if not self.decode_resume:
            raise ValueError(
                "prefill/decode replica roles do not compose with "
                "--no-decode-resume: the handoff IS a decode "
                "checkpoint/resume (docs/SCALING.md); drop one flag"
            )
        if self.parallel_config.pipeline_parallel_size > 1:
            raise ValueError(
                "prefill/decode replica roles do not compose with "
                "--pipeline-parallel-size > 1 yet (the staged runner "
                "has no KV-tier gather/scatter plumbing)"
            )
        # a max-length prompt whose KV cannot fit the tier can NEVER
        # hand off: its capture hits the budget rung deterministically
        # and every retry 503s the same way.  Warn loudly at boot —
        # the operator should size --kv-host-cache-gb (or cap
        # --max-model-len) before clients discover this per-request.
        import numpy as _np

        mcfg = self.model_config
        # quantized pages store 1-byte values (ops/kv_quant.py); the
        # per-page scale sidecar is noise at this warning's granularity
        itemsize = (
            1
            if self.cache_config.kv_quantization != "none"
            else _np.dtype(self.cache_config.cache_dtype).itemsize
        )
        per_token = (
            2 * mcfg.num_layers * mcfg.num_kv_heads * mcfg.head_dim
            * itemsize
        )
        worst = per_token * self.max_model_len
        budget = self.kv_host_cache_gb * (1 << 30)
        if worst > budget:
            _logger.warning(
                "replica roles: a max-length prompt's KV (~%d MiB at "
                "--max-model-len %d) exceeds the host tier budget "
                "(--kv-host-cache-gb %.1f) — such prompts can never "
                "hand off and will fail retryable every time; raise "
                "the tier budget or cap the model length",
                worst >> 20, self.max_model_len, self.kv_host_cache_gb,
            )

    @property
    def max_model_len(self) -> int:
        return self.model_config.max_model_len

    @staticmethod
    def from_args(args: Any) -> "EngineConfig":
        """Build from the parsed CLI namespace (tgis_utils/args.py)."""
        revision = getattr(args, "revision", None)
        model_path = args.model
        if not Path(model_path).exists():
            # hub id: resolve (model, revision) to the cached snapshot
            # directory — tgis_utils/hub applies local path > cache
            # override > HF cache, offline-only
            from ..tgis_utils import hub

            try:
                model_path = hub.get_model_path(model_path, revision)
            except Exception as e:
                # keep the wire-visible boot error (termination log +
                # healthcheck parse "config.json") for a model that is
                # neither a local path nor a cached snapshot
                raise ValueError(
                    f"model path {model_path!r} has no config.json and is "
                    "not a cached hub snapshot; only local model paths are "
                    "supported (use `model-util download-weights` to fetch "
                    "from the HF hub)"
                ) from e
        model_config = ModelConfig.from_pretrained(
            model_path,
            max_model_len=args.max_model_len,
            dtype=args.dtype,
        )
        moe_dispatch = getattr(args, "moe_dispatch", "dense")
        if model_config.num_experts > 0 and moe_dispatch != "dense":
            model_config = dataclasses.replace(
                model_config,
                moe_dispatch=moe_dispatch,
                moe_capacity_factor=getattr(
                    args, "moe_capacity_factor", 1.25
                ),
            )
        max_len = model_config.max_model_len
        buckets = tuple(
            b for b in SchedulerConfig.prefill_buckets if b < max_len
        ) + (max_len,)
        # --kv-cache-dtype folds into the --kv-quantization validation
        # (docs/QUANTIZATION.md "Flags"): the old path resolved ANY
        # dtype string and handed it straight to make_kv_caches — a
        # float8 raw cast with no scales and no kernel-support check,
        # failing as a downstream trace error.  Quantized spellings now
        # route to the real quantized-page path; everything else must
        # be a dtype the kernels actually serve.
        kvq = (
            getattr(args, "kv_quantization", "none") or "none"
        ).lower()
        kcd = str(args.kv_cache_dtype or "auto").lower()
        _KCD_QUANT = {
            "float8_e4m3": "fp8", "float8_e4m3fn": "fp8", "fp8": "fp8",
            "int8": "int8",
        }
        if kcd in _KCD_QUANT:
            mapped = _KCD_QUANT[kcd]
            if kvq not in ("none", mapped):
                raise ValueError(
                    f"--kv-cache-dtype {args.kv_cache_dtype} conflicts "
                    f"with --kv-quantization {kvq}; drop "
                    "--kv-cache-dtype (it is subsumed — "
                    "docs/QUANTIZATION.md)"
                )
            _logger.warning(
                "--kv-cache-dtype %s is subsumed by --kv-quantization "
                "%s: serving the scaled quantized-page path, not a raw "
                "dtype cast (docs/QUANTIZATION.md)",
                args.kv_cache_dtype, mapped,
            )
            kvq = mapped
            cache_dtype = model_config.dtype
        elif kcd == "auto":
            cache_dtype = model_config.dtype
        elif kcd in ("bfloat16", "float16", "float32"):
            cache_dtype = resolve_dtype(kcd)
        else:
            raise ValueError(
                f"--kv-cache-dtype {args.kv_cache_dtype!r} is not a "
                "KV layout the kernels serve: use auto/bfloat16/"
                "float16/float32 for full-precision pages, or "
                "--kv-quantization int8|fp8 (spellings fp8/int8/"
                "float8_e4m3 here map to it) for quantized pages "
                "(docs/QUANTIZATION.md)"
            )
        return EngineConfig(
            model_config=model_config,
            cache_config=CacheConfig(
                block_size=(
                    getattr(args, "block_size", None)
                    or model_config.default_block_size
                ),
                num_blocks=0,  # auto-size from HBM at engine boot
                enable_prefix_caching=getattr(
                    args, "enable_prefix_caching", False
                ),
                cache_dtype=cache_dtype,
                kv_quantization=kvq,
            ),
            scheduler_config=SchedulerConfig(
                max_num_seqs=args.max_num_seqs,
                max_num_batched_tokens=(
                    args.max_num_batched_tokens or max(2048, max_len)
                ),
                prefill_buckets=buckets,
                num_decode_steps=args.num_scheduler_steps,
            ),
            parallel_config=ParallelConfig(
                tensor_parallel_size=args.tensor_parallel_size or 1,
                pipeline_parallel_size=args.pipeline_parallel_size,
                data_parallel_size=args.data_parallel_size,
                # no `or 1` coercion: --dp-replicas 0 must reach the
                # >= 1 validation and be rejected, not silently boot
                # a single replica
                dp_replicas=getattr(args, "dp_replicas", 1),
                sequence_parallel_size=getattr(
                    args, "sequence_parallel_size", 1
                ) or 1,
                sequence_parallel_mode=getattr(
                    args, "sequence_parallel_mode", "ring"
                ),
            ),
            lora_config=LoRAConfig(
                enabled=args.enable_lora,
                max_loras=args.max_loras,
                max_lora_rank=args.max_lora_rank,
                pool=getattr(args, "lora_pool", True),
                max_cpu_loras=getattr(args, "max_cpu_loras", 0) or 0,
                prefetch_concurrency=getattr(
                    args, "lora_prefetch_concurrency", 2
                ),
                gathered=getattr(args, "lora_gathered", True),
            ),
            speculative=SpeculativeConfig.from_args(args, model_config),
            tokenizer=args.tokenizer,
            revision=revision,
            trust_remote_code=getattr(args, "trust_remote_code", False),
            seed=args.seed,
            max_logprobs=args.max_logprobs,
            hbm_memory_utilization=args.hbm_memory_utilization,
            swap_space_gib=getattr(args, "swap_space", 0.0) or 0.0,
            kv_host_cache_gb=(
                0.0
                if getattr(args, "no_kv_host_cache", False)
                else float(getattr(args, "kv_host_cache_gb", 0.0) or 0.0)
            ),
            kv_disk_cache_gb=(
                0.0
                if getattr(args, "no_kv_host_cache", False)
                else float(getattr(args, "kv_disk_cache_gb", 0.0) or 0.0)
            ),
            kv_disk_cache_dir=getattr(args, "kv_disk_cache_dir", None),
            kvnet_listen=getattr(args, "kvnet_listen", None),
            kvnet_peers=tuple(
                p.strip()
                for p in (getattr(args, "kvnet_peers", None) or "").split(",")
                if p.strip()
            ),
            kvnet_node_id=getattr(args, "kvnet_node_id", None),
            kvnet_timeout_s=float(
                getattr(args, "kvnet_timeout", 5.0) or 5.0
            ),
            unified_arena=getattr(args, "unified_arena", True),
            quantization=args.quantization,
            otlp_traces_endpoint=args.otlp_traces_endpoint,
            slo_config=getattr(args, "slo_config", None),
            ledger_log=getattr(args, "ledger_log", None),
            disable_log_stats=getattr(args, "disable_log_stats", False),
            disable_log_requests=args.disable_log_requests,
            watchdog_deadline_s=float(
                getattr(args, "watchdog_deadline", 120.0) or 0.0
            ),
            watchdog_action=getattr(args, "watchdog_action", "snapshot")
            or "snapshot",
            dump_dir=getattr(args, "dump_dir", None),
            max_engine_restarts=int(
                getattr(args, "max_engine_restarts", 0) or 0
            ),
            engine_restart_window_s=float(
                getattr(args, "engine_restart_window", 300.0) or 0.0
            ),
            engine_restart_backoff_s=float(
                getattr(args, "engine_restart_backoff", 0.5) or 0.0
            ),
            decode_resume=not getattr(args, "no_decode_resume", False),
            replica_role=getattr(args, "replica_role", "mixed")
            or "mixed",
            dp_replica_roles=tuple(
                part.strip()
                for part in (
                    getattr(args, "dp_replica_roles", None) or ""
                ).split(",")
                if part.strip()
            ),
            frontdoor=FrontdoorConfig.from_args(args),
            attention_backend=getattr(
                args, "attention_backend", "ragged"
            ) or "ragged",
        )
