"""Weight loading: HF safetensors checkpoints → model param pytrees.

The TPU-native analog of vLLM's weight loader consumed through engine boot
(reference capability surface, SURVEY.md §2.3 "engine lifecycle").  Reads
every ``*.safetensors`` shard in a model directory and maps HF parameter
names onto the pytree layout of models/llama.py, transposing projection
matrices to ``[in, out]`` orientation.

When a sharding function is provided (parallel/sharding.py), each tensor is
placed onto the device mesh as it is loaded so host memory never holds more
than one full tensor (required for 70B-class models on a v5e slice).
"""

from __future__ import annotations

from pathlib import Path
from typing import TYPE_CHECKING, Callable, Optional

import jax
import jax.numpy as jnp
from safetensors import safe_open

from vllm_tgis_adapter_tpu.logging import init_logger

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig

logger = init_logger(__name__)

PlaceFn = Callable[[str, jax.Array], jax.Array]


def _np_to_jnp(tensor, dtype) -> jax.Array:
    return jnp.asarray(tensor).astype(dtype)


class CheckpointIndex:
    """Lazy name→shard index over a directory of safetensors files.

    Tensors are read one at a time on demand so host memory never holds
    more than one full tensor alongside the (possibly sharded) params —
    required for 70B-class models whose full checkpoint exceeds host RAM
    headroom and whose unsharded weights exceed one chip's HBM.
    """

    def __init__(self, model_path: str):
        files = sorted(Path(model_path).glob("*.safetensors"))
        if not files:
            raise ValueError(f"no *.safetensors files found in {model_path}")
        self._by_name: dict[str, Path] = {}
        # one open handle per shard (mmap-backed, cheap) — reopening per
        # tensor would re-parse each multi-GB shard's header ~720 times
        # for a 70B checkpoint
        self._handles: dict[Path, object] = {}
        for file in files:
            # framework="flax" decodes bf16 natively (numpy cannot)
            f = safe_open(file, framework="flax")
            self._handles[file] = f
            for name in f.keys():  # noqa: SIM118
                self._by_name[name] = file
        self._taken: set[str] = set()

    def __contains__(self, name: str) -> bool:
        return name in self._by_name and name not in self._taken

    def pop(self, name: str) -> jax.Array:
        self._taken.add(name)
        return self._handles[self._by_name[name]].get_tensor(name)

    def remaining(self) -> list[str]:
        return [n for n in self._by_name if n not in self._taken]


def load_checkpoint_tensors(model_path: str) -> dict:
    """Eager {hf_name: array} across all shards (tests/small models)."""
    index = CheckpointIndex(model_path)
    return {name: index.pop(name) for name in index.remaining()}



def open_checkpoint_index(config: "ModelConfig", model_path: str):
    """CheckpointIndex, wrapped for int4 (AWQ/GPTQ) checkpoints so
    quantized projections surface as plain fp ``.weight`` tensors
    (engine/quantized.py dequant-on-load)."""
    raw = CheckpointIndex(model_path)
    method = getattr(config, "checkpoint_quant", None)
    if method:
        from vllm_tgis_adapter_tpu.engine.quantized import (
            Int4CheckpointIndex,
        )

        logger.info(
            "int4 %s checkpoint: dequantizing group-wise (group_size=%d) "
            "to %s at load", method,
            config.checkpoint_quant_group_size, config.dtype.__name__,
        )
        raw = Int4CheckpointIndex(
            raw, method=method,
            group_size=config.checkpoint_quant_group_size,
        )
    return raw


def load_llama_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """Build the LlamaForCausalLM param pytree from a HF checkpoint."""
    place = place or (lambda _name, x: x)
    dtype = config.dtype
    raw = open_checkpoint_index(config, model_path)
    # gemma lineage: HF's RMSNorm computes (1 + w) * x̂; folding the
    # offset into the stored weight once here keeps the runtime norm
    # the plain w * x̂ shared by the whole family
    norm_offset = getattr(config, "norm_weight_offset", 0.0)

    def take(name: str, transpose: bool = False) -> jax.Array:
        if name not in raw:
            raise ValueError(f"checkpoint is missing tensor {name!r}")
        x = _np_to_jnp(raw.pop(name), dtype)
        if transpose:
            x = x.T
        if norm_offset and name.endswith(("layernorm.weight",
                                          "norm.weight")):
            x = x + norm_offset
        return place(name, x)

    params: dict = {
        "embed": take("model.embed_tokens.weight"),
        "final_norm": take("model.norm.weight"),
        "layers": [],
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = take("lm_head.weight", transpose=True)
    elif "lm_head.weight" in raw:
        raw.pop("lm_head.weight")

    # quantization-aware KV scales (docs/QUANTIZATION.md "Calibrated
    # scales"): checkpoints calibrated for fp8/int8 KV caches ship
    # per-layer k_scale/v_scale tensors (scalar or per-kv-head).  They
    # are collected into [L, Hkv] floors the quantized page cache uses
    # as the page-scale floor instead of pure amax; the runner pops
    # them off the pytree before the params reach any jitted program.
    import numpy as _np

    k_floors = _np.zeros((config.num_layers, config.num_kv_heads),
                         _np.float32)
    v_floors = _np.zeros_like(k_floors)
    saw_floors = False
    for i in range(config.num_layers):
        for which, dst in (("k_scale", k_floors), ("v_scale", v_floors)):
            name = f"model.layers.{i}.self_attn.{which}"
            if name not in raw:
                continue
            saw_floors = True
            val = _np.asarray(raw.pop(name), _np.float32).reshape(-1)
            # scalar broadcasts over heads; per-head vectors map 1:1
            dst[i, :] = (
                val[0] if val.size == 1 else val[: config.num_kv_heads]
            )
    if saw_floors:
        logger.info(
            "checkpoint carries calibrated k_scale/v_scale tensors: "
            "quantized KV pages will floor their page scales at the "
            "calibrated values (--kv-quantization)"
        )
        params["kv_scale_floors"] = (k_floors, v_floors)

    for i in range(config.num_layers):
        prefix = f"model.layers.{i}"
        layer = {
            "input_norm": take(f"{prefix}.input_layernorm.weight"),
            "post_attn_norm": take(f"{prefix}.post_attention_layernorm.weight"),
            "wq": take(f"{prefix}.self_attn.q_proj.weight", transpose=True),
            "wk": take(f"{prefix}.self_attn.k_proj.weight", transpose=True),
            "wv": take(f"{prefix}.self_attn.v_proj.weight", transpose=True),
            "wo": take(f"{prefix}.self_attn.o_proj.weight", transpose=True),
        }
        if getattr(config, "qk_norm", False):
            # qwen3 per-head-dim q/k RMSNorms
            layer["q_norm"] = take(f"{prefix}.self_attn.q_norm.weight")
            layer["k_norm"] = take(f"{prefix}.self_attn.k_norm.weight")
        if config.num_experts > 0:
            # mixtral: per-expert FFNs stacked into [E, ...] tensors
            # (w1=gate, w3=up, w2=down in HF naming); the stacked arrays
            # get their final mesh placement from shard_llama_params
            moe = f"{prefix}.block_sparse_moe"
            layer["router"] = take(f"{moe}.gate.weight", transpose=True)

            def stack(which: str, transpose: bool) -> jax.Array:
                return jnp.stack([
                    take(f"{moe}.experts.{e}.{which}.weight",
                         transpose=transpose)
                    for e in range(config.num_experts)
                ])

            layer["experts_gate"] = stack("w1", True)
            layer["experts_up"] = stack("w3", True)
            layer["experts_down"] = stack("w2", True)
        else:
            layer["w_gate"] = take(f"{prefix}.mlp.gate_proj.weight",
                                   transpose=True)
            layer["w_up"] = take(f"{prefix}.mlp.up_proj.weight",
                                 transpose=True)
            layer["w_down"] = take(f"{prefix}.mlp.down_proj.weight",
                                   transpose=True)
        if config.attention_bias:
            layer["bq"] = take(f"{prefix}.self_attn.q_proj.bias")
            layer["bk"] = take(f"{prefix}.self_attn.k_proj.bias")
            layer["bv"] = take(f"{prefix}.self_attn.v_proj.bias")
        params["layers"].append(layer)

    ignored = [n for n in raw.remaining() if "rotary_emb" not in n]
    if ignored:
        logger.warning("ignored %d unexpected checkpoint tensors: %s",
                       len(ignored), ignored[:5])
    return params


def load_opt_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """OPT checkpoint → the shared decoder param pytree.

    HF OPT names: ``model.decoder.layers.N.self_attn.{q,k,v,out}_proj``,
    ``fc1``/``fc2``, ``self_attn_layer_norm`` (pre-attention LN) and the
    confusingly-named per-layer ``final_layer_norm`` (pre-MLP LN), plus a
    decoder-level ``final_layer_norm`` and the offset-by-2
    ``embed_positions`` table.  Some exports drop the ``model.`` prefix;
    both spellings are accepted.
    """
    place = place or (lambda _name, x: x)
    dtype = config.dtype
    raw = open_checkpoint_index(config, model_path)

    def take(name: str, transpose: bool = False) -> jax.Array:
        for cand in (f"model.{name}", name):
            if cand in raw:
                x = _np_to_jnp(raw.pop(cand), dtype)
                if transpose:
                    x = x.T
                return place(cand, x)
        raise ValueError(f"checkpoint is missing tensor {name!r}")

    params: dict = {
        "embed": take("decoder.embed_tokens.weight"),
        "pos_embed": take("decoder.embed_positions.weight"),
        "final_norm": take("decoder.final_layer_norm.weight"),
        "final_norm_bias": take("decoder.final_layer_norm.bias"),
        "layers": [],
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = take("lm_head.weight", transpose=True)
    else:
        # tied exports often still materialise the duplicate tensor
        for cand in ("lm_head.weight", "model.lm_head.weight"):
            if cand in raw:
                raw.pop(cand)

    for i in range(config.num_layers):
        prefix = f"decoder.layers.{i}"
        layer = {
            "input_norm": take(f"{prefix}.self_attn_layer_norm.weight"),
            "input_norm_bias": take(f"{prefix}.self_attn_layer_norm.bias"),
            "post_attn_norm": take(f"{prefix}.final_layer_norm.weight"),
            "post_attn_norm_bias": take(f"{prefix}.final_layer_norm.bias"),
            "wq": take(f"{prefix}.self_attn.q_proj.weight", transpose=True),
            "wk": take(f"{prefix}.self_attn.k_proj.weight", transpose=True),
            "wv": take(f"{prefix}.self_attn.v_proj.weight", transpose=True),
            "wo": take(f"{prefix}.self_attn.out_proj.weight",
                       transpose=True),
            "w_up": take(f"{prefix}.fc1.weight", transpose=True),
            "w_down": take(f"{prefix}.fc2.weight", transpose=True),
        }
        if config.attention_bias:
            layer["bq"] = take(f"{prefix}.self_attn.q_proj.bias")
            layer["bk"] = take(f"{prefix}.self_attn.k_proj.bias")
            layer["bv"] = take(f"{prefix}.self_attn.v_proj.bias")
        if config.attention_out_bias:
            layer["bo"] = take(f"{prefix}.self_attn.out_proj.bias")
        if config.mlp_bias:
            layer["b_up"] = take(f"{prefix}.fc1.bias")
            layer["b_down"] = take(f"{prefix}.fc2.bias")
        params["layers"].append(layer)

    ignored = raw.remaining()
    if ignored:
        logger.warning("ignored %d unexpected checkpoint tensors: %s",
                       len(ignored), ignored[:5])
    return params


def _make_take(raw, dtype, place, prefixes):
    """Tensor lookup over alternative name prefixes; ``placed=False``
    returns the raw host array (for tensors that are re-laid-out before
    placement, e.g. fused QKV)."""

    def take(name: str, transpose: bool = False, placed: bool = True):
        for pre in prefixes:
            cand = pre + name
            if cand in raw:
                x = _np_to_jnp(raw.pop(cand), dtype)
                if transpose:
                    x = x.T
                return place(cand, x) if placed else x
        raise ValueError(f"checkpoint is missing tensor {name!r}")

    return take


def _split_fused_qkv(take, place, prefix: str, h: int, dh: int, d: int,
                     *, bias: bool) -> dict:
    """De-interleave a head-major fused ``[H·3·Dh, d]`` query_key_value
    tensor (the HF gpt_neox AND bloom layout: each head's q, k, v rows
    adjacent) into per-projection ``[in, out]`` matrices, placed under
    q/k/v_proj alias names so the standard Megatron column-parallel
    specs apply (parallel/sharding.py suffix table)."""
    out = {}
    fused_w = take(
        f"{prefix}.query_key_value.weight", placed=False
    ).reshape(h, 3, dh, d)
    for j, proj in enumerate(("q", "k", "v")):
        out[f"w{proj}"] = place(
            f"{prefix}.{proj}_proj.weight",
            fused_w[:, j].reshape(h * dh, d).T,
        )
    if bias:
        fused_b = take(
            f"{prefix}.query_key_value.bias", placed=False
        ).reshape(h, 3, dh)
        for j, proj in enumerate(("q", "k", "v")):
            out[f"b{proj}"] = place(
                f"{prefix}.{proj}_proj.bias",
                fused_b[:, j].reshape(h * dh),
            )
    return out


def load_gpt_neox_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """GPT-NeoX / Pythia checkpoint → the shared decoder param pytree.

    The attention projection ships FUSED and head-interleaved:
    ``query_key_value.weight`` is ``[H·3·Dh, d]`` with each head's q, k,
    v rows adjacent.  De-interleave to per-projection matrices BEFORE
    mesh placement, so the split tensors land with the standard Megatron
    column-parallel specs (placed under q/k/v_proj alias names, matching
    parallel/sharding.py's suffix table).
    """
    place = place or (lambda _name, x: x)
    raw = open_checkpoint_index(config, model_path)
    h, dh, d = config.num_heads, config.head_dim, config.hidden_size
    take = _make_take(raw, config.dtype, place, ("",))

    params: dict = {
        "embed": take("gpt_neox.embed_in.weight"),
        "final_norm": take("gpt_neox.final_layer_norm.weight"),
        "final_norm_bias": take("gpt_neox.final_layer_norm.bias"),
        "layers": [],
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = take("embed_out.weight", transpose=True)

    for i in range(config.num_layers):
        prefix = f"gpt_neox.layers.{i}"
        layer = {
            "input_norm": take(f"{prefix}.input_layernorm.weight"),
            "input_norm_bias": take(f"{prefix}.input_layernorm.bias"),
            "post_attn_norm": take(
                f"{prefix}.post_attention_layernorm.weight"
            ),
            "post_attn_norm_bias": take(
                f"{prefix}.post_attention_layernorm.bias"
            ),
            "wo": take(f"{prefix}.attention.dense.weight", transpose=True),
            "bo": take(f"{prefix}.attention.dense.bias"),
            "w_up": take(f"{prefix}.mlp.dense_h_to_4h.weight",
                         transpose=True),
            "b_up": take(f"{prefix}.mlp.dense_h_to_4h.bias"),
            "w_down": take(f"{prefix}.mlp.dense_4h_to_h.weight",
                           transpose=True),
            "b_down": take(f"{prefix}.mlp.dense_4h_to_h.bias"),
        }
        layer |= _split_fused_qkv(
            take, place, f"{prefix}.attention", h, dh, d,
            bias=config.attention_bias,
        )
        params["layers"].append(layer)

    # attention.bias / masked_bias are HF's precomputed causal-mask
    # buffers, not weights
    ignored = [
        n for n in raw.remaining()
        if "rotary_emb" not in n
        and not n.endswith(("attention.bias", "attention.masked_bias"))
    ]
    if ignored:
        logger.warning("ignored %d unexpected checkpoint tensors: %s",
                       len(ignored), ignored[:5])
    return params


def load_bloom_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """BLOOM checkpoint → the shared decoder param pytree.

    Layers live under ``h.{i}`` with the same fused head-interleaved
    ``query_key_value`` layout as GPT-NeoX (``[H·3·Dh, d]``, each head's
    q/k/v rows adjacent — HF BloomAttention._split_heads), de-interleaved
    before placement under q/k/v_proj alias names.  A LayerNorm sits
    directly on the embeddings (``word_embeddings_layernorm``); the head
    is tied.  Both bare and ``transformer.``-prefixed exports load.
    """
    place = place or (lambda _name, x: x)
    raw = open_checkpoint_index(config, model_path)
    h, dh, d = config.num_heads, config.head_dim, config.hidden_size
    take = _make_take(raw, config.dtype, place, ("", "transformer."))

    params: dict = {
        "embed": take("word_embeddings.weight"),
        "embed_norm": take("word_embeddings_layernorm.weight"),
        "embed_norm_bias": take("word_embeddings_layernorm.bias"),
        "final_norm": take("ln_f.weight"),
        "final_norm_bias": take("ln_f.bias"),
        "layers": [],
    }
    for cand in ("lm_head.weight",):  # tied; drop duplicate exports
        if cand in raw:
            raw.pop(cand)

    for i in range(config.num_layers):
        prefix = f"h.{i}"
        layer = {
            "input_norm": take(f"{prefix}.input_layernorm.weight"),
            "input_norm_bias": take(f"{prefix}.input_layernorm.bias"),
            "post_attn_norm": take(
                f"{prefix}.post_attention_layernorm.weight"
            ),
            "post_attn_norm_bias": take(
                f"{prefix}.post_attention_layernorm.bias"
            ),
            "wo": take(f"{prefix}.self_attention.dense.weight",
                       transpose=True),
            "bo": take(f"{prefix}.self_attention.dense.bias"),
            "w_up": take(f"{prefix}.mlp.dense_h_to_4h.weight",
                         transpose=True),
            "b_up": take(f"{prefix}.mlp.dense_h_to_4h.bias"),
            "w_down": take(f"{prefix}.mlp.dense_4h_to_h.weight",
                           transpose=True),
            "b_down": take(f"{prefix}.mlp.dense_4h_to_h.bias"),
        }
        layer |= _split_fused_qkv(
            take, place, f"{prefix}.self_attention", h, dh, d, bias=True,
        )
        params["layers"].append(layer)

    ignored = raw.remaining()
    if ignored:
        logger.warning("ignored %d unexpected checkpoint tensors: %s",
                       len(ignored), ignored[:5])
    return params


def load_gpt2_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """GPT-2 checkpoint → the shared decoder param pytree.

    HF GPT-2 stores projections as Conv1D — already ``[in, out]``, so no
    transpose anywhere.  ``attn.c_attn.weight`` is the fused ``[d, 3d]``
    projection whose COLUMNS split into plain q|k|v thirds (heads are
    contiguous within each third, unlike the neox/bloom per-head
    interleave).  ``wpe`` is the learned position table (no offset);
    the head is tied to ``wte``.  Both bare and ``transformer.``-prefixed
    exports load.
    """
    place = place or (lambda _name, x: x)
    raw = open_checkpoint_index(config, model_path)
    d = config.hidden_size
    take = _make_take(raw, config.dtype, place, ("", "transformer."))

    params: dict = {
        "embed": take("wte.weight"),
        "pos_embed": take("wpe.weight"),
        "final_norm": take("ln_f.weight"),
        "final_norm_bias": take("ln_f.bias"),
        "layers": [],
    }
    for cand in ("lm_head.weight",):  # tied; drop duplicate exports
        if cand in raw:
            raw.pop(cand)

    for i in range(config.num_layers):
        prefix = f"h.{i}"
        fused_w = take(f"{prefix}.attn.c_attn.weight", placed=False)
        fused_b = take(f"{prefix}.attn.c_attn.bias", placed=False)
        layer = {
            "input_norm": take(f"{prefix}.ln_1.weight"),
            "input_norm_bias": take(f"{prefix}.ln_1.bias"),
            "post_attn_norm": take(f"{prefix}.ln_2.weight"),
            "post_attn_norm_bias": take(f"{prefix}.ln_2.bias"),
            "wo": take(f"{prefix}.attn.c_proj.weight"),
            "bo": take(f"{prefix}.attn.c_proj.bias"),
            "w_up": take(f"{prefix}.mlp.c_fc.weight"),
            "b_up": take(f"{prefix}.mlp.c_fc.bias"),
            "w_down": take(f"{prefix}.mlp.c_proj.weight"),
            "b_down": take(f"{prefix}.mlp.c_proj.bias"),
        }
        for j, proj in enumerate(("q", "k", "v")):
            layer[f"w{proj}"] = place(
                f"{prefix}.{proj}_proj.weight",
                fused_w[:, j * d:(j + 1) * d],
            )
            layer[f"b{proj}"] = place(
                f"{prefix}.{proj}_proj.bias",
                fused_b[j * d:(j + 1) * d],
            )
        params["layers"].append(layer)

    ignored = [n for n in raw.remaining()
               if not n.endswith(("attn.bias", "attn.masked_bias"))]
    if ignored:
        logger.warning("ignored %d unexpected checkpoint tensors: %s",
                       len(ignored), ignored[:5])
    return params


def load_phi3_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """Phi-3 checkpoint → the shared decoder param pytree.

    Llama block chemistry with two FUSED projections: ``qkv_proj`` is
    ``[(H+2·Hkv)·Dh, d]`` with q, k, v stacked as contiguous ROW slices
    (not per-head interleaved like neox/bloom), and ``gate_up_proj`` is
    ``[2f, d]`` with gate on top of up.  Both split before placement so
    the standard Megatron column-parallel specs apply.
    """
    place = place or (lambda _name, x: x)
    raw = open_checkpoint_index(config, model_path)
    h, hkv, dh = config.num_heads, config.num_kv_heads, config.head_dim
    f = config.intermediate_size
    take = _make_take(raw, config.dtype, place, ("",))

    params: dict = {
        "embed": take("model.embed_tokens.weight"),
        "final_norm": take("model.norm.weight"),
        "layers": [],
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = take("lm_head.weight", transpose=True)
    elif "lm_head.weight" in raw:
        raw.pop("lm_head.weight")

    for i in range(config.num_layers):
        prefix = f"model.layers.{i}"
        fused_qkv = take(f"{prefix}.self_attn.qkv_proj.weight",
                         placed=False)
        fused_gu = take(f"{prefix}.mlp.gate_up_proj.weight", placed=False)
        q_rows, kv_rows = h * dh, hkv * dh
        layer = {
            "input_norm": take(f"{prefix}.input_layernorm.weight"),
            "post_attn_norm": take(
                f"{prefix}.post_attention_layernorm.weight"
            ),
            "wq": place(f"{prefix}.self_attn.q_proj.weight",
                        fused_qkv[:q_rows].T),
            "wk": place(f"{prefix}.self_attn.k_proj.weight",
                        fused_qkv[q_rows : q_rows + kv_rows].T),
            "wv": place(f"{prefix}.self_attn.v_proj.weight",
                        fused_qkv[q_rows + kv_rows :].T),
            "wo": take(f"{prefix}.self_attn.o_proj.weight",
                       transpose=True),
            "w_gate": place(f"{prefix}.mlp.gate_proj.weight",
                            fused_gu[:f].T),
            "w_up": place(f"{prefix}.mlp.up_proj.weight", fused_gu[f:].T),
            "w_down": take(f"{prefix}.mlp.down_proj.weight",
                           transpose=True),
        }
        params["layers"].append(layer)

    ignored = [n for n in raw.remaining() if "rotary_emb" not in n]
    if ignored:
        logger.warning("ignored %d unexpected checkpoint tensors: %s",
                       len(ignored), ignored[:5])
    return params


#: Hugging Face ``falcon_h1`` tensor names of one layer (after
#: ``model.layers.<i>.``) -> (pytree key, transpose to [in, out])
FALCON_H1_LAYER_TENSORS = {
    "input_layernorm.weight": ("input_norm", False),
    "pre_ff_layernorm.weight": ("pre_ff_norm", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "mamba.in_proj.weight": ("in_proj", True),
    "mamba.conv1d.weight": ("conv_w", False),  # [C, 1, taps], reshaped
    "mamba.conv1d.bias": ("conv_b", False),
    "mamba.dt_bias": ("dt_bias", False),
    "mamba.A_log": ("a_log", False),
    "mamba.D": ("d", False),
    "mamba.norm.weight": ("mixer_norm", False),
    "mamba.out_proj.weight": ("out_proj", True),
    "feed_forward.gate_proj.weight": ("w_gate", True),
    "feed_forward.up_proj.weight": ("w_up", True),
    "feed_forward.down_proj.weight": ("w_down", True),
}


def falcon_h1_tensor_names(config: "ModelConfig") -> list[str]:
    """Every checkpoint tensor ``load_falcon_h1_params`` asks for."""
    names = ["model.embed_tokens.weight", "model.final_layernorm.weight"]
    if not config.tie_word_embeddings:
        names.append("lm_head.weight")
    for i in range(config.num_layers):
        names += [
            f"model.layers.{i}.{name}"
            for name in FALCON_H1_LAYER_TENSORS
            if config.mixer.rms_norm or name != "mamba.norm.weight"
        ]
    return names


def load_falcon_h1_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """Build the FalconH1ForCausalLM param pytree (models/falcon_h1.py)
    from a Hugging Face ``falcon_h1`` checkpoint: projections transposed
    to ``[in, out]``, the depthwise convolution's ``[channels, 1, taps]``
    weight to ``[taps, channels]``."""
    raw = open_checkpoint_index(config, model_path)
    place = place or (lambda _n, x: x)
    take = _make_take(raw, config.dtype, place, ("",))
    # the scan's per-head scalars stay as precise as the checkpoint has
    # them: A_log and dt_bias pass through an exp
    take_f32 = _make_take(raw, jnp.float32, place, ("",))
    embed = take("model.embed_tokens.weight")
    params: dict = {
        "embed": embed,
        "final_norm": take("model.final_layernorm.weight"),
        "lm_head": (
            embed.T if config.tie_word_embeddings
            else take("lm_head.weight", transpose=True)
        ),
        "layers": [],
    }
    for i in range(config.num_layers):
        layer = {}
        for name, (key, transpose) in FALCON_H1_LAYER_TENSORS.items():
            if key == "mixer_norm" and not config.mixer.rms_norm:
                continue
            get = take_f32 if key in ("a_log", "dt_bias", "d") else take
            x = get(f"model.layers.{i}.{name}", transpose=transpose)
            if key == "conv_w":
                x = x[:, 0, :].T
            layer[key] = x
        params["layers"].append(layer)
    ignored = raw.remaining()
    if ignored:
        logger.warning("ignored %d unexpected checkpoint tensors: %s",
                       len(ignored), ignored[:5])
    return params


#: Hugging Face ``deepseek_v2`` tensor names of one layer's attention and
#: norms (after ``model.layers.<i>.``) -> (pytree key, transpose to
#: [in, out]); ``kv_b_proj`` is split a head into the absorbed form's
#: ``w_kc`` and ``w_vc`` (models/deepseek_v2.py)
DEEPSEEK_V2_LAYER_TENSORS = {
    "input_layernorm.weight": ("input_norm", False),
    "post_attention_layernorm.weight": ("post_norm", False),
    "self_attn.q_a_proj.weight": ("q_a", True),
    "self_attn.q_a_layernorm.weight": ("q_a_norm", False),
    "self_attn.q_b_proj.weight": ("q_b", True),
    "self_attn.kv_a_proj_with_mqa.weight": ("kv_a", True),
    "self_attn.kv_a_layernorm.weight": ("kv_a_norm", False),
    "self_attn.kv_b_proj.weight": ("kv_b", False),
    "self_attn.o_proj.weight": ("wo", True),
}
_SWIGLU = (("gate_proj", "w_gate"), ("up_proj", "w_up"),
           ("down_proj", "w_down"))


def deepseek_v2_tensor_names(config: "ModelConfig") -> list[str]:
    """Every checkpoint tensor ``load_deepseek_v2_params`` asks for: of
    an expert layer's routed experts, the held ones under their
    published indices."""
    ex = config.experts
    names = ["model.embed_tokens.weight", "model.norm.weight"]
    if not config.tie_word_embeddings:
        names.append("lm_head.weight")
    for i in range(config.num_layers):
        p = f"model.layers.{i}"
        names += [f"{p}.{name}" for name in DEEPSEEK_V2_LAYER_TENSORS]
        if i < ex.first_dense_layers:
            names += [f"{p}.mlp.{hf}.weight" for hf, _ in _SWIGLU]
            continue
        names.append(f"{p}.mlp.gate.weight")
        names += [f"{p}.mlp.shared_experts.{hf}.weight" for hf, _ in _SWIGLU]
        names += [f"{p}.mlp.experts.{e}.{hf}.weight"
                  for e in range(*ex.held) for hf, _ in _SWIGLU]
    return names


def load_deepseek_v2_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """Build the DeepseekV2ForCausalLM param pytree
    (models/deepseek_v2.py) from a Hugging Face ``deepseek_v2``
    checkpoint: projections transposed to ``[in, out]``; ``kv_b_proj``
    split a head into ``w_kc [H, nope, rank]`` and ``w_vc [H, rank, v]``
    (the absorbed form); the rotary columns of ``q_b_proj`` and
    ``kv_a_proj_with_mqa`` regrouped from interleaved pairs to halves
    here, once, as the published code regroups them at every call; the
    held experts' matrices stacked by expert."""
    raw = open_checkpoint_index(config, model_path)
    place = place or (lambda _n, x: x)
    take = _make_take(raw, config.dtype, place, ("",))
    mla, ex = config.mla, config.experts
    h, nope, rope_dim = (config.num_heads, mla.qk_nope_head_dim,
                         mla.qk_rope_head_dim)
    halves = jnp.concatenate([jnp.arange(0, rope_dim, 2),
                              jnp.arange(1, rope_dim, 2)])
    embed = take("model.embed_tokens.weight")
    params: dict = {
        "embed": embed,
        "final_norm": take("model.norm.weight"),
        "lm_head": (
            embed.T if config.tie_word_embeddings
            else take("lm_head.weight", transpose=True)
        ),
        "layers": [],
    }
    for i in range(config.num_layers):
        p = f"model.layers.{i}"
        layer = {
            key: take(f"{p}.{name}", transpose=transpose)
            for name, (key, transpose) in DEEPSEEK_V2_LAYER_TENSORS.items()
        }
        q_b = layer["q_b"].reshape(-1, h, nope + rope_dim)
        layer["q_b"] = jnp.concatenate(
            [q_b[..., :nope], q_b[..., nope:][..., halves]], axis=-1
        ).reshape(layer["q_b"].shape)
        kv_a = layer["kv_a"]
        layer["kv_a"] = jnp.concatenate(
            [kv_a[:, :mla.kv_lora_rank],
             kv_a[:, mla.kv_lora_rank:][:, halves]], axis=-1)
        kv_b = layer.pop("kv_b").reshape(
            h, nope + mla.v_head_dim, mla.kv_lora_rank)
        layer["w_kc"] = kv_b[:, :nope]
        layer["w_vc"] = jnp.swapaxes(kv_b[:, nope:], 1, 2)
        if i < ex.first_dense_layers:
            mlp = f"{p}.mlp"
        else:
            mlp = f"{p}.mlp.shared_experts"
            layer["router"] = take(f"{p}.mlp.gate.weight", transpose=True)
            for hf, key in _SWIGLU:
                layer[f"experts_{key[2:]}"] = jnp.stack([
                    take(f"{p}.mlp.experts.{e}.{hf}.weight", transpose=True)
                    for e in range(*ex.held)
                ])
        for hf, key in _SWIGLU:
            layer[key] = take(f"{mlp}.{hf}.weight", transpose=True)
        params["layers"].append(layer)
    ignored = raw.remaining()
    if ignored:
        logger.warning("ignored %d unexpected checkpoint tensors: %s",
                       len(ignored), ignored[:5])
    return params


def cohere2_moe_tensor_names(config: "ModelConfig") -> list[str]:
    """Every checkpoint tensor ``load_cohere2_moe_params`` asks for: of
    a layer's routed experts, the held ones under their published
    indices; the head is the embedding."""
    ex = config.experts
    names = ["model.embed_tokens.weight", "model.norm.weight"]
    for i in range(config.num_layers):
        p = f"model.layers.{i}"
        names.append(f"{p}.input_layernorm.weight")
        names += [f"{p}.self_attn.{n}_proj.weight" for n in "qkvo"]
        names.append(f"{p}.mlp.gate.weight")
        names += [f"{p}.mlp.shared_experts.{j}.{hf}.weight"
                  for j in range(ex.num_shared) for hf, _ in _SWIGLU]
        names += [f"{p}.mlp.experts.{e}.{hf}.weight"
                  for e in range(*ex.held) for hf, _ in _SWIGLU]
    return names


def load_cohere2_moe_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """Build the Cohere2MoeForCausalLM param pytree
    (models/cohere2_moe.py) from a ``cohere2_moe`` checkpoint:
    projections transposed to ``[in, out]``; in the layers that rotate,
    each head's columns of ``q_proj`` and ``k_proj`` regrouped from
    interleaved pairs to halves, once, so that the runtime rotates
    halves; the shared experts laid side by side as ONE SwiGLU (gate and
    up along the output axis, down along the input axis: its output is
    their sum); the held experts' matrices stacked by expert."""
    raw = open_checkpoint_index(config, model_path)
    place = place or (lambda _n, x: x)
    take = _make_take(raw, config.dtype, place, ("",))
    ex, dh = config.experts, config.head_dim
    halves = jnp.concatenate([jnp.arange(0, dh, 2), jnp.arange(1, dh, 2)])

    def regrouped(w: jax.Array) -> jax.Array:  # [d, heads * dh]
        return w.reshape(w.shape[0], -1, dh)[..., halves].reshape(w.shape)

    params: dict = {
        "embed": take("model.embed_tokens.weight"),
        "final_norm": take("model.norm.weight"),
        "layers": [],
    }
    for i in range(config.num_layers):
        p = f"model.layers.{i}"
        layer = {
            "input_norm": take(f"{p}.input_layernorm.weight"),
            "router": take(f"{p}.mlp.gate.weight", transpose=True),
        }
        for n in "qkvo":
            layer[f"w{n}"] = take(f"{p}.self_attn.{n}_proj.weight",
                                  transpose=True)
        if config.layer_rotates(i):
            layer["wq"] = regrouped(layer["wq"])
            layer["wk"] = regrouped(layer["wk"])
        for hf, key in _SWIGLU:
            layer[f"experts_{key[2:]}"] = jnp.stack([
                take(f"{p}.mlp.experts.{e}.{hf}.weight", transpose=True)
                for e in range(*ex.held)
            ])
            layer[key] = jnp.concatenate([
                take(f"{p}.mlp.shared_experts.{j}.{hf}.weight",
                     transpose=True)
                for j in range(ex.num_shared)
            ], axis=0 if hf == "down_proj" else 1)
        params["layers"].append(layer)
    ignored = raw.remaining()
    if ignored:
        logger.warning("ignored %d unexpected checkpoint tensors: %s",
                       len(ignored), ignored[:5])
    return params


def load_model_params(
    config: "ModelConfig",
    model_path: str,
    place: Optional[PlaceFn] = None,
) -> dict:
    """Dispatch to the checkpoint layout for ``config.model_type``."""
    if config.model_type == "deepseek_v2":
        return load_deepseek_v2_params(config, model_path, place)
    if config.model_type == "cohere2_moe":
        return load_cohere2_moe_params(config, model_path, place)
    if config.model_type == "falcon_h1":
        return load_falcon_h1_params(config, model_path, place)
    if config.model_type == "opt":
        return load_opt_params(config, model_path, place)
    if config.model_type == "gpt_neox":
        return load_gpt_neox_params(config, model_path, place)
    if config.model_type == "bloom":
        return load_bloom_params(config, model_path, place)
    if config.model_type == "gpt2":
        return load_gpt2_params(config, model_path, place)
    if config.model_type == "phi3":
        return load_phi3_params(config, model_path, place)
    return load_llama_params(config, model_path, place)


# ------------------------------------------------------------- quantization

# Per-layer 2-D projection weights eligible for weight-only int8 (the
# decode-phase HBM bandwidth dominators).  Embeddings, lm_head, norms,
# biases and the mixtral expert stacks stay in the model dtype: the first
# two feed gather/logits numerics, the rest are small.
INT8_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _quantize_int8(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-output-channel int8: w ≈ q8 · scale[out].

    The scale factors out of the contraction over the in dim, so
    ``(x @ q8) * scale`` reproduces ``x @ w`` exactly up to the rounding
    step — the standard weight-only scheme the reference gets from
    vLLM's quantization engine (consumed via
    /root/reference/src/vllm_tgis_adapter/tgis_utils/args.py:127-136).
    """
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_params_int8(params: dict) -> dict:
    """Replace eligible projection leaves with ``{key}_q8`` + ``{key}_scale``
    pairs (models/llama.py ``linear`` consumes either representation).

    Runs after (possibly sharded) load: each int8 leaf keeps its source
    weight's mesh placement, and the [out] scale vector takes the
    weight's out-axis spec, so Megatron TP semantics are unchanged.
    Memory drops ~2× (bf16) / ~4× (f32) for the quantized leaves, and
    the KV-pool auto-sizing (kv_cache.resolve_num_blocks) sees the
    savings because it reads free HBM after weights are resident.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    for layer in params.get("layers", []):
        for key in INT8_QUANT_KEYS:
            w = layer.pop(key, None)
            if w is None:
                continue
            q, scale = _quantize_int8(w)
            sh = getattr(w, "sharding", None)
            if isinstance(sh, NamedSharding):
                q = jax.device_put(q, sh)
                out_axis = sh.spec[1] if len(sh.spec) > 1 else None
                scale = jax.device_put(
                    scale, NamedSharding(sh.mesh, PartitionSpec(out_axis))
                )
            layer[key + "_q8"] = q
            layer[key + "_scale"] = scale
    return params
