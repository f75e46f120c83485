"""Sharding rules: llama param pytree + paged KV cache onto the mesh.

Megatron-style tensor parallelism expressed as ``PartitionSpec`` leaves —
the XLA SPMD partitioner turns these into the same comm pattern the
reference stack gets from hand-written NCCL calls inside vLLM (one
all-reduce after attention-out and one after mlp-down per layer):

* wq/wk/wv ``[d, H·Dh]``: column-parallel (heads split across tp)
* wo ``[H·Dh, d]``: row-parallel → psum of partial sums
* w_gate/w_up ``[d, f]``: column-parallel; w_down ``[f, d]``: row-parallel
* embed ``[V, d]``: vocab-parallel; lm_head ``[d, V]``: column-parallel
  (logits arrive vocab-sharded; the sampler's reductions gather them)
* KV cache ``[L, slots, Hkv, Dh]``: head-sharded — each tp shard holds the
  pages for its own kv heads, so paged reads/writes are shard-local
* norms / biases on the hidden dim: replicated

No activation specs are needed: annotating the params is enough for the
partitioner to propagate Megatron sharding through the whole step fn.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vllm_tgis_adapter_tpu.parallel.mesh import TP_AXIS

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.config import ModelConfig


def validate_tp_divisibility(config: "ModelConfig", tp: int) -> None:
    """Fail fast (like vLLM's engine-boot check) when tp can't split the model."""
    problems = []
    if config.num_heads % tp:
        problems.append(f"num_heads={config.num_heads}")
    if config.num_kv_heads % tp:
        problems.append(f"num_kv_heads={config.num_kv_heads}")
    expert_parallel = config.num_experts > 0 and config.num_experts % tp == 0
    if not expert_parallel and config.intermediate_size % tp:
        # MoE models whose expert count divides tp shard the EXPERT axis
        # instead of the ffn dim, so the ffn constraint doesn't apply
        problems.append(f"intermediate_size={config.intermediate_size}")
    # an indivisible vocabulary (granite's 49155 is odd) is not a
    # problem: llama_param_specs then keeps embed / lm_head whole on
    # every device
    if problems:
        raise ValueError(
            f"tensor_parallel_size={tp} does not divide "
            + ", ".join(problems)
        )


_LAYER_SPECS = {
    "input_norm": P(None),
    "input_norm_bias": P(None),
    "post_attn_norm": P(None),
    "post_attn_norm_bias": P(None),
    "wq": P(None, TP_AXIS),
    "wk": P(None, TP_AXIS),
    "wv": P(None, TP_AXIS),
    "wo": P(TP_AXIS, None),
    "w_gate": P(None, TP_AXIS),
    "w_up": P(None, TP_AXIS),
    "w_down": P(TP_AXIS, None),
    "bq": P(TP_AXIS),
    "bk": P(TP_AXIS),
    "bv": P(TP_AXIS),
    # qwen3 per-head-dim q/k norms: [head_dim] vectors, replicated
    "q_norm": P(None),
    "k_norm": P(None),
    # row-parallel output biases: replicated, added once after the psum
    "bo": P(None),
    "b_down": P(None),
    # column-parallel fc1 bias follows its weight's tp split
    "b_up": P(TP_AXIS),
    "router": P(None, None),
}

# mixtral MoE expert stacks [E, ...]: EXPERT-parallel when tp divides E
# (each shard computes its local experts over all tokens; the dense
# routing sum becomes a psum the partitioner merges with the layer's
# existing output all-reduce), else Megatron-style within-expert ffn
# sharding on the trailing dims
_EXPERT_EP_SPECS = {
    "experts_gate": P(TP_AXIS, None, None),
    "experts_up": P(TP_AXIS, None, None),
    "experts_down": P(TP_AXIS, None, None),
}
_EXPERT_FFN_SPECS = {
    "experts_gate": P(None, None, TP_AXIS),
    "experts_up": P(None, None, TP_AXIS),
    "experts_down": P(None, TP_AXIS, None),
}


def _whole_if_indivisible(spec: P, shape: tuple, tp: int) -> P:
    """``spec`` with every axis that tp does not divide kept whole: a
    vocabulary that does not split tp ways (granite's 49155 is odd)
    replicates its table (0.2 GB for granite-2b) instead of refusing
    the model."""
    return P(*(
        axis if axis is None or dim % tp == 0 else None
        for axis, dim in zip(spec, shape)
    ))


def llama_param_specs(params: dict, tp: int = 1) -> dict:
    """PartitionSpec pytree matching models/llama.py's param layout."""
    # emit a spec for exactly the keys present: pipeline stages carry
    # partial trees (embed on stage 0 only, final norm / lm_head on the
    # last), and tree.map requires identical dict structure
    top_specs = {
        "embed": P(TP_AXIS, None),
        "final_norm": P(None),
        "final_norm_bias": P(None),
        "embed_norm": P(None),
        "embed_norm_bias": P(None),
        # tiny table (max_len rows); replicate rather than shard
        "pos_embed": P(None, None),
        "lm_head": P(None, TP_AXIS),
    }
    specs: dict = {
        name: top_specs[name] for name in params if name != "layers"
    }
    for name in ("embed", "lm_head"):
        if name in params:
            specs[name] = _whole_if_indivisible(
                specs[name], params[name].shape, tp
            )

    def layer_spec(layer: dict) -> dict:
        expert_specs = _EXPERT_FFN_SPECS
        if "experts_gate" in layer:
            num_experts = layer["experts_gate"].shape[0]
            if tp > 1 and num_experts % tp == 0:
                expert_specs = _EXPERT_EP_SPECS

        def spec_of(name: str) -> P:
            # weight-only int8 leaves (engine/weights.py): the q8 matrix
            # keeps its source weight's spec; the [out] scale vector
            # follows the weight's out axis (tp-split for column-parallel
            # weights, replicated for row-parallel ones)
            if name.endswith("_q8"):
                name = name[: -len("_q8")]
            elif name.endswith("_scale"):
                base = _LAYER_SPECS[name[: -len("_scale")]]
                return P(base[1] if len(base) > 1 else None)
            return expert_specs.get(name) or _LAYER_SPECS[name]

        return {name: spec_of(name) for name in layer}

    specs["layers"] = [layer_spec(layer) for layer in params["layers"]]
    return specs


def shard_llama_params(mesh: Mesh, params: dict) -> dict:
    """device_put every leaf onto the mesh with its Megatron spec.

    (tree.map uses ``params``' structure, so the PartitionSpec leaves of
    ``specs`` are passed through whole — they are never flattened even
    though PartitionSpec subclasses tuple.)
    """
    specs = llama_param_specs(params, tp=mesh.shape[TP_AXIS])
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        params,
        specs,
    )


# HF checkpoint-name → spec for shard-on-load (engine/weights.py PlaceFn):
# names seen AFTER the loader's transpose to [in, out] orientation.
_HF_NAME_SPECS = (
    ("embed_tokens.weight", P(TP_AXIS, None)),
    ("lm_head.weight", P(None, TP_AXIS)),
    ("q_proj.weight", P(None, TP_AXIS)),
    ("k_proj.weight", P(None, TP_AXIS)),
    ("v_proj.weight", P(None, TP_AXIS)),
    ("o_proj.weight", P(TP_AXIS, None)),
    ("gate_proj.weight", P(None, TP_AXIS)),
    ("up_proj.weight", P(None, TP_AXIS)),
    ("down_proj.weight", P(TP_AXIS, None)),
    ("q_proj.bias", P(TP_AXIS)),
    ("k_proj.bias", P(TP_AXIS)),
    ("v_proj.bias", P(TP_AXIS)),
    # mixtral per-expert FFNs (w1=gate, w3=up: column-parallel; w2=down:
    # row-parallel after the loader's transpose).  Sharding each expert
    # tensor as it is read keeps the anti-OOM invariant for the model
    # family with the LARGEST weights; shard_llama_params may later
    # redistribute the stacked [E, ...] arrays onto the expert axis (EP)
    ("w1.weight", P(None, TP_AXIS)),
    ("w3.weight", P(None, TP_AXIS)),
    ("w2.weight", P(TP_AXIS, None)),
    # OPT lineage: out_proj/fc1/fc2 + biases, learned position table
    ("out_proj.weight", P(TP_AXIS, None)),
    ("out_proj.bias", P()),
    ("fc1.weight", P(None, TP_AXIS)),
    ("fc1.bias", P(TP_AXIS)),
    ("fc2.weight", P(TP_AXIS, None)),
    ("fc2.bias", P()),
    ("embed_positions.weight", P()),
    # gpt_neox lineage: attention.dense (row-parallel out), h_to_4h
    # (column) / 4h_to_h (row) MLP, vocab-parallel embed_in, and
    # embed_out placed post-transpose like lm_head
    ("attention.dense.weight", P(TP_AXIS, None)),
    ("attention.dense.bias", P()),
    ("dense_h_to_4h.weight", P(None, TP_AXIS)),
    ("dense_h_to_4h.bias", P(TP_AXIS)),
    ("dense_4h_to_h.weight", P(TP_AXIS, None)),
    ("dense_4h_to_h.bias", P()),
    ("embed_in.weight", P(TP_AXIS, None)),
    ("embed_out.weight", P(None, TP_AXIS)),
    # bloom: vocab-parallel embeddings, replicated final norm (the
    # generic norm.weight/bias suffixes catch the layernorms)
    ("word_embeddings.weight", P(TP_AXIS, None)),
    ("ln_f.weight", P(None)),
    ("ln_f.bias", P(None)),
    # gpt2: Conv1D (already [in, out]); attn/mlp c_proj are both
    # row-parallel, c_fc column-parallel, wte vocab-parallel, wpe + the
    # ln_1/ln_2 norms replicate via the default P()
    ("c_proj.weight", P(TP_AXIS, None)),
    ("c_proj.bias", P()),
    ("c_fc.weight", P(None, TP_AXIS)),
    ("c_fc.bias", P(TP_AXIS)),
    ("wte.weight", P(TP_AXIS, None)),
    ("norm.weight", P(None)),
    ("norm.bias", P(None)),
    ("layernorm.weight", P(None)),
)


def hf_name_spec(name: str) -> P:
    for suffix, spec in _HF_NAME_SPECS:
        if name.endswith(suffix):
            return spec
    return P()


def make_place_fn(mesh: Mesh):
    """PlaceFn for the weight loader: shard each tensor onto the mesh as it
    is read, so no device ever materialises the full unsharded model
    (70B-class models exceed one chip's HBM — sharding after a full load
    would OOM device 0)."""

    tp = mesh.shape[TP_AXIS]

    def place(name: str, x: jax.Array) -> jax.Array:
        # validate_tp_divisibility vouches for every axis but the
        # vocabulary's
        spec = _whole_if_indivisible(hf_name_spec(name), x.shape, tp)
        return jax.device_put(x, NamedSharding(mesh, spec))

    return place


def cache_sharding(mesh: Mesh) -> NamedSharding:
    """KV cache ``[L, Hkv, slots, Dh]``: shard the kv-head axis on tp."""
    return NamedSharding(mesh, P(None, TP_AXIS, None, None))


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Replicated placement for host-built step inputs (token ids, tables).

    Data-parallel batch sharding will split these on the dp axis; with a
    single engine replica they are replicated so every tp shard sees the
    full batch.
    """
    return NamedSharding(mesh, P())
