"""Model registry.

Maps HF ``model_type`` strings to TPU-native model implementations.  The
llama decoder skeleton covers the whole flagship lineage the reference
stack serves through vLLM (BASELINE.json configs: Llama-3, granite,
Mistral); architecture deltas (GQA ratio, biases, granite multipliers) are
data in ModelConfig, not code forks.
"""

from __future__ import annotations

from .cohere2_moe import Cohere2MoeForCausalLM
from .deepseek_v2 import DeepseekV2ForCausalLM
from .falcon_h1 import FalconH1ForCausalLM
from .llama import LlamaForCausalLM

_REGISTRY = {
    "llama": LlamaForCausalLM,
    "mistral": LlamaForCausalLM,
    "granite": LlamaForCausalLM,
    "qwen2": LlamaForCausalLM,
    # sparse-MoE variant of the same skeleton: layers carry a router +
    # stacked expert FFNs instead of one dense MLP (llama.py _moe_mlp)
    "mixtral": LlamaForCausalLM,
    # OPT lineage (BASELINE.json: opt-125m): learned positions,
    # pre-LayerNorm + biases, fc1/ReLU/fc2 — static config branches in
    # the same skeleton (config.py _from_opt_config)
    "opt": LlamaForCausalLM,
    # GPT-NeoX / Pythia: partial rotary, parallel attn+MLP residual,
    # fused-QKV checkpoints (config.py _from_gpt_neox_config)
    "gpt_neox": LlamaForCausalLM,
    # BLOOM (the original TGIS flagship): ALiBi position biases,
    # embedding LayerNorm, fused-QKV, tied head
    # (config.py _from_bloom_config)
    "bloom": LlamaForCausalLM,
    # GPT-2: learned positions (no offset), Conv1D fused c_attn split
    # into column thirds by the loader (config.py _from_gpt2_config)
    "gpt2": LlamaForCausalLM,
    # Gemma: GeGLU MLP, (1+w) RMSNorm folded into weights at load,
    # sqrt(hidden)-scaled embeddings, tied head (config.py from_hf_config)
    "gemma": LlamaForCausalLM,
    # Phi-3: llama block chemistry with fused qkv_proj / gate_up_proj
    # checkpoints split row-wise by the loader (weights.py
    # load_phi3_params); mini variants also carry a sliding window
    "phi3": LlamaForCausalLM,
    # Qwen3: qwen2 lineage plus per-head-dim q/k RMSNorms applied before
    # rotary (config.py qk_norm; llama.py _qkv)
    "qwen3": LlamaForCausalLM,
    # Falcon-H1: a Mamba-2 mixer beside attention in every layer; a
    # class of its own, with per-sequence state that is not KV pages
    # (models/falcon_h1.py, docs/STATE.md)
    "falcon_h1": FalconH1ForCausalLM,
    # DeepSeek-V2: latent attention over a pool of latent rows, and
    # group-routed experts of which the server holds a share; a class
    # of its own (models/deepseek_v2.py, docs/LATENT_ATTENTION.md)
    "deepseek_v2": DeepseekV2ForCausalLM,
    # Command A+: window and full attention layers in one stack over a
    # cache in two groups, sigmoid-routed experts of which the server
    # holds a share; a class of its own (models/cohere2_moe.py,
    # docs/ATTENTION.md)
    "cohere2_moe": Cohere2MoeForCausalLM,
}


def get_model_class(model_type: str):
    cls = _REGISTRY.get(model_type)
    if cls is None:
        supported = sorted(k for k, v in _REGISTRY.items() if v is not None)
        raise ValueError(
            f"model_type {model_type!r} is not supported yet; "
            f"supported: {supported}"
        )
    return cls
