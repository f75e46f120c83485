"""Deterministic tiny-model fixtures built offline (no network).

The reference's tests boot a real tiny HF model downloaded from the hub
(tests/conftest.py:85-89 in the reference); this environment has no network
egress, so we synthesise an equivalent: a 2-layer llama-architecture
checkpoint with a from-scratch byte-level BPE tokenizer, saved in standard
HF format so the whole load path (config.json → safetensors → tokenizer) is
exercised for real.
"""

from __future__ import annotations

import json
from pathlib import Path

_CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "hello world, this is a tiny test corpus for a tiny tokenizer",
    "The capital of France is Paris. The capital of Italy is Rome.",
    "0 1 2 3 4 5 6 7 8 9 10 11 12 13 14 15 16 17 18 19 20",
    "def main():\n    print('hello')\n    return 0\n",
    '{"name": "value", "list": [1, 2, 3], "flag": true}',
    "to be or not to be, that is the question",
    "pack my box with five dozen liquor jugs",
]

TINY_LLAMA_CONFIG = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "vocab_size": 512,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "max_position_embeddings": 512,
    "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "bos_token_id": 1,
    "eos_token_id": 2,
    "torch_dtype": "float32",
}


def hf_reference_model(model_dir: str, **kwargs):
    """Torch-side gold reference for numerical-parity tests (shared by
    test_model_correctness / test_opt / test_gpt_neox / sliding-window
    so HF loading settings cannot silently diverge between families).
    kwargs pass through (e.g. attn_implementation='eager', which the
    sliding-window tests need for HF to honor the band mask)."""
    import torch
    from transformers import AutoModelForCausalLM

    hf = AutoModelForCausalLM.from_pretrained(
        model_dir, torch_dtype=torch.float32, **kwargs
    )
    hf.eval()
    return hf


def build_tiny_mistral(path: str, seed: int = 0,
                       sliding_window: int | None = 8) -> str:
    """Tiny mistral-architecture checkpoint: llama tensor naming with a
    sliding-window config (the v0.1 lineage's distinguishing feature)."""
    build_tiny_llama(path, seed=seed)
    cfg = json.load(open(Path(path) / "config.json"))
    cfg["architectures"] = ["MistralForCausalLM"]
    cfg["model_type"] = "mistral"
    cfg["sliding_window"] = sliding_window
    with open(Path(path) / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)
    return path


def hf_tokenize(model_dir: str, text: str) -> list:
    from transformers import AutoTokenizer

    return AutoTokenizer.from_pretrained(model_dir)(text).input_ids


def build_tokenizer(path: str, vocab_size: int = 512):
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=vocab_size,
        special_tokens=["<unk>", "<s>", "</s>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False,
    )
    tok.train_from_iterator(_CORPUS, trainer=trainer)
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok,
        unk_token="<unk>",
        bos_token="<s>",
        eos_token="</s>",
        pad_token="</s>",
    )
    # pad the vocab to exactly vocab_size so every model id round-trips
    # through convert_ids_to_tokens (BPE training on the tiny corpus stops
    # short of the requested size)
    n_missing = vocab_size - len(fast)
    if n_missing > 0:
        fast.add_tokens([f"<filler_{i}>" for i in range(n_missing)])
    fast.save_pretrained(path)
    return fast


def write_llama_safetensors(path: str, *, vocab_size: int,
                            hidden_size: int, intermediate_size: int,
                            num_layers: int, num_heads: int,
                            num_kv_heads: int, head_dim: int,
                            seed: int = 0) -> None:
    """HF-format llama ``model.safetensors`` with seed-deterministic
    random weights, shaped by the given arch — the single source of the
    tensor-name layout the loader expects (engine/weights.py)."""
    import numpy as np
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    d = hidden_size
    dh = head_dim

    def w(shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w((vocab_size, d)),
        "model.norm.weight": np.ones(d, dtype=np.float32),
        "lm_head.weight": w((vocab_size, d)),
    }
    for i in range(num_layers):
        p = f"model.layers.{i}"
        tensors |= {
            f"{p}.input_layernorm.weight": np.ones(d, dtype=np.float32),
            f"{p}.post_attention_layernorm.weight": np.ones(d, dtype=np.float32),
            f"{p}.self_attn.q_proj.weight": w((num_heads * dh, d)),
            f"{p}.self_attn.k_proj.weight": w((num_kv_heads * dh, d)),
            f"{p}.self_attn.v_proj.weight": w((num_kv_heads * dh, d)),
            f"{p}.self_attn.o_proj.weight": w((d, num_heads * dh)),
            f"{p}.mlp.gate_proj.weight": w((intermediate_size, d)),
            f"{p}.mlp.up_proj.weight": w((intermediate_size, d)),
            f"{p}.mlp.down_proj.weight": w((d, intermediate_size)),
        }
    save_file(tensors, Path(path) / "model.safetensors")


def build_tiny_llama(path: str, seed: int = 0) -> str:
    """Write config.json + model.safetensors + tokenizer to ``path``."""
    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)

    tokenizer = build_tokenizer(path)
    cfg = dict(TINY_LLAMA_CONFIG)
    cfg["vocab_size"] = max(cfg["vocab_size"], len(tokenizer))
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    write_llama_safetensors(
        path,
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        seed=seed,
    )
    return str(out)


def build_tiny_mixtral(path: str, seed: int = 0, num_experts: int = 4,
                       experts_per_tok: int = 2) -> str:
    """Tiny mixtral-architecture checkpoint: llama attention skeleton with
    a router + per-expert FFNs in HF block_sparse_moe naming."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)

    tokenizer = build_tokenizer(path)
    cfg = dict(TINY_LLAMA_CONFIG)
    cfg["architectures"] = ["MixtralForCausalLM"]
    cfg["model_type"] = "mixtral"
    cfg["num_local_experts"] = num_experts
    cfg["num_experts_per_tok"] = experts_per_tok
    cfg["vocab_size"] = max(cfg["vocab_size"], len(tokenizer))
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    rng = np.random.default_rng(seed)
    d = cfg["hidden_size"]
    dh = cfg["head_dim"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    inter = cfg["intermediate_size"]
    vocab = cfg["vocab_size"]

    def w(shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w((vocab, d)),
        "model.norm.weight": np.ones(d, dtype=np.float32),
        "lm_head.weight": w((vocab, d)),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        tensors |= {
            f"{p}.input_layernorm.weight": np.ones(d, dtype=np.float32),
            f"{p}.post_attention_layernorm.weight": np.ones(d, dtype=np.float32),
            f"{p}.self_attn.q_proj.weight": w((h * dh, d)),
            f"{p}.self_attn.k_proj.weight": w((hkv * dh, d)),
            f"{p}.self_attn.v_proj.weight": w((hkv * dh, d)),
            f"{p}.self_attn.o_proj.weight": w((d, h * dh)),
            f"{p}.block_sparse_moe.gate.weight": w((num_experts, d)),
        }
        for e in range(num_experts):
            q = f"{p}.block_sparse_moe.experts.{e}"
            tensors |= {
                f"{q}.w1.weight": w((inter, d)),
                f"{q}.w2.weight": w((d, inter)),
                f"{q}.w3.weight": w((inter, d)),
            }
    save_file(tensors, out / "model.safetensors")
    return str(out)


TINY_OPT_CONFIG = {
    "architectures": ["OPTForCausalLM"],
    "model_type": "opt",
    "vocab_size": 512,
    "hidden_size": 64,
    "ffn_dim": 128,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "max_position_embeddings": 512,
    "word_embed_proj_dim": 64,
    "do_layer_norm_before": True,
    "enable_bias": True,
    "activation_function": "relu",
    "tie_word_embeddings": True,
    "bos_token_id": 1,
    "eos_token_id": 2,
    "pad_token_id": 2,
    "torch_dtype": "float32",
}


def build_tiny_opt(path: str, seed: int = 0) -> str:
    """Tiny OPT-architecture checkpoint in HF naming (BASELINE.json's
    opt-125m config class): learned offset-by-2 positions, pre-LayerNorm
    with biases, fc1/ReLU/fc2, tied lm_head."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)

    tokenizer = build_tokenizer(path)
    cfg = dict(TINY_OPT_CONFIG)
    cfg["vocab_size"] = max(cfg["vocab_size"], len(tokenizer))
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    rng = np.random.default_rng(seed)
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    inter = cfg["ffn_dim"]
    vocab = cfg["vocab_size"]

    def w(shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    def b(n):
        return (rng.standard_normal(n) * 0.01).astype(np.float32)

    tensors = {
        "model.decoder.embed_tokens.weight": w((vocab, d)),
        "model.decoder.embed_positions.weight": w(
            (cfg["max_position_embeddings"] + 2, d)
        ),
        "model.decoder.final_layer_norm.weight": np.ones(d, np.float32),
        "model.decoder.final_layer_norm.bias": b(d),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.decoder.layers.{i}"
        tensors |= {
            f"{p}.self_attn_layer_norm.weight": np.ones(d, np.float32),
            f"{p}.self_attn_layer_norm.bias": b(d),
            f"{p}.final_layer_norm.weight": np.ones(d, np.float32),
            f"{p}.final_layer_norm.bias": b(d),
            f"{p}.self_attn.q_proj.weight": w((d, d)),
            f"{p}.self_attn.q_proj.bias": b(d),
            f"{p}.self_attn.k_proj.weight": w((d, d)),
            f"{p}.self_attn.k_proj.bias": b(d),
            f"{p}.self_attn.v_proj.weight": w((d, d)),
            f"{p}.self_attn.v_proj.bias": b(d),
            f"{p}.self_attn.out_proj.weight": w((d, d)),
            f"{p}.self_attn.out_proj.bias": b(d),
            f"{p}.fc1.weight": w((inter, d)),
            f"{p}.fc1.bias": b(inter),
            f"{p}.fc2.weight": w((d, inter)),
            f"{p}.fc2.bias": b(d),
        }
    save_file(tensors, out / "model.safetensors")
    return str(out)


TINY_GPT_NEOX_CONFIG = {
    "architectures": ["GPTNeoXForCausalLM"],
    "model_type": "gpt_neox",
    "vocab_size": 512,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "max_position_embeddings": 512,
    "rotary_pct": 0.25,
    "rotary_emb_base": 10000,
    "layer_norm_eps": 1e-5,
    "use_parallel_residual": True,
    "hidden_act": "gelu",
    "tie_word_embeddings": False,
    "bos_token_id": 1,
    "eos_token_id": 2,
    "torch_dtype": "float32",
}


def build_tiny_gpt_neox(path: str, seed: int = 0) -> str:
    """Tiny GPT-NeoX/Pythia checkpoint in HF naming: fused
    head-interleaved query_key_value, parallel residual, partial rotary,
    untied embed_out head."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)

    tokenizer = build_tokenizer(path)
    cfg = dict(TINY_GPT_NEOX_CONFIG)
    cfg["vocab_size"] = max(cfg["vocab_size"], len(tokenizer))
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    rng = np.random.default_rng(seed)
    d = cfg["hidden_size"]
    inter = cfg["intermediate_size"]
    vocab = cfg["vocab_size"]

    def w(shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    def b(n):
        return (rng.standard_normal(n) * 0.01).astype(np.float32)

    tensors = {
        "gpt_neox.embed_in.weight": w((vocab, d)),
        "gpt_neox.final_layer_norm.weight": np.ones(d, np.float32),
        "gpt_neox.final_layer_norm.bias": b(d),
        "embed_out.weight": w((vocab, d)),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"gpt_neox.layers.{i}"
        tensors |= {
            f"{p}.input_layernorm.weight": np.ones(d, np.float32),
            f"{p}.input_layernorm.bias": b(d),
            f"{p}.post_attention_layernorm.weight": np.ones(d, np.float32),
            f"{p}.post_attention_layernorm.bias": b(d),
            f"{p}.attention.query_key_value.weight": w((3 * d, d)),
            f"{p}.attention.query_key_value.bias": b(3 * d),
            f"{p}.attention.dense.weight": w((d, d)),
            f"{p}.attention.dense.bias": b(d),
            f"{p}.mlp.dense_h_to_4h.weight": w((inter, d)),
            f"{p}.mlp.dense_h_to_4h.bias": b(inter),
            f"{p}.mlp.dense_4h_to_h.weight": w((d, inter)),
            f"{p}.mlp.dense_4h_to_h.bias": b(d),
        }
    save_file(tensors, out / "model.safetensors")
    return str(out)


TINY_BLOOM_CONFIG = {
    "architectures": ["BloomForCausalLM"],
    "model_type": "bloom",
    "vocab_size": 512,
    "hidden_size": 64,
    "n_layer": 2,
    "n_head": 4,
    "layer_norm_epsilon": 1e-5,
    "apply_residual_connection_post_layernorm": False,
    "tie_word_embeddings": True,
    "bos_token_id": 1,
    "eos_token_id": 2,
    "torch_dtype": "float32",
}


def build_tiny_bloom(path: str, seed: int = 0) -> str:
    """Tiny BLOOM checkpoint in HF naming: ALiBi (no position params),
    word_embeddings_layernorm, fused head-interleaved query_key_value,
    tied head."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)

    tokenizer = build_tokenizer(path)
    cfg = dict(TINY_BLOOM_CONFIG)
    cfg["vocab_size"] = max(cfg["vocab_size"], len(tokenizer))
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    rng = np.random.default_rng(seed)
    d = cfg["hidden_size"]
    inter = 4 * d
    vocab = cfg["vocab_size"]

    def w(shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    def b(n):
        return (rng.standard_normal(n) * 0.01).astype(np.float32)

    tensors = {
        "word_embeddings.weight": w((vocab, d)),
        "word_embeddings_layernorm.weight": np.ones(d, np.float32),
        "word_embeddings_layernorm.bias": b(d),
        "ln_f.weight": np.ones(d, np.float32),
        "ln_f.bias": b(d),
    }
    for i in range(cfg["n_layer"]):
        p = f"h.{i}"
        tensors |= {
            f"{p}.input_layernorm.weight": np.ones(d, np.float32),
            f"{p}.input_layernorm.bias": b(d),
            f"{p}.post_attention_layernorm.weight": np.ones(d, np.float32),
            f"{p}.post_attention_layernorm.bias": b(d),
            f"{p}.self_attention.query_key_value.weight": w((3 * d, d)),
            f"{p}.self_attention.query_key_value.bias": b(3 * d),
            f"{p}.self_attention.dense.weight": w((d, d)),
            f"{p}.self_attention.dense.bias": b(d),
            f"{p}.mlp.dense_h_to_4h.weight": w((inter, d)),
            f"{p}.mlp.dense_h_to_4h.bias": b(inter),
            f"{p}.mlp.dense_4h_to_h.weight": w((d, inter)),
            f"{p}.mlp.dense_4h_to_h.bias": b(d),
        }
    save_file(tensors, out / "model.safetensors")
    return str(out)


TINY_GPT2_CONFIG = {
    "architectures": ["GPT2LMHeadModel"],
    "model_type": "gpt2",
    "vocab_size": 512,
    "n_embd": 64,
    "n_layer": 2,
    "n_head": 4,
    "n_positions": 512,
    "n_ctx": 512,
    "layer_norm_epsilon": 1e-5,
    "activation_function": "gelu_new",
    "bos_token_id": 1,
    "eos_token_id": 2,
    "torch_dtype": "float32",
}


def build_tiny_gpt2(path: str, seed: int = 0) -> str:
    """Tiny GPT-2 checkpoint in HF naming: Conv1D ([in, out]) weights,
    fused c_attn in plain q|k|v column thirds, wte/wpe, tied head."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)

    tokenizer = build_tokenizer(path)
    cfg = dict(TINY_GPT2_CONFIG)
    cfg["vocab_size"] = max(cfg["vocab_size"], len(tokenizer))
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    rng = np.random.default_rng(seed)
    d = cfg["n_embd"]
    inter = 4 * d
    vocab = cfg["vocab_size"]

    def w(shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    def b(n):
        return (rng.standard_normal(n) * 0.01).astype(np.float32)

    tensors = {
        "transformer.wte.weight": w((vocab, d)),
        "transformer.wpe.weight": w((cfg["n_positions"], d)),
        "transformer.ln_f.weight": np.ones(d, np.float32),
        "transformer.ln_f.bias": b(d),
    }
    for i in range(cfg["n_layer"]):
        p = f"transformer.h.{i}"
        tensors |= {
            f"{p}.ln_1.weight": np.ones(d, np.float32),
            f"{p}.ln_1.bias": b(d),
            f"{p}.ln_2.weight": np.ones(d, np.float32),
            f"{p}.ln_2.bias": b(d),
            f"{p}.attn.c_attn.weight": w((d, 3 * d)),  # Conv1D [in, out]
            f"{p}.attn.c_attn.bias": b(3 * d),
            f"{p}.attn.c_proj.weight": w((d, d)),
            f"{p}.attn.c_proj.bias": b(d),
            f"{p}.mlp.c_fc.weight": w((d, inter)),
            f"{p}.mlp.c_fc.bias": b(inter),
            f"{p}.mlp.c_proj.weight": w((inter, d)),
            f"{p}.mlp.c_proj.bias": b(d),
        }
    save_file(tensors, out / "model.safetensors")
    return str(out)


def build_tiny_lora_adapter(path: str, seed: int = 7, rank: int = 4,
                            arch: dict | None = None) -> str:
    """PEFT-format LoRA adapter matching the tiny llama fixture: real
    random A/B weights on q/v projections of both layers (the reference's
    fixture adapters carry dummy weights; ours are live so generation
    with the adapter measurably diverges from the base model).
    ``arch`` overrides the fixture config for non-tiny hosts (same keys
    as TINY_LLAMA_CONFIG)."""
    import json as json_mod

    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)
    cfg = arch or TINY_LLAMA_CONFIG
    d = cfg["hidden_size"]
    dh = cfg["head_dim"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    rng = np.random.default_rng(seed)

    json_mod.dump(
        {
            "peft_type": "LORA",
            "r": rank,
            "lora_alpha": 4 * rank,  # strong scaling: visible deltas
            "target_modules": ["q_proj", "v_proj"],
            "base_model_name_or_path": "tiny-llama",
        },
        open(out / "adapter_config.json", "w"),
        indent=2,
    )

    def w(shape):
        return (rng.standard_normal(shape) * 0.5).astype(np.float32)

    tensors = {}
    for i in range(cfg["num_hidden_layers"]):
        p = f"base_model.model.model.layers.{i}.self_attn"
        tensors[f"{p}.q_proj.lora_A.weight"] = w((rank, d))
        tensors[f"{p}.q_proj.lora_B.weight"] = w((h * dh, rank))
        tensors[f"{p}.v_proj.lora_A.weight"] = w((rank, d))
        tensors[f"{p}.v_proj.lora_B.weight"] = w((hkv * dh, rank))
    save_file(tensors, out / "adapter_model.safetensors")
    return str(out)


def build_tiny_gemma(path: str, seed: int = 0) -> str:
    """Tiny gemma-architecture checkpoint: llama-style tensor names with
    gemma block chemistry — GeGLU (gelu_pytorch_tanh), (1+w) RMSNorm,
    sqrt(hidden)-scaled embeddings, tied head (no lm_head tensor)."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)

    tokenizer = build_tokenizer(path)
    cfg = dict(TINY_LLAMA_CONFIG)
    cfg["architectures"] = ["GemmaForCausalLM"]
    cfg["model_type"] = "gemma"
    cfg["hidden_activation"] = "gelu_pytorch_tanh"
    cfg["tie_word_embeddings"] = True
    cfg["vocab_size"] = max(cfg["vocab_size"], len(tokenizer))
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    rng = np.random.default_rng(seed)
    d = cfg["hidden_size"]
    dh = cfg["head_dim"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    inter = cfg["intermediate_size"]
    vocab = cfg["vocab_size"]

    def w(shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    # HF gemma norms store w with (1+w) applied at runtime: random small
    # values (not ones) so the offset path is actually exercised
    def norm():
        return (rng.standard_normal(d) * 0.1).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w((vocab, d)),
        "model.norm.weight": norm(),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        tensors |= {
            f"{p}.input_layernorm.weight": norm(),
            f"{p}.post_attention_layernorm.weight": norm(),
            f"{p}.self_attn.q_proj.weight": w((h * dh, d)),
            f"{p}.self_attn.k_proj.weight": w((hkv * dh, d)),
            f"{p}.self_attn.v_proj.weight": w((hkv * dh, d)),
            f"{p}.self_attn.o_proj.weight": w((d, h * dh)),
            f"{p}.mlp.gate_proj.weight": w((inter, d)),
            f"{p}.mlp.up_proj.weight": w((inter, d)),
            f"{p}.mlp.down_proj.weight": w((d, inter)),
        }
    save_file(tensors, out / "model.safetensors")
    return str(out)


def build_tiny_phi3(path: str, seed: int = 0) -> str:
    """Tiny phi3-architecture checkpoint: llama block chemistry with the
    HF phi-3 FUSED projections — qkv_proj (q|k|v stacked row slices) and
    gate_up_proj (gate over up) — untied head."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)

    tokenizer = build_tokenizer(path)
    cfg = dict(TINY_LLAMA_CONFIG)
    cfg["architectures"] = ["Phi3ForCausalLM"]
    cfg["model_type"] = "phi3"
    cfg["pad_token_id"] = 0
    cfg["vocab_size"] = max(cfg["vocab_size"], len(tokenizer))
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    rng = np.random.default_rng(seed)
    d = cfg["hidden_size"]
    dh = cfg["head_dim"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    inter = cfg["intermediate_size"]
    vocab = cfg["vocab_size"]

    def w(shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w((vocab, d)),
        "model.norm.weight": np.ones(d, dtype=np.float32),
        "lm_head.weight": w((vocab, d)),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        tensors |= {
            f"{p}.input_layernorm.weight": np.ones(d, dtype=np.float32),
            f"{p}.post_attention_layernorm.weight": np.ones(
                d, dtype=np.float32
            ),
            f"{p}.self_attn.qkv_proj.weight": w(((h + 2 * hkv) * dh, d)),
            f"{p}.self_attn.o_proj.weight": w((d, h * dh)),
            f"{p}.mlp.gate_up_proj.weight": w((2 * inter, d)),
            f"{p}.mlp.down_proj.weight": w((d, inter)),
        }
    save_file(tensors, out / "model.safetensors")
    return str(out)


def build_tiny_qwen3(path: str, seed: int = 0) -> str:
    """Tiny qwen3-architecture checkpoint: llama tensor names plus
    per-layer head-dim q_norm/k_norm weights."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)

    tokenizer = build_tokenizer(path)
    cfg = dict(TINY_LLAMA_CONFIG)
    cfg["architectures"] = ["Qwen3ForCausalLM"]
    cfg["model_type"] = "qwen3"
    cfg["vocab_size"] = max(cfg["vocab_size"], len(tokenizer))
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    rng = np.random.default_rng(seed)
    d = cfg["hidden_size"]
    dh = cfg["head_dim"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    inter = cfg["intermediate_size"]
    vocab = cfg["vocab_size"]

    def w(shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    def norm(n):
        return (1.0 + rng.standard_normal(n) * 0.1).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w((vocab, d)),
        "model.norm.weight": norm(d),
        "lm_head.weight": w((vocab, d)),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        tensors |= {
            f"{p}.input_layernorm.weight": norm(d),
            f"{p}.post_attention_layernorm.weight": norm(d),
            f"{p}.self_attn.q_proj.weight": w((h * dh, d)),
            f"{p}.self_attn.k_proj.weight": w((hkv * dh, d)),
            f"{p}.self_attn.v_proj.weight": w((hkv * dh, d)),
            f"{p}.self_attn.o_proj.weight": w((d, h * dh)),
            f"{p}.self_attn.q_norm.weight": norm(dh),
            f"{p}.self_attn.k_norm.weight": norm(dh),
            f"{p}.mlp.gate_proj.weight": w((inter, d)),
            f"{p}.mlp.up_proj.weight": w((inter, d)),
            f"{p}.mlp.down_proj.weight": w((d, inter)),
        }
    save_file(tensors, out / "model.safetensors")
    return str(out)


# ---------------------------------------------------------- int4 checkpoints


def _pack_int32_nibbles(vals, axis):
    """int4 values → int32 words, 8 per word along ``axis`` (sequential
    nibble order) — the inverse of engine/quantized._unpack_int32_nibbles."""
    import numpy as np

    vals = np.asarray(vals).astype(np.int64) & 0xF
    vals = vals.astype(np.uint32)
    new_shape = list(vals.shape)
    new_shape[axis] //= 8
    grouped = vals.reshape(
        new_shape[:axis] + [new_shape[axis], 8] + new_shape[axis + 1:]
    )
    shifts = (np.arange(8, dtype=np.uint32) * 4).reshape(
        (1,) * (axis + 1) + (8,) + (1,) * (grouped.ndim - axis - 2)
    )
    # ascontiguousarray: safetensors serialises the raw buffer, so a
    # non-contiguous result would be written scrambled
    return np.ascontiguousarray(
        (grouped << shifts).sum(axis=axis + 1).astype(np.int32)
    )


def quantize_checkpoint_int4(src_dir, dst_dir, *, method="awq",
                             group_size=8, desc_act=False, seed=0):
    """Re-write a tiny fp checkpoint in the AWQ / AutoGPTQ int4 wire
    format (qweight/qzeros/scales[/g_idx] + quantization_config) so the
    dequant-on-load path (engine/quantized.py) can be pinned without
    network access.  Returns the destination path."""
    import json
    import shutil
    from pathlib import Path

    import numpy as np
    from safetensors.numpy import save_file

    from safetensors import safe_open

    AWQ_ORDER = (0, 2, 4, 6, 1, 3, 5, 7)
    src, dst = Path(src_dir), Path(dst_dir)
    dst.mkdir(parents=True, exist_ok=True)
    for f in src.iterdir():
        if f.name != "model.safetensors":
            shutil.copy(f, dst / f.name)

    rng = np.random.default_rng(seed)
    quant_suffixes = ("q_proj.weight", "k_proj.weight", "v_proj.weight",
                      "o_proj.weight", "gate_proj.weight",
                      "up_proj.weight", "down_proj.weight",
                      # phi-3 fused projections quantize as single linears
                      "qkv_proj.weight", "gate_up_proj.weight")
    out_tensors = {}
    with safe_open(src / "model.safetensors", framework="numpy") as fh:
        for name in fh.keys():
            w = fh.get_tensor(name)
            if not name.endswith(quant_suffixes):
                out_tensors[name] = w
                continue
            prefix = name[: -len(".weight")]
            wt = w.astype(np.float32).T  # [in, out]
            in_f, out_f = wt.shape
            assert in_f % group_size == 0 and out_f % 8 == 0
            groups = in_f // group_size
            if method == "gptq" and desc_act:
                g_idx = rng.permutation(
                    np.repeat(np.arange(groups), group_size)
                ).astype(np.int32)
            else:
                g_idx = np.repeat(np.arange(groups), group_size)
            # per (group, out-col) asymmetric int4 quantization
            scales = np.zeros((groups, out_f), np.float32)
            zeros = np.zeros((groups, out_f), np.int32)
            q = np.zeros((in_f, out_f), np.int32)
            for g in range(groups):
                rows = np.nonzero(g_idx == g)[0]
                block = wt[rows]
                # the quantization range must include 0 so the zero-point
                # lands in [0, 15] (an all-negative group would otherwise
                # clip z and shift the whole block by |hi|)
                lo = np.minimum(block.min(axis=0), 0.0)
                hi = np.maximum(block.max(axis=0), 0.0)
                s = np.maximum((hi - lo) / 15.0, 1e-8)
                # gptq: floor 1 keeps the stored-minus-one convention
                # invertible (z=0 would wrap to 15 on unpack)
                z_floor = 1 if method == "gptq" else 0
                z = np.clip(np.round(-lo / s), z_floor, 15)
                scales[g], zeros[g] = s, z.astype(np.int32)
                q[rows] = np.clip(
                    np.round(block / s) + z, 0, 15
                ).astype(np.int32)
            if method == "awq":
                # nibble interleave along out: inverse of the unpack order
                order = np.arange(out_f).reshape(-1, 8)[
                    :, list(AWQ_ORDER)
                ].reshape(-1)
                inv = np.empty_like(order)
                inv[order] = np.arange(out_f)
                out_tensors[f"{prefix}.qweight"] = _pack_int32_nibbles(
                    q[:, inv], axis=1)
                out_tensors[f"{prefix}.qzeros"] = _pack_int32_nibbles(
                    zeros[:, inv], axis=1)
                out_tensors[f"{prefix}.scales"] = scales.astype(np.float16)
            else:  # gptq
                out_tensors[f"{prefix}.qweight"] = _pack_int32_nibbles(
                    q, axis=0)
                # classic stored-minus-one zero-point convention
                out_tensors[f"{prefix}.qzeros"] = _pack_int32_nibbles(
                    zeros - 1, axis=1)
                out_tensors[f"{prefix}.scales"] = scales.astype(np.float16)
                if desc_act:
                    out_tensors[f"{prefix}.g_idx"] = g_idx
    save_file(out_tensors, dst / "model.safetensors")

    cfg_path = dst / "config.json"
    cfg = json.loads(cfg_path.read_text())
    if method == "awq":
        cfg["quantization_config"] = {
            "quant_method": "awq", "bits": 4, "group_size": group_size,
            "version": "gemm", "zero_point": True,
        }
    else:
        cfg["quantization_config"] = {
            "quant_method": "gptq", "bits": 4, "group_size": group_size,
            "desc_act": desc_act, "sym": False,
        }
    cfg_path.write_text(json.dumps(cfg, indent=2))
    return str(dst)


# every muP multiplier away from 1, so that a forward pass that drops
# one of them disagrees with the reference
TINY_FALCON_H1_CONFIG = {
    "architectures": ["FalconH1ForCausalLM"],
    "model_type": "falcon_h1",
    "vocab_size": 512,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 3,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "mamba_d_ssm": 64,
    "mamba_n_heads": 4,
    "mamba_d_head": 16,
    "mamba_d_state": 16,
    "mamba_n_groups": 2,
    "mamba_d_conv": 4,
    "mamba_chunk_size": 8,
    "mamba_expand": 1,
    "mamba_conv_bias": True,
    "mamba_proj_bias": False,
    "mamba_rms_norm": True,
    "mamba_norm_before_gate": False,
    "mamba_use_mlp": True,
    "attention_bias": False,
    "mlp_bias": False,
    "projectors_bias": False,
    "hidden_act": "silu",
    "attention_in_multiplier": 0.9,
    "attention_out_multiplier": 0.7,
    "key_multiplier": 0.6,
    "embedding_multiplier": 3.0,
    "lm_head_multiplier": 0.25,
    "ssm_in_multiplier": 0.8,
    "ssm_out_multiplier": 0.5,
    "ssm_multipliers": [0.7, 1.3, 0.6, 1.4, 0.8],
    "mlp_multipliers": [1.2, 0.45],
    "max_position_embeddings": 512,
    "rope_theta": 10000.0,
    "rope_scaling": None,
    "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
    "bos_token_id": 1,
    "eos_token_id": 2,
    "torch_dtype": "float32",
}


def build_tiny_falcon_h1(path: str, seed: int = 0, **overrides) -> str:
    """Tiny Falcon-H1 checkpoint in Hugging Face's ``falcon_h1`` tensor
    names: attention and a Mamba-2 mixer side by side in each of three
    layers.  ``A_log``, ``dt_bias`` and ``D`` are filled as Mamba-2
    publishes them; the matrices at 1/sqrt(fan in), so that all three
    branches of a layer are of comparable size."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)
    build_tokenizer(path)
    cfg = dict(TINY_FALCON_H1_CONFIG, **overrides)
    with open(out / "config.json", "w") as f:
        json.dump(cfg, f, indent=2)

    rng = np.random.default_rng(seed)
    d, ffn, vocab = (cfg["hidden_size"], cfg["intermediate_size"],
                     cfg["vocab_size"])
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    mh, d_ssm = cfg["mamba_n_heads"], cfg["mamba_d_ssm"]
    conv_dim = d_ssm + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]

    def w(shape, fan_in=None):
        std = (fan_in or shape[-1]) ** -0.5
        return (rng.standard_normal(shape) * std).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w((vocab, d), 1.0),
        "model.final_layernorm.weight": np.ones(d, np.float32),
        "lm_head.weight": w((vocab, d)),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), mh))
        tensors |= {
            f"{p}.input_layernorm.weight": np.ones(d, np.float32),
            f"{p}.pre_ff_layernorm.weight": np.ones(d, np.float32),
            f"{p}.self_attn.q_proj.weight": w((h * dh, d)),
            f"{p}.self_attn.k_proj.weight": w((kv * dh, d)),
            f"{p}.self_attn.v_proj.weight": w((kv * dh, d)),
            f"{p}.self_attn.o_proj.weight": w((d, h * dh)),
            f"{p}.mamba.in_proj.weight": w((d_ssm + conv_dim + mh, d)),
            f"{p}.mamba.conv1d.weight": w(
                (conv_dim, 1, cfg["mamba_d_conv"]), 4.0),
            f"{p}.mamba.conv1d.bias": w((conv_dim,), 16.0),
            f"{p}.mamba.dt_bias": (dt + np.log(-np.expm1(-dt))).astype(
                np.float32),
            f"{p}.mamba.A_log": np.log(rng.uniform(1.0, 16.0, mh)).astype(
                np.float32),
            f"{p}.mamba.D": np.ones(mh, np.float32),
            f"{p}.mamba.norm.weight": (
                1.0 + 0.1 * rng.standard_normal(d_ssm)).astype(np.float32),
            f"{p}.mamba.out_proj.weight": w((d, d_ssm)),
            f"{p}.feed_forward.gate_proj.weight": w((ffn, d)),
            f"{p}.feed_forward.up_proj.weight": w((ffn, d)),
            f"{p}.feed_forward.down_proj.weight": w((d, ffn)),
        }
    save_file(tensors, out / "model.safetensors")
    return str(out)


# a tiny DeepSeek-V2: one dense layer, then three layers of 16 routed
# experts in 4 groups (2 groups and 3 experts a token) beside 2 shared
# ones; latent attention with YaRN in every layer
TINY_DEEPSEEK_V2_CONFIG = {
    "architectures": ["DeepseekV2ForCausalLM"],
    "model_type": "deepseek_v2",
    "vocab_size": 512,
    "hidden_size": 64,
    "intermediate_size": 128,
    "moe_intermediate_size": 32,
    "num_hidden_layers": 4,
    "first_k_dense_replace": 1,
    "moe_layer_freq": 1,
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "q_lora_rank": 24,
    "kv_lora_rank": 32,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8,
    "v_head_dim": 16,
    "n_routed_experts": 16,
    "n_shared_experts": 2,
    "n_group": 4,
    "topk_group": 2,
    "num_experts_per_tok": 3,
    "routed_scaling_factor": 4.0,
    "norm_topk_prob": False,
    "scoring_func": "softmax",
    "topk_method": "group_limited_greedy",
    "attention_bias": False,
    "hidden_act": "silu",
    "max_position_embeddings": 512,
    "rope_theta": 10000.0,
    "rope_scaling": {
        "type": "yarn", "factor": 4, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707,
    },
    "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "bos_token_id": 1,
    "eos_token_id": 2,
    "torch_dtype": "float32",
}


def build_tiny_deepseek_v2(path: str, seed: int = 0, held=None,
                           **overrides) -> str:
    """Tiny DeepSeek-V2 checkpoint in Hugging Face's ``deepseek_v2``
    tensor names.  ``held = (first, last)`` writes only those experts of
    each expert layer, under their published indices, and counts them in
    ``n_routed_experts`` (the router keeps all its rows): one chip's
    share of the same model, since every tensor is drawn as in the whole
    checkpoint of that seed.  The norms' weights are away from 1 and
    the router's rows at std 1, so that routing is decided by more than
    rounding."""
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)
    build_tokenizer(path)
    cfg = dict(TINY_DEEPSEEK_V2_CONFIG, **overrides)
    total = cfg["n_routed_experts"]
    lo, hi = held or (0, total)
    with open(out / "config.json", "w") as f:
        json.dump(dict(cfg, n_routed_experts=hi - lo), f, indent=2)

    rng = np.random.default_rng(seed)
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f_moe = cfg["moe_intermediate_size"]

    def w(shape, std=None):
        std = std or shape[-1] ** -0.5
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def norm(n):
        return (1.0 + 0.1 * rng.standard_normal(n)).astype(np.float32)

    def swiglu(prefix, width):
        return {
            f"{prefix}.gate_proj.weight": w((width, d)),
            f"{prefix}.up_proj.weight": w((width, d)),
            f"{prefix}.down_proj.weight": w((d, width)),
        }

    tensors = {
        "model.embed_tokens.weight": w((vocab, d), 1.0),
        "model.norm.weight": norm(d),
        "lm_head.weight": w((vocab, d)),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        tensors |= {
            f"{p}.input_layernorm.weight": norm(d),
            f"{p}.post_attention_layernorm.weight": norm(d),
            f"{p}.self_attn.q_a_proj.weight": w((q_rank, d)),
            f"{p}.self_attn.q_a_layernorm.weight": norm(q_rank),
            f"{p}.self_attn.q_b_proj.weight": w((h * (nope + rope), q_rank)),
            f"{p}.self_attn.kv_a_proj_with_mqa.weight": w(
                (kv_rank + rope, d)),
            f"{p}.self_attn.kv_a_layernorm.weight": norm(kv_rank),
            f"{p}.self_attn.kv_b_proj.weight": w((h * (nope + v), kv_rank)),
            f"{p}.self_attn.o_proj.weight": w((d, h * v)),
        }
        if i < cfg["first_k_dense_replace"]:
            tensors |= swiglu(f"{p}.mlp", cfg["intermediate_size"])
            continue
        tensors[f"{p}.mlp.gate.weight"] = w((total, d), 1.0 / 8)
        tensors |= swiglu(f"{p}.mlp.shared_experts",
                          cfg["n_shared_experts"] * f_moe)
        for e in range(total):  # every expert drawn, the held ones kept
            expert = swiglu(f"{p}.mlp.experts.{e}", f_moe)
            if lo <= e < hi:
                tensors |= expert
    save_file(tensors, out / "model.safetensors")
    return str(out)


# Command A+ (``cohere2_moe``) in miniature: one period of three window
# layers and one full one, 8 query heads over 2 KV heads (4 a KV head),
# window 8, 8 sigmoid-routed experts (2 a token) beside 2 averaged shared
# ones, a tied head (tests/test_cohere2_moe.py).
TINY_COHERE2_MOE_CONFIG = {
    "architectures": ["Cohere2MoeForCausalLM"],
    "model_type": "cohere2_moe",
    "attention_bias": False,
    "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0,
    "head_dim": 16,
    "hidden_act": "silu",
    "hidden_size": 64,
    "intermediate_size": 32,
    "layer_norm_eps": 1e-5,
    "layer_switch": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "logit_scale": 1,
    "max_position_embeddings": 256,
    "norm_topk_prob": True,
    "num_attention_heads": 8,
    "num_experts": 8,
    "num_experts_per_tok": 2,
    "num_hidden_layers": 4,
    "num_key_value_heads": 2,
    "num_shared_experts": 2,
    "position_embedding_type": "rope_gptj",
    "rope_theta": 50000,
    "rotary_pct": 1,
    "shared_expert_combination_strategy": "average",
    "sliding_window": 8,
    "tie_word_embeddings": True,
    "use_parallel_block": True,
    "use_qk_norm": False,
    "vocab_size": 512,
    "bos_token_id": 1,
    "eos_token_id": 2,
    "torch_dtype": "float32",
}


def cohere2_moe_tensors(cfg: dict, seed: int = 0) -> dict:
    """Every tensor of the WHOLE tiny checkpoint (all the router's
    experts), by name, float32: norms' weights away from 1, the router's
    rows at std 1 so that routing is decided by more than rounding."""
    import numpy as np

    rng = np.random.default_rng(seed)
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f = cfg["intermediate_size"]

    def w(shape, std=None):
        std = std or shape[-1] ** -0.5
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def norm(n):
        return (1.0 + 0.1 * rng.standard_normal(n)).astype(np.float32)

    def swiglu(prefix):
        return {
            f"{prefix}.gate_proj.weight": w((f, d)),
            f"{prefix}.up_proj.weight": w((f, d)),
            f"{prefix}.down_proj.weight": w((d, f)),
        }

    tensors = {
        "model.embed_tokens.weight": w((vocab, d), 1.0),
        "model.norm.weight": norm(d),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}"
        tensors |= {
            f"{p}.input_layernorm.weight": norm(d),
            f"{p}.self_attn.q_proj.weight": w((h * dh, d)),
            f"{p}.self_attn.k_proj.weight": w((kv * dh, d)),
            f"{p}.self_attn.v_proj.weight": w((kv * dh, d)),
            f"{p}.self_attn.o_proj.weight": w((d, h * dh)),
            f"{p}.mlp.gate.weight": w((cfg["num_experts"], d), 1.0 / 8),
        }
        for j in range(cfg["num_shared_experts"]):
            tensors |= swiglu(f"{p}.mlp.shared_experts.{j}")
        for e in range(cfg["num_experts"]):
            tensors |= swiglu(f"{p}.mlp.experts.{e}")
    return tensors


def build_tiny_cohere2_moe(path: str, seed: int = 0, held=None,
                           **overrides) -> str:
    """Tiny ``cohere2_moe`` checkpoint.  ``held = (first, last)`` writes
    only those experts of each layer, under their published indices, and
    counts them in ``num_experts`` (the router keeps all its rows): one
    chip's share of the same model, since every tensor is drawn as in
    the whole checkpoint of that seed."""
    import re

    from safetensors.numpy import save_file

    out = Path(path)
    out.mkdir(parents=True, exist_ok=True)
    build_tokenizer(path)
    cfg = dict(TINY_COHERE2_MOE_CONFIG, **overrides)
    lo, hi = held or (0, cfg["num_experts"])
    with open(out / "config.json", "w") as f:
        json.dump(dict(cfg, num_experts=hi - lo), f, indent=2)
    tensors = {
        name: t for name, t in cohere2_moe_tensors(cfg, seed).items()
        if not (m := re.search(r"\.mlp\.experts\.(\d+)\.", name))
        or lo <= int(m.group(1)) < hi
    }
    save_file(tensors, out / "model.safetensors")
    return str(out)
