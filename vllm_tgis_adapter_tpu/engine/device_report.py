"""What device the engine is on, and what it decided because of it.

One block, logged at boot and served under ``device`` in
``GET /debug/state``: a report of decisions the code already makes
(ops/attention.py ``_use_pallas`` / ``_pallas_interpret``, the runner's
donation, kv_cache.py's pool sizing), so that a server that came up on
the wrong device, kernel or pool says so instead of just being slow.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

from vllm_tgis_adapter_tpu.engine.kv_cache import (
    device_memory_stats,
    state_slab_bytes,
)
from vllm_tgis_adapter_tpu.ops.attention import (
    _pallas_interpret,
    _use_pallas,
)

if TYPE_CHECKING:
    from vllm_tgis_adapter_tpu.engine.core import LLMEngine

_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def device_report(engine: "LLMEngine") -> dict:
    import jax

    mesh = getattr(engine.runner, "mesh", None)
    devices = (
        list(mesh.devices.flat)
        if mesh is not None
        else list(engine._devices or jax.local_devices()[:1])  # noqa: SLF001
    )
    pallas = _use_pallas()
    ccfg = engine.config.cache_config
    state_used, state_slots = engine.state_slot_usage()
    memory = []
    for d in devices:
        stats = device_memory_stats(d)
        memory.append(
            {"id": d.id, **{k: stats[k] for k in _MEMORY_KEYS if k in stats}}
        )
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": jax.device_count(),
        "engine_devices": len(devices),
        "attention": "pallas" if pallas else "xla",
        # None under XLA attention: there is no Pallas program at all
        "pallas_mode": (
            ("interpret" if _pallas_interpret() else "compiled")
            if pallas else None
        ),
        "caches_donated": engine.runner.caches_donated,
        "kv_pool": {
            "sized_from": engine.kv_pool_sized_from,
            "num_blocks": ccfg.num_blocks,
            "block_size": ccfg.block_size,
        },
        # the recurrent-state slab beside the pool (docs/STATE.md);
        # zeros for a model whose only per-sequence state is KV pages
        "state_slab": {
            "slots": state_slots,
            "slots_used": state_used,
            "bytes": state_slab_bytes(engine.config),
        },
        # a model with a latent cache (docs/LATENT_ATTENTION.md): what a
        # row of the pool holds and what a token costs; the share of its
        # experts this server holds
        **_latent_report(engine),
        "weights_load_s": engine.weights_load_s,
        "memory": memory,
    }


def _latent_report(engine: "LLMEngine") -> dict:
    m = engine.config.model_config
    if not m.has_latent_cache:
        return {}
    import jax.numpy as jnp

    mla, ex, ccfg = m.mla, m.experts, engine.config.cache_config
    rope_end = mla.rope_offset + mla.qk_rope_head_dim
    return {
        "latent_pool": {
            "row": f"c_kv[0:{mla.kv_lora_rank}] | "
                   f"k_pe[{mla.rope_offset}:{rope_end}] | "
                   f"zeros to {mla.row_width} lanes",
            "row_values": mla.latent_dim,
            "row_lanes": mla.row_width,
            "bytes_per_token": m.num_layers * mla.row_width
            * jnp.dtype(ccfg.cache_dtype).itemsize,
            "tokens": ccfg.num_blocks * ccfg.block_size,
        },
        "held_experts": {
            "first": ex.first_held, "last": ex.held[1] - 1,
            "held": ex.num_held, "routed": ex.num_routed,
            "expert_layers": m.num_layers - ex.first_dense_layers,
        },
    }


def format_report(report: dict) -> str:
    """The boot log line."""
    pool = report["kv_pool"]
    return (
        f"device: platform={report['platform']} "
        f"kind={report['device_kind']!r} count={report['device_count']} "
        f"(engine uses {report['engine_devices']}); "
        f"attention={report['attention']}"
        + (f"/{report['pallas_mode']}" if report["pallas_mode"] else "")
        + f"; caches_donated={report['caches_donated']}; "
        f"kv_pool={pool['num_blocks']}x{pool['block_size']} "
        f"sized_from={pool['sized_from']}"
        + (
            f"; state_slab={report['state_slab']['slots']} slots "
            f"{report['state_slab']['bytes'] / 1e9:.2f} GB"
            if report["state_slab"]["slots"] else ""
        )
        + (
            f"; latent_pool={report['latent_pool']['tokens']} tokens x "
            f"{report['latent_pool']['bytes_per_token']} B; experts "
            f"{report['held_experts']['first']}-"
            f"{report['held_experts']['last']} of "
            f"{report['held_experts']['routed']} held"
            if "latent_pool" in report else ""
        )
    )
