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
        "weights_load_s": engine.weights_load_s,
        "memory": memory,
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
    )
