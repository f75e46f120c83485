"""Quantized KV pages: int8/fp8 storage with per-page-per-head scales.

Decode throughput on TPU is HBM-bandwidth- and KV-capacity-bound, and
since PRs 9-11 the KV page is the unit of *everything* — the device
pool, the host prefix tier, decode checkpoints, and prefill→decode
handoffs all move whole pages.  Halving page bytes therefore doubles
effective capacity across the entire stack at once: more pages per HBM
budget → bigger ragged batches → direct tok/s (ROADMAP item 5; the
Gemma-on-TPU serving comparison in PAPERS.md is the low-precision
precedent).  This module implements the storage scheme and every
quantize/dequantize primitive the rest of the stack composes
(docs/QUANTIZATION.md):

* **Storage.**  ``--kv-quantization int8`` stores pages as symmetric
  int8 (``q ∈ [-127, 127]``); ``fp8`` stores ``float8_e4m3fn`` (max
  normal 448).  The cache becomes a :class:`QuantizedKVCache` — the
  quantized ``data`` array in the familiar head-leading
  ``[L, Hkv, num_slots, Dh]`` layout plus a f32 ``scale`` sidecar
  ``[L, Hkv, num_pages]``: ONE dequant scale per (layer, kv head,
  physical page).  ``none`` (the default) keeps plain arrays and is
  byte-identical to the pre-quantization engine — none of the helpers
  below emit a single different op for raw arrays.

* **Scale discipline (the token-identity anchor).**  A page's scale is
  (re)set exactly when its FIRST slot is written: from that row's
  per-head ``|amax|`` times a fixed headroom margin.  Every write in
  the same dispatch — and every later append to the page — quantizes
  with the post-update scale (values past the range clip).  Because a
  position's K/V is a pure function of the token history, the scale is
  REPRODUCIBLE no matter which path writes slot 0 (solo prefill, a
  ragged chunk, a decode step, a speculative verify span, or a
  checkpoint-resume tail recompute): demote→promote through the host
  tier, decode checkpoint/resume, and prefill→decode handoffs all stay
  token-identical under quantization.  A running per-page amax would
  be tighter but is NOT append-consistent — growing the scale would
  silently rescale previously stored integers.

* **Dequantization at the page read.**  The Pallas ragged kernel
  multiplies each DMA'd page tile by its one scale scalar in-register
  (ops/ragged_attention.py); the XLA reference path multiplies after
  the page gather (ops/attention.py ``paged_decode_attention_xla``).
  Softmax stays f32 either way, so quantization only perturbs the K/V
  operands, never the accumulation.

* **Page movement.**  ``gather_kv_page`` / ``restore_kv_page`` are the
  jitted per-page entry points the host tier and checkpoint paths ride
  (engine/runner.py wraps them in ``track_jit``): one fixed
  block-shaped program each, quantized or not — the scale column
  travels WITH the page, so tier entries, checkpoints and role
  handoffs carry the sidecar for free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: accepted --kv-quantization values (engine/config.py validates).
SCHEMES = ("none", "int8", "fp8")

#: headroom multiplier on the scale-setting row's |amax|: later tokens
#: appended to the page clip only when they exceed MARGIN x the first
#: token's per-head amax.  Costs one effective bit of int8 precision;
#: K/V magnitudes are near-stationary across positions, so the clip
#: rate stays negligible (tests/test_kv_quant.py roundtrip bounds).
SCALE_MARGIN = 2.0

_EPS = 1e-8


def storage_dtype(scheme: str):
    """Quantized storage dtype for ``scheme`` (``none`` → None)."""
    if scheme == "int8":
        return jnp.int8
    if scheme == "fp8":
        return jnp.float8_e4m3fn
    return None


def qmax_for(dtype) -> float:
    """Largest representable magnitude quantization targets."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        return 127.0
    return 448.0  # float8_e4m3fn max normal


def scale_bytes_per_page(num_layers: int, kv_heads: int) -> int:
    """Sidecar bytes ONE page adds (both caches): 2 x [L, Hkv] f32."""
    return 2 * num_layers * kv_heads * 4


@jax.tree_util.register_pytree_node_class
class QuantizedKVCache:
    """One quantized K (or V) cache: ``data`` + per-page ``scale``.

    ``data``  — ``[L, Hkv, num_slots, Dh]`` int8 / float8_e4m3fn
    ``scale`` — ``[L, Hkv, num_pages]`` f32, dequant multiplier per
    physical page (``num_pages = num_slots // block_size``); 0 marks a
    never-written page (its garbage content is masked by context
    length everywhere it could be read).

    Registered as a pytree so it flows through ``jax.jit`` / ``scan``
    carries / donation exactly like the raw array it replaces; the
    ``shape`` / ``dtype`` properties keep the handful of geometry reads
    (``k_cache.shape[2]``) working unchanged.

    ``floor`` (optional, ``[L, Hkv]`` f32) is the CALIBRATED per-layer
    per-head scale floor loaded from checkpoints that ship
    ``k_scale``/``v_scale`` tensors (engine/weights.py): models whose
    K/V outliers punish pure-amax scaling set their page scales to
    ``max(amax-derived, floor)`` at the slot-0 write.  None (the
    default, and every checkpoint without the tensors) is bit-identical
    to the pre-floor engine.
    """

    __slots__ = ("data", "scale", "block_size", "floor")

    def __init__(self, data, scale, block_size: int, floor=None):
        self.data = data
        self.scale = scale
        self.block_size = block_size
        self.floor = floor

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    def tree_flatten(self):
        return (self.data, self.scale, self.floor), self.block_size

    @classmethod
    def tree_unflatten(cls, block_size, children):
        data, scale, floor = children
        return cls(data, scale, block_size, floor)


def is_quantized(cache) -> bool:
    return isinstance(cache, QuantizedKVCache)


def make_kv_cache(
    shape: tuple, dtype, scheme: str = "none", block_size: int = 16,
    scale_floor=None,
):
    """Zeroed cache in the layout ``scheme`` dictates.

    ``none`` returns the plain zeros array the engine always built —
    byte-identical off.  int8/fp8 return a :class:`QuantizedKVCache`
    with an all-zero scale sidecar (every page starts "never written").
    ``scale_floor`` ([L, Hkv] f32 or None) attaches the calibrated
    per-head scale floor from quantization-aware checkpoints.
    """
    qdtype = storage_dtype(scheme)
    if qdtype is None:
        return jnp.zeros(shape, dtype=dtype)
    num_layers, kv_heads, num_slots, _ = shape
    return QuantizedKVCache(
        jnp.zeros(shape, dtype=qdtype),
        jnp.zeros(
            (num_layers, kv_heads, num_slots // block_size), jnp.float32
        ),
        block_size,
        floor=(
            None
            if scale_floor is None
            else jnp.asarray(scale_floor, jnp.float32)
        ),
    )


def layer_data(cache, i):
    """The per-layer array attention kernels read (quantized or not)."""
    if is_quantized(cache):
        return cache.data[i]
    return cache[i]


def layer_scales(k_cache, v_cache, i):
    """``kv_scales`` operand for the attention ops: ``(k_scale[i],
    v_scale[i])`` (each ``[Hkv, num_pages]`` f32) or None when the
    caches are unquantized."""
    if is_quantized(k_cache):
        return (k_cache.scale[i], v_cache.scale[i])
    return None


def pool_data(k_cache, v_cache):
    """The whole ``[L, Hkv, slots, Dh]`` K and V pools (quantized or
    not), for kernels that address the layer themselves."""
    if is_quantized(k_cache):
        return k_cache.data, v_cache.data
    return k_cache, v_cache


def pool_scales(k_cache, v_cache):
    """``kv_scales`` of the whole pools: ``(k_scale, v_scale)`` (each
    ``[L, Hkv, num_pages]`` f32) or None when they are unquantized."""
    if is_quantized(k_cache):
        return (k_cache.scale, v_cache.scale)
    return None


def dequantize(x, scale):
    """Dequantize gathered page values: ``x * scale`` in f32.

    ``scale`` must broadcast against ``x`` with the trailing head-dim
    axis already expanded by the caller (one scale per page covers
    every slot and every head-dim lane of that page's tile).
    """
    return x.astype(jnp.float32) * scale


def _quantize_values(x, qdtype, qmax):
    """f32 ``x`` (already divided by scale) → storage dtype, saturating."""
    if jnp.dtype(qdtype) == jnp.dtype(jnp.int8):
        return jnp.clip(jnp.round(x), -qmax, qmax).astype(jnp.int8)
    # float8_e4m3fn has no inf: out-of-range casts become NaN, so clip
    # to the max normal first (saturation semantics, like the MXU)
    return jnp.clip(x, -qmax, qmax).astype(qdtype)


#: the TPU's minor tile dimension
LANES = 128


@functools.lru_cache(maxsize=None)
def cache_head_dim(head_dim: int, device=None) -> int:
    """Row width of a KV pool for heads of ``head_dim``: ``head_dim``
    itself wherever the device keeps it the minor axis of the
    ``[L, Hkv, slots, Dh]`` pool, else ``head_dim`` rounded up to whole
    lanes (rows zero-padded).

    A TPU lays out arrays whose last axis is narrower than a lane tile
    with ANOTHER axis minor (slots, here).  The scatter that writes the
    pool and the Pallas kernel that reads it both need Dh minor, so on
    such a device every step would re-lay the whole pool twice.  The
    question is put to the device's compiler (what layout does it give
    an argument of this shape?), not to a platform name; the CPU keeps
    every shape row-major and pads nothing.
    """
    if head_dim % LANES == 0:
        return head_dim
    from jax.sharding import SingleDeviceSharding

    device = device or jax.local_devices()[0]
    probe = jax.ShapeDtypeStruct(
        (1, 8, 4096, head_dim), jnp.bfloat16,
        sharding=SingleDeviceSharding(device),
    )
    layout = (
        jax.jit(lambda x: x).lower(probe).compile()
        .input_formats[0][0].layout
    )
    if tuple(layout.major_to_minor) == (0, 1, 2, 3):
        return head_dim
    return -(-head_dim // LANES) * LANES


def pad_to_cache_width(x: jax.Array, cache) -> jax.Array:
    """Zero-pad the last (head) axis of ``x`` to ``cache``'s row width
    (:func:`cache_head_dim`); zeros add nothing to q·k and the padded
    output columns are cut off by the caller."""
    pad = cache.shape[-1] - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _per_shard(mesh, fn, num_args):
    """``fn(pool, idx[, vals])`` over each tp shard's own
    ``[L, Hkv/tp, slots, Dh]`` block (``pool``, ``vals`` and the result
    split on the kv-head axis, ``idx`` whole).  The flat view below
    merges the sharded kv-head axis into the row axis, which the SPMD
    partitioner could only honour by re-sharding the pool; inside
    shard_map every device works on the heads it holds."""
    if mesh is None:
        return fn
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    pool = P(None, "tp", None, None)
    return shard_map(
        fn, mesh=mesh, in_specs=(pool, P(), pool)[:num_args],
        out_specs=pool, check_vma=False,
    )


def _flat_rows(data, first_layer, num_layers, idx):
    """Row numbers, in the flat ``[L·Hkv·slots, Dh]`` view of a pool, of
    slots ``idx`` under every kv head of ``num_layers`` layers from
    ``first_layer``: ``[num_layers·Hkv·n]``.  Slots past the pool
    (padding, to be dropped) stay out of bounds of the flat view too —
    there they would land in the next head's first rows."""
    _, num_kv, num_slots, head_dim = data.shape
    lead = first_layer * num_kv + jnp.arange(
        num_layers * num_kv, dtype=jnp.int32
    )
    rows = lead[:, None] * num_slots + idx[None, :]
    return jnp.where(
        idx[None, :] >= num_slots, data.size // head_dim, rows
    ).reshape(-1)


def take_slots(data, idx, mesh=None):
    """``jnp.take(data, idx, axis=2)`` → ``[L, Hkv, n, Dh]``, read
    through the flat view (see :func:`put_slots`)."""

    def local(data, idx):
        num_layers, num_kv, _, head_dim = data.shape
        return jnp.take(
            data.reshape(-1, head_dim),
            _flat_rows(data, 0, num_layers, idx), axis=0, mode="clip",
        ).reshape(num_layers, num_kv, idx.shape[0], head_dim)

    return _per_shard(mesh, local, 2)(data, idx)


def put_slots(data, idx, vals, mesh=None, layer=0):
    """``data.at[layer:layer + n, :, idx].set(vals, mode="drop")`` for
    ``data`` ``[L, Hkv, slots, Dh]`` and ``vals`` ``[n, Hkv, len(idx),
    Dh]``, written as a row scatter into the flat ``[L·Hkv·slots, Dh]``
    view.

    Same result, different program: indexed on the third axis the TPU
    compiler moves the WHOLE pool to a slots-major layout and back on
    every step (two pool-sized copies and temporaries: the step program
    of a real pool does not fit the chip).  The flat view is a bitcast
    of the pool's own layout and its scatter index is the major axis,
    so the update happens in place.
    """

    def local(data, idx, vals):
        head_dim = data.shape[-1]
        flat = data.reshape(-1, head_dim)
        flat = flat.at[_flat_rows(data, layer, vals.shape[0], idx)].set(
            vals.astype(data.dtype).reshape(-1, head_dim), mode="drop"
        )
        return flat.reshape(data.shape)

    return _per_shard(mesh, local, 3)(data, idx, vals)


def scatter_layer(cache, i, safe_slots, vals, mesh=None):
    """Scatter this step's K (or V) rows into layer ``i`` of ``cache``.

    ``vals`` is ``[T, Hkv, Dh]``; ``safe_slots`` is ``[T]`` with padding
    rows remapped to ``num_slots`` (positive out-of-bounds, dropped by
    the scatter).  For a raw cache this is EXACTLY the historical
    ``cache.at[i, :, safe_slots].set(vals.astype(dtype), mode="drop")``.

    For a quantized cache the scale sidecar updates first: every page
    whose slot 0 is among this dispatch's writes re-sets its scale from
    that row's per-head |amax| (x SCALE_MARGIN), then ALL rows quantize
    with the post-update scales and scatter.  One slot is written at
    most once per dispatch (spans are disjoint), so the scatter-max
    candidates never race.
    """
    vals = pad_to_cache_width(vals, cache)
    if not is_quantized(cache):
        return put_slots(
            cache, safe_slots, jnp.swapaxes(vals, 0, 1)[None], mesh, layer=i
        )
    data, scale = cache.data, cache.scale
    bs = cache.block_size
    num_pages = scale.shape[2]
    qmax = qmax_for(data.dtype)
    pages = safe_slots // bs  # [T]; padding rows land OOB and drop
    vt = jnp.swapaxes(vals.astype(jnp.float32), 0, 1)  # [Hkv, T, Dh]
    amax = jnp.max(jnp.abs(vt), axis=-1)  # [Hkv, T]
    setter = (safe_slots % bs == 0).astype(jnp.int32)  # [T]
    # per-page candidate amax from the slot-0 rows of THIS dispatch
    # (at most one such row per page — spans write each slot once)
    cand = (
        jnp.zeros((vt.shape[0], num_pages), jnp.float32)
        .at[:, pages]
        .max(amax * setter[None, :].astype(jnp.float32), mode="drop")
    )
    fresh = (
        jnp.zeros((num_pages,), jnp.int32)
        .at[pages]
        .max(setter, mode="drop")
    )
    set_scale = jnp.maximum(cand * SCALE_MARGIN, _EPS) / qmax
    if cache.floor is not None:
        # quantization-aware checkpoint (docs/QUANTIZATION.md
        # "Calibrated scales"): the calibrated per-head scale FLOORS
        # the amax-derived value at the slot-0 write — outlier-prone
        # heads keep the headroom the calibration measured, while the
        # floor itself never shrinks an amax that genuinely exceeds it
        set_scale = jnp.maximum(set_scale, cache.floor[i][:, None])
    layer_scale = jnp.where(
        fresh[None, :] == 1,
        set_scale,
        scale[i],
    )
    scale = scale.at[i].set(layer_scale)
    row_scale = jnp.take(
        layer_scale, jnp.clip(pages, 0, num_pages - 1), axis=1
    )  # [Hkv, T]; padding rows read garbage their scatter then drops
    q = _quantize_values(
        vt / jnp.maximum(row_scale, _EPS)[..., None], data.dtype, qmax
    )
    data = put_slots(data, safe_slots, q[None], mesh, layer=i)
    return QuantizedKVCache(data, scale, bs, floor=cache.floor)


# ------------------------------------------------- per-page movement ops
#
# The jitted entry points the host KV tier, decode checkpoints and
# prefill→decode handoffs ride (engine/runner.py gather_kv_block /
# restore_kv_block wrap these in track_jit "gather_kv" / "scatter_kv"):
# ``idx`` is always exactly one page's block_size slots, so each holds
# ONE compiled shape forever, quantized or not.  Registered in
# tools/tpulint/config.py JIT_REGISTRY.


def gather_kv_page(k_cache, v_cache, idx, mesh=None):
    """Gather one page from both caches for host-tier demotion.

    Raw caches return ``(k, v)`` slot gathers — the historical
    contract.  Quantized caches return ``(k, v, k_scale, v_scale)``
    where the scale columns are ``[L, Hkv]`` f32: the sidecar travels
    with the page into the tier entry (and through checkpoints and
    role handoffs, which reference the same entries).
    """
    if not is_quantized(k_cache):
        return take_slots(k_cache, idx, mesh), take_slots(v_cache, idx, mesh)
    page = idx[0] // k_cache.block_size
    return (
        take_slots(k_cache.data, idx, mesh),
        take_slots(v_cache.data, idx, mesh),
        k_cache.scale[:, :, page],
        v_cache.scale[:, :, page],
    )


def restore_kv_page(k_cache, v_cache, idx, *arrays, mesh=None):
    """Scatter one promoted/checkpointed page back into both caches.

    ``arrays`` is exactly what ``gather_kv_page`` produced (the tier
    stores and re-stages it verbatim, so the quantized roundtrip is
    BIT-exact — no requantization, token identity preserved).  Raw
    caches scatter values with a dtype cast, the historical behavior.
    """
    if not is_quantized(k_cache):
        k_host, v_host = arrays
        return (
            put_slots(k_cache, idx, k_host, mesh),
            put_slots(v_cache, idx, v_host, mesh),
        )
    k_host, v_host, k_scale, v_scale = arrays
    page = idx[0] // k_cache.block_size
    bs = k_cache.block_size
    return (
        QuantizedKVCache(
            put_slots(k_cache.data, idx, k_host, mesh),
            k_cache.scale.at[:, :, page].set(k_scale),
            bs,
            floor=k_cache.floor,
        ),
        QuantizedKVCache(
            put_slots(v_cache.data, idx, v_host, mesh),
            v_cache.scale.at[:, :, page].set(v_scale),
            bs,
            floor=v_cache.floor,
        ),
    )
