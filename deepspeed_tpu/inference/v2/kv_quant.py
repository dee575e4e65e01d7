"""int8/fp8 KV-cache quantization for the paged ragged engine.

The paged KV pool (``ragged/manager.py``: ``[L, NB, KH, bs, D]``) is the
HBM tensor that caps servable concurrency per chip — at production batch
sizes TPU serving is capacity-bound, not FLOPs-bound (PAPERS.md: arxiv
2605.25645). Storing K/V as **symmetric int8 with one scale per
(layer, block, kv-head)** halves the per-block bytes vs bf16, so a fixed
HBM byte budget buys ~2x the blocks → ~2x the concurrent sequences
(docs/SERVING.md "KV quantization"). Scales live in dense planes
``[L, NB, KH]`` alongside the pools, indexed by the same pool block id —
a prefix-cache-shared block therefore shares its scale for free.

Write path (``paged_model.py``): a ragged chunk's KV lands in at most
``TB = (C + 2·bs - 2)//bs`` pool blocks per sequence, a *static* bound —
so the quantized write is the read-modify-write of only the touched
blocks that every pool gets (``kv_write.py``), with the code in between:

1. gather the touched int8 blocks and their scales, dequantize;
2. zero stale slots (positions >= the sequence's context length — content
   from freed tenants or speculative rollback must not leak into scales);
3. scatter the new bf16 K/V into their (block, slot) positions;
4. re-quantize the whole touched block at a **monotone** scale:
   ``max(amax/127, previous scale)`` for blocks that already hold this
   sequence's tokens, plain ``amax/127`` for freshly allocated blocks
   (which is how a freed block's stale scale is invalidated — a new
   tenant's first write ignores the plane entry, no device traffic).

The monotone rule makes steady-state decode *exact*: while the scale is
unchanged, dequantize→requantize round-trips int8 values bit-for-bit
(``round(q·s/s) = q``), so a block is only ever re-coded when a genuinely
larger activation arrives. After a ``trim_sequence`` rollback the scale
may stay inflated by trimmed drafts — re-quantization on the next write
is correct but not byte-identical to a never-drafted run, which is why
speculation under kv_quant is bounded-divergent rather than byte-lossless
(docs/SERVING.md "KV quantization" interaction matrix).

Read path: the scale planes ride into ``ops/paged_attention.py`` as extra
operands (``k_scale``/``v_scale`` ``[L, NB, KH]``, read at the layer
index like the pools); the Pallas kernel dequantizes each streamed block
in VMEM with its scalar scale, the XLA fallback multiplies the gathered
context. TP serving shards the planes over the kv-head axis exactly like
the pools.
"""

from __future__ import annotations

import jax.numpy as jnp

from ...ops.quantizer import FP8_MAX
from .kv_write import place_rows

# Symmetric int8: values in [-127, 127] (−128 unused, keeps the code
# symmetric around zero) with scale = amax / 127.
Q_MAX = 127.0
# Floor for scales so an all-zero block can't divide by zero; far below
# any real activation scale.
SCALE_EPS = 1e-8

#: quantized KV representations: int8 (PR 6) and float8_e4m3fn on the
#: reserved ``kv_quant.dtype`` surface — same pool/scale machinery, the
#: pool dtype and the qmax the scale maps amax onto are the only
#: differences (scale = amax / 448 spreads each block over e4m3's
#: dynamic range; the floating mantissa keeps small values' relative
#: precision where int8 spends its codes uniformly).
SUPPORTED_DTYPES = ("int8", "fp8_e4m3")
SUPPORTED_GRANULARITIES = ("block",)


def pool_dtype(dtype: str):
    """The jnp dtype KV pool slabs are stored as for a quantized
    representation name (both are 1 byte/element — the 2x/4x byte cut
    vs bf16/fp32 is identical; fp8 trades int8's uniform code spacing
    for floating relative precision)."""
    if dtype == "fp8_e4m3":
        return jnp.float8_e4m3fn
    return jnp.int8


def qmax_of(dtype) -> float:
    """Symmetric range limit the per-block scale maps amax onto, from a
    representation name or a pool dtype."""
    if "float8" in str(dtype) or str(dtype) == "fp8_e4m3":
        return FP8_MAX
    return Q_MAX


def validate_kv_quant(dtype: str, scale_granularity: str) -> None:
    """Reject config combinations this implementation does not encode:
    ``int8``/``fp8_e4m3`` x ``block`` (per block x kv-head x layer) are
    real; coarser scale granularities remain reserved."""
    if dtype not in SUPPORTED_DTYPES:
        raise ValueError(f"kv_quant.dtype {dtype!r} not supported "
                         f"(implemented: {SUPPORTED_DTYPES})")
    if scale_granularity not in SUPPORTED_GRANULARITIES:
        raise ValueError(
            f"kv_quant.scale_granularity {scale_granularity!r} not "
            f"supported (implemented: {SUPPORTED_GRANULARITIES})")


def kv_bytes_per_block(model_cfg, block_size: int, quant: bool,
                       dtype=None) -> int:
    """HBM bytes one KV pool block costs across the layers that keep
    per-token K/V (all of them, but for a hybrid block's recurrent
    layers): K and V slabs ``[L, KH, bs, D]`` at the pool dtype, plus
    (quantized) two f32 scale entries per (layer, kv-head). The unit of
    the fixed-byte-budget comparison: at equal ``num_blocks *
    kv_bytes_per_block`` an int8 pool holds ~2x the bf16 blocks."""
    layers = getattr(model_cfg, "num_attn_layers", model_cfg.num_layers)
    if getattr(model_cfg, "is_latent", False):
        # one leaf, a token's padded latent row: what it occupies on the
        # chip, not ``latent_dim`` numbers (never quantized: refused)
        return layers * block_size * model_cfg.latent_width \
            * jnp.dtype(dtype or model_cfg.dtype).itemsize
    slab = layers * model_cfg.kv_heads * block_size * model_cfg.head_dim
    if quant:
        return 2 * slab * 1 + 2 * layers * model_cfg.kv_heads * 4
    itemsize = jnp.dtype(dtype or model_cfg.dtype).itemsize
    return 2 * slab * itemsize


def quantized_block_write(pool, scale, new_vals, plan, layer):
    """Merge new K or V rows into layer ``layer`` of a quantized pool (the
    quantized counterpart of the reference ``linear_blocked_kv_rotary``
    scatter).

    ``pool`` [L, NB, KH, bs, D] int8 or float8_e4m3fn — the whole stacked
    cache, of which only the touched blocks of the one layer are gathered
    and scattered back, so a caller that owns the buffer (the paged
    forward's scan carry) has it updated in place; the representation is
    derived from ``pool.dtype``, so the paged forward needs no extra
    plumbing; ``scale`` [L, NB, KH] f32; ``new_vals`` [N*C, KH, D] (the
    chunk's rows, sequence-major, as ``plan`` was built). Returns the
    updated (pool, scale). The monotone-scale rule keeps steady-state
    decode exact for both representations: while the scale is unchanged,
    dequantize→requantize round-trips the stored code bit-for-bit
    (int8: ``round(q·s/s) = q``; fp8: the nearest-e4m3 cast of
    ``q·s/s`` is ``q``).
    """
    qmax = qmax_of(pool.dtype)
    old_scale = scale[layer, plan["gather_ids"]]                 # [N, TB, KH]
    deq = (pool[layer, plan["gather_ids"]].astype(jnp.float32)
           * old_scale[:, :, :, None, None])
    deq = jnp.where(plan["live_slots"][:, :, None, :, None], deq, 0.0)
    deq = place_rows(deq, new_vals, plan)
    amax = jnp.max(jnp.abs(deq), axis=(3, 4))                    # [N, TB, KH]
    prior = jnp.where(plan["has_prior"][:, :, None], old_scale, 0.0)
    new_scale = jnp.maximum(jnp.maximum(amax / qmax, prior), SCALE_EPS)
    scaled = deq / new_scale[:, :, :, None, None]
    if pool.dtype == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    else:
        # float8: the cast rounds to nearest representable — no integer
        # rounding step, and the clip keeps inf out of the pool
        q = jnp.clip(scaled, -qmax, qmax).astype(pool.dtype)
    pool = pool.at[layer, plan["scatter_ids"]].set(q, mode="drop")
    scale = scale.at[layer, plan["scatter_ids"]].set(new_scale, mode="drop")
    return pool, scale
