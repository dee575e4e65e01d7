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
``TB = ceil((C-1)/bs) + 2`` pool blocks per sequence, a *static* bound —
so the quantized write is a read-modify-write of only the touched blocks:

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
operands (``k_scale``/``v_scale`` ``[NB, KH]`` per layer); the Pallas
kernel dequantizes each streamed block in VMEM with its scalar scale, the
XLA fallback multiplies the gathered context. TP serving shards the
planes over the kv-head axis exactly like the pools.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from ...ops.quantizer import FP8_MAX

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
    """HBM bytes one KV pool block costs across all layers: K and V slabs
    ``[L, KH, bs, D]`` at the pool dtype, plus (quantized) two f32 scale
    entries per (layer, kv-head). The unit of the fixed-byte-budget
    comparison: at equal ``num_blocks * kv_bytes_per_block`` an int8 pool
    holds ~2x the bf16 blocks."""
    slab = (model_cfg.num_layers * model_cfg.kv_heads * block_size
            * model_cfg.head_dim)
    if quant:
        return 2 * slab * 1 + 2 * model_cfg.num_layers * model_cfg.kv_heads * 4
    itemsize = jnp.dtype(dtype or model_cfg.dtype).itemsize
    return 2 * slab * itemsize


def blocks_for_budget(budget_bytes: int, model_cfg, block_size: int,
                      quant: bool, dtype=None) -> int:
    """How many pool blocks a KV byte budget buys at this representation
    (bench's concurrency-at-fixed-HBM comparison; at least 1)."""
    return max(1, int(budget_bytes)
               // kv_bytes_per_block(model_cfg, block_size, quant, dtype))


def touched_block_plan(block_tables, start_pos, n_tokens, chunk: int,
                       block_size: int, num_blocks: int) -> Dict[str, object]:
    """Static-shape plan of the pool blocks this step's KV writes touch.

    A row writing ``n_tokens`` new tokens from ``start_pos`` lands in the
    logical blocks ``start_pos//bs .. (start_pos+n_tokens-1)//bs`` — at
    most ``TB = (C-1)//bs + 2`` of them for a chunk width C, regardless of
    alignment. The plan is layer-invariant (same coordinates for every
    layer's pool), so ``paged_model`` computes it once per forward and
    closes over it in the scanned layer body.

    Ownership invariant (why the full-block scatter back is safe): the
    touched window starts at ``start_pos//bs``, and every block at or past
    that index belongs exclusively to the writing sequence — prefix-cache
    sharing only ever covers *full* blocks strictly below the matched
    length (block-aligned), trims into indexed blocks are refused, and
    padding rows (``n_tokens == 0``) produce an empty window.
    """
    N, MB = block_tables.shape
    bs = block_size
    TB = (chunk - 1) // bs + 2
    ctx_len = start_pos + n_tokens                                   # [N]
    first_blk = start_pos // bs                                      # [N]
    tidx = first_blk[:, None] + jnp.arange(TB)[None, :]              # [N, TB]
    ids = jnp.take_along_axis(block_tables,
                              jnp.clip(tidx, 0, MB - 1), axis=1)     # [N, TB]
    touched = (tidx * bs < ctx_len[:, None]) & (tidx < MB) & (ids >= 0)
    # gather side clamps (garbage rows are masked below); scatter side
    # uses the positive out-of-range sentinel NB, which mode="drop"
    # really drops (-1 would wrap — same trick as the unquantized write)
    gather_ids = jnp.where(touched, jnp.clip(ids, 0, num_blocks - 1), 0)
    scatter_ids = jnp.where(touched, ids, num_blocks)
    # live KV slots of each touched block: global position < ctx_len.
    # Slots past that hold stale content (freed tenant / trimmed drafts)
    # and are zeroed so they can neither inflate the scale nor survive
    # the re-quantized write-back.
    slot_pos = tidx[:, :, None] * bs + jnp.arange(bs)[None, None, :]
    live_slots = (slot_pos < ctx_len[:, None, None]) & touched[:, :, None]
    # per-token scatter coordinates into the gathered [N, TB, ...] view
    positions = start_pos[:, None] + jnp.arange(chunk)[None, :]      # [N, C]
    valid = jnp.arange(chunk)[None, :] < n_tokens[:, None]
    t_tok = positions // bs - first_blk[:, None]                     # [N, C]
    n_flat = jnp.repeat(jnp.arange(N), chunk)
    t_flat = jnp.where(valid, t_tok, TB).reshape(-1)                 # TB drops
    slot_flat = (positions % bs).reshape(-1)
    # blocks already holding this sequence's quantized tokens keep a
    # monotone scale; a freshly allocated block ignores the stale plane
    # entry of its previous tenant (the "scale invalidation on free")
    has_prior = (tidx * bs < start_pos[:, None]) & touched
    return {"gather_ids": gather_ids, "scatter_ids": scatter_ids,
            "live_slots": live_slots, "has_prior": has_prior,
            "n_flat": n_flat, "t_flat": t_flat, "slot_flat": slot_flat}


def quantized_block_write(pool, scale, new_vals, plan):
    """Merge new K or V rows into a quantized pool (the quantized
    counterpart of the reference ``linear_blocked_kv_rotary`` scatter).

    ``pool`` [NB, KH, bs, D] int8 or float8_e4m3fn — the representation
    is derived from ``pool.dtype``, so the paged forward needs no extra
    plumbing; ``scale`` [NB, KH] f32; ``new_vals`` [N*C, KH, D] (row
    order matches ``plan``'s flattened token coordinates). Returns the
    updated (pool, scale). The monotone-scale rule keeps steady-state
    decode exact for both representations: while the scale is unchanged,
    dequantize→requantize round-trips the stored code bit-for-bit
    (int8: ``round(q·s/s) = q``; fp8: the nearest-e4m3 cast of
    ``q·s/s`` is ``q``).
    """
    qmax = qmax_of(pool.dtype)
    deq = (pool[plan["gather_ids"]].astype(jnp.float32)
           * scale[plan["gather_ids"]][:, :, :, None, None])
    deq = jnp.where(plan["live_slots"][:, :, None, :, None], deq, 0.0)
    deq = deq.at[plan["n_flat"], plan["t_flat"], :, plan["slot_flat"], :].set(
        new_vals.astype(jnp.float32), mode="drop")
    amax = jnp.max(jnp.abs(deq), axis=(3, 4))                    # [N, TB, KH]
    prior = jnp.where(plan["has_prior"][:, :, None],
                      scale[plan["gather_ids"]], 0.0)
    new_scale = jnp.maximum(jnp.maximum(amax / qmax, prior), SCALE_EPS)
    scaled = deq / new_scale[:, :, :, None, None]
    if pool.dtype == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    else:
        # float8: the cast rounds to nearest representable — no integer
        # rounding step, and the clip keeps inf out of the pool
        q = jnp.clip(scaled, -qmax, qmax).astype(pool.dtype)
    pool = pool.at[plan["scatter_ids"]].set(q, mode="drop")
    scale = scale.at[plan["scatter_ids"]].set(new_scale, mode="drop")
    return pool, scale
