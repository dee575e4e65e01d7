"""Pallas paged (block-table) attention — the FastGen decode/serving hot op.

Counterpart of the reference's ragged kernel suite
(``inference/v2/kernels/ragged_ops/blocked_flash/blocked_flash.cpp`` — the
blocked flash attention over "atoms" — plus ``atom_builder/atom_builder.cpp``
which splits the ragged batch into fixed-size attention atoms). The TPU-first
design needs no atom decomposition: the grid *is* the atom walk —
``(seqs, kv_heads, table_blocks)`` with the table dimension innermost, each
step streaming one KV block from the paged pool through VMEM into an online
softmax.

- **q** [N, C, H, D]: per-sequence chunk of new tokens (C = 1 for pure
  decode; Dynamic SplitFuse feeds prompt chunks through the same path).
- **KV pool** [L, NB, KH, bs, D] plus a ``layer`` scalar: the engine's
  whole stacked cache, read where it lies — no layer's slab is sliced out
  of it. The pool's per-(layer, block, kv-head) slab is the trailing
  [bs, D] — exactly one tileable VMEM block, DMA'd directly by a BlockSpec
  index map that *dereferences the layer and the block table* (both
  scalar-prefetched, so indices are known before the body runs). A
  [NB, KH, bs, D] pool with no ``layer`` is the one-layer case.
  No [N, max_ctx, H, D] gather is ever materialized in HBM and GQA needs
  no ``jnp.repeat`` — each grid step matmuls the [G·C, D] query group
  against the shared [bs, D] KV block.
- **Dead blocks** (past a sequence's context length) are skipped by
  ``pl.when`` for compute and — because the index map clamps them to the
  sequence's last live block, and Pallas only issues a DMA when the mapped
  index changes — cost no HBM traffic either (same mechanism as the causal
  clamp in flash_attention.py).
- Masking: query row r (= g·C + ci) has global position start_pos + ci;
  KV slot s in table block b has position b·bs + s; attend iff
  kv_pos <= q_pos (causal over the shared pool) and kv_pos < ctx_len.

The XLA gather formulation (``paged_attention_xla``) remains as the
off-TPU fallback and the numeric reference for the kernel tests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_utils import on_tpu as _on_tpu
from .pallas_utils import pl, pltpu

NEG_INF = -1e30
LANES = 128
#: the most query rows (G·C) one grid step's VMEM block may hold; every
#: shape the accepted configurations run (G·C <= 1024) is under it
MAX_QUERY_ROWS = 2048

# Test hook: force the Pallas path in interpreter mode off-TPU (same pattern
# as ops/flash_attention.py).
_FORCE_INTERPRET = False


def _use_interpret() -> bool:
    return _FORCE_INTERPRET or not _on_tpu()


# ------------------------------------------------------------------- kernel

def _paged_kernel(layer_ref, tables_ref, startp_ref, ntok_ref, slopes_ref,
                  q_ref, k_ref, v_ref, *refs, block_size: int, chunk: int,
                  groups: int, sm_scale: float, alibi: bool, window: int,
                  quant: bool):
    """One (n, kh, b) grid step: fold table block b of sequence n into the
    online softmax of its [G·C, D] query group. With ``quant`` the KV
    pools are int8/fp8 and two extra SMEM operands carry sequence n's
    per-(table slot, kv-head) dequantization scales, flat [MB·KH]
    (docs/SERVING.md "KV quantization") — the block is dequantized in
    VMEM right after its DMA, so HBM only ever holds the 1-byte payload."""
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    n = pl.program_id(0)
    kh = pl.program_id(1)
    b = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(b == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx_len = startp_ref[n] + ntok_ref[n]
    live = b * block_size < ctx_len
    if window:
        # sliding window: the earliest position any query row of this chunk
        # attends is startp − window + 1 — blocks wholly before it are dead
        live = live & (b * block_size + block_size - 1
                       >= startp_ref[n] - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale        # [G*C, D]
        k = k_ref[0, 0, 0].astype(jnp.float32)                # [bs, D]
        v = v_ref[0, 0, 0].astype(jnp.float32)
        if quant:
            si = b * pl.num_programs(1) + kh
            k = k * ks_ref[0, 0, si]
            v = v * vs_ref[0, 0, si]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G*C, bs]
        # causal + context mask: q row r is chunk pos r % C at global
        # position startp + r % C; KV slot col is position b*bs + col.
        ci = lax.broadcasted_iota(jnp.int32, s.shape, 0) % chunk
        qpos = startp_ref[n] + ci
        kvpos = b * block_size + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if alibi:
            # ALiBi logit bias: slope[head] · kv_position (row r of this
            # kv-head group belongs to head kh·G + r//C). Slopes live in
            # SMEM; the static G-unroll keeps reads scalar.
            gi = lax.broadcasted_iota(jnp.int32, s.shape, 0) // chunk
            slope = jnp.zeros_like(s[:, :1])
            for g in range(groups):
                slope = jnp.where(gi[:, :1] == g, slopes_ref[kh, g], slope)
            s = s + slope * kvpos.astype(jnp.float32)
        keep = (kvpos <= qpos) & (kvpos < ctx_len)
        if window:
            keep = keep & (qpos - kvpos < window)
        s = jnp.where(keep, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]               # [G*C, 128]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(b == nb - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)


def _clamp_tables(block_tables, ctx_len, block_size, start_pos=None,
                  window=0):
    """Replace dead/unallocated table entries with the sequence's nearest
    live block id so the kernel's index map repeats it (no DMA is issued when
    the mapped block doesn't change between grid steps). Dead entries are
    those past the context length and — with a sliding window — those wholly
    before ``start_pos − window + 1``."""
    N, MB = block_tables.shape
    live_blocks = jnp.maximum(-(-ctx_len // block_size), 1)        # [N] >= 1
    cols = jnp.arange(MB)[None, :]
    last_live = jnp.clip(live_blocks - 1, 0, MB - 1)[:, None]
    idx = jnp.minimum(cols, last_live)
    if window and start_pos is not None:
        first_live = jnp.clip((start_pos - window + 1) // block_size,
                              0, MB - 1)[:, None]
        idx = jnp.maximum(idx, first_live)
    tbl = jnp.take_along_axis(block_tables, idx, axis=1)
    return jnp.maximum(tbl, 0).astype(jnp.int32)


def _stacked(k_pool, v_pool, k_scale, v_scale, layer):
    """The one calling convention underneath: stacked [L, NB, KH, bs, D]
    pools (and [L, NB, KH] scale planes) read at a ``layer`` scalar. A
    per-layer [NB, KH, bs, D] pool is layer 0 of a stack of one — a
    reshape, not a copy."""
    if k_pool.ndim == 4:
        if layer is not None:
            raise ValueError("a layer index needs the stacked "
                             "[L, NB, KH, bs, D] pool")
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    elif layer is None:
        raise ValueError("a stacked [L, NB, KH, bs, D] pool needs its layer")
    return k_pool, v_pool, k_scale, v_scale, jnp.asarray(layer, jnp.int32)


def _paged_pallas(q, k_pool, v_pool, block_tables, start_pos, n_tokens, *,
                  alibi_slopes=None, window: int = 0, sm_scale=None,
                  k_scale=None, v_scale=None, layer=None, interpret: bool):
    k_pool, v_pool, k_scale, v_scale, layer = _stacked(
        k_pool, v_pool, k_scale, v_scale, layer)
    N, C, H, D = q.shape
    _, NB, KH, bs, _ = k_pool.shape
    G = H // KH
    MB = block_tables.shape[1]
    quant = k_scale is not None
    sm_scale = 1.0 / math.sqrt(D) if sm_scale is None else float(sm_scale)

    # [N, C, H, D] -> [N, KH, G*C, D]: row r = g*C + ci
    qh = q.transpose(0, 2, 1, 3).reshape(N, KH, G * C, D)

    ctx_len = start_pos + n_tokens
    tables = _clamp_tables(block_tables, ctx_len, bs, start_pos, window)
    startp = start_pos.astype(jnp.int32)
    ntok = n_tokens.astype(jnp.int32)
    alibi = alibi_slopes is not None
    # slopes regrouped [KH, G] so the kernel reads its kv-head's row
    slopes = (jnp.asarray(alibi_slopes, jnp.float32).reshape(KH, G)
              if alibi else jnp.zeros((KH, G), jnp.float32))

    kernel = functools.partial(_paged_kernel, block_size=bs, chunk=C,
                               groups=G, sm_scale=sm_scale, alibi=alibi,
                               window=window, quant=quant)
    # index maps see every scalar-prefetch ref; only the layer and the
    # table are used
    kv_spec = pl.BlockSpec(
        (1, 1, 1, bs, D),
        lambda n, kh, b, lyr, tbl, *_: (lyr[0], tbl[n, b], kh, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, G * C, D), lambda n, kh, b, *_: (n, kh, 0, 0)),
        kv_spec, kv_spec,
    ]
    operands = [qh, k_pool, v_pool]
    if quant:
        # per-(block, kv-head) dequant scales, gathered through the
        # (clamped) block table to [N, 1, MB·KH]: one SMEM row per
        # sequence, fetched when n changes and read as a scalar at
        # b·KH + kh. A (1, 1) block of the [NB, KH] plane breaks the TPU
        # (8, 128)-or-whole-array block rule, and whole planes outgrow
        # the 1 MB of SMEM with the pool; a row's size follows the table.
        scale_spec = pl.BlockSpec((1, 1, MB * KH),
                                  lambda n, kh, b, *_: (n, 0, 0),
                                  memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        operands += [
            jnp.asarray(s, jnp.float32)[layer, tables].reshape(N, 1, MB * KH)
            for s in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N, KH, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G * C, D),
                               lambda n, kh, b, *_: (n, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G * C, D), jnp.float32),
            pltpu.VMEM((G * C, LANES), jnp.float32),
            pltpu.VMEM((G * C, LANES), jnp.float32),
        ],
    )
    out_dt = q.dtype
    o = pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, KH, G * C, D), out_dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(layer.reshape(1), tables, startp, ntok, slopes, *operands)
    # [N, KH, G*C, D] -> [N, C, H, D]
    return (o.reshape(N, KH, G, C, D).transpose(0, 3, 1, 2, 4)
            .reshape(N, C, H, D))


# ----------------------------------------------------------- XLA reference

def paged_attention_xla(q, k_pool, v_pool, block_tables, start_pos, n_tokens,
                        alibi_slopes=None, window: int = 0, sm_scale=None,
                        k_scale=None, v_scale=None, layer=None):
    """Dense-gather formulation (the pre-Pallas path): gather the table into
    [N, MB*bs, KH, D] and mask. Numerically the kernel's reference, with
    the kernel's arguments: stacked pools read at ``layer`` (only the
    table's blocks of that layer are gathered), or one layer's pool.
    ``k_scale``/``v_scale`` [L, NB, KH]: per-(block, kv-head)
    dequantization scales for int8 pools (docs/SERVING.md "KV
    quantization") — gathered through the same block table and applied to
    the gathered context."""
    k_pool, v_pool, k_scale, v_scale, layer = _stacked(
        k_pool, v_pool, k_scale, v_scale, layer)
    N, C, H, D = q.shape
    _, NB, KH, bs, _ = k_pool.shape
    G = H // KH
    MB = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(D) if sm_scale is None else float(sm_scale)

    ctx_positions = jnp.arange(MB * bs)
    tbl = jnp.maximum(block_tables, 0)
    # pool [NB, KH, bs, D] -> per-seq [N, MB, KH, bs, D] -> [N, KH, MB*bs, D]
    k_ctx = k_pool[layer, tbl]
    v_ctx = v_pool[layer, tbl]
    if k_scale is not None:
        k_ctx = (k_ctx.astype(jnp.float32)
                 * k_scale[layer, tbl][:, :, :, None, None]).astype(q.dtype)
        v_ctx = (v_ctx.astype(jnp.float32)
                 * v_scale[layer, tbl][:, :, :, None, None]).astype(q.dtype)
    k_ctx = k_ctx.transpose(0, 2, 1, 3, 4).reshape(N, KH, MB * bs, D)
    v_ctx = v_ctx.transpose(0, 2, 1, 3, 4).reshape(N, KH, MB * bs, D)

    qg = q.reshape(N, C, KH, G, D)
    s = jnp.einsum("nckgd,nksd->nkgcs", qg, k_ctx).astype(jnp.float32) * sm_scale
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(KH, G)
        s = s + (slopes[None, :, :, None, None]
                 * ctx_positions[None, None, None, None, :])
    ctx_len = (start_pos + n_tokens)[:, None]
    qpos = start_pos[:, None] + jnp.arange(C)[None, :]          # [N, C]
    causal = qpos[:, None, None, :, None] >= ctx_positions[None, None, None, None, :]
    valid = (ctx_positions[None, :] < ctx_len)[:, None, None, None, :]
    keep = causal & valid
    if window:
        keep = keep & (qpos[:, None, None, :, None]
                       - ctx_positions[None, None, None, None, :] < window)
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("nkgcs,nksd->nckgd", p, v_ctx)
    return o.reshape(N, C, H, D)


# ------------------------------------------------------------------- public

def pallas_supported(num_heads: int, kv_heads: int, head_dim: int,
                     force_interpret: bool = False) -> bool:
    """Static eligibility of the Pallas kernel for a head geometry — the
    single source of truth shared by the runtime dispatch below and the
    v2 module registry's heuristics (inference/v2/modules.py)."""
    return (kv_heads > 0 and num_heads % kv_heads == 0
            and head_dim % 8 == 0
            and (_on_tpu() or force_interpret or _FORCE_INTERPRET))


def _pallas_ok(q, k_pool) -> bool:
    N, C, H, D = q.shape
    KH = k_pool.shape[-3]
    return pallas_supported(H, KH, D)


def paged_attention(q, k_pool, v_pool, block_tables, start_pos, n_tokens,
                    alibi_slopes=None, window: int = 0, sm_scale=None,
                    k_scale=None, v_scale=None, layer=None):
    """Block-table paged attention.

    q [N, C, H, D]; k/v pool [L, NB, KH, bs, D] read at the scalar
    ``layer`` (traced or static) — the serving forward hands over its
    whole cache and no slab of it is materialised — or one layer's
    [NB, KH, bs, D] with ``layer`` left out; block_tables [N, MB]
    (entries < 0 = unallocated); start_pos/n_tokens [N]. The pool must
    already contain this chunk's K/V (write-then-attend, like the
    reference's blocked_kv_rotary-then-blocked_flash sequence).
    ``alibi_slopes`` [H]: optional ALiBi bias slopes (BLOOM-family
    serving) — bias slope·kv_position is added to the logits in-kernel.
    ``window`` > 0: sliding-window attention (Mistral serving — reference
    inference/v2/model_implementations/mistral/model.py:202); KV blocks
    wholly before the window are skipped for compute and DMA.
    ``k_scale``/``v_scale`` [L, NB, KH] (or [NB, KH] beside a one-layer
    pool): per-(block, kv-head) dequantization scales for int8 KV pools
    (docs/SERVING.md "KV quantization") — dequantization happens inside
    the kernel (VMEM) / after the gather (XLA path), so HBM only ever
    holds the int8 pool.
    Rows beyond n_tokens are garbage (masked out downstream).
    """
    if _pallas_ok(q, k_pool):
        def kernel(q, start_pos, n_tokens):
            return _paged_pallas(
                q, k_pool, v_pool, block_tables, start_pos, n_tokens,
                alibi_slopes=alibi_slopes, window=window, sm_scale=sm_scale,
                k_scale=k_scale, v_scale=v_scale, layer=layer,
                interpret=_use_interpret())

        N, C, H, _ = q.shape
        tile = max(1, MAX_QUERY_ROWS // (H // k_pool.shape[-3]))
        if C <= tile:
            return kernel(q, start_pos, n_tokens)
        # A query group of G·C rows is one VMEM block (with its float32
        # accumulator and softmax statistics): a long chunk of a wide
        # group is cut along C and each piece walks the table on its own.
        # The pool already holds the whole chunk's K/V and the mask goes
        # by position, so a piece is the same call at a later start. A
        # piece past a row's valid tokens is given a context of 0: every
        # block of its walk is dead.
        outs = []
        for c0 in range(0, C, tile):
            n_sub = jnp.clip(n_tokens - c0, 0, tile)
            outs.append(kernel(
                q[:, c0:c0 + tile],
                jnp.where(n_sub > 0, start_pos + c0, 0), n_sub))
        return jnp.concatenate(outs, axis=1)
    return paged_attention_xla(q, k_pool, v_pool, block_tables, start_pos,
                               n_tokens, alibi_slopes=alibi_slopes,
                               window=window, sm_scale=sm_scale,
                               k_scale=k_scale, v_scale=v_scale, layer=layer)
