"""Pallas flash attention for TPU — the training attention hot op.

Counterpart of the reference's fused attention CUDA kernels
(``csrc/transformer/ds_transformer_cuda.cpp:1055`` softmax/dropout/gemm
pipeline and the inference ``softmax.cu:562``): blocked online-softmax
attention computed entirely in VMEM, tiled to the MXU, so the [T, S]
logits matrix never materializes in HBM.

Design (round 2 — replaces the whole-[S,D] BlockSpec + XLA-recompute
backward of round 1):

- **Forward**: grid ``(B, H, T//bq, S//bkv)`` with the KV dimension
  innermost; K/V stream through the grid block-by-block while the output
  block and the online-softmax row statistics accumulate in VMEM scratch.
  VMEM holds O(bq·D + bkv·D), independent of sequence length, so long
  contexts are not VMEM-capped. The kernel saves the logsumexp rows
  (``lse = m + log l``) as a residual for the backward, lane-replicated
  to [B, H, T, 128] (the TPU-tileable row-stat layout).
- **Backward**: two Pallas kernels with the standard recompute-by-block
  formulation using the saved row statistics:
  ``dq[i] = Σ_j (p_ij ∘ (do_i v_j^T − δ_i)) k_j · scale`` and
  ``(dk_j, dv_j) = Σ_{h∈group, i} (…)``, where ``p_ij = exp(q_i k_j^T·scale
  − lse_i)`` and ``δ_i = rowsum(do_i ∘ o_i)`` (recomputed in-kernel from
  the o/do blocks — cheaper than a second replicated residual). Nothing
  of size [T, S] ever exists; each kernel is O(bq·bkv) VMEM.
- **GQA**: handled by BlockSpec *index maps* (query head h reads KV head
  ``h // group``) — no ``jnp.repeat``, no copied K/V in HBM. The dkv
  kernel accumulates over the query heads of each group in-grid, emitting
  gradients at KV-head granularity directly.

Layout convention: q [B, T, H, D], k/v [B, S, KH, D]. Causal masking
supports T != S with the usual ``row + (S−T) >= col`` offset alignment.
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
LANES = 128        # scratch lane width for row statistics (VPU register shape)
STAT_LANES = 8     # lane width of the saved lse residual (min tileable, 16x
                   # smaller than a 128-lane residual; only column 0 is read)

# Test hook: force the Pallas path in interpreter mode off-TPU so CI (CPU)
# exercises the same kernel code the TPU runs.
_FORCE_INTERPRET = False


# ----------------------------------------------------------------- fwd kernel

def _window_live(causal, window, i, j, block_q, block_kv, offs):
    """Is grid block (i, j) inside the causal / sliding-window band?

    Row r (global q position ``i·bq + r + offs``) attends to col c iff
    ``r >= c`` (causal) and ``r − c < window`` (sliding window; Mistral
    semantics — the window includes self). A KV block is dead when every
    (row, col) pair violates either bound."""
    live = True
    if causal:
        row_max = i * block_q + block_q - 1 + offs
        live = row_max >= j * block_kv
    if window:
        row_min = i * block_q + offs
        live = live & (j * block_kv + block_kv - 1 > row_min - window)
    return live


def _band_mask(s, causal, window, i, j, block_q, block_kv, offs,
               masked_val=NEG_INF):
    """Apply the causal + sliding-window mask to a [bq, bkv] logit block."""
    if not causal and not window:
        return s
    rows = i * block_q + offs + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = j * block_kv + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = rows >= cols if causal else (rows == rows)
    if window:
        keep = keep & (rows - cols < window)
    return jnp.where(keep, s, masked_val)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                causal: bool, sm_scale: float, block_q: int, block_kv: int,
                q_len: int, kv_len: int, window: int):
    """One (b, h, i, j) grid step: fold KV block j into q block i's online
    softmax. Scratch: acc [bq, D]; m/l [bq, 128] lane-replicated, f32."""
    j = pl.program_id(3)
    nj = pl.num_programs(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: KV blocks entirely above the diagonal contribute nothing.
    # Sliding window: blocks entirely before the window contribute nothing.
    offs = kv_len - q_len
    live = _window_live(causal, window, i, j, block_q, block_kv, offs)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                     # [bkv, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bkv]
        s = _band_mask(s, causal, window, i, j, block_q, block_kv, offs)
        m_prev, l_prev = m_ref[...], l_ref[...]                 # [bq, 128]
        m_cur = jnp.max(s, axis=-1, keepdims=True)              # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)                      # [bq, 128]
        alpha = jnp.exp(m_prev - m_new)
        # Self-healing invariant (do not break): a q row fully masked in
        # its first live KV block has m_new == NEG_INF, so p = exp(s -
        # NEG_INF) = exp(0) = 1 transiently pollutes acc/l. This is
        # harmless ONLY because (a) NEG_INF is finite (-1e30, never -inf:
        # -inf - -inf = nan) and (b) the KV loop ascends j with the
        # diagonal block always live, so a later block with finite max
        # rescales the garbage by alpha = exp(NEG_INF - m) = 0 exactly.
        # Reordering the loop or switching NEG_INF to -inf silently
        # corrupts windowed outputs.
        p = jnp.exp(s - m_new[:, :1])                           # [bq, bkv]
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[:, :STAT_LANES]


# --------------------------------------------------------------- dq kernel

def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
               dq_acc, *, causal: bool, sm_scale: float, block_q: int,
               block_kv: int, q_len: int, kv_len: int, window: int):
    """Grid (B, H, T//bq, S//bkv); accumulates dq for q block i over KV."""
    j = pl.program_id(3)
    nj = pl.num_programs(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    offs = kv_len - q_len
    live = _window_live(causal, window, i, j, block_q, block_kv, offs)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                     # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                     # [bkv, D]
        v = v_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)                   # [bq, D]
        lse = lse_ref[0, 0][:, :1]                              # [bq, 1]
        delta = jnp.sum(do * o, axis=-1, keepdims=True)         # [bq, 1]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)                                    # [bq, bkv]
        p = _band_mask(p, causal, window, i, j, block_q, block_kv, offs,
                       masked_val=0.0)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale                        # [bq, bkv]
        dq_acc[...] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _flush():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


# -------------------------------------------------------------- dkv kernel

def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                sm_scale: float, block_q: int, block_kv: int, q_len: int,
                kv_len: int, num_q_blocks: int, window: int):
    """Grid (B, KH, S//bkv, group*T//bq): accumulate dk/dv for KV block j
    over all query blocks of all query heads sharing this KV head (GQA)."""
    t = pl.program_id(3)
    nt = pl.num_programs(3)
    j = pl.program_id(2)
    i = t % num_q_blocks       # query block within the current query head

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    offs = kv_len - q_len
    live = _window_live(causal, window, i, j, block_q, block_kv, offs)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                     # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                     # [bkv, D]
        v = v_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]                              # [bq, 1]
        delta = jnp.sum(do * o, axis=-1, keepdims=True)         # [bq, 1]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - lse)                                    # [bq, bkv]
        p = _band_mask(p, causal, window, i, j, block_q, block_kv, offs,
                       masked_val=0.0)
        dv_acc[...] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc[...] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _flush():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


# ------------------------------------------------------- pallas entry points

def _use_interpret() -> bool:
    return _FORCE_INTERPRET or not _on_tpu()


def _block_sizes(T, S, block_q, block_kv):
    return min(block_q, T), min(block_kv, S)


def _pallas_ok(T, S, D, block_q, block_kv) -> bool:
    bq, bkv = _block_sizes(T, S, block_q, block_kv)
    # bq/bkv are sublane/lane-facing block dims → multiples of 128; D blocks
    # always cover the whole head dim, so any multiple of 8 is tileable.
    return (T % bq == 0 and S % bkv == 0
            and D % 8 == 0 and bq % 128 == 0 and bkv % 128 == 0)


def _dim_sem(n):
    return pltpu.CompilerParams(
        dimension_semantics=tuple(["parallel"] * (n - 1) + ["arbitrary"]))


def _causal_kv_clamp(causal, bq, bkv, offs, window=0):
    """Index-map clamp: map fully-masked (above-diagonal, and — with a
    sliding window — before-the-window) KV blocks back to the nearest live
    block. Pallas only issues a DMA when the mapped block index *changes*
    between consecutive grid steps, so the dead iterations (skipped by
    ``pl.when`` in-kernel) also fetch nothing — restoring the KV-traffic
    saving of a band-trimmed loop without a data-dependent grid."""
    def clamp(i, j):
        if not causal and not window:
            return j
        out = j
        if window:
            first = jnp.maximum((i * bq + offs - window + 1) // bkv, 0)
            out = jnp.maximum(out, first)
        if causal:
            diag = jnp.maximum((i * bq + bq - 1 + offs) // bkv, 0)
            out = jnp.minimum(out, diag)
        return out
    return clamp


def _fwd_pallas(q, k, v, causal, block_q, block_kv, window, sm_scale=None,
                *, interpret):
    B, T, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    group = H // KH
    bq, bkv = _block_sizes(T, S, block_q, block_kv)
    sm_scale = 1.0 / math.sqrt(D) if sm_scale is None else float(sm_scale)
    # head-major views: q [B,H,T,D], k/v [B,KH,S,D]
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    clamp = _causal_kv_clamp(causal, bq, bkv, S - T, window)
    grid = (B, H, T // bq, S // bkv)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=sm_scale, block_q=bq,
        block_kv=bkv, q_len=T, kv_len=S, window=window)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j: (b, h // group, clamp(i, j), 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j: (b, h // group, clamp(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, STAT_LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=_dim_sem(4),
        interpret=interpret,
    )(qh, kh, vh)
    return o, lse        # o in head-major [B,H,T,D]; caller transposes


def _bwd_pallas(q, k, v, o_hm, lse, g, causal, block_q, block_kv, window,
                sm_scale=None, *, interpret):
    B, T, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    group = H // KH
    bq, bkv = _block_sizes(T, S, block_q, block_kv)
    sm_scale = 1.0 / math.sqrt(D) if sm_scale is None else float(sm_scale)

    qh = q.transpose(0, 2, 1, 3)         # [B,H,T,D]
    kh = k.transpose(0, 2, 1, 3)         # [B,KH,S,D]
    vh = v.transpose(0, 2, 1, 3)
    doh = g.transpose(0, 2, 1, 3)        # [B,H,T,D]

    nqb = T // bq
    clamp = _causal_kv_clamp(causal, bq, bkv, S - T, window)
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bkv, D),
                           lambda b, h, i, j: (b, h // group, clamp(i, j), 0))
    stat_spec = pl.BlockSpec((1, 1, bq, STAT_LANES),
                             lambda b, h, i, j: (b, h, i, 0))
    dq_kernel = functools.partial(
        _dq_kernel, causal=causal, sm_scale=sm_scale, block_q=bq,
        block_kv=bkv, q_len=T, kv_len=S, window=window)
    dqh = pl.pallas_call(
        dq_kernel,
        name="flash_attention_dq",
        grid=(B, H, nqb, S // bkv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, stat_spec],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_dim_sem(4),
        interpret=interpret,
    )(qh, kh, vh, o_hm, doh, lse)

    # dk/dv: grid walks every (group member, q block) pair for each KV block;
    # query-side specs decode (head, q block) from the flattened index t.
    # Causal: q blocks entirely before the KV block are dead — clamp them up
    # to the first live q block so their DMAs coalesce away (see
    # _causal_kv_clamp for the mechanism). Sliding window: q blocks entirely
    # past the window are dead — clamp them down to the last live q block.
    offs = S - T

    def q_block(j, t):
        i = t % nqb
        if not causal and not window:
            return i
        if causal:
            num = j * bkv - offs - bq + 1
            i_min = jnp.clip(-((-num) // bq), 0, nqb - 1)
            i = jnp.maximum(i, i_min)
        if window:
            i_max = jnp.clip((j * bkv + bkv + window - 2 - offs) // bq,
                             0, nqb - 1)
            i = jnp.minimum(i, i_max)
        return i

    def q_map(b, kh_, j, t):
        return (b, kh_ * group + t // nqb, q_block(j, t), 0)

    qg_spec = pl.BlockSpec((1, 1, bq, D), q_map)
    kvg_spec = pl.BlockSpec((1, 1, bkv, D), lambda b, kh_, j, t: (b, kh_, j, 0))
    statg_spec = pl.BlockSpec((1, 1, bq, STAT_LANES), q_map)
    dkv_kernel = functools.partial(
        _dkv_kernel, causal=causal, sm_scale=sm_scale, block_q=bq,
        block_kv=bkv, q_len=T, kv_len=S, num_q_blocks=nqb, window=window)
    dkh, dvh = pl.pallas_call(
        dkv_kernel,
        name="flash_attention_dkv",
        grid=(B, KH, S // bkv, group * nqb),
        in_specs=[qg_spec, kvg_spec, kvg_spec, qg_spec, qg_spec, statg_spec],
        out_specs=[
            pl.BlockSpec((1, 1, bkv, D), lambda b, kh_, j, t: (b, kh_, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, kh_, j, t: (b, kh_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KH, S, D), k.dtype),
            jax.ShapeDtypeStruct((B, KH, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, D), jnp.float32),
            pltpu.VMEM((bkv, D), jnp.float32),
        ],
        compiler_params=_dim_sem(4),
        interpret=interpret,
    )(qh, kh, vh, o_hm, doh, lse)

    return (dqh.transpose(0, 2, 1, 3), dkh.transpose(0, 2, 1, 3),
            dvh.transpose(0, 2, 1, 3))


# ------------------------------------------------------------------- reference

def _attention_xla(q, k, v, causal: bool, window: int = 0, sm_scale=None):
    """Grouped-head XLA attention reference (no KV repeat: einsum over the
    [KH, group] factorization)."""
    B, T, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    group = H // KH
    scale = 1.0 / math.sqrt(D) if sm_scale is None else float(sm_scale)
    qg = q.reshape(B, T, KH, group, D)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) * scale
    qpos = jnp.arange(T)[:, None] + (S - T)
    kpos = jnp.arange(S)[None, :]
    if causal:
        s = jnp.where((qpos >= kpos)[None, None, None], s, NEG_INF)
    if window:
        s = jnp.where((qpos - kpos < window)[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v)
    return o.reshape(B, T, H, D)


# ------------------------------------------------------------------ public api

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_kv: int = 512, window: int = 0, sm_scale=None):
    """Blocked flash attention; Pallas on TPU, XLA elsewhere.

    q: [B, T, H, D]; k/v: [B, S, KH, D] with H % KH == 0 (GQA/MQA).
    ``window`` > 0 enables sliding-window attention (Mistral semantics:
    query position p attends to key positions (p − window, p]; requires
    ``causal=True``). Blocks wholly outside the band are skipped for both
    compute and HBM traffic (reference parity:
    inference/v2/model_implementations/mistral/model.py:202).
    """
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_kv, window,
                        sm_scale)
    return out


def pallas_enabled(q, k, block_q, block_kv) -> bool:
    """Will ``flash_attention`` run the Pallas kernels for these operands
    (tileable shape, on TPU) or the XLA formulation? Depends on T, S and D
    only, so a batch/head shard answers as the whole does."""
    B, T, H, D = q.shape
    S = k.shape[1]
    if not _pallas_ok(T, S, D, block_q, block_kv):
        return False
    return _on_tpu() or _FORCE_INTERPRET


def _flash_fwd(q, k, v, causal, block_q, block_kv, window=0, sm_scale=None):
    if window and not causal:
        raise ValueError("sliding window requires causal attention")
    if pallas_enabled(q, k, block_q, block_kv):
        o_hm, lse = _fwd_pallas(q, k, v, causal, block_q, block_kv, window,
                                sm_scale, interpret=_use_interpret())
        return o_hm.transpose(0, 2, 1, 3), (q, k, v, o_hm, lse)
    o = _attention_xla(q, k, v, causal, window, sm_scale)
    return o, (q, k, v, None, None)


def _flash_bwd(causal, block_q, block_kv, window, sm_scale, res, g):
    q, k, v, o_hm, lse = res
    if o_hm is not None and pallas_enabled(q, k, block_q, block_kv):
        return _bwd_pallas(q, k, v, o_hm, lse, g, causal, block_q, block_kv,
                           window, sm_scale, interpret=_use_interpret())
    _, vjp = jax.vjp(
        lambda q, k, v: _attention_xla(q, k, v, causal, window, sm_scale),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
