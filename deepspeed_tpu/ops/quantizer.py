"""Blockwise int8/int4 quantization — the TPU-native quantization kernel set.

Counterpart of the reference's CUDA quantization suite
(``csrc/quantization/quantize.cu:151`` symmetric/asymmetric int4/int8 kernels,
``quantize_intX.cu``, ``pt_binding.cpp:298``): symmetric blockwise
quantization along the last dim, used by

- ZeRO++ qwZ/qgZ (``parallel/zeropp.py``): quantized weight all-gather and
  gradient all-to-all reduce (reference ``partition_parameters.py:679``
  CUDAQuantizer + ``coalesced_collectives.py:31`` all_to_all_quant_reduce);
- ZeRO-Inference weight-only quantization (``inference/quantization.py``):
  int8/int4 params dequantized on the fly (reference
  ``deepspeed/inference/quantization/layers.py``);
- optional int4 *packing* (two nibbles per int8 byte) for wire/HBM size —
  the reference's swizzled int4 layouts reduce to this on TPU since block
  layout is the compiler's job.

Format: for ``x[..., N]`` with block size ``B``, ``q[..., N]`` int8 and
``scales[..., ceil(N/B)]`` f32 with ``x ≈ q * scales`` (symmetric,
zero-point free — the TPU-friendly choice: dequant is one fused
multiply). Ragged tails (``N % B != 0``) are handled by zero-padding the
last group internally; the stored arrays keep the logical N.

``dtype="fp8_e4m3"`` stores ``q`` as ``float8_e4m3fn`` instead of int8
(same byte width, floating mantissa): ``scale = amax / 448`` maps each
group onto e4m3's dynamic range. Weight serving
(``inference/v2/weight_quant.py``) and fp8 KV pools
(``inference/v2/kv_quant.py``) both ride this entry point.

A Pallas kernel handles the (quantize, dequantize) hot pair on TPU
(interpret mode in the CPU tests, Mosaic-compiled for a described v5e in
``tests/test_tpu_compile.py``); the XLA formulation runs off TPU and for
shapes with no TPU-tileable split — a rule on the shape, read before the
call — and is the reference. :func:`quantized_matmul` is the serving hot
op: matmul straight from the quantized representation — the Pallas path
streams the 1-byte payload and applies the group scales in VMEM, the XLA
formulation fuses the dequant multiply into the dot's operand read; both
accumulate in fp32.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_utils import on_tpu as _on_tpu
from .pallas_utils import pl, pltpu

_FORCE_INTERPRET = False    # test hook (same pattern as flash_attention.py)


def _use_interpret() -> bool:
    return _FORCE_INTERPRET or not _on_tpu()


#: max finite magnitude of float8_e4m3fn — the fp8 counterpart of
#: ``qmax(8)``; group scale = amax / FP8_MAX maps each quant group onto
#: the format's full dynamic range.
FP8_MAX = 448.0


def qmax(bits: int) -> int:
    """Symmetric range limit: 127 for int8, 7 for int4."""
    return (1 << (bits - 1)) - 1


def choose_block(n: int, want: int = 128) -> int:
    """Largest divisor of n that is <= want (quant groups must tile the dim)."""
    b = min(want, n)
    while n % b != 0:
        b -= 1
    return b


def _pad_tail(x, block: int):
    """Zero-pad the last dim up to a multiple of ``block`` (ragged-tail
    support): padding is zeros, so it can neither inflate a group's amax
    nor survive the round-trip slice back to the logical width."""
    n = x.shape[-1]
    rem = n % block
    if rem == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, block - rem)]
    return jnp.pad(x, pad)


# ----------------------------------------------------------------- XLA path

def _quantize_xla(x, bits: int, block: int, dtype: str = "int8"):
    n = x.shape[-1]
    xp = _pad_tail(x.astype(jnp.float32), block)
    *lead, np_ = xp.shape
    nb = np_ // block
    xb = xp.reshape(*lead, nb, block)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    if dtype == "fp8_e4m3":
        scale = amax / FP8_MAX
        inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
        q = jnp.clip(xb * inv, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn)
    else:
        scale = amax / qmax(bits)
        inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
        q = jnp.clip(jnp.round(xb * inv), -qmax(bits),
                     qmax(bits)).astype(jnp.int8)
    q = q.reshape(*lead, np_)[..., :n]
    return q, scale[..., 0].reshape(*lead, nb)


def _dequantize_xla(q, scales, block: int, dtype):
    n = q.shape[-1]
    qp = _pad_tail(q.astype(jnp.float32), block)
    *lead, np_ = qp.shape
    nb = np_ // block
    xb = qp.reshape(*lead, nb, block)
    out = xb * scales.reshape(*lead, nb, 1)
    return out.reshape(*lead, np_)[..., :n].astype(dtype)


# -------------------------------------------------------------- Pallas path

def _quant_kernel(x_ref, q_ref, s_ref, *, bits: int, block: int):
    x = x_ref[...].astype(jnp.float32)                       # [rows, n]
    rows, n = x.shape
    xb = x.reshape(rows, n // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = amax / qmax(bits)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(xb * inv), -qmax(bits), qmax(bits))
    q_ref[...] = q.reshape(rows, n).astype(jnp.int8)
    s_ref[...] = scale[..., 0]


def _dequant_kernel(q_ref, s_ref, o_ref, *, block: int):
    q = q_ref[...].astype(jnp.float32)                       # [rows, n]
    rows, n = q.shape
    xb = q.reshape(rows, n // block, block) * s_ref[...][..., None]
    o_ref[...] = xb.reshape(rows, n).astype(o_ref.dtype)


#: bytes a kernel's tiles may claim of the 16 MB scoped VMEM a Mosaic
#: kernel gets on v5e — the rest is the compiler's for fp32 temporaries
_VMEM_TILE_BUDGET = 6 * 1024 * 1024


def _tile(dim: int, cap: int, unit: int) -> int:
    """Largest multiple of ``unit`` that divides ``dim`` and is <= ``cap``;
    0 when none does."""
    t = min(cap, dim) // unit * unit
    while t >= unit and dim % t != 0:
        t -= unit
    return t


def _row_tile(rows: int, n: int) -> int:
    """Row tile of the (quantize, dequantize) pair, sized from ``n``: an
    fp32 and a 1-byte [tile, n] block, each double-buffered, must fit the
    tile budget (a fixed 256 rows asked 20 MB of the 16 MB at n = 8192).
    Multiples of 32 — the int8 sublane tile — are preferred; 0 when no
    multiple of 8 fits."""
    cap = min(_VMEM_TILE_BUDGET // (10 * n), 256)
    return _tile(rows, cap, 32) or _tile(rows, cap, 8)


def _pallas_2d_ok(rows: int, n: int, block: int) -> bool:
    return ((_on_tpu() or _FORCE_INTERPRET)
            and n % block == 0 and n % 128 == 0 and rows % 8 == 0
            and _row_tile(rows, n) > 0)


def _quantize_pallas(x2, bits: int, block: int):
    rows, n = x2.shape
    tile_r = _row_tile(rows, n)
    kern = functools.partial(_quant_kernel, bits=bits, block=block)
    return pl.pallas_call(
        kern,
        name="quantize_blockwise",
        grid=(rows // tile_r,),
        in_specs=[pl.BlockSpec((tile_r, n), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tile_r, n), lambda i: (i, 0)),
                   pl.BlockSpec((tile_r, n // block), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, n), jnp.int8),
                   jax.ShapeDtypeStruct((rows, n // block), jnp.float32)],
        interpret=_use_interpret(),
    )(x2)


def _dequantize_pallas(q2, s2, block: int, dtype):
    rows, n = q2.shape
    tile_r = _row_tile(rows, n)
    kern = functools.partial(_dequant_kernel, block=block)
    return pl.pallas_call(
        kern,
        name="dequantize_blockwise",
        grid=(rows // tile_r,),
        in_specs=[pl.BlockSpec((tile_r, n), lambda i: (i, 0)),
                  pl.BlockSpec((tile_r, n // block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_r, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), dtype),
        interpret=_use_interpret(),
    )(q2, s2)


# ------------------------------------------------------------------- public

def _infer_block(n: int, n_groups: int, block: Optional[int]) -> int:
    """Resolve the block size for a (q, scales) pair.

    Inference assumes the canonical divisor layout (``B = N / groups``,
    what ``quantize_blockwise`` produces whenever its block tiles the
    dim — including the ``block=None`` default). A layout quantized with
    an explicit RAGGED block (``N % B != 0``) must pass the same
    ``block=`` back: the group count alone cannot reconstruct it, and
    when ``groups`` happens to divide ``N`` a wrong divisor would be
    inferred silently. The detectable half (``N % groups != 0``) is
    refused here; the contract covers the rest."""
    if block:
        return block
    if n % n_groups != 0:
        raise ValueError(
            f"cannot infer block size for N={n} with {n_groups} scale "
            "groups (ragged-tail layout) — pass the block= it was "
            "quantized with")
    return n // n_groups


def quantize_blockwise(x, bits: int = 8, block: Optional[int] = None,
                       dtype: str = "int8") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x[..., N] → (q [..., N], scales f32 [..., ceil(N/B)]).

    ``dtype="int8"`` (default): symmetric int8 (or int4 via ``bits=4`` —
    one value per int8 slot in [-7, 7]; use :func:`pack_int4` to halve
    storage/wire bytes). ``dtype="fp8_e4m3"``: float8_e4m3fn payload with
    ``scale = amax / 448``. Ragged tails (``N % B != 0``) quantize the
    short last group against its own amax — such layouts only arise from
    an explicit ragged ``block=``, and the SAME block must be passed to
    ``dequantize_blockwise``/``quantized_matmul`` (group count alone
    cannot reconstruct a ragged block; see ``_infer_block``).
    """
    n = x.shape[-1]
    block = block or choose_block(n)
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    if (dtype == "int8" and rows > 0
            and _pallas_2d_ok(rows, n, block)):
        q2, s2 = _quantize_pallas(x.reshape(rows, n), bits, block)
        return q2.reshape(x.shape), s2.reshape(*lead, n // block)
    return _quantize_xla(x, bits, block, dtype)


def dequantize_blockwise(q, scales, block: Optional[int] = None,
                         dtype=jnp.float32):
    n = q.shape[-1]
    block = _infer_block(n, scales.shape[-1], block)
    lead = q.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    if (q.dtype == jnp.int8 and rows > 0
            and _pallas_2d_ok(rows, n, block)):
        out2 = _dequantize_pallas(q.reshape(rows, n),
                                  scales.reshape(rows, n // block),
                                  block, dtype)
        return out2.reshape(q.shape)
    return _dequantize_xla(q, scales, block, dtype)


# ------------------------------------------------- quantized matmul (serving)

def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, block: int):
    """One (i, j, kk) grid step: fold ``x`` tile [bm, bk] × weight tile
    [bk, bn] into the fp32 accumulator. The scale of weight row k in
    column group g is applied to *x's column k* — ``(x · s_g) @ q_g`` is
    the same sum as ``x @ (q_g · s_g)``, and a [1, bk] scale row
    broadcasts over x's sublanes where a [bk, 1] column would need a
    (bk, 1) block the TPU lowering refuses. HBM only ever holds the
    1-byte payload + the f32 scale plane."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                       # [bm, bk]
    for g in range(q_ref.shape[1] // block):
        cols = slice(g * block, (g + 1) * block)
        acc_ref[:, cols] += lax.dot_general(
            x * s_ref[g], q_ref[:, cols].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _qmm_tiles(m: int, k: int, n: int, block: int):
    """(bm, bk, bn) for the Pallas matmul, or None when the shape has no
    TPU-tileable split: bm | m in sublane multiples; bn | n in whole
    lane-aligned scale groups; bk | k in lane multiples (x's minor dim),
    or all of a short k."""
    if block % 128 or n % block or m % 8 or k % 8:
        return None
    bm = _tile(m, 256, 8)
    bn = _tile(n, 512, block)
    bk = _tile(k, 1024, 128) or (k if k <= 1024 else 0)
    return (bm, bk, bn) if bm and bn and bk else None


def _qmm_pallas_ok(m: int, k: int, n: int, block: int) -> bool:
    return ((_on_tpu() or _FORCE_INTERPRET)
            and _qmm_tiles(m, k, n, block) is not None)


def _qmm_pallas(x2, q, s, block: int, out_dtype):
    m, k = x2.shape
    n = q.shape[-1]
    bm, bk, bn = _qmm_tiles(m, k, n, block)
    # scale plane [K, n/B] -> [n/B, 1, K]: a tile's groups are whole
    # leading rows and each row lies along lanes, like x's columns
    st = s.T.reshape(n // block, 1, k)
    kern = functools.partial(_qmm_kernel, block=block)
    return pl.pallas_call(
        kern,
        name="quantized_matmul",
        grid=(m // bm, n // bn, k // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                  pl.BlockSpec((bn // block, 1, bk),
                               lambda i, j, kk: (j, 0, kk))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(),
    )(x2, q, st)


def quantized_matmul(x, q, scales, block: Optional[int] = None,
                     out_dtype=None):
    """``x[..., K] @ dequant(q[K, N], scales[K, ceil(N/B)])`` with fp32
    accumulation — the weight-serving hot op (int8/fp8 weights,
    ``inference/v2/weight_quant.py``).

    Pallas path (TPU, shapes ``_qmm_tiles`` accepts): tiled matmul that
    reads the 1-byte payload straight from HBM — the point of weight
    quantization on memory-bound decode — and applies the group scales
    in VMEM. XLA formulation (off TPU, or shapes with no tileable
    split): dequantize-then-dot, where the dequant multiply fuses into
    the dot's operand read. Both accumulate in fp32 and agree to fp32
    rounding.
    """
    out_dtype = out_dtype or x.dtype
    kdim, n = q.shape
    block = _infer_block(n, scales.shape[-1], block)
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    if m > 0 and _qmm_pallas_ok(m, kdim, n, block):
        out2 = _qmm_pallas(x.reshape(m, kdim), q,
                           scales.astype(jnp.float32), block, out_dtype)
        return out2.reshape(*lead, n)
    w = _dequantize_xla(q, scales.astype(jnp.float32), block, jnp.float32)
    y = lax.dot_general(x.astype(jnp.float32), w,
                        (((x.ndim - 1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def pack_int4(q):
    """int8 values in [-7, 7], even last dim → packed uint8 [..., N/2]
    (low nibble = even index). The wire/HBM format for 4-bit payloads."""
    lo = (q[..., 0::2].astype(jnp.int32) & 0xF)
    hi = (q[..., 1::2].astype(jnp.int32) & 0xF) << 4
    return (lo | hi).astype(jnp.uint8)


def unpack_int4(p):
    """Inverse of :func:`pack_int4` → int8 [..., N*2]."""
    lo = (p.astype(jnp.int32) & 0xF)
    hi = (p.astype(jnp.int32) >> 4) & 0xF
    # sign-extend 4-bit two's complement
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*p.shape[:-1], p.shape[-1] * 2).astype(jnp.int8)
