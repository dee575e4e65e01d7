"""Pallas paged (block-table) attention — the FastGen decode/serving hot op.

Counterpart of the reference's ragged kernel suite
(``inference/v2/kernels/ragged_ops/blocked_flash/blocked_flash.cpp`` — the
blocked flash attention over "atoms" — plus ``atom_builder/atom_builder.cpp``
which splits the ragged batch into fixed-size attention atoms). The TPU-first
design needs no atom decomposition: the grid is ``(seqs, kv_heads / KHt)``
and a loop inside each step walks the sequence's **live** table blocks,
``T`` at a time, from HBM through VMEM into an online softmax. The cost
follows the live KV bytes and the query-key pairs, not the table's width.

- **q** [N, C, H, D]: per-sequence chunk of new tokens (C = 1 for pure
  decode; Dynamic SplitFuse feeds prompt chunks through the same path).
- **KV pool** [L, NB, KH, bs, D] plus a ``layer`` scalar: the engine's
  whole stacked cache, read where it lies (``memory_space=pl.ANY``) — no
  layer's slab is sliced out of it. A block's [KHt, bs, D] slab (all of
  [KH, bs, D] when the step holds every head: contiguous) is copied by
  ``make_async_copy`` at ``[layer, table[n, b], heads]`` — layer, table and
  lengths are scalar-prefetched — into one of two buffer slots, while the
  other slot's T blocks are folded; the copy in flight is the walk's next
  turn or, under a walk's last turn, **the first turn of the grid step
  after it**: the grid runs in order and the buffers outlive a step, so a
  batch of short contexts reads its K/V back to back instead of exposing
  one copy a sequence (``_paged_kernel`` has the pairing of starts and
  waits). A [NB, KH, bs, D] pool with no
  ``layer`` is the one-layer case. No [N, max_ctx, H, D] gather is ever
  materialized in HBM and GQA needs no ``jnp.repeat`` — each turn's two
  dots are batched over the heads: q [KHt, G·C, D] · k [KHt, T·bs, D].
- **The walk** runs from the sliding window's first live block to the
  context's last, ``ceil(live / T)`` turns read from ``start_pos +
  n_tokens``: a slot past the context (or of a padded row with no tokens)
  costs no grid step, no copy and no arithmetic, and its table entry is
  never dereferenced. Inside a turn, a block place that is not live is
  not copied and its scores are masked; the V buffer starts the call as
  zeros, so such a place holds zeros or an earlier turn's rows (this
  walk's or one before it), never a NaN for 0 · NaN to carry into the sum.
- **Tiles**: ``KHt`` and ``T`` come from the shapes the call sees (G·C, D,
  the local KH, bs, the dtypes) against ``VMEM_BUDGET`` (``_tiles``):
  blocks first, up to ``KEY_TILE`` keys a turn, then every head that
  fits — all heads for a one-token step, one for a 1,024-row group.
- **Precision**: q, k and v enter both dots as the pool stores them
  (bf16 as served) with float32 accumulation; ``sm_scale`` multiplies the
  scores, so q is not rounded again; the softmax statistics and the
  accumulator are float32; p is cast to the pool's dtype for p · v (what
  ``paged_attention_xla`` does). An int8/fp8 payload is converted to the
  query's dtype — exactly — and its per-(block, kv-head) scales multiply
  the scores (K) and the probabilities (V) instead of the tile.
- Masking: query row r (= g·C + ci) has global position start_pos + ci;
  KV slot s in table block b has position b·bs + s; attend iff
  kv_pos <= q_pos (causal over the shared pool) and kv_pos < ctx_len
  (and, under a window, kv_pos > q_pos − window). **A chunk step folds
  the turns that lie wholly inside every row's view without the mask**:
  a step of a lane tile of query rows or more a K/V head
  (``_stages_scores``) keeps a turn's scores in a VMEM scratch of its
  own, and rewrites them there with the mask only where a pair can be
  masked. A turn is inside every row's view when each key of it lies at
  or before the step's first query position (so inside the context too)
  and inside the window of its last — decided from the step's scalars
  before the tile is touched (``_unmasked_span``). The mask would have
  kept each of its scores, so no bit of the output moves; of a long
  chunk's turns only those on the diagonal and at the window's tail
  still pay the mask's compares and select. Such a turn never holds a
  place no block was copied into: its first key lies at or after the
  window's first live block and its last before the context's end. A
  one-token step masks every turn (a few registers a head: the branch
  would cost more than the mask), and so does
  ``paged_attention_masked``, whose block mask is per query and per
  block.

The XLA gather formulation (``paged_attention_xla``) remains as the
off-TPU fallback and the numeric reference for the kernel tests.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .pallas_utils import on_tpu as _on_tpu
from .pallas_utils import pl, pltpu

NEG_INF = -1e30
LANES = 128
#: the most query rows (G·C) one grid step may hold; every shape the
#: accepted configurations run (G·C <= 1024) is under it
MAX_QUERY_ROWS = 2048
#: bytes of VMEM one grid step may claim — its query and output blocks,
#: the float32 accumulator and softmax statistics, both slots of the K and
#: V buffers and the score tiles (``_step_bytes``). Under the 16 MiB of
#: scoped VMEM a v5e core grants a kernel by default, with room for what
#: the compiler adds.
VMEM_BUDGET = 12 * 2 ** 20
#: the most keys one loop turn's dots span; the heads a step holds are
#: whatever the budget leaves beside them
KEY_TILE = 512

# Test hook: force the Pallas path in interpreter mode off-TPU (same pattern
# as ops/flash_attention.py).
_FORCE_INTERPRET = False
# Test hook: no step stages its scores, so every turn is masked — the
# reference the staged steps' outputs are held to, bit for bit.
_MASK_EVERY_TURN = False


def _use_interpret() -> bool:
    return _FORCE_INTERPRET or not _on_tpu()


# -------------------------------------------------------------------- tiles

def _step_bytes(kh_t: int, blocks: int, rows: int, head_dim: int,
                block_size: int, q_dtype, pool_dtype) -> int:
    """VMEM one grid step claims with ``kh_t`` KV heads and ``blocks``
    table blocks a loop turn, from the shapes alone."""
    q_b, pool_b = jnp.dtype(q_dtype).itemsize, jnp.dtype(pool_dtype).itemsize
    rows = -(-rows // 16) * 16                    # a bf16 sublane tile
    keys = blocks * block_size
    per_head = (
        2 * 2 * rows * head_dim * q_b             # q and o blocks, pipelined
        + rows * head_dim * 4                     # accumulator
        + 2 * rows * LANES * 4                    # running max and sum
        + 2 * 2 * keys * head_dim * pool_b        # K and V, two slots each
        + 3 * rows * keys * 4)                    # scores, p, p as stored
    if pool_b == 1:
        per_head += 2 * keys * head_dim * q_b     # the payload, converted
    return kh_t * per_head


def _tiles(rows: int, head_dim: int, kv_heads: int, block_size: int,
           table_blocks: int, q_dtype, pool_dtype):
    """``(KHt, T)``: the KV heads a grid step holds and the table blocks a
    loop turn folds, from what the call sees. Blocks first, up to
    ``KEY_TILE`` keys (a dot over one 64-key block fills half the MXU's
    columns and re-scales the accumulator eight times as often); then
    every head the budget leaves room for — all of them for a one-token
    step, one for a long chunk of a wide group."""
    def fits(kh_t, blocks):
        return _step_bytes(kh_t, blocks, rows, head_dim, block_size,
                           q_dtype, pool_dtype) <= VMEM_BUDGET

    blocks = max(1, min(KEY_TILE // block_size, table_blocks))
    while blocks > 1 and not fits(1, blocks):
        blocks //= 2
    kh_t = max((d for d in range(1, kv_heads + 1)
                if kv_heads % d == 0 and fits(d, blocks)), default=1)
    return kh_t, blocks


#: ``(min, max, div)`` for the kernel's int32 scalars, none negative —
#: ``lax`` and not ``jnp``: the kernel is traced and lowered once a
#: forward program, and a ``jnp`` ``//``, ``%`` or ``where`` is a nested
#: function of a dozen equations where these are one — and for the host's
#: arrays
_SCALARS = lax.min, lax.max, lax.div
_ARRAYS = np.minimum, np.maximum, np.floor_divide


def _live_blocks(start_pos, n_tokens, block_size: int, window: int,
                 slots: int, ops=_SCALARS):
    """The table blocks [first, last) a walk covers: up to the context's
    end (and the table's) and, with a sliding window, from the earliest
    position any query row of the chunk attends, start_pos − window + 1.
    One rule for the kernel's scalars and for the host's count of its
    grid steps (``grid_steps``, ``ops=_ARRAYS``)."""
    least, most, div = ops
    last = least(div(start_pos + n_tokens + (block_size - 1), block_size),
                 slots)
    first = div(most(start_pos - (window - 1), 0), block_size) \
        if window else 0
    return first, last


def _stages_scores(rows: int) -> bool:
    """Whether a step of ``rows`` (G·C) query rows a K/V head keeps a
    turn's float32 scores in a VMEM scratch of its own and masks there
    only the turns that hold a masked pair (``_paged_kernel``): a chunk
    step's — a lane tile of rows or more. A step of fewer rows, a
    one-token step above all, holds its scores in a few registers a
    head and its mask is a few dozen vector operations: the branch round
    it costs more than it saves there (measured: PERF.md section 6,
    PR 59), and it masks every turn."""
    return rows >= LANES


def _unmasked_span(start_pos, ctx_len, chunk: int, window: int,
                   ops=_SCALARS):
    """The key positions [lo, hi) that every query row of a step attends:
    at or before the step's *first* query position and inside the context
    and, with a sliding window, inside the window of its *last* query
    position, start_pos + chunk − 1 (``lo`` may be negative: no key is).
    A turn that lies wholly inside the span holds no pair the mask would
    drop. One rule for the kernel's scalars and for the host's count
    (``grid_steps``, ``ops=_ARRAYS``), as ``_live_blocks`` is."""
    return (start_pos + (chunk - window) if window else 0,
            ops[0](start_pos + 1, ctx_len))


# ------------------------------------------------------------------- kernel

class _Walk(NamedTuple):
    """A grid step's walk, as scalars: its row of the table, the first
    K/V head it holds, its live blocks [first, last) and the turns
    [lo, hi) that hold them."""
    row: Any
    head0: Any
    first: Any
    last: Any
    lo: Any
    hi: Any


def _paged_kernel(layer_ref, tables_ref, startp_ref, ntok_ref, slopes_ref,
                  q_ref, k_hbm, v_hbm, *refs, chunk: int, groups: int,
                  kv_heads: int, sm_scale: float, alibi: bool, window: int,
                  quant: bool, by_head: bool = False, masked: bool = False):
    """One (n, head group) grid step: sequence n's [KHt, G·C, D] query
    rows against its live table blocks, ``T`` blocks a loop turn. The
    pools stay in HBM; a turn's blocks are copied through the table into
    one slot of the [2, KHt, T, bs, D] buffers while the other slot's are
    folded into the online softmax, and the loop runs from the window's
    first live block to the context's last — a table slot past it costs
    nothing. With ``quant`` the pools are int8/fp8 and two SMEM operands
    carry sequence n's per-(table slot, kv-head) dequantization scales,
    flat [MB·KH] (docs/SERVING.md "KV quantization"): the payload goes
    into the dots as it is (converted to the query's dtype, which holds it
    exactly) and the scales multiply the scores and the probabilities.

    **A copy stays in flight across grid steps.** The grid runs in order
    (``"arbitrary"``), and the buffers, the semaphores and two SMEM
    scalars outlive a step. Every fold of a turn first starts the copies
    of *the turn after it*: the walk's next turn or, from the walk's last
    turn, the first turn of the grid step after this one — (n, h + 1),
    else (n + 1, 0), read from the same scalars that step will read —
    into the slot the fold is not reading. ``state[1]`` tells that step
    its first turn is on its way, ``state[0]`` the slot it lands in (a
    running count of turns, not ``turn % 2`` of each walk: a walk's last
    turn and the next walk's first must not meet in one slot). The
    pairing of starts and waits, per semaphore ``sem[K/V, slot]`` and per
    block copy of [KHt, bs, D]:

    - the copies of a walk's first turn are started once — by the grid
      step before it, from its last fold, if that step walks at least one
      turn and this one does too; else by this step, before its loop —
      and waited once, by the fold of that turn;
    - the copies of a later turn are started by the fold of the turn
      before it and waited by its own fold;
    - both sides take a turn's live blocks from the same rule
      (``span``), so a wait names as many block copies as were started;
      a step that walks nothing (a padded row, a piece past a row's
      tokens) starts nothing, waits for nothing and is fetched nothing,
      and the last grid step fetches nothing: no copy outlives the call.

    The two variants of a block-sparse layer (a step holds one K/V head
    in both). ``by_head``: the table is a K/V head's own, row ``n ·
    kv_heads + head`` of [N·KH, W] — the blocks that head's query
    selected, in order, the query's own block last, so that the walk,
    the context's length and the causal mask read it as a context of its
    own (``paged_attention_select``). ``masked``: one more operand, the
    step's [C, MB'] int8 mask of the table blocks each query position
    attends; a turn's T columns are spread over its keys by a product
    with a 0/1 matrix on the matrix unit and join the causal mask
    (``paged_attention_masked``).

    **The mask, where a pair can be masked.** A step of many query
    rows (``_stages_scores``: a chunk step) is handed one scratch more,
    [KHt, G·C, T·bs] float32: a turn's scores go there from the first
    dot, and one ``pl.when`` rewrites them in place with the mask if the
    turn holds a pair the mask would drop. A turn whose keys all lie inside ``_unmasked_span`` — at or before the
    step's first query position and inside the context; under a window,
    inside the window of the step's last query position — holds none,
    and is folded as it is. Not a second fold: the kernel is traced and
    lowered once a forward program, and the dots, the scales, ALiBi's
    bias and the online softmax are the one copy every turn runs. Every
    place of such a turn was copied (the walk's ``first`` lies at or
    before it, the context's end after it), so no stale row of ``k_buf``
    meets an unmasked score. A step with no such scratch — a one-token
    step, every step of ``masked``, every step under
    ``_MASK_EVERY_TURN`` (the tests' reference) — masks every turn."""
    if quant:
        ks_ref, vs_ref, *refs = refs
    if masked:
        mask_ref, *refs = refs
    o_ref, k_buf, v_buf, sem, state, acc_ref, m_ref, l_ref, *rest = refs
    s_ref = rest[0] if rest else None       # a chunk step's scores
    _, kh_t, T, bs, D = k_buf.shape
    rows, keys = q_ref.shape[2], T * bs
    last_slot = tables_ref.shape[1] - 1
    n, h = pl.program_id(0), pl.program_id(1)
    n_seqs = startp_ref.shape[0]
    layer = layer_ref[0]

    def walk(n, h):
        """Grid step (n, h)'s walk (``_live_blocks``); the step after
        the grid's last walks nothing."""
        row = lax.min(n, n_seqs - 1)
        first, last = _live_blocks(startp_ref[row], ntok_ref[row], bs,
                                   window, last_slot + 1)
        last = lax.select(n < n_seqs, last, jnp.int32(0))
        return _Walk(row * kv_heads + h if by_head else row, h * kh_t,
                     first, last, lax.div(first, T) if window else 0,
                     pl.cdiv(last, T))

    mine = walk(n, h)
    kh0, lo, hi = mine.head0, mine.lo, mine.hi
    startp = startp_ref[n]
    ctx_len = startp + ntok_ref[n]
    # the walk of the grid step after this one
    wrap = h + 1 == kv_heads // kh_t
    nxt = walk(n + wrap.astype(jnp.int32),
               lax.select(wrap, jnp.int32(0), h + 1))

    def span(w, turn):
        """The live blocks of walk ``w``'s turn, [b0, b1): none past the
        walk's last turn, none of a walk with no live block."""
        return lax.max(w.first, turn * T), lax.min(w.last, turn * T + T)

    # a loop over a turn's live blocks, not unrolled and with no branch a
    # block: the kernel is traced once for every forward program (54 a
    # dense engine), and each cond or loop it holds is host time at set-up
    def each_live(w, b0, b1, slot, act):
        def one(b, _):
            for i, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                act(pltpu.make_async_copy(
                    hbm.at[layer, tables_ref[w.row, b], pl.ds(w.head0, kh_t)],
                    buf.at[slot, :, lax.rem(b, T)], sem.at[i, slot]))

        lax.fori_loop(b0, b1, one, None)

    def start(w, b0, b1, slot):
        each_live(w, b0, b1, slot, lambda dma: dma.start())

    # a place of a turn that no block is copied into keeps what it held:
    # an earlier turn's rows — this walk's or a walk's before it, finite —
    # or the zeros of the call's first step, never the NaN that would
    # reach the sum through 0 · NaN (its scores are masked). Once a call:
    # a later step's zeroing would wipe the turn fetched for it
    @pl.when((n == 0) & (h == 0))
    def _():
        v_buf[...] = jnp.zeros_like(v_buf)
        state[0] = 0
        state[1] = 0

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    # the slot of this walk's first turn, and whether the step before
    # this one has started its copies: else they start here
    slot0, primed = state[0], state[1]
    b0, b1 = span(mine, lo)
    start(mine, b0, lax.select(primed == 1, b0, b1), slot0)
    state[0] = (slot0 + lax.max(hi - lo, 0)) & 1
    state[1] = ((hi > lo) & (nxt.hi > nxt.lo)).astype(jnp.int32)

    # q row r = g·C + ci sits at global position startp + ci; ALiBi's
    # slope belongs to head (kh0 + k)·G + g. Neither moves with the turn.
    row = lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1)
    qpos = startp + (row % chunk if groups > 1 else row)
    if alibi:
        # slopes live in SMEM; the static unroll keeps the reads scalar
        slope = jnp.stack([
            functools.reduce(
                lambda acc, g: jnp.where(row[0] // chunk == g,
                                         slopes_ref[kh0 + k, g], acc),
                range(groups), jnp.zeros((rows, 1), jnp.float32))
            for k in range(kh_t)])                            # [KHt, G·C, 1]

    if s_ref is not None:
        # the key positions every row of the step attends
        seen_lo, seen_hi = _unmasked_span(startp, ctx_len, chunk, window)

    key_blk = lax.broadcasted_iota(jnp.int32, (1, keys), 1) // bs

    def key_scales(ref, turn):
        """[KHt, 1, T·bs]: each key's block scale, from SMEM scalars."""
        return jnp.stack([
            functools.reduce(
                lambda acc, t: jnp.where(
                    key_blk == t,
                    ref[0, 0, jnp.minimum(turn * T + t, last_slot)
                        * kv_heads + kh0 + k], acc),
                range(T), jnp.zeros((1, keys), jnp.float32))
            for k in range(kh_t)])

    def fold(turn, _):
        slot = (slot0 + turn - lo) & 1
        # the turn after this one: the walk's, or the next grid step's
        # first, by scalar selects — one loop of starts either way
        more = turn + 1 < hi
        ahead = _Walk(*(lax.select(more, a, b) for a, b in zip(mine, nxt)))
        start(ahead, *span(ahead, lax.select(more, turn + 1, nxt.lo)),
              1 - slot)
        each_live(mine, *span(mine, turn), slot, lambda dma: dma.wait())
        q = q_ref[0]                                          # [KHt, G·C, D]
        k = k_buf[slot].reshape(kh_t, keys, D)
        v = v_buf[slot].reshape(kh_t, keys, D)
        if quant:
            k, v = k.astype(q.dtype), v.astype(q.dtype)
        s = lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
        if quant:
            s = s * key_scales(ks_ref, turn)
        key0 = turn * keys
        kvpos = key0 + lax.broadcasted_iota(jnp.int32, (1, 1, keys), 2)
        if alibi:
            s = s + slope * kvpos.astype(jnp.float32)

        def mask(s):
            # causal over the shared pool, and inside the context
            keep = (kvpos <= qpos) & (kvpos < ctx_len)
            if window:
                keep = keep & (kvpos > qpos - window)
            if masked:
                # the turn's T mask columns lie inside one 128-lane tile
                # (T is a power of two): the tile times E[l, key] = (l is
                # the key's block) gives each key its block's bit
                lane0 = pl.multiple_of((turn * T) // LANES * LANES, LANES)
                tile = mask_ref[0, 0, :, pl.ds(lane0, LANES)]     # [C, 128]
                spread = (lax.broadcasted_iota(jnp.int32, (LANES, keys), 0)
                          == turn * T - lane0
                          + lax.broadcasted_iota(jnp.int32, (LANES, keys), 1)
                          // bs)
                bit = jnp.dot(tile.astype(jnp.float32).astype(jnp.bfloat16),
                              spread.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)  # [C, T·bs]
                if groups > 1:
                    bit = jnp.concatenate([bit] * groups, axis=0)
                keep = keep & (bit[None] > 0.5)
            return jnp.where(keep, s, NEG_INF)

        if s_ref is not None:
            # the scores wait in the step's scratch, and a turn inside
            # every row's view (``_unmasked_span``) is folded as it is:
            # the mask would keep each of its scores. One guard round
            # the mask's few equations, not a second fold — and the
            # guard stays where it is: most of what a chunk call gains
            # comes with the boundary it puts between the first dot and
            # the softmax, not with the arithmetic it skips (PERF.md
            # section 7, "What PR 59 leaves open")
            s_ref[...] = s
            edge = key0 + keys > seen_hi
            if window:
                edge = edge | (key0 < seen_lo)

            @pl.when(edge)
            def _():
                s_ref[...] = mask(s_ref[...])

            s = s_ref[...]                             # [KHt, G·C, T·bs]
        else:
            s = mask(s)
        m_prev, l_prev = m_ref[...], l_ref[...]               # [KHt, G·C, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., :1])
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        if quant:
            p = p * key_scales(vs_ref, turn)
        acc_ref[...] = acc_ref[...] * alpha[..., :1] + lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    lax.fori_loop(lo, hi, fold, None)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l[..., :1]).astype(o_ref.dtype)


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
                  k_scale=None, v_scale=None, layer=None, interpret: bool,
                  by_head: bool = False, block_mask=None):
    """``by_head``: ``block_tables`` is [N·KH, W], a K/V head's own row
    (``_paged_kernel``). ``block_mask`` [N, KH, C, MB'] int8, MB' whole
    lane tiles: the table blocks each query position attends."""
    k_pool, v_pool, k_scale, v_scale, layer = _stacked(
        k_pool, v_pool, k_scale, v_scale, layer)
    N, C, H, D = q.shape
    _, NB, KH, bs, _ = k_pool.shape
    G = H // KH
    MB = block_tables.shape[1]
    quant = k_scale is not None
    masked = block_mask is not None
    sm_scale = 1.0 / math.sqrt(D) if sm_scale is None else float(sm_scale)
    kh_t, T = _tiles(G * C, D, KH, bs, MB, q.dtype, k_pool.dtype)
    if by_head or masked:
        # one K/V head a step (its own table, its own mask), and a turn
        # of 2^i blocks: its mask columns never straddle a lane tile
        kh_t, T = 1, 1 << (T.bit_length() - 1)
    # a block mask is per query and per block: every turn is masked
    staged = not (masked or _MASK_EVERY_TURN) and _stages_scores(G * C)

    # [N, C, H, D] -> [N, KH, G*C, D]: row r = g*C + ci
    qh = q.transpose(0, 2, 1, 3).reshape(N, KH, G * C, D)
    if not quant:
        qh = qh.astype(k_pool.dtype)      # the MXU gets what the pool holds
    # an unallocated slot (< 0) is never live; 0 keeps its entry a block id
    tables = jnp.maximum(block_tables, 0).astype(jnp.int32)
    alibi = alibi_slopes is not None
    # slopes regrouped [KH, G] so the kernel reads its kv-heads' rows
    slopes = (jnp.asarray(alibi_slopes, jnp.float32).reshape(KH, G)
              if alibi else jnp.zeros((KH, G), jnp.float32))

    kernel = functools.partial(_paged_kernel, chunk=C, groups=G, kv_heads=KH,
                               sm_scale=sm_scale, alibi=alibi, window=window,
                               quant=quant, by_head=by_head, masked=masked)
    q_spec = pl.BlockSpec((1, kh_t, G * C, D), lambda n, h, *_: (n, h, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, pool_spec, pool_spec]
    operands = [qh, k_pool, v_pool]
    if quant:
        # per-(block, kv-head) dequant scales, gathered through the block
        # table to [N, 1, MB·KH]: one SMEM row per sequence, fetched when
        # n changes and read as a scalar at b·KH + kh. A (1, 1) block of
        # the [NB, KH] plane breaks the TPU (8, 128)-or-whole-array block
        # rule, and whole planes outgrow the 1 MB of SMEM with the pool;
        # a row's size follows the table.
        scale_spec = pl.BlockSpec((1, 1, MB * KH), lambda n, h, *_: (n, 0, 0),
                                  memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        operands += [
            jnp.asarray(s, jnp.float32)[layer, tables].reshape(N, 1, MB * KH)
            for s in (k_scale, v_scale)]
    if masked:
        in_specs.append(pl.BlockSpec((1, 1) + block_mask.shape[2:],
                                     lambda n, h, *_: (n, h, 0, 0)))
        operands.append(block_mask)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N, KH // kh_t),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, kh_t, T, bs, D), k_pool.dtype),
            pltpu.VMEM((2, kh_t, T, bs, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),                 # [K/V, slot]
            pltpu.SMEM((2,), jnp.int32),     # first turn's slot, fetched?
            pltpu.VMEM((kh_t, G * C, D), jnp.float32),
            pltpu.VMEM((kh_t, G * C, LANES), jnp.float32),
            pltpu.VMEM((kh_t, G * C, LANES), jnp.float32),
            # a chunk step's scores of a turn (``_step_bytes`` counts them)
            *([pltpu.VMEM((kh_t, G * C, T * bs), jnp.float32)]
              if staged else []),
        ],
    )
    o = pl.pallas_call(
        kernel,
        name="paged_attention_select" if by_head
        else "paged_attention_mask" if masked else "paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, KH, G * C, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # in order: a step's last turn fetches for the step after it
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(layer.reshape(1), tables, start_pos.astype(jnp.int32),
      n_tokens.astype(jnp.int32), slopes, *operands)
    # [N, KH, G*C, D] -> [N, C, H, D]
    return (o.reshape(N, KH, G, C, D).transpose(0, 3, 1, 2, 4)
            .reshape(N, C, H, D))


# ----------------------------------------------------------- XLA reference

def paged_attention_xla(q, k_pool, v_pool, block_tables, start_pos, n_tokens,
                        alibi_slopes=None, window: int = 0, sm_scale=None,
                        k_scale=None, v_scale=None, layer=None,
                        block_mask=None):
    """Dense-gather formulation (the pre-Pallas path): gather the table into
    [N, MB*bs, KH, D] and mask. Numerically the kernel's reference, with
    the kernel's arguments: stacked pools read at ``layer`` (only the
    table's blocks of that layer are gathered), or one layer's pool.
    ``k_scale``/``v_scale`` [L, NB, KH]: per-(block, kv-head)
    dequantization scales for int8 pools (docs/SERVING.md "KV
    quantization") — gathered through the same block table and applied to
    the gathered context. ``block_mask`` [N, C, KH, MB]: the table blocks
    each query position attends (``paged_attention_masked``)."""
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
    if block_mask is not None:
        keep = keep & (jnp.repeat(block_mask, bs, axis=-1) > 0
                       ).transpose(0, 2, 1, 3)[:, :, None]
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


def _chunk_tile(chunk: int, group: int) -> int:
    """The tokens of one piece of a chunk whose query group (``group``
    query heads a KV head) is over ``MAX_QUERY_ROWS`` rows: the largest
    divisor of the chunk that fits, so that the pieces are alike (one
    kernel, no ragged last piece) whatever the group — 6 heads a KV head
    cut a 1,024-token chunk into four pieces of 256, as 8 do."""
    most = max(1, MAX_QUERY_ROWS // group)
    if chunk <= most:
        return chunk
    return max(d for d in range(1, most + 1) if chunk % d == 0)


def _pieces(chunk: int, tile: int, start_pos, n_tokens, xp=jnp):
    """A chunk cut along C into pieces of ``tile`` positions: ``(c0,
    start_pos, n_tokens)`` of each. The pool already holds the whole
    chunk's K/V and the mask goes by position, so a piece is the same
    call at a later start. A piece past a row's valid tokens is given a
    context of 0: every block of its walk is dead."""
    for c0 in range(0, chunk, tile):
        n_sub = xp.clip(n_tokens - c0, 0, tile)
        yield c0, xp.where(n_sub > 0, start_pos + c0, 0), n_sub


@functools.cache
def _grid_shape(chunk, group, head_dim, kv_heads, block_size, table_blocks,
                q_dtype, pool_dtype):
    """``(tile, head groups, T, staged)``: the positions of one piece of
    a call's chunk, the grid steps a row of a piece takes, the table
    blocks one of its turns folds and whether a step's scores wait in a
    scratch (``_stages_scores``) — a forward asks this of the same few
    shapes, put after put."""
    tile = _chunk_tile(chunk, group)
    kh_t, blocks = _tiles(group * tile, head_dim, kv_heads, block_size,
                          table_blocks, q_dtype, pool_dtype)
    return tile, kv_heads // kh_t, blocks, _stages_scores(group * tile)


def grid_steps(start_pos, n_tokens, *, chunk: int, heads: int, kv_heads: int,
               head_dim: int, block_size: int, table_blocks: int,
               window: int = 0, q_dtype=jnp.bfloat16, pool_dtype=jnp.bfloat16):
    """``(steps, primed, turns, unmasked)`` of one ``paged_attention``
    call, on the host from its rows' ``start_pos`` / ``n_tokens`` (numpy)
    and its shapes: the (row, head group) grid steps that walk at least
    one turn, over the call's pieces; those of them whose first turn the
    grid step before them had fetched — a live step behind a live step of
    the same piece; the turns those steps fold, and the turns of them
    that a step with its scores in a scratch folds without the mask,
    those wholly inside every row's view (``_paged_kernel``: the
    kernel's own rules, ``_chunk_tile``, ``_pieces``, ``_tiles``,
    ``_live_blocks``, ``_stages_scores`` and ``_unmasked_span``, on
    numbers)."""
    tile, head_groups, T, staged = _grid_shape(
        chunk, heads // kv_heads, head_dim, kv_heads, block_size,
        table_blocks, jnp.dtype(q_dtype), jnp.dtype(pool_dtype))
    keys = T * block_size
    steps = primed = turns = unmasked = 0
    for _, start, n_sub in _pieces(chunk, tile, np.asarray(start_pos),
                                   np.asarray(n_tokens), xp=np):
        first, last = _live_blocks(start, n_sub, block_size, window,
                                   table_blocks, ops=_ARRAYS)
        live = last > first
        rows = int(live.sum())
        steps += head_groups * rows
        # a row's later head groups follow its own; its first follows the
        # last of the row before it
        primed += (head_groups - 1) * rows + int((live[1:] & live[:-1]).sum())
        # the walk's turns [lo, hi), and those of a staged step inside
        # the span every row of the piece attends
        lo, hi = first // T, -(-last // T)
        turns += head_groups * int(((hi - lo) * live).sum())
        if staged:
            seen_lo, seen_hi = _unmasked_span(start, start + n_sub, tile,
                                              window, ops=_ARRAYS)
            inside = np.minimum(hi, seen_hi // keys) \
                - np.maximum(lo, -(-np.maximum(seen_lo, 0) // keys))
            unmasked += head_groups * int((np.maximum(inside, 0) * live).sum())
    return steps, primed, turns, unmasked


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
    inference/v2/model_implementations/mistral/model.py:202); the walk
    starts at the window's first live block.
    ``k_scale``/``v_scale`` [L, NB, KH] (or [NB, KH] beside a one-layer
    pool): per-(block, kv-head) dequantization scales for int8 KV pools
    (docs/SERVING.md "KV quantization") — the kernel applies them to the
    scores and the probabilities, the XLA path to the gathered context,
    so HBM only ever holds the int8 pool.
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
        tile = _chunk_tile(C, H // k_pool.shape[-3])
        if C <= tile:
            return kernel(q, start_pos, n_tokens)
        # A query group of G·C rows is one VMEM block (with its float32
        # accumulator and softmax statistics): a long chunk of a wide
        # group is cut along C and each piece walks the table on its own
        # (``_pieces``).
        return jnp.concatenate(
            [kernel(q[:, c0:c0 + tile], start, n_sub) for c0, start, n_sub
             in _pieces(C, tile, start_pos, n_tokens)], axis=1)
    return paged_attention_xla(q, k_pool, v_pool, block_tables, start_pos,
                               n_tokens, alibi_slopes=alibi_slopes,
                               window=window, sm_scale=sm_scale,
                               k_scale=k_scale, v_scale=v_scale, layer=layer)


# ------------------------------------------------ block-sparse (InfLLM-V2)

def _select_context(n_blocks, positions, block_size: int):
    """A one-token row's selected table read as a context of its own:
    ``(start_pos, n_tokens)`` — the row's own block is the table's last,
    so its keys run to ``(n_blocks − 1) · bs + position % bs``; a padded
    row (no blocks) has no token."""
    real = n_blocks > 0
    return (jnp.where(real, (n_blocks - 1) * block_size
                      + positions % block_size, 0).astype(jnp.int32),
            real.astype(jnp.int32))


def paged_attention_select(q, k_pool, v_pool, tables, n_blocks, positions,
                           sm_scale=None, layer=None):
    """One-token rows over the blocks each K/V head selected — **the
    kernel reads those blocks and no other**. q [N, 1, H, D]; pools
    [L, NB, KH, bs, D] read at ``layer``; ``tables`` [N, KH, W]: pool
    block ids in the order of their positions, the row's own block last
    among its ``n_blocks`` [N] (0: a padded row); ``positions`` [N]: the
    query's position, which says how far into its own block it sees.
    Every other selected block lies wholly in its past, so the table is
    walked as a context of ``(n_blocks − 1) · bs + position % bs + 1``
    keys (kernel ``paged_attention_select``; the XLA gather off the
    chip)."""
    N, _, H, D = q.shape
    KH, bs = k_pool.shape[-3], k_pool.shape[-2]
    start, ntok = _select_context(n_blocks, positions, bs)
    if _pallas_ok(q, k_pool):
        return _paged_pallas(
            q, k_pool, v_pool, tables.reshape(N * KH, -1), start, ntok,
            sm_scale=sm_scale, layer=layer, interpret=_use_interpret(),
            by_head=True)
    k_pool, v_pool, _, _, layer = _stacked(k_pool, v_pool, None, None, layer)
    sm_scale = 1.0 / math.sqrt(D) if sm_scale is None else float(sm_scale)
    W = tables.shape[-1]
    heads = jnp.arange(KH)[None, :, None]
    tbl = jnp.maximum(tables, 0)
    k_ctx = k_pool[layer, tbl, heads].reshape(N, KH, W * bs, D)
    v_ctx = v_pool[layer, tbl, heads].reshape(N, KH, W * bs, D)
    s = jnp.einsum("nkgd,nksd->nkgs", q.reshape(N, KH, H // KH, D), k_ctx
                   ).astype(jnp.float32) * sm_scale
    keep = jnp.arange(W * bs)[None, :] < (start + ntok)[:, None]
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("nkgs,nksd->nkgd", p, v_ctx).reshape(N, 1, H, D)


def paged_attention_masked(q, k_pool, v_pool, block_tables, start_pos,
                           n_tokens, block_mask, sm_scale=None, layer=None):
    """Chunk rows under a block mask: ``paged_attention`` in which query
    position c of row n attends table block b of K/V head h only where
    ``block_mask[n, c, h, b]`` is set (int8 [N, C, KH, MB]), causal
    inside it. Every live block is walked, the mask decides what enters
    the softmax (kernel ``paged_attention_mask``)."""
    if not _pallas_ok(q, k_pool):
        return paged_attention_xla(q, k_pool, v_pool, block_tables,
                                   start_pos, n_tokens, sm_scale=sm_scale,
                                   layer=layer, block_mask=block_mask)
    N, C, H, _ = q.shape
    MB = block_mask.shape[-1]
    # [N, KH, C, MB'] with the blocks on whole lane tiles
    mask = jnp.pad(block_mask.transpose(0, 2, 1, 3).astype(jnp.int8),
                   ((0, 0), (0, 0), (0, 0), (0, -MB % LANES)))
    tile = _chunk_tile(C, H // k_pool.shape[-3])
    outs = [_paged_pallas(
        q[:, c0:c0 + tile], k_pool, v_pool, block_tables, start, n_sub,
        sm_scale=sm_scale, layer=layer, interpret=_use_interpret(),
        block_mask=mask[:, :, c0:c0 + tile])
        for c0, start, n_sub in _pieces(C, tile, start_pos, n_tokens)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
