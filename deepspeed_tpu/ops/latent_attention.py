"""Latent (MLA) attention over the paged latent pool: the two kernels of a
``"latent"`` layer in serving (models/hybrid.py; docs/SERVING.md "The
pool contract").

The pool is ``[L, NB, bs, W]``: a token's row is ``[c (R) | k_r | 0…]``
— the normed latent, the rotated key part every head shares, zeros up to
whole 128-lane tiles (``TransformerConfig.latent_width``). No head axis.

**Absorbed** (``latent_decode``, kernel ``mla_decode``): one query
position a row. A head's query against a key is ``q~_h · c + q_rope_h ·
k_r`` with ``q~_h = q_nope_h · W_kb_h^T``, so the row ``[q~_h | q_rope_h
| 0…]`` is multiplied with the pool's rows as they lie, and the values
are the first R lanes of the same rows: an MQA whose one "KV head"
serves every query head. Grid ``(rows,)``; each step walks its
sequence's live table blocks once, ``T`` blocks a loop turn through two
VMEM slots, for all heads together — a block is read once a row a layer,
not once a head. bf16 into both dots, float32 statistics and
accumulator. ``W_kb`` / ``W_vb`` are applied outside.

**Expanded** (``latent_prefill``, kernel ``mla_prefill``): a chunk's
queries against K/V heads rebuilt from the latents of the live context.
Rebuilding costs ``2 · R · heads · (nope + v)`` FLOPs a key whatever the
chunk, so a chunk pays it once a layer and then multiplies at head
widths ``nope + rope`` and ``v`` instead of ``R + rope`` and ``R``. The
context is taken ``expand_tile`` keys at a time — a loop with a dynamic
trip count, so a short context rebuilds little — and each turn gathers
the tile's blocks, rebuilds ``k_nope`` / ``v`` (the caller's ``expand``,
under the ``kv_expand`` scope) and folds them into the running softmax
with one kernel call: grid ``(heads, query blocks, key blocks)``, the
float32 accumulator and the statistics carried from turn to turn through
HBM, aliased in and out (``m`` in lanes 0-63 of one array, ``l`` in
lanes 64-127). The key's rope part is read off the latent rows as they
lie (lanes R…W, zeros behind ``k_r``) and the query's is padded to
match, so that dot is one aligned 128-lane tile.

Where the two cross: per (query, key) pair a head costs
``2 · (R + rope + R)`` FLOPs absorbed and ``2 · (nope + rope + v)``
expanded, plus the rebuild a key. At the published widths (R 512, rope
64, nope 128, v 128, 128 heads) that is 278.5 k against 81.9 k a pair
and 33.6 M a key: equal at 33.6 M / (278.5 k − 81.9 k) = 171 query rows.
``ABSORB_MAX_QUERIES`` is the widest chunk that stays absorbed (a chunk
is bucketed to a power of two: 128 absorbed, 256 expanded).

Each has an XLA twin, the formulation off the TPU and the numeric
reference of the kernel tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_utils import on_tpu as _on_tpu
from .pallas_utils import pl, pltpu

NEG_INF = -1e30
LANES = 128
#: the widest chunk whose rows run absorbed, each as a one-position row
#: (the arithmetic above: the paths cross at 171 query rows)
ABSORB_MAX_QUERIES = 128
#: keys one loop turn of the absorbed kernel folds (on the chip, 32 rows
#: of 12,000 keys: 1.28 ms at 512, 1.10 at 1,024, 1.05 at 2,048; 4,096
#: does not fit the kernel's VMEM)
KEY_TILE = 2048
#: keys whose K/V one turn of the expanded path rebuilds: [tile, heads,
#: nope + v] bf16 is 256 MiB at the published widths. The carry's trip
#: through HBM (2 x 128 MiB read and written a turn) is paid once a
#: tile, and a context's last tile is rebuilt whole: 4,096 balances the
#: two (half a tile wasted a chunk against one carry trip a tile)
EXPAND_TILE = 4096
#: query and key rows of one grid step of the expanded kernel (on the
#: chip, a 2,048-token chunk at a context of 12,288, all 128 heads: 21.1
#: ms at 512 x 1,024, 33.3 at 1,024 x 512, 18.5 at 1,024 x 1,024, 17.0
#: at 512 x 2,048, 16.9 at 1,024 x 2,048, 17.5 at 1,024 x 4,096 -- wide
#: key blocks rescale the accumulator less often)
BLOCK_Q, BLOCK_K = 1024, 2048
_HALF = LANES // 2

# Test hook: force the Pallas path in interpreter mode off-TPU (same pattern
# as ops/paged_attention.py).
_FORCE_INTERPRET = False


def _use_pallas() -> bool:
    return _FORCE_INTERPRET or _on_tpu()


def _interpret() -> bool:
    return not _on_tpu()


# ---------------------------------------------------------------- absorbed

def _decode_kernel(layer_ref, tables_ref, ctx_ref, q_ref, pool_hbm, o_ref,
                   buf, sem, acc_ref, m_ref, l_ref, *, sm_scale: float):
    """One row: its [heads, W] queries against its sequence's live
    blocks, ``T`` blocks a turn. The pool stays in HBM; a turn's blocks
    are copied through the table into one slot of ``buf`` [2, T, bs, W]
    while the other slot's are folded."""
    _, T, bs, W = buf.shape
    keys = T * bs
    R = acc_ref.shape[-1]
    n = pl.program_id(0)
    layer = layer_ref[0]
    ctx_len = ctx_ref[n]
    last = jnp.minimum(pl.cdiv(ctx_len, bs), tables_ref.shape[1])

    def each_live(turn, slot, act):
        def one(b, _):
            act(pltpu.make_async_copy(
                pool_hbm.at[layer, tables_ref[n, b]],
                buf.at[slot, b % T], sem.at[slot]))

        lax.fori_loop(turn * T, jnp.minimum(last, turn * T + T), one, None)

    # a place no block is copied into keeps these zeros or an earlier
    # turn's rows, never a NaN for 0 · NaN to carry into the sum
    buf[...] = jnp.zeros_like(buf)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    each_live(0, 0, lambda dma: dma.start())

    def fold(turn, _):
        slot = turn % 2
        each_live(turn + 1, 1 - slot, lambda dma: dma.start())
        each_live(turn, slot, lambda dma: dma.wait())
        q = q_ref[0]                                          # [heads, W]
        kv = buf[slot].reshape(keys, W)
        s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        kvpos = turn * keys + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        s = jnp.where(kvpos < ctx_len, s, NEG_INF)            # [heads, keys]
        m_prev, l_prev = m_ref[...], l_ref[...]               # [heads, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + lax.dot_general(
            p.astype(kv.dtype), kv[:, :R], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    lax.fori_loop(0, pl.cdiv(last, T), fold, None)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)


def _decode_pallas(q, pool, layer, tables, ctx_len, rank, sm_scale):
    N, H, W = q.shape
    _, NB, bs, _ = pool.shape
    T = max(1, min(KEY_TILE // bs, tables.shape[1]))
    row = pl.BlockSpec((1, H, W), lambda n, *_: (n, 0, 0))
    return pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale),
        name="mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, rank), lambda n, *_: (n, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, T, bs, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, rank), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.maximum(tables, 0).astype(jnp.int32), ctx_len.astype(jnp.int32),
      q.astype(pool.dtype), pool)


def latent_decode_xla(q, pool, layer, tables, ctx_len, rank, sm_scale):
    """The absorbed form as a dense gather (off the TPU; the kernel's
    numeric reference): ``latent_decode``'s arguments."""
    N, H, W = q.shape
    kv = pool[layer, jnp.maximum(tables, 0)].reshape(N, -1, W)
    s = jnp.einsum("nhw,nsw->nhs", q.astype(pool.dtype), kv,
                   preferred_element_type=jnp.float32) * sm_scale
    keep = jnp.arange(kv.shape[1])[None, :] < ctx_len[:, None]
    s = jnp.where(keep[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("nhs,nsr->nhr", p.astype(pool.dtype), kv[..., :rank],
                   preferred_element_type=jnp.float32)
    return jnp.where(ctx_len[:, None, None] > 0, o / l, 0.0).astype(q.dtype)


def latent_decode(q, pool, layer, tables, ctx_len, rank: int,
                  sm_scale: float):
    """Absorbed latent attention of one query position a row.

    q [N, heads, W]: ``[q~ | q_rope | 0…]`` a head, laid out as the
    pool's rows; pool [L, NB, bs, W] read at the scalar ``layer``;
    tables [N, MB] (entries < 0 = unallocated); ctx_len [N]: the keys row
    n attends, positions ``0 … ctx_len − 1`` of its table (its own
    position + 1; 0 for a padded row, whose output is zeros). The pool
    already holds the row's own latent. Returns the attended latents
    [N, heads, rank]."""
    if _use_pallas() and pool.shape[-2] % 16 == 0 and rank % LANES == 0:
        return _decode_pallas(q, pool, layer, tables, ctx_len, rank,
                              float(sm_scale))
    return latent_decode_xla(q, pool, layer, tables, ctx_len, rank,
                             float(sm_scale))


# ---------------------------------------------------------------- expanded

def _prefill_kernel(pos_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, acc_in,
                    st_in, acc_ref, st_ref, *, sm_scale: float):
    """One (head, query block, key block) step of one tile: the running
    softmax of the block's query rows, carried in ``acc_ref`` / ``st_ref``
    (resident across the key blocks), over the tile's keys that are live
    — at or before the row's own position and inside the context."""
    bq, bk = qn_ref.shape[1], kn_ref.shape[1]
    i, j = pl.program_id(1), pl.program_id(2)
    q0 = pos_ref[0] + i * bq
    k0 = pos_ref[1] + j * bk
    ctx_len = pos_ref[2]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = acc_in[...]
        st_ref[...] = st_in[...]

    def fold(masked: bool):
        contract = (((1,), (1,)), ((), ()))
        s = (lax.dot_general(qn_ref[0], kn_ref[0], contract,
                             preferred_element_type=jnp.float32)
             + lax.dot_general(qr_ref[0], kr_ref[...], contract,
                               preferred_element_type=jnp.float32)) * sm_scale
        if masked:
            qpos = q0 + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            kpos = k0 + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            keep = (kpos <= qpos) & (kpos < ctx_len)
            s = jnp.where(keep, s, NEG_INF)
        st = st_ref[0]                                        # [bq, 128]
        m_prev, l_prev = st[:, :1], st[:, _HALF:_HALF + 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:      # a row with no live key yet: exp(NEG - NEG) = 1
            p = jnp.where(keep, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0]
        acc_ref[0] = acc_ref[0] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        lane = lax.broadcasted_iota(jnp.int32, st.shape, 1)
        st_ref[0] = jnp.where(lane < _HALF, m_new, l_new)

    # a block wholly before the first query row and inside the context
    # needs no mask (most blocks of a long context); one that the diagonal
    # or the context's end crosses builds it; one past either is skipped
    live = (k0 <= q0 + bq - 1) & (k0 < ctx_len)
    whole = (k0 + bk - 1 <= q0) & (k0 + bk <= ctx_len)
    pl.when(live & whole)(lambda: fold(False))
    pl.when(live & jnp.logical_not(whole))(lambda: fold(True))


def _prefill_tile_pallas(q_nope, q_rope, k_nope, k_r, v, acc, stats, pos,
                         sm_scale):
    H, C, dn = q_nope.shape
    Tk, dv, wr = k_nope.shape[1], v.shape[2], k_r.shape[1]
    bq, bk = min(BLOCK_Q, C), min(BLOCK_K, Tk)

    def key_block(i, j, pos):
        """The key block step (i, j) reads: j, held at the last live one
        so that a dead step copies nothing new."""
        last = jnp.minimum(pos[0] + (i + 1) * bq - 1, pos[2] - 1) - pos[1]
        return jnp.clip(last // bk, 0, j)

    q_map = lambda h, i, j, pos: (h, i, 0)                    # noqa: E731
    kv_map = lambda h, i, j, pos: (h, key_block(i, j, pos), 0)  # noqa: E731
    carry = lambda width: pl.BlockSpec((1, bq, width), q_map)   # noqa: E731
    return pl.pallas_call(
        functools.partial(_prefill_kernel, sm_scale=sm_scale),
        name="mla_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H, C // bq, Tk // bk),
            in_specs=[carry(dn), carry(wr),
                      pl.BlockSpec((1, bk, dn), kv_map),
                      pl.BlockSpec((bk, wr), lambda h, i, j, pos:
                                   (key_block(i, j, pos), 0)),
                      pl.BlockSpec((1, bk, dv), kv_map),
                      carry(dv), carry(LANES)],
            out_specs=[carry(dv), carry(LANES)]),
        out_shape=[jax.ShapeDtypeStruct(acc.shape, acc.dtype),
                   jax.ShapeDtypeStruct(stats.shape, stats.dtype)],
        # the carry is updated where it lies (operands count the scalars)
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=_interpret(),
    )(pos, q_nope, q_rope, k_nope, k_r, v, acc, stats)


def _prefill_tile_xla(q_nope, q_rope, k_nope, k_r, v, acc, stats, pos,
                      sm_scale):
    """One tile's fold in plain XLA, the kernel's arguments and carry."""
    C, Tk = q_nope.shape[1], k_nope.shape[1]
    s = (jnp.einsum("hcd,htd->hct", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("hcd,td->hct", q_rope, k_r,
                      preferred_element_type=jnp.float32)) * sm_scale
    qpos = pos[0] + jnp.arange(C)[:, None]
    kpos = pos[1] + jnp.arange(Tk)[None, :]
    keep = ((kpos <= qpos) & (kpos < pos[2]))[None]
    s = jnp.where(keep, s, NEG_INF)
    m_prev, l_prev = stats[..., :1], stats[..., _HALF:_HALF + 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jnp.einsum("hct,htd->hcd", p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
    lane = jnp.arange(LANES)[None, None, :]
    return acc, jnp.where(lane < _HALF, m_new, l_new)


def expand_tile(table_blocks: int, block_size: int) -> int:
    """The keys one turn of the expanded path rebuilds: ``EXPAND_TILE``,
    or the whole table where that is shorter; whole blocks."""
    return min(EXPAND_TILE // block_size, table_blocks) * block_size


def latent_prefill(q_nope, q_rope, pool, layer, table, start_pos, n_tokens,
                   expand, rank: int, v_width: int, sm_scale: float):
    """Expanded latent attention of one chunk row.

    q_nope [C, heads, nope] and q_rope [C, heads, rope]: the chunk's
    queries, row i at position ``start_pos + i``; pool [L, NB, bs, W] at
    ``layer``, which already holds the chunk's own latents; table [MB]:
    the row's block table; ``expand(c [T, rank]) -> (k_nope [heads, T,
    nope], v [heads, T, v_width])`` rebuilds a tile's K/V heads from its
    latents (the caller's weights and scope). Keys are live up to
    ``start_pos + n_tokens`` and causally. Returns [C, heads, v]; rows
    at or beyond ``n_tokens`` are garbage."""
    C, H, dn = q_nope.shape
    _, NB, bs, W = pool.shape
    wr = W - rank
    tile = expand_tile(table.shape[0], bs)
    pallas = (_use_pallas() and rank % LANES == 0 and dn % LANES == 0
              and C % min(BLOCK_Q, C) == 0 and tile % min(BLOCK_K, tile) == 0
              and min(C, tile) % 16 == 0)
    fold = _prefill_tile_pallas if pallas else _prefill_tile_xla
    qn = q_nope.transpose(1, 0, 2)
    qr = jnp.pad(q_rope.transpose(1, 0, 2),
                 ((0, 0), (0, 0), (0, wr - q_rope.shape[-1])))
    ctx_len = (start_pos + n_tokens).astype(jnp.int32)
    table = jnp.maximum(table, 0).astype(jnp.int32)
    # the table padded to whole tiles: a tile's slice never runs off it
    blocks = tile // bs
    padded = jnp.pad(table, (0, -table.shape[0] % blocks))

    def turn(t, carry):
        acc, stats = carry
        with jax.named_scope("kv_expand"):
            ids = lax.dynamic_slice(padded, (t * blocks,), (blocks,))
            rows = pool[layer, ids].reshape(tile, W)
            k_nope, v = expand(rows[:, :rank])
        with jax.named_scope("attend"):
            pos = jnp.stack([start_pos.astype(jnp.int32),
                             (t * tile).astype(jnp.int32), ctx_len])
            acc, stats = fold(qn, qr, k_nope, rows[:, rank:], v, acc, stats,
                              pos, float(sm_scale))
        return acc, stats

    with jax.named_scope("attend"):
        lane = jnp.arange(LANES)[None, None, :]
        init = (jnp.zeros((H, C, v_width), jnp.float32),
                jnp.broadcast_to(jnp.where(lane < _HALF, NEG_INF, 0.0),
                                 (H, C, LANES)).astype(jnp.float32))
    acc, stats = lax.fori_loop(0, -(-ctx_len // tile), turn, init)
    with jax.named_scope("attend"):
        l = jnp.maximum(stats[..., _HALF:_HALF + 1], 1e-30)
        return (acc / l).transpose(1, 0, 2).astype(q_nope.dtype)


# ------------------------------------------------------- cost, from shapes

def expand_positions(start_pos: int, n_tokens: int, tile: int) -> int:
    """Context positions whose K/V a chunk of ``n_tokens`` from
    ``start_pos`` rebuilds: its context in whole tiles."""
    return -(-(start_pos + n_tokens) // tile) * tile if n_tokens else 0
