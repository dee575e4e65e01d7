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
is bucketed to a power of two: 128 absorbed, 256 expanded); with a window
the rebuild is of ``window + chunk`` keys for ``window`` pairs a query and
the crossing moves (``absorb_max_queries``: 300 rows, so 256, at R 1024,
nope 192, v 128 and a window of 513).

**A window** (a ``"latent_window"`` layer): both walks start at the block
of ``pos − window + 1`` and mask what lies before it; the window group's
blocks behind it are gone from the table (-1) and are never read.

**A selection** (a ``"latent_sparse"`` layer; models/hybrid.py
``index_*``): ``index_score`` (kernel ``index_score``) walks the paged
index-key pool ``[L, NB, bs, D]`` of a row's live table and writes ``Σ_h
w_h · relu(q_h · k_s)`` for each key, ``INDEX_QUERIES`` query positions
of one sequence a grid step (they share the walk). The top-k over those
scores is exact and no sort (``hybrid.index_select`` / ``index_keep``:
the k-th largest score counted out bit by bit, then a compaction of the
kept positions or the mask; ``lax.top_k`` sorts the row whole on the
TPU). A one-position row then attends its selected rows only, in
ascending order of position: ``latent_sparse_decode`` gathers the selected
``[c | k_r]`` rows through the table (an XLA gather, under ``attend``)
and the kernel ``mla_sparse_decode`` runs the absorbed product over
them, one grid step a row. A chunk attends expanded under the
selection's mask (``latent_prefill``'s ``keep``): each query's own set,
the same result, and the rebuild is shared by the chunk's queries.

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
#: the widest chunk whose rows run absorbed, each as a one-position row,
#: of a whole-context kind (the arithmetic above: the paths cross at 171
#: query rows: ``absorb_max_queries(512, 128, 64, 128)``)
ABSORB_MAX_QUERIES = 128
#: query positions of one sequence that a grid step of ``index_score``
#: scores against each tile of keys it copies (their scores [8 x 64, 2048]
#: float32 are 4 MiB of VMEM)
INDEX_QUERIES = 8
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
                   buf, sem, acc_ref, m_ref, l_ref, *, sm_scale: float,
                   window: int = 0):
    """One row: its [heads, W] queries against its sequence's live
    blocks, ``T`` blocks a turn. The pool stays in HBM; a turn's blocks
    are copied through the table into one slot of ``buf`` [2, T, bs, W]
    while the other slot's are folded. ``window``: the walk starts at
    the block of the window's first key, and ``tables_ref`` holds the
    row's table from that block on (a whole table a query position of a
    chunk would not fit the scalar memory)."""
    _, T, bs, W = buf.shape
    keys = T * bs
    R = acc_ref.shape[-1]
    n = pl.program_id(0)
    layer = layer_ref[0]
    ctx_len = ctx_ref[n]
    if window:
        first = jnp.maximum(ctx_len - window, 0) // bs
        last = jnp.minimum(pl.cdiv(ctx_len, bs), first + tables_ref.shape[1])
    else:
        first = 0
        last = jnp.minimum(pl.cdiv(ctx_len, bs), tables_ref.shape[1])

    def each_live(turn, slot, act):
        def one(b, _):
            at = b - first if window else b
            act(pltpu.make_async_copy(
                pool_hbm.at[layer, tables_ref[n, at]],
                buf.at[slot, at % T], sem.at[slot]))

        if window:
            lax.fori_loop(first + turn * T,
                          jnp.minimum(last, first + turn * T + T), one, None)
        else:
            lax.fori_loop(turn * T, jnp.minimum(last, turn * T + T), one,
                          None)

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
        if window:
            kvpos = kvpos + first * bs
            s = jnp.where((kvpos < ctx_len) & (kvpos >= ctx_len - window),
                          s, NEG_INF)
        else:
            s = jnp.where(kvpos < ctx_len, s, NEG_INF)        # [heads, keys]
        m_prev, l_prev = m_ref[...], l_ref[...]               # [heads, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + lax.dot_general(
            p.astype(kv.dtype), kv[:, :R], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    lax.fori_loop(0, pl.cdiv(last - first if window else last, T), fold,
                  None)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)


def _decode_pallas(q, pool, layer, tables, ctx_len, rank, sm_scale,
                   window=0):
    N, H, W = q.shape
    _, NB, bs, _ = pool.shape
    T = max(1, min(KEY_TILE // bs, tables.shape[1]))
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale)
    if window:      # a window's keys lie in window / bs + 1 blocks
        width = min(-(-window // bs) + 1, tables.shape[1])
        T = min(T, width)
        kernel = functools.partial(kernel, window=window)
        first = jnp.maximum(ctx_len - window, 0) // bs
        tables = jnp.take_along_axis(tables, jnp.minimum(
            first[:, None] + jnp.arange(width)[None, :],
            tables.shape[1] - 1), axis=1)
    row = pl.BlockSpec((1, H, W), lambda n, *_: (n, 0, 0))
    return pl.pallas_call(
        kernel,
        name="mla_window_decode" if window else "mla_decode",
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


def latent_decode_xla(q, pool, layer, tables, ctx_len, rank, sm_scale,
                      window=0):
    """The absorbed form as a dense gather (off the TPU; the kernel's
    numeric reference): ``latent_decode``'s arguments."""
    N, H, W = q.shape
    kv = pool[layer, jnp.maximum(tables, 0)].reshape(N, -1, W)
    s = jnp.einsum("nhw,nsw->nhs", q.astype(pool.dtype), kv,
                   preferred_element_type=jnp.float32) * sm_scale
    keep = jnp.arange(kv.shape[1])[None, :] < ctx_len[:, None]
    if window:
        keep &= jnp.arange(kv.shape[1])[None, :] >= ctx_len[:, None] - window
    s = jnp.where(keep[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("nhs,nsr->nhr", p.astype(pool.dtype), kv[..., :rank],
                   preferred_element_type=jnp.float32)
    return jnp.where(ctx_len[:, None, None] > 0, o / l, 0.0).astype(q.dtype)


def latent_decode(q, pool, layer, tables, ctx_len, rank: int,
                  sm_scale: float, window: int = 0):
    """Absorbed latent attention of one query position a row.

    q [N, heads, W]: ``[q~ | q_rope | 0…]`` a head, laid out as the
    pool's rows; pool [L, NB, bs, W] read at the scalar ``layer``;
    tables [N, MB] (entries < 0 = unallocated); ctx_len [N]: the keys row
    n attends, positions ``0 … ctx_len − 1`` of its table (its own
    position + 1; 0 for a padded row, whose output is zeros). The pool
    already holds the row's own latent. ``window`` > 0: the last
    ``window`` of them only (kernel ``mla_window_decode``; table entries
    behind the window may be -1). Returns the attended latents
    [N, heads, rank]."""
    extra = (int(window),) if window else ()
    if _use_pallas() and pool.shape[-2] % 16 == 0 and rank % LANES == 0:
        return _decode_pallas(q, pool, layer, tables, ctx_len, rank,
                              float(sm_scale), *extra)
    return latent_decode_xla(q, pool, layer, tables, ctx_len, rank,
                             float(sm_scale), *extra)


# ---------------------------------------------------------------- expanded

def _prefill_kernel(pos_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, *rest,
                    sm_scale: float, window: int = 0, selected: bool = False,
                    joined: bool = False):
    """One (head, query block, key block) step of one tile: the running
    softmax of the block's query rows, carried in ``acc_ref`` / ``st_ref``
    (resident across the key blocks), over the tile's keys that are live
    — at or before the row's own position and inside the context, within
    ``window`` of it, and, where the layer selects (``selected``: an int8
    operand [bq, bk] behind ``v``), among the row's selected keys.
    ``joined``: the rope parts ride behind the nope ones in ``qn`` /
    ``kn``, one dot."""
    keep_ref = rest[0] if selected else None
    acc_in, st_in, acc_ref, st_ref = rest[-4:]
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
        if joined:
            s = lax.dot_general(qn_ref[0], kn_ref[0], contract,
                                preferred_element_type=jnp.float32) * sm_scale
        else:
            s = (lax.dot_general(qn_ref[0], kn_ref[0], contract,
                                 preferred_element_type=jnp.float32)
                 + lax.dot_general(qr_ref[0], kr_ref[...], contract,
                                   preferred_element_type=jnp.float32)
                 ) * sm_scale
        if masked:
            qpos = q0 + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            kpos = k0 + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            keep = (kpos <= qpos) & (kpos < ctx_len)
            if window:
                keep &= kpos > qpos - window
            if selected:
                keep &= keep_ref[...] != 0
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
    if window:          # its last key inside the first row's window
        live &= k0 + bk - 1 > q0 - window
    if window or selected:
        pl.when(live)(lambda: fold(True))
        return
    whole = (k0 + bk - 1 <= q0) & (k0 + bk <= ctx_len)
    pl.when(live & whole)(lambda: fold(False))
    pl.when(live & jnp.logical_not(whole))(lambda: fold(True))


def _prefill_tile_pallas(q_nope, q_rope, k_nope, k_r, v, acc, stats, pos,
                         sm_scale, window=0, keep=None, joined=False):
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
    kernel = functools.partial(_prefill_kernel, sm_scale=sm_scale)
    name, extra, extra_specs = "mla_prefill", (), []
    if window:
        kernel, name = functools.partial(kernel, window=window), \
            "mla_window_prefill"
    if joined:
        kernel = functools.partial(kernel, joined=True)
    if keep is not None:
        kernel, name = functools.partial(kernel, selected=True), \
            "mla_sparse_prefill"
        extra = (keep,)
        extra_specs = [pl.BlockSpec((bq, bk), lambda h, i, j, pos:
                                    (i, key_block(i, j, pos)))]
    n_in = 6 + len(extra)       # operands before the carry, scalars too
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H, C // bq, Tk // bk),
            in_specs=[carry(dn), carry(wr),
                      pl.BlockSpec((1, bk, dn), kv_map),
                      pl.BlockSpec((bk, wr), lambda h, i, j, pos:
                                   (key_block(i, j, pos), 0)),
                      pl.BlockSpec((1, bk, dv), kv_map)] + extra_specs
            + [carry(dv), carry(LANES)],
            out_specs=[carry(dv), carry(LANES)]),
        out_shape=[jax.ShapeDtypeStruct(acc.shape, acc.dtype),
                   jax.ShapeDtypeStruct(stats.shape, stats.dtype)],
        # the carry is updated where it lies (operands count the scalars)
        input_output_aliases={n_in: 0, n_in + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=_interpret(),
    )(pos, q_nope, q_rope, k_nope, k_r, v, *extra, acc, stats)


def _prefill_tile_xla(q_nope, q_rope, k_nope, k_r, v, acc, stats, pos,
                      sm_scale, window=0, keep=None, joined=False):
    """One tile's fold in plain XLA, the kernel's arguments and carry."""
    C, Tk = q_nope.shape[1], k_nope.shape[1]
    s = jnp.einsum("hcd,htd->hct", q_nope, k_nope,
                   preferred_element_type=jnp.float32)
    if not joined:
        s = s + jnp.einsum("hcd,td->hct", q_rope, k_r,
                           preferred_element_type=jnp.float32)
    s = s * sm_scale
    qpos = pos[0] + jnp.arange(C)[:, None]
    kpos = pos[1] + jnp.arange(Tk)[None, :]
    live = (kpos <= qpos) & (kpos < pos[2])
    if window:
        live &= kpos > qpos - window
    if keep is not None:
        live &= keep != 0
    keep = live[None]
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


def expand_tile(table_blocks: int, block_size: int, window: int = 0) -> int:
    """The keys one turn of the expanded path rebuilds: ``EXPAND_TILE``,
    or the whole table where that is shorter; whole blocks. Under a
    window a chunk's keys are ``window + chunk``, and a tile is the power
    of two that holds the window (1,024 for 513): a 2,048-token chunk then
    rebuilds four tiles at most where one of 4,096 would make it two."""
    tile = EXPAND_TILE
    if window:
        tile = min(tile, max(block_size, 1 << (int(window) - 1).bit_length()))
    return min(tile // block_size, table_blocks) * block_size


def latent_prefill(q_nope, q_rope, pool, layer, table, start_pos, n_tokens,
                   expand, rank: int, v_width: int, sm_scale: float,
                   window: int = 0, keep=None):
    """Expanded latent attention of one chunk row.

    q_nope [C, heads, nope] and q_rope [C, heads, rope]: the chunk's
    queries, row i at position ``start_pos + i``; pool [L, NB, bs, W] at
    ``layer``, which already holds the chunk's own latents; table [MB]:
    the row's block table; ``expand(c [T, rank]) -> (k_nope [heads, T,
    nope], v [heads, T, v_width])`` rebuilds a tile's K/V heads from its
    latents (the caller's weights and scope). Keys are live up to
    ``start_pos + n_tokens`` and causally; ``window`` > 0: a query's last
    ``window`` keys only, and the loop starts at the tile of the first
    one (kernel ``mla_window_prefill``); ``keep`` [C, keys] int8 (keys:
    the table's, in whole tiles): each query's selected keys, the others
    masked (kernel ``mla_sparse_prefill``). Returns [C, heads, v]; rows
    at or beyond ``n_tokens`` are garbage."""
    C, H, dn = q_nope.shape
    _, NB, bs, W = pool.shape
    wr = W - rank
    tile = expand_tile(table.shape[0], bs, window)
    options = {}
    if window:
        options["window"] = int(window)
    # a nope width that is no whole lane tile but is one with the rope
    # behind it (192 + 64): the rope parts ride in the same dot
    dr = q_rope.shape[-1]
    if dn % LANES and (dn + dr) % LANES == 0:
        options["joined"] = True
        q_nope = jnp.concatenate([q_nope, q_rope], axis=-1)
        dn += dr
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
            if "joined" in options:
                k_nope = jnp.concatenate([k_nope, jnp.broadcast_to(
                    rows[None, :, rank:rank + dr], (H, tile, dr))], axis=-1)
        with jax.named_scope("attend"):
            pos = jnp.stack([start_pos.astype(jnp.int32),
                             (t * tile).astype(jnp.int32), ctx_len])
            if keep is not None:
                options["keep"] = lax.dynamic_slice(
                    keep, (0, t * tile), (C, tile))
            acc, stats = fold(qn, qr, k_nope, rows[:, rank:], v, acc, stats,
                              pos, float(sm_scale), **options)
        return acc, stats

    with jax.named_scope("attend"):
        lane = jnp.arange(LANES)[None, None, :]
        init = (jnp.zeros((H, C, v_width), jnp.float32),
                jnp.broadcast_to(jnp.where(lane < _HALF, NEG_INF, 0.0),
                                 (H, C, LANES)).astype(jnp.float32))
    first = jnp.maximum(start_pos - window + 1, 0).astype(jnp.int32) // tile \
        if window else 0
    acc, stats = lax.fori_loop(first, -(-ctx_len // tile), turn, init)
    with jax.named_scope("attend"):
        l = jnp.maximum(stats[..., _HALF:_HALF + 1], 1e-30)
        return (acc / l).transpose(1, 0, 2).astype(q_nope.dtype)


# --------------------------------------------------------------- selection

def absorb_max_queries(rank: int, nope: int, rope: int, v: int,
                       window: int = 0) -> int:
    """The widest chunk (a power of two) whose positions are cheaper
    absorbed than expanded, from the kind's widths (the heads cancel).
    A pair costs ``2 · (2 · rank + rope)`` absorbed and ``2 · (nope +
    rope + v)`` expanded a head, the rebuild ``2 · rank · (nope + v)`` a
    key a head. The whole context: a chunk of C rebuilds its context for
    C queries' pairs with it, equal at ``rebuild / (absorbed −
    expanded)`` rows. A window: ``window`` pairs a query, ``window + C``
    keys rebuilt, equal at ``rebuild · window / (window · (absorbed −
    expanded) − rebuild)``; where the rebuild of the window alone costs
    more than that, absorbed at any width."""
    gain = 2.0 * (2 * rank + rope - nope - rope - v)
    rebuild = 2.0 * rank * (nope + v)
    if gain <= 0:
        return 1 << 30
    if window:
        if window * gain <= rebuild:
            return 1 << 30
        cross = rebuild * window / (window * gain - rebuild)
    else:
        cross = rebuild / gain
    return 1 << max(int(cross).bit_length() - 1, 0)


def _index_kernel(layer_ref, tables_ref, ctx_ref, q_ref, w_ref, pool_hbm,
                  o_ref, buf, sem):
    """One row: its ``Q`` query positions' [Q x heads, D] index queries
    against its sequence's live index keys, ``T`` blocks a turn through
    two VMEM slots (``_decode_kernel``'s walk). A turn writes its keys'
    scores [Q, T x bs]; turns past the context write nothing."""
    _, T, bs, D = buf.shape
    keys = T * bs
    Q = o_ref.shape[2]
    n = pl.program_id(0)
    layer = layer_ref[0]
    last = jnp.minimum(pl.cdiv(ctx_ref[n], bs), tables_ref.shape[1])

    def each_live(turn, slot, act):
        def one(b, _):
            act(pltpu.make_async_copy(
                pool_hbm.at[layer, tables_ref[n, b]],
                buf.at[slot, b % T], sem.at[slot]))

        lax.fori_loop(turn * T, jnp.minimum(last, turn * T + T), one, None)

    buf[...] = jnp.zeros_like(buf)
    each_live(0, 0, lambda dma: dma.start())

    def score(turn, _):
        slot = turn % 2
        each_live(turn + 1, 1 - slot, lambda dma: dma.start())
        each_live(turn, slot, lambda dma: dma.wait())
        s = lax.dot_general(q_ref[0], buf[slot].reshape(keys, D),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[0][:, :1]         # [Q x heads, keys]
        o_ref[0, turn] = jnp.sum(s.reshape(Q, -1, keys), axis=1)

    lax.fori_loop(0, pl.cdiv(last, T), score, None)


def _index_pallas(q, w, pool, layer, tables, ctx_len, Q):
    N, QH, D = q.shape
    _, NB, bs, _ = pool.shape
    T = max(1, min(KEY_TILE // bs, tables.shape[1]))
    turns = -(-tables.shape[1] // T)
    row = lambda width: pl.BlockSpec(                          # noqa: E731
        (1, QH, width), lambda n, *_: (n, 0, 0))
    out = pl.pallas_call(
        _index_kernel,
        name="index_score",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N,),
            in_specs=[row(D), row(LANES), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, turns, Q, T * bs),
                                   lambda n, *_: (n, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, T, bs, D), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((N, turns, Q, T * bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.maximum(tables, 0).astype(jnp.int32), ctx_len.astype(jnp.int32),
      q.astype(pool.dtype),
      jnp.broadcast_to(w.astype(jnp.float32)[..., None], (N, QH, LANES)),
      pool)
    return out.transpose(0, 2, 1, 3).reshape(N, Q, -1)[
        ..., :tables.shape[1] * bs]


def index_score_xla(q, w, pool, layer, tables, ctx_len, Q):
    """The scores as a dense gather (off the TPU; the kernel's numeric
    reference): ``index_score``'s arguments."""
    N, QH, D = q.shape
    k = pool[layer, jnp.maximum(tables, 0)].reshape(N, -1, D)
    s = jnp.einsum("nqd,nsd->nqs", q.astype(pool.dtype), k,
                   preferred_element_type=jnp.float32)
    s = jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None]
    return jnp.sum(s.reshape(N, Q, QH // Q, -1), axis=2)


def index_score(q, w, pool, layer, tables, ctx_len, queries: int = 1):
    """The indexer's scores over the paged index-key pool.

    q [N, Q x heads, D] and w [N, Q x heads]: the index queries and head
    weights of ``Q = queries`` positions of one sequence a row, position
    by position; pool [L, NB, bs, D] read at the scalar ``layer``; tables
    [N, MB]; ctx_len [N]: the keys a row's last position may see (0: a
    padded row). Returns [N, Q, MB x bs] float32, ``Σ_h w_h · relu(q_h ·
    k_s)`` at key s; what lies at or beyond a row's ``ctx_len`` (and,
    for an earlier position of the row, beyond itself) is for the caller
    to mask: it may hold anything."""
    if _use_pallas() and pool.shape[-2] % 16 == 0 \
            and pool.shape[-1] % LANES == 0 \
            and (q.shape[1] // queries) % 8 == 0:
        return _index_pallas(q, w, pool, layer, tables, ctx_len, queries)
    return index_score_xla(q, w, pool, layer, tables, ctx_len, queries)


def _sparse_decode_kernel(n_ref, q_ref, kv_ref, o_ref, *, sm_scale: float):
    """One row: its [heads, W] queries against its K gathered rows, of
    which the first ``n`` are selected; one softmax, no walk."""
    R = o_ref.shape[-1]
    kv = kv_ref[0]                                            # [K, W]
    s = lax.dot_general(q_ref[0], kv, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    at = lax.broadcasted_iota(jnp.int32, (1, kv.shape[0]), 1)
    keep = at < n_ref[pl.program_id(0)]
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.where(keep, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = lax.dot_general(p.astype(kv.dtype), kv[:, :R],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    o_ref[0] = (o / l).astype(o_ref.dtype)


def _sparse_decode_pallas(q, kv, n_sel, rank, sm_scale):
    N, H, W = q.shape
    K = kv.shape[1]
    return pl.pallas_call(
        functools.partial(_sparse_decode_kernel, sm_scale=sm_scale),
        name="mla_sparse_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N,),
            in_specs=[pl.BlockSpec((1, H, W), lambda n, *_: (n, 0, 0)),
                      pl.BlockSpec((1, K, W), lambda n, *_: (n, 0, 0))],
            out_specs=pl.BlockSpec((1, H, rank), lambda n, *_: (n, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((N, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=_interpret(),
    )(n_sel.astype(jnp.int32), q.astype(kv.dtype), kv)


def sparse_decode_xla(q, kv, n_sel, rank, sm_scale):
    """The absorbed product over gathered rows in plain XLA (off the
    TPU; the kernel's numeric reference)."""
    s = jnp.einsum("nhw,nsw->nhs", q.astype(kv.dtype), kv,
                   preferred_element_type=jnp.float32) * sm_scale
    keep = (jnp.arange(kv.shape[1])[None, :] < n_sel[:, None])[:, None, :]
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.where(keep, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("nhs,nsr->nhr", p.astype(kv.dtype), kv[..., :rank],
                   preferred_element_type=jnp.float32)
    return (o / l).astype(q.dtype)


def latent_sparse_decode(q, pool, layer, tables, idx, n_sel, rank: int,
                         sm_scale: float):
    """Absorbed latent attention of one query position a row over its
    selected keys only.

    q [N, heads, W] as ``latent_decode``'s; idx [N, K]: the row's
    selected positions, of which the first ``n_sel`` [N] count (0: a
    padded row, whose output is zeros). The K rows are gathered from
    ``pool`` [L, NB, bs, W] at ``layer`` through ``tables`` [N, MB] —
    position p lies in block ``tables[p // bs]`` at slot ``p % bs`` —
    and attended as they lie. Returns the attended latents [N, heads,
    rank]."""
    bs = pool.shape[2]
    blocks = jnp.take_along_axis(jnp.maximum(tables, 0), idx // bs, axis=1)
    kv = pool[layer, blocks, idx % bs]                        # [N, K, W]
    if _use_pallas() and kv.shape[1] % 16 == 0 and rank % LANES == 0:
        return _sparse_decode_pallas(q, kv, n_sel, rank, float(sm_scale))
    return sparse_decode_xla(q, kv, n_sel, rank, float(sm_scale))


# ------------------------------------------------------- cost, from shapes

def expand_positions(start_pos: int, n_tokens: int, tile: int) -> int:
    """Context positions whose K/V a chunk of ``n_tokens`` from
    ``start_pos`` rebuilds: its context in whole tiles."""
    return -(-(start_pos + n_tokens) // tile) * tile if n_tokens else 0
