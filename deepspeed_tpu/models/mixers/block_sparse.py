"""``"block_sparse"``: a ``"full"`` layer (its projections, norms and
gate: ``attention.py``) whose query, from position ``block_dense_len``
on, attends whole blocks of keys only — the first, those its window
reaches, and the ``block_topk`` others of largest score, chosen a K/V
head with **no weights**: the head's queries against the means of
overlapping runs of its keys (``block_*`` below).

In serving its cache is ``k`` / ``v`` and, beside them in the same blocks
of the same table, ``kc`` [L, NB, per, KH, D]: the compressed keys, a
kernel a stride, written by the forward that brings a kernel's last key
(``compress_written``). A layer scores its queries against the table's
(``selection``) and attends the chosen blocks: a one-token row through a
table a K/V head (``paged_attention_select``: those blocks are read and
no other), a chunk row under a block mask a query a K/V head
(``paged_attention_masked``: every live block walked). A query short of
``block_dense_len`` selects every block of its past. The block is the
pool's: ``kv_block_size`` is ``block_select_size``.

Scopes (docs/OBSERVABILITY.md): ``sparse_attn`` rounds the layer's
``qkv``, ``kv_write``, ``attend`` and ``attn_out`` and adds
``block_compress``, ``block_score`` and ``block_select``."""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...ops import paged_attention
from . import attention
from .attention import full_out, full_qkv
from .base import Mixer, held
from .select import index_kept, index_select

KIND = "block_sparse"
scope = jax.named_scope
#: positions of a chunk whose block scores a layer takes at a time
#: (``selection``): every head's softmax over the table's compressed keys
#: is 101 MB of float32 at 128 positions, 32 heads and 6,208 kernels
BLOCK_SCORE_ROWS = 128


def check(cfg):
    stride, size = cfg.block_kernel_stride, cfg.block_select_size
    if min(stride, size, cfg.block_topk, cfg.block_window,
           cfg.block_init_blocks) <= 0 \
            or cfg.block_kernel_size % stride \
            or size % stride or cfg.block_window % size \
            or cfg.block_dense_len < (
                cfg.block_window + (cfg.block_init_blocks + 1) * size):
        raise ValueError(
            "\"block_sparse\" layers need a kernel and a "
            "block that are whole strides, a window of whole "
            "blocks, topk and init_blocks > 0, and a "
            "block_dense_len past the window and the initial "
            "blocks (a selecting query's window never holds "
            "them)")


def pool(cfg, block_size: int):
    """``k`` / ``v`` and the compressed keys a block's kernels end in,
    beside them: a kernel a stride, [rows, KH, D] (the index dimensions
    of its write lead: inference/v2/kv_write.py says why)."""
    if block_size != cfg.block_select_size:
        raise ValueError(
            f"kv_block_size {block_size}: a \"block_sparse\" "
            "layer selects pool blocks, so the pool's block "
            f"is block_select_size ({cfg.block_select_size})")
    return dict(attention.pool(cfg, block_size),
                kc=(block_size // cfg.block_kernel_stride, cfg.kv_heads,
                    cfg.head_dim))


class BlockSizes(NamedTuple):
    """A block-sparse layer's sizes: a compressed key is the mean of
    ``kernel`` keys, one every ``stride``; a ``block`` of keys is
    attended whole; ``per`` kernels begin in a block and each spans
    ``ratio`` strides."""
    kernel: int
    stride: int
    block: int
    topk: int
    init: int
    window: int
    dense_len: int

    @property
    def per(self) -> int:
        return self.block // self.stride

    @property
    def ratio(self) -> int:
        return self.kernel // self.stride

    @property
    def table_width(self) -> int:
        """The most blocks a one-token row attends: a selecting query's
        initial blocks, its ``topk`` and the blocks its window reaches,
        or every block of a context short of ``dense_len``."""
        return max(self.init + self.topk + self.window // self.block + 1,
                   -(-self.dense_len // self.block))


def block_sizes(cfg) -> BlockSizes:
    return BlockSizes(cfg.block_kernel_size, cfg.block_kernel_stride,
                      cfg.block_select_size, cfg.block_topk,
                      cfg.block_init_blocks, cfg.block_window,
                      cfg.block_dense_len)


def block_compress(z: BlockSizes, k):
    """Compressed keys of a run of keys ``k`` [..., W, KH, D] that begins
    at a whole stride, ``W = stride · (n + ratio − 1)``: the ``n`` means
    [..., n, KH, D] of ``kernel`` consecutive keys, one a stride — each
    the mean of its ``ratio`` strides' means, in float32, returned in
    ``k``'s type."""
    lead, (W, KH, D) = k.shape[:-3], k.shape[-3:]
    n = W // z.stride - (z.ratio - 1)
    strides = jnp.mean(k.astype(jnp.float32).reshape(
        lead + (W // z.stride, z.stride, KH, D)), axis=-3)
    return (sum(strides[..., i:i + n, :, :] for i in range(z.ratio))
            / z.ratio).astype(k.dtype)


def block_visible(z: BlockSizes, t):
    """The kernels wholly in the causal past of position ``t``: those j
    with ``stride · j + kernel <= t + 1``."""
    return jnp.maximum((t + 1 - z.kernel) // z.stride + 1, 0)


def block_scores(cfg, q, kc, t):
    """Each block's score for the queries ``q`` [..., C, H, D] at
    positions ``t`` [..., C], against the compressed keys ``kc``
    [..., J, KH, D] (J a whole number of blocks' kernels, kernel j at
    row j): a head's softmax over the kernels it may see, summed over
    the K/V head's queries, and for a block the largest over the kernels
    that overlap it -> [..., C, KH, J / per] float32."""
    z = block_sizes(cfg)
    lead, (C, H, D) = q.shape[:-3], q.shape[-3:]
    J, KH = kc.shape[-3], kc.shape[-2]
    s = jnp.einsum("...ckgd,...jkd->...ckgj",
                   q.reshape(lead + (C, KH, H // KH, D)), kc,
                   preferred_element_type=jnp.float32) \
        * (cfg.attn_scale or 1.0 / math.sqrt(D))
    live = (jnp.arange(J) < block_visible(z, t)[..., None]
            )[..., None, None, :]
    s = jnp.where(live, s, -1e30)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = jnp.sum(p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30),
                axis=-2)                                  # [..., C, KH, J]
    blocks = p.shape[:-1] + (J // z.per, z.per)
    score = jnp.max(p.reshape(blocks), axis=-1)
    for i in range(1, z.ratio):     # the kernels that began a block before
        before = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(i, 0)])[..., :J]
        score = jnp.maximum(score, before.reshape(blocks)[..., 0])
    return score


def _block_parts(z: BlockSizes, t, blocks: int):
    """For queries at positions ``t`` [...] over ``blocks`` blocks:
    ``(forced, candidates, first_w, cur)`` — the blocks a selecting
    query attends whatever their score (the initial ones and those that
    hold one of its last ``window`` positions), those it chooses among
    (the rest of its past), its window's first block and its own."""
    b = jnp.arange(blocks)
    first_w = (jnp.maximum(t - z.window + 1, 0) // z.block)[..., None]
    cur = (t // z.block)[..., None]
    forced = ((b >= first_w) | (b < z.init)) & (b <= cur)
    return forced, (b >= z.init) & (b < first_w), first_w[..., 0], \
        cur[..., 0]


def block_keep(cfg, scores, t):
    """The blocks each query attends, as a mask: ``scores``
    [..., C, KH, blocks] (``block_scores``), ``t`` [..., C] ->
    [..., C, KH, blocks] bool. A query short of ``dense_len`` attends
    every block of its past; another its forced blocks and the ``topk``
    candidates of largest score, ties to the lower block."""
    z = block_sizes(cfg)
    forced, cand, _, cur = _block_parts(z, t, scores.shape[-1])
    kept = index_kept(scores, jnp.broadcast_to(
        cand[..., None, :], scores.shape), z.topk)
    causal = jnp.arange(scores.shape[-1]) <= cur[..., None]
    return jnp.where((t < z.dense_len)[..., None, None],
                     causal[..., None, :], forced[..., None, :] | kept)


def block_select(cfg, scores, t):
    """The same selection as a table, for one-token rows: ``scores``
    [N, KH, blocks], ``t`` [N] -> ``(table [N, KH, width] int32, n
    [N])``: the ``n`` blocks each row attends in ascending order, its
    own block last (``width``: ``BlockSizes.table_width``; entries past
    ``n`` are 0 and not to be read). The count is one for a row's K/V
    heads: it follows from the position alone."""
    z = block_sizes(cfg)
    _, cand, first_w, cur = _block_parts(z, t, scores.shape[-1])
    idx, n = index_select(scores, jnp.broadcast_to(
        cand[:, None, :], scores.shape), z.topk)        # [N, KH, k]
    n = n[:, :1]                                         # [N, 1]
    slot = jnp.arange(z.table_width, dtype=jnp.int32)
    chosen = jnp.take_along_axis(
        idx, jnp.broadcast_to(jnp.clip(slot - z.init, 0, idx.shape[-1] - 1),
                              idx.shape[:-1] + slot.shape), axis=-1)
    window = first_w[:, None] + slot - z.init - n        # [N, width]
    picked = jnp.where(slot < z.init, slot,
                       jnp.where(slot < z.init + n[..., None], chosen,
                                 window[:, None, :]))
    dense = t < z.dense_len
    count = jnp.where(dense, cur + 1, z.init + n[:, 0] + cur - first_w + 1)
    table = jnp.where(dense[:, None, None], slot, picked)
    return jnp.where(slot < count[:, None, None], table, 0).astype(
        jnp.int32), count.astype(jnp.int32)


def block_keep_dense(cfg, q, k):
    """A whole sequence's selection, plain XLA (no cache: training and
    the reference path): q [B, T, H, D], k [B, T, KH, D] -> the keys each
    query attends, [B, T, KH, T] bool (causal)."""
    z = block_sizes(cfg)
    T = q.shape[1]
    blocks = -(-T // z.block)
    at = jnp.arange(T)
    with jax.named_scope("block_compress"):
        width = z.stride * (blocks * z.per + z.ratio - 1)
        kc = block_compress(z, jnp.pad(
            k, ((0, 0), (0, width - T), (0, 0), (0, 0))))
    with jax.named_scope("block_score"):
        scores = block_scores(cfg, q, kc, at[None, :])
    with jax.named_scope("block_select"):
        keep = jnp.repeat(block_keep(cfg, scores, at[None, :]), z.block,
                          axis=-1)[..., :T]
    return keep & (at[None, :] <= at[:, None])[None, :, None, :]


def block_attend_dense(cfg, q, k, v, keep):
    """Attention of q [B, T, H, D] over k, v [B, T, KH, D] under ``keep``
    [B, T, KH, T], plain XLA -> [B, T, H, D]."""
    B, T, H, D = q.shape
    KH = k.shape[2]
    s = jnp.einsum("btkgd,bskd->bkgts", q.reshape(B, T, KH, H // KH, D), k,
                   preferred_element_type=jnp.float32) \
        * (cfg.attn_scale or 1.0 / math.sqrt(D))
    p = jax.nn.softmax(jnp.where(keep.transpose(0, 2, 1, 3)[:, :, None], s,
                                 -1e30), axis=-1)
    return jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32
                      ).astype(v.dtype).reshape(B, T, H, D)


# ------------------------------------------------------------------ serving

def compress_written(cfg, block_size: int, k_pool, kc_pool, layer, table,
                     start_pos, n_tokens, chunk: int):
    """The compressed keys this forward completes, written into the
    ``kc`` leaf (under ``block_compress``): kernel j — the mean of
    keys ``stride·j … stride·j + kernel − 1`` — belongs to the
    forward that brings its last key, which may be a block after the
    one it began in, and goes to row ``j % per`` of table block
    ``j // per``. ``k_pool`` holds this forward's keys already: a
    row's run of keys, from the first kernel it completes to its last
    position, is read from whole blocks of its table and every
    stride's mean is taken once. A kernel that ends at or past a
    row's valid tokens is dropped."""
    z = block_sizes(cfg)
    bs, MB, NB = block_size, table.shape[1], kc_pool.shape[1]
    N = table.shape[0]
    nk = -(-chunk // z.stride)
    width = z.stride * (nk + z.ratio - 1)
    j0 = jnp.maximum((start_pos - z.kernel + z.stride) // z.stride, 0)
    j = j0[:, None] + jnp.arange(nk)[None, :]              # [N, nk]
    ends = j * z.stride + z.kernel - 1
    valid = (ends >= start_pos[:, None]) \
        & (ends < (start_pos + n_tokens)[:, None])
    lo = j0 * z.stride
    n_blocks = (width + 2 * bs - z.stride - 1) // bs
    ids = jnp.take_along_axis(
        table, jnp.clip((lo // bs)[:, None] + jnp.arange(n_blocks),
                        0, MB - 1), axis=1)
    run = k_pool[layer, jnp.maximum(ids, 0)]       # [N, nb, KH, bs, D]
    run = run.transpose(0, 1, 3, 2, 4).reshape(
        (N, n_blocks * bs) + run.shape[2:3] + run.shape[4:])
    run = jax.vmap(lambda r, at: lax.dynamic_slice_in_dim(
        r, at, width, axis=0))(run, lo % bs)
    rows = block_compress(z, run)                          # [N, nk, KH, D]
    block = jnp.take_along_axis(table, jnp.clip(j // z.per, 0, MB - 1),
                                axis=1)
    # the sentinel NB: a positive out-of-range id, really dropped
    block = jnp.where(valid & (block >= 0), block, NB)
    return kc_pool.at[layer, block, j % z.per].set(rows, mode="drop")


def selection(cfg, q, kc_pool, layer, table, positions):
    """A layer's selection for a forward's [N, C] queries q [N, C, H, D]
    at ``positions`` [N, C] (a padded one's is whatever): the table's
    compressed keys are scored (``block_score``) and the blocks chosen
    (``block_select``). One position a row -> ``(tables [N, KH, W] of
    pool block ids, n [N])``, what ``paged_attention_select`` walks; a
    chunk -> the int8 mask [N, C, KH, MB] over the table's blocks,
    ``BLOCK_SCORE_ROWS`` positions at a time."""
    N, C = positions.shape
    ctx = kc_pool[layer, jnp.maximum(table, 0)]    # [N, MB, per, KH, D]
    ctx = ctx.reshape((N, -1) + ctx.shape[3:])             # [N, J, KH, D]
    if C == 1:
        with scope("block_score"):
            scores = block_scores(cfg, q, ctx, positions)[:, 0]
        with scope("block_select"):
            picked, n = block_select(cfg, scores, positions[:, 0])
            return jnp.take_along_axis(
                jnp.maximum(table, 0)[:, None, :], picked, axis=-1), n
    rows = math.gcd(C, BLOCK_SCORE_ROWS)

    def some(xs):
        qb, at = xs
        with scope("block_score"):
            scores = block_scores(cfg, qb, ctx, at)
        with scope("block_select"):
            return block_keep(cfg, scores, at).astype(jnp.int8)

    split = lambda a: jnp.moveaxis(                        # noqa: E731
        a.reshape((N, C // rows, rows) + a.shape[2:]), 1, 0)
    keep = lax.map(some, (split(q), split(positions)))
    return jnp.moveaxis(keep, 0, 1).reshape((N, C) + keep.shape[3:])


def reference(cfg, fwd):
    turn = fwd.rope(cfg, KIND)

    def mixer(h1, lp, _):
        with scope("qkv"):
            q, k, v, gate = full_qkv(cfg, h1, lp, turn)
        keep = block_keep_dense(cfg, q, k)
        with scope("attend"):
            attn = block_attend_dense(cfg, q, k, v, keep)
        with scope("attn_out"):
            return full_out(cfg, attn, gate, lp)
    return mixer


def paged(cfg, fwd):
    N, C = fwd.shape
    pools, table = fwd.pools, fwd.tables[fwd.group_of[KIND]]
    start_pos, n_tokens = fwd.start_pos, fwd.n_tokens
    turn = fwd.rope(cfg, KIND)
    hold = held(cfg)

    def attend(q, layer):
        """The layer behind its ``kv_write``: the kernels this forward
        ends, the selection, and the attention over what it chose."""
        with scope("block_compress"):
            pools["kc"] = compress_written(
                cfg, fwd.block_size, pools["k"], pools["kc"], layer, table,
                start_pos, n_tokens, C)
        picked = selection(cfg, q, pools["kc"], layer, table, fwd.positions)
        with scope("attend"):
            if C == 1:
                blocks, n = picked
                return paged_attention.paged_attention_select(
                    q, pools["k"], pools["v"], blocks,
                    jnp.where(n_tokens > 0, n, 0), start_pos,
                    sm_scale=cfg.attn_scale, layer=layer)
            return paged_attention.paged_attention_masked(
                q, pools["k"], pools["v"], table, start_pos, n_tokens,
                picked, sm_scale=cfg.attn_scale, layer=layer)

    def mixer(h1, lp, i):
        layer = fwd.layer(KIND, i)
        with scope("qkv"):
            q, k, v, gate = full_qkv(cfg, h1, lp, turn, hold)
        with scope("kv_write"):
            attention.write_kv(cfg, fwd, KIND, k, v, layer)
        attn = attend(q, layer)
        with scope("attn_out"):
            return full_out(cfg, attn, gate, lp)
    return mixer


# ----------------------------------------------------------------- counters

#: kept in ``put_totals``; ``last_put`` alone also splits them by kernel
TOTALS = ("sparse_rows_dense", "sparse_rows_selected",
          "sparse_blocks_live", "sparse_blocks_selected")


def count(cfg, staged, bucket_chunk: int, block_size: int):
    """What the layers' selection keeps of this forward, a layer and a
    K/V head (every one's is the same count: it follows from the
    positions alone). ``sparse_rows_dense`` / ``sparse_rows_selected``:
    the query positions short of ``block_dense_len``, which attend every
    block of their past, and the ones that select;
    ``sparse_blocks_live``: the blocks the positions could see, summed
    over them; ``sparse_blocks_selected``: of those the ones attended —
    a selecting position's initial blocks, its ``block_topk`` (fewer
    while fewer lie before its window) and the blocks its window
    reaches. And by kernel: the one-token rows' (``sparse_ones`` and
    ``sparse_blocks_ones``, what ``paged_attention_select`` reads) and
    the chunk rows' (``sparse_q_chunk``, the selected query-key pairs
    ``sparse_pairs_chunk`` and ``sparse_keys_chunk``, the keys every
    position of a row reads whatever it selects: its initial blocks, its
    window, itself)."""
    z = block_sizes(cfg)
    counts = dict.fromkeys(TOTALS + (
        "sparse_ones", "sparse_blocks_ones", "sparse_q_chunk",
        "sparse_pairs_chunk", "sparse_keys_chunk"), 0)
    for seq, toks in staged:
        n, seen = len(toks), seq.seen_tokens
        t = seen + np.arange(n, dtype=np.int64)
        live = t // z.block + 1
        first_w = np.maximum(t - z.window + 1, 0) // z.block
        chosen = np.clip(first_w - z.init, 0, z.topk)
        picked = np.where(t < z.dense_len, live,
                          z.init + chosen + t // z.block - first_w + 1)
        dense = int((t < z.dense_len).sum())
        counts["sparse_rows_dense"] += dense
        counts["sparse_rows_selected"] += n - dense
        counts["sparse_blocks_live"] += int(live.sum())
        counts["sparse_blocks_selected"] += int(picked.sum())
        if n == 1:
            counts["sparse_ones"] += 1
            counts["sparse_blocks_ones"] += int(picked[0])
        else:
            # the keys a position attends: its whole blocks but its
            # own, and its own up to itself
            counts["sparse_q_chunk"] += n
            counts["sparse_pairs_chunk"] += int(
                ((picked - 1) * z.block + t % z.block + 1).sum())
            counts["sparse_keys_chunk"] += int(min(
                seen + n, z.init * z.block + z.window + n - 1))
    return counts


BLOCK_SPARSE = Mixer(
    init=attention.init, specs=attention.specs, reference=reference,
    paged=paged, scope="sparse_attn", check=check, pool=pool, totals=TOTALS,
    record=("sparse_rows_", "sparse_blocks_", "sparse_ones",
            "sparse_q_chunk", "sparse_pairs_chunk", "sparse_keys_chunk"),
    count=count, holds=True)
