"""The ``minicpm_sala`` block (MiniCPM-SALA, ``model_type:
minicpm_sala``): *lightning* layers — linear attention under one scalar
decay a head — beside *sparse* layers (``minicpm4`` = InfLLM-V2: softmax
attention over whole blocks of keys that a query's K/V group chooses with
no weights), every layer followed by a dense gated MLP, under MiniCPM's
muP multipliers: its plain reference (forward pass and loss), its
arithmetic, the scope names it adds and the cost functions of the kernels
its cell reads, found by the name a configuration's file gives
(``"block": "minicpm_sala"``).

The reference is straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. It keeps **no state and no
cache**: a lightning layer is its quadratic form (a query against every
earlier key under the decay ``λ^(t-s)``), a sparse layer attends under a
mask over all keys that is built from the equations below, key range by
key range. It imports nothing from ``deepspeed_tpu``; what it shares with
the program is the parameter tree's naming. Query rows are taken a block
at a time and position-wise parts a block of rows at a time, so that the
check fits beside the resident engine. x̂ = x·rsqrt(mean x² + eps), gain
w:

    x₀      = scale_emb · E[tokens]                     (``embed_scale``)
    r       = scale_depth / sqrt(num_hidden_layers as PUBLISHED)
                                                        (``residual_scale``)
    x      += r · Mixer(norm_in(x));  x += r · MLP(norm_mlp(x))
    MLP(h)  = (silu(h·W_gate) ⊙ h·W_up)·W_down
    logits  = norm_final(x)·W_head / (hidden_size / dim_model_base)
                                                        (``logit_scale``)

*Lightning layer* (``lightning_num_heads`` heads of
``lightning_head_dim``): ``q = norm_head(h·W_q)``, ``k = norm_head(h·W_k)``
(one gain of the head's width a projection, shared by the heads), ``v =
h·W_v``; rotary (θ, the whole head, rotate-half) on q and k; per head
``o_t = Σ_{s ≤ t} λ_h^(t-s) (q_t·k_s) v_s / sqrt(D)`` with ``λ_h =
exp(-2^(-8(h+1)/heads))`` — which is ``S_t = λ_h S_{t-1} + k_t v_tᵀ``,
``o_t = S_tᵀ q_t / sqrt(D)`` unrolled; ``y = (sigmoid(h·W_g) ⊙
norm(o))·W_o`` with the output norm over the joined heads. No activation
on q, k, v.

*Sparse layer* (``num_heads`` query heads over ``num_kv_heads`` K/V heads,
groups of G): ``q = norm_head(h·W_q)``, ``k = norm_head(h·W_k)``, ``v =
h·W_v``, **no rotary**. A query at position t < ``block_dense_len``
attends every key s ≤ t. Another, per K/V group:

1. compressed keys ``K̃_j = mean(k_s, stride·j ≤ s < stride·j + kernel)``
   for every j with ``stride·j + kernel ≤ t + 1``;
2. ``p_{h,j} = softmax_j(q_h·K̃_j / sqrt(D))`` for each of the group's
   heads, ``P_j = Σ_h p_{h,j}``;
3. block b (keys ``block·b … block·b + block − 1``) scores ``B_b = max_j
   P_j`` over the kernels of step 1 that overlap it;
4. attended blocks: the first ``block_init_blocks``; every block that
   holds a position in ``t − window + 1 … t``; and of the others the
   ``block_topk`` of largest ``B_b`` (ties to the lower b);
5. softmax over the keys s ≤ t of those blocks, scale ``1/sqrt(D)``; ``y
   = (sigmoid(h·W_g) ⊙ o)·W_o``.

**Departures from the source**, each in the configuration's ``assumed``:
the source's coarse-kernel approximation of step 2's normaliser is left
out (the exact softmax is computed); ``dense_len`` is applied a query
position, so that chunking cannot change a result (the source applies it
a call, by the call's key length); ``mup_denominator`` has no use in a
forward.

**A selection's edge.** Step 4 is a hard choice; ``selected_blocks``
gives each position's set and the gap between the last block in and the
first block out, relative to the largest score (``SELECT_EPS`` is the gap
under which a test does not ask two selections to be the same set);
``logits`` does not withhold an answer for it.

``arch`` is the ``transformer_config`` group of a configuration's file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.blocks import pangu_ultra_moe as pangu
from benchmark.blocks import trinity

#: scope names this block adds below ``layers`` (``scopes.py``): the two
#: layer kinds and their parts, and the dense MLP inside ``mlp``
SCOPES = ("lightning_attn", "lightning_proj", "lightning_scan",
          "lightning_out", "sparse_attn", "block_compress", "block_score",
          "block_select", "dense_mlp")
LIGHTNING_SCOPES = ("lightning_attn", "lightning_proj", "lightning_scan",
                    "lightning_out")
SELECT_SCOPES = ("block_compress", "block_score", "block_select")
#: the sparse layers' two attention kernels (``ops/paged_attention.py``)
SELECT_KERNEL, MASK_KERNEL = "paged_attention_select", "paged_attention_mask"

#: published key -> TransformerConfig field, for ``model.check_consistent``
PUBLISHED_TO_FIELD = {
    "head_dim": "head_size",
    "lightning_nh": "lightning_num_heads",
    "lightning_head_dim": "lightning_head_dim",
    "qk_norm": "qk_norm",
    "scale_emb": "embed_scale",
}

_rms, _rotary, _by_rows, ROW_BLOCK = (trinity._rms, trinity._rotary,
                                      trinity._by_rows, trinity.ROW_BLOCK)
_dense_mlp = pangu._dense_mlp
Q_BLOCK = pangu.Q_BLOCK
#: the relative gap (last block in − first block out, over the position's
#: largest block score) above which a float32 program's selection is
#: asked to be the reference's set, block for block
SELECT_EPS = 1e-5

KINDS = ("lightning", "block_sparse")


def check_scalings(config: dict) -> None:
    """The muP multipliers of ``transformer_config`` against the
    published keys they follow from (the published depth, not the cut)."""
    arch = config["transformer_config"]
    depth = config.get("published", {}).get("num_hidden_layers",
                                            config["num_hidden_layers"])
    want = {"residual_scale": config["scale_depth"] / math.sqrt(depth),
            "logit_scale": config["dim_model_base"] / config["hidden_size"]}
    for name, value in want.items():
        if abs(arch[name] - value) > 1e-9 * abs(value):
            raise ValueError(f"transformer_config.{name}={arch[name]!r}, "
                             f"the published keys give {value!r}")


def _blocks(a, n, q_block):
    """a [T, ...] -> [n, q_block, ...], padded with zeros."""
    pad = n * q_block - a.shape[0]
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (n, q_block) + a.shape[1:])


def _softmax_rows(s, seen):
    """softmax of s over its last axis under ``seen``; a row that sees
    nothing (a padded query) is zeros, not NaN."""
    p = jnp.where(seen, jnp.exp(s - jnp.max(
        jnp.where(seen, s, -1e30), -1, keepdims=True)), 0.0)
    return p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)


def _gated_out(h, o, lp):
    """(sigmoid(h·W_g) ⊙ o)·W_o, a block of rows at a time."""
    f32, H = jnp.float32, h.shape[-1]
    return _by_rows(lambda r: (r[:, H:] * jax.nn.sigmoid(
        r[:, :H] @ lp["wg"].astype(f32))) @ lp["wo"].astype(f32),
        jnp.concatenate([h, o], -1), ROW_BLOCK)


def _lightning(h, lp, arch, q_block):
    """h [T, hidden] -> the lightning layer's output [T, hidden], by the
    quadratic form."""
    T = h.shape[0]
    nh, hd = arch["lightning_num_heads"], arch["lightning_head_dim"]
    eps, f32 = arch["norm_eps"], jnp.float32
    at = jnp.arange(T)
    proj = lambda name: _by_rows(                              # noqa: E731
        lambda r: r @ lp[name].astype(f32), h, ROW_BLOCK)
    q, k, v = (proj(n).reshape(T, nh, hd) for n in ("wq", "wk", "wv"))
    if arch.get("qk_norm"):
        q = _rms(q, lp["q_norm_w"].astype(f32), eps)
        k = _rms(k, lp["k_norm_w"].astype(f32), eps)
    if "lightning" in (arch.get("rope_kinds") or KINDS):
        q = _rotary(q, arch["rope_theta"], at)
        k = _rotary(k, arch["rope_theta"], at)
    slope = jnp.exp2(-8.0 * jnp.arange(1, nh + 1, dtype=f32) / nh)
    n = -(-T // q_block)

    def block(xs):
        start, qb = xs
        pos = start + jnp.arange(q_block)
        back = pos[:, None] - at[None, :]                      # t − s
        seen = back >= 0
        decay = jnp.where(seen[None], jnp.exp(
            -slope[:, None, None] * jnp.maximum(back, 0)[None]), 0.0)
        w = jnp.einsum("thd,shd->hts", qb, k) * decay
        return jnp.einsum("hts,shd->thd", w, v) / math.sqrt(hd)

    o = jax.lax.map(block, (jnp.arange(n) * q_block, _blocks(q, n, q_block))
                    ).reshape(n * q_block, nh * hd)[:T]
    o = _rms(o, lp["out_norm_w"].astype(f32), eps)
    return _gated_out(h, o, lp)


def _selection(q, k, pos, arch):
    """Steps 1-4 for the queries q [Q, KH, G, D] at positions ``pos``
    [Q] against all keys k [T, KH, D]: ``(blocks [Q, KH, NB] bool — the
    blocks each attends —, margin [Q])``; a query short of ``dense_len``
    attends every block of its past, at an infinite margin."""
    T, KH, D = k.shape
    kernel, stride = arch["block_kernel_size"], arch["block_kernel_stride"]
    size, topk = arch["block_select_size"], arch["block_topk"]
    NB = -(-T // size)
    b_lo = jnp.arange(NB) * size                     # a block's first key
    past = b_lo[None, :] <= pos[:, None]
    dense = pos < arch["block_dense_len"]
    J = (T - kernel) // stride + 1
    if J <= 0:
        return jnp.broadcast_to(past[:, None, :], (len(pos), KH, NB)), \
            jnp.full(pos.shape, jnp.inf, jnp.float32)
    j_lo = jnp.arange(J) * stride                    # a kernel's first key
    comp = jnp.mean(k[j_lo[:, None] + jnp.arange(kernel)[None, :]], axis=1)
    exists = (j_lo[None, :] + kernel <= pos[:, None] + 1)[:, None, None, :]
    p = _softmax_rows(jnp.einsum("qkgd,jkd->qkgj", q, comp)
                      / math.sqrt(D), exists)
    P = jnp.sum(p, axis=2)                                     # [Q, KH, J]
    overlap = (j_lo[None, :] <= b_lo[:, None] + size - 1) \
        & (j_lo[None, :] + kernel - 1 >= b_lo[:, None])        # [NB, J]
    B = jnp.max(jnp.where(overlap[None, None] & exists, P[:, :, None, :],
                          -jnp.inf), axis=-1)                  # [Q, KH, NB]
    forced = (jnp.arange(NB)[None, :] < arch.get("block_init_blocks", 1)) \
        | ((b_lo[None, :] + size - 1 >= pos[:, None]
            - arch["block_window"] + 1) & past)
    among = jnp.where((past & ~forced)[:, None, :], B, -jnp.inf)
    kk = min(topk + 1, NB)
    top, idx = jax.lax.top_k(among, kk)          # ties: the lower b first
    chosen = jnp.any((idx[..., :topk, None] == jnp.arange(NB))
                     & jnp.isfinite(top[..., :topk, None]), axis=-2)
    if kk <= topk:
        margin = jnp.full(pos.shape, jnp.inf, jnp.float32)
    else:
        gap = jnp.where(jnp.isfinite(top[..., topk]),
                        top[..., topk - 1] - top[..., topk], jnp.inf)
        margin = jnp.min(gap / (jnp.max(jnp.where(
            jnp.isfinite(top), top, 0.0), axis=-1) + 1e-30), axis=-1)
    blocks = jnp.where(dense[:, None, None], past[:, None, :],
                       forced[:, None, :] | chosen)
    return blocks, jnp.where(dense, jnp.inf, margin)


def _sparse(h, lp, arch, q_block):
    """h [T, hidden] -> (the sparse layer's output [T, hidden], the
    selection's margin [T], the attended blocks [T, KH, NB])."""
    T = h.shape[0]
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    G, eps, f32 = nh // kvh, arch["norm_eps"], jnp.float32
    size = arch["block_select_size"]
    at = jnp.arange(T)
    proj = lambda name: _by_rows(                              # noqa: E731
        lambda r: r @ lp[name].astype(f32), h, ROW_BLOCK)
    q = proj("wq").reshape(T, kvh, G, hd)
    k, v = (proj(n).reshape(T, kvh, hd) for n in ("wk", "wv"))
    if arch.get("qk_norm"):
        q = _rms(q, lp["q_norm_w"].astype(f32), eps)
        k = _rms(k, lp["k_norm_w"].astype(f32), eps)
    if "block_sparse" in (arch.get("rope_kinds") or KINDS):
        q = _rotary(q.reshape(T, nh, hd), arch["rope_theta"], at
                    ).reshape(T, kvh, G, hd)
        k = _rotary(k, arch["rope_theta"], at)
    n = -(-T // q_block)

    def block(xs):
        start, qb = xs
        pos = start + jnp.arange(q_block)
        blocks, margin = _selection(qb, k, pos, arch)
        seen = jnp.repeat(blocks, size, axis=-1)[..., :T] \
            & (at[None, None, :] <= pos[:, None, None])        # [Q, KH, T]
        seen = seen.transpose(1, 0, 2)[:, None]                # [KH,1,Q,T]
        p = _softmax_rows(jnp.einsum("qkgd,skd->kgqs", qb, k)
                          / math.sqrt(hd), seen)
        return jnp.einsum("kgqs,skd->qkgd", p, v).reshape(
            q_block, nh * hd), margin, blocks

    o, margin, blocks = jax.lax.map(
        block, (jnp.arange(n) * q_block, _blocks(q, n, q_block)))
    o = o.reshape(n * q_block, nh * hd)[:T]
    y = _gated_out(h, o, lp)
    return y, margin.reshape(-1)[:T], \
        blocks.reshape((n * q_block,) + blocks.shape[2:])[:T]


def _layer(x, lp, kind, arch, q_block):
    """x [T, hidden] -> (x after the layer, its selection's margin [T] —
    infinite for a lightning layer)."""
    eps, f32 = arch["norm_eps"], jnp.float32
    r = arch.get("residual_scale", 1.0)
    h = _by_rows(lambda rows: _rms(rows, lp["attn_norm_w"].astype(f32), eps),
                 x, ROW_BLOCK)
    if kind == "lightning":
        y = _lightning(h, lp, arch, q_block)
        margin = jnp.full(x.shape[:1], jnp.inf, f32)
    else:
        y, margin, _ = _sparse(h, lp, arch, q_block)
    x = x + r * y
    x = _by_rows(lambda rows: rows + r * _dense_mlp(
        _rms(rows, lp["mlp_norm_w"].astype(f32), eps), lp), x, ROW_BLOCK)
    return x, margin


def _embed(params, tokens, arch):
    return params["embed"]["wte"][tokens].astype(jnp.float32) \
        * arch.get("embed_scale", 1.0)


def _logits_one(params, tokens, arch, q_block):
    """tokens [T] -> (float32 logits [T, vocab], the least margin [T] of
    the position's selections)."""
    pattern = tuple(arch["layer_pattern"])
    x = _embed(params, tokens, arch)

    def period(carry, slots):
        x, select = carry
        for kind, lp in zip(pattern, slots):
            x, s = _layer(x, lp, kind, arch, q_block)
            select = jnp.minimum(select, s)
        return (x, select), None

    slots = tuple(params["layers"][f"slot{i}"] for i in range(len(pattern)))
    (x, select), _ = jax.lax.scan(
        period, (x, jnp.full(x.shape[:1], jnp.inf, jnp.float32)), slots)
    w_norm = params["final_norm"]["w"].astype(jnp.float32)
    head = params["lm_head"]["w"]
    lg = _by_rows(lambda r: (_rms(r, w_norm, arch["norm_eps"])
                             @ head.astype(jnp.float32))
                  * arch.get("logit_scale", 1.0), x, ROW_BLOCK)
    return lg, select


def logits(params, tokens, arch, q_block=Q_BLOCK):
    """Reference logits for one sequence, at the highest matmul
    precision."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block)[0]


def selection_margins(params, tokens, arch, q_block=Q_BLOCK):
    """The least relative margin [T] of each position's selections over
    the sparse layers (infinite while nothing is cut)."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block)[1]


def selected_blocks(params, tokens, arch, slot: int = 0, q_block=Q_BLOCK):
    """``(blocks [T, KH, NB] bool, margin [T])`` of the first period's
    sparse layer at ``slot``, computed on the embedding as the layer's
    input — the model's first layer's own input where ``slot`` is 0 (the
    published stack opens with a sparse layer), so that a program's
    selection there can be held against it, set by set."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a[0], params["layers"][f"slot{slot}"])
        h = _rms(_embed(params, tokens, arch),
                 lp["attn_norm_w"].astype(jnp.float32), arch["norm_eps"])
        _, margin, blocks = _sparse(h, lp, arch, q_block)
        return blocks, margin


def loss(params, input_ids, arch, q_block=Q_BLOCK):
    """Mean next-token negative log-likelihood over ``input_ids``
    [B, T+1] (inputs are [:, :-1], labels [:, 1:])."""
    with jax.default_matmul_precision("highest"):
        def one(ids):
            lg = _logits_one(params, ids[:-1], arch, q_block)[0]
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, ids[1:, None], axis=-1)[:, 0]
            return jnp.mean(logz - gold)

        return jnp.mean(jax.lax.map(one, input_ids))


# -------------------------------------------------------------- arithmetic

def layer_kinds(arch: dict) -> dict:
    """{"lightning": n, "block_sparse": n} over the layers."""
    pattern = tuple(arch["layer_pattern"])
    periods = arch["num_layers"] // len(pattern)
    return {kind: periods * pattern.count(kind) for kind in KINDS}


def mixer_matmul_params(arch: dict, kind: str) -> int:
    """One mixer's projections: lightning q, k, v, gate, o at the joined
    heads' width; sparse q, gate, o at the query heads' and k, v at the
    K/V heads'."""
    h = arch["hidden_size"]
    if kind == "lightning":
        return 5 * h * arch["lightning_num_heads"] \
            * arch["lightning_head_dim"]
    hd = arch["head_size"]
    return h * hd * (3 * arch["num_heads"] + 2 * arch["num_kv_heads"])


def matmul_params(arch: dict) -> float:
    """Weights a token is multiplied with once in a forward pass: every
    mixer's projections, every layer's MLP, the output head."""
    h = arch["hidden_size"]
    kinds = layer_kinds(arch)
    return (sum(kinds[k] * mixer_matmul_params(arch, k) for k in KINDS)
            + arch["num_layers"] * 3 * h * arch["intermediate_size"]
            + h * arch["vocab_size"])


def state_bytes(arch: dict) -> int:
    """A sequence's recurrent state over the lightning layers, float32."""
    return layer_kinds(arch)["lightning"] * arch["lightning_num_heads"] \
        * arch["lightning_head_dim"] ** 2 * 4


def kv_token_bytes(arch: dict, el_bytes: int = 2) -> float:
    """A token's bytes in the pool over the sparse layers: k, v and its
    share of the compressed keys (one a stride)."""
    row = arch["num_kv_heads"] * arch["head_size"] * el_bytes
    return layer_kinds(arch)["block_sparse"] \
        * (2 * row + row / arch["block_kernel_stride"])


def paged_select_cost(arch: dict, query_tokens: int, blocks_selected: int,
                      el_bytes: int = 2) -> dict:
    """One sparse layer's one-token rows (kernel
    ``paged_attention_select``), the least work: each K/V head's
    selected blocks read once a row, k and v, and every query head
    against their keys twice (q·k, p·v); the queries in and the outputs
    out. ``blocks_selected``: the attended blocks summed over the rows,
    a K/V head."""
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    keys = blocks_selected * arch["block_select_size"]
    return {"flops": 4.0 * nh * hd * keys,
            "bytes": 2.0 * kvh * hd * el_bytes * keys
            + 2.0 * nh * hd * el_bytes * query_tokens}


def paged_mask_cost(arch: dict, query_tokens: int, keys_read: int,
                    pairs_selected: int, el_bytes: int = 2) -> dict:
    """One sparse layer's chunk rows (kernel ``paged_attention_mask``),
    the least work the mathematics asks, whatever the kernel walks:
    every query head against the keys its position attends
    (``pairs_selected``, a K/V head's queries sharing a set), twice;
    the keys every query of the row must read whatever it selects
    (``keys_read``: its initial blocks, its window and itself — a lower
    bound of the union), k and v, once a row."""
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    return {"flops": 4.0 * nh * hd * pairs_selected,
            "bytes": 2.0 * kvh * hd * el_bytes * keys_read
            + 2.0 * nh * hd * el_bytes * query_tokens}
