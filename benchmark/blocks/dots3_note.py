"""The ``dots3_note`` block (dots3-note-prev, ``model_type: dots3_note``):
latent attention (MLA) in every layer in two layouts — *whole-context*
layers behind a learned top-k selection of keys (an indexer), *window*
layers at widths of their own — a head-wise output gate, a leading dense
layer, every later layer followed by a sigmoid-routed sparse FFN with a
selection bias and one ungated shared expert, two norms a layer: its
plain reference (forward pass and loss), its arithmetic, the scope names
it adds and the cost functions of the kernels its cell reads, found by
the name a configuration's file gives (``"block": "dots3_note"``).

The reference is straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, in the **expanded** form:
keys and values of every head are rebuilt from the latents of **all**
earlier positions and attended as plain multi-head attention under a
mask — no cache, no absorbed product, no gather, no kernel. It imports
nothing from ``deepspeed_tpu``; what it shares with the program is the
parameter tree's naming. Pieces are taken a block at a time only where a
piece is independent of the rest (query rows, head groups, MLP slabs,
held experts), so that the check fits beside the resident engine.
Written from the published ``config.json`` and, where it has no key, from
the family's conventions (the configuration's ``assumed``); x̂ =
x·rsqrt(mean x² + eps), gain w; ρ(r) = √(hidden / r) where
``apply_mla_qkv_lora_rescale``, else 1:

    x₀      = E[tokens]
    h       = norm_in(x)
    c_q     = norm_q(h·W_qa)·ρ(q_rank)
    q       = c_q·W_qb  → heads of [q_nope | q_rope]
    [c_kv | k_r] = h·W_kva
    c       = norm_kv(c_kv)·ρ(kv_rank);  k_r = rope(k_r; θ);  q_rope = rope(q_rope; θ)
    k_nope_h = c·W_kb_h;  v_h = c·W_vb_h
    whole-context layers — the indexer:
      qI    = c_q·W_Iq → [index heads, index dim], its first ``rope`` numbers rotated (θ)
      kI    = LayerNorm(h·W_Ik) (gain, bias, eps), its first ``rope`` numbers rotated
      w     = h·W_Iw · heads_I^-1/2 · dim_I^-1/2
      I(t,s) = Σ_j w(t,j) · relu(qI(t,j)·kI(s)),  s ≤ t
      S_t   = the index_topk positions s ≤ t of largest I(t,·) (all while t < index_topk)
      keys(t) = S_t
    window layers: keys(t) = (t − window, t]     (``window`` keys, the query's own among them)
    s_h(t,s) = (q_nope_h(t)·k_nope_h(s) + q_rope_h(t)·k_r(s)) / √(nope + rope)
    a_h     = softmax_{s ∈ keys(t)}(s_h)·v_h
    a_h    *= sigmoid(h·W_g)_h                       (one gate a head)
    x       = x + concat_h(a_h)·W_o
    h       = norm_mlp(x)
    layer 0:     m = (silu(h·W_gate) ⊙ h·W_up)·W_down
    the others:  s = σ(h·W_r) over ALL experts; S = top-k of s + b;
                 w_e = route_scale · s_e / (Σ_{j∈S} s_j + 1e-20)
                 m = shared(h) + Σ_{e∈S, e held} w_e · expert_e(h)
    x       = x + m
    logits  = norm_final(x)·W_head

**Only the experts the configuration holds are summed**
(``moe_held_experts = [lo, n]``), as in ``blocks/trinity.py``, whose
sparse FFN this is: its routing, its held share, its shared expert and
its rule for ill-conditioned routing decisions are imported from there.

**A selection's edge.** ``S_t`` is a hard choice too, but of 2,048 keys
whose attention weights are near-uniform: a bfloat16 program that swaps a
key at the edge for its neighbour moves the layer's output by what one
key of 2,048 weighs. ``selection_margins`` gives each position's gap
between the last key in and the first key out, relative to the largest
score of the position (``SELECT_EPS`` is the gap under which a test
does not ask two selections to be the same set); ``logits`` does not
withhold an answer for it.

``arch`` is the ``transformer_config`` group of a configuration's file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.blocks import pangu_ultra_moe as pangu
from benchmark.blocks import trinity

#: scope names this block adds below ``layers`` (``scopes.py``): the two
#: latent layer kinds round ``qkv`` / ``kv_write`` / ``attend`` /
#: ``attn_out``, the rebuilding of K/V heads for a chunk, the indexer and
#: its parts, the index key's write, the leading layer's dense MLP, and
#: the sparse FFN's parts inside ``mlp``
SCOPES = ("latent_attn", "window_latent_attn", "kv_expand", "index",
          "index_proj", "index_score", "index_select", "index_write",
          "dense_mlp", "router", "experts", "shared_expert")
#: the scope round each attention kind's layers, under the names the
#: readers of K/V by layer group ask for (``kv_group_readers.path_share``)
ATTN_SCOPES = {"latent": "latent_attn", "full": "latent_attn",
               "window": "window_latent_attn"}
INDEX_SCOPES = ("index", "index_proj", "index_score", "index_select")

#: published key -> TransformerConfig field, for ``model.check_consistent``
#: (``n_routed_experts`` in the file is the share held,
#: ``first_k_dense_replace`` the length of ``lead_layers`` and
#: ``sliding_window_size`` the window)
PUBLISHED_TO_FIELD = {
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "index_n_heads": "index_n_heads",
    "index_head_dim": "index_head_dim",
    "index_topk": "index_topk",
    "sliding_window_size": "sliding_window",
    "swa_num_attention_heads": "swa_num_heads",
    "swa_q_lora_rank": "swa_q_lora_rank",
    "swa_kv_lora_rank": "swa_kv_lora_rank",
    "swa_qk_nope_head_dim": "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim": "swa_qk_rope_head_dim",
    "swa_v_head_dim": "swa_v_head_dim",
    "swa_rope_theta": "swa_rope_theta",
    "apply_mla_qkv_lora_rescale": "latent_rescale",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_intermediate_size",
    "norm_topk_prob": "moe_norm_topk",
    "routed_scaling_factor": "moe_route_scale",
}

_rms, _rotary, _by_rows = trinity._rms, trinity._rotary, trinity._by_rows
_held, shared_part, ROW_BLOCK = trinity._held, trinity.shared_part, \
    trinity.ROW_BLOCK
_dense_mlp = pangu._dense_mlp
HEAD_GROUP, Q_BLOCK = pangu.HEAD_GROUP, pangu.Q_BLOCK
#: a routing decision within this of the selection's edge gets no answer
#: (``trinity.TIE_MARGIN`` says why; the same sparse FFN, the same number)
TIE_MARGIN = trinity.TIE_MARGIN
#: the relative gap (last key in − first key out, over the position's
#: largest score) above which a float32 program's selection is asked to
#: be the reference's set, key for key: float32 sums of 64 products taken
#: in another order differ by about 1e-6 of the largest
SELECT_EPS = 1e-5

KINDS = ("latent_sparse", "latent_window")


def sizes(arch, kind):
    """(heads, q_rank, kv_rank, nope, rope, v, θ, window) of ``kind``'s
    layers: the model's own keys, or the ``swa_*`` ones."""
    if kind == "latent_window":
        return (arch["swa_num_heads"], arch["swa_q_lora_rank"],
                arch["swa_kv_lora_rank"], arch["swa_qk_nope_head_dim"],
                arch["swa_qk_rope_head_dim"], arch["swa_v_head_dim"],
                arch["swa_rope_theta"], arch["sliding_window"])
    return (arch["num_heads"], arch["q_lora_rank"], arch["kv_lora_rank"],
            arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
            arch["v_head_dim"], arch["rope_theta"], 0)


def _rescale(arch, rank):
    return math.sqrt(arch["hidden_size"] / rank) \
        if arch.get("latent_rescale") else 1.0


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def _rotate_head(x, dr, theta, at):
    """x [T, heads, D]: its first ``dr`` numbers rotated, the rest kept."""
    return jnp.concatenate([_rotary(x[..., :dr], theta, at), x[..., dr:]], -1)


def _selection(h, c_q, lp, arch, q_block):
    """The indexer of a whole-context layer on its normed input h
    [T, hidden] and query latent c_q [T, q_rank]: ``(keep [T, T] — the
    keys each query attends — and the edge's relative margin [T]``,
    infinite while a query has no more live keys than ``index_topk``)."""
    T = h.shape[0]
    f32, eps = jnp.float32, arch["norm_eps"]
    hi, di, topk = (arch["index_n_heads"], arch["index_head_dim"],
                    arch["index_topk"])
    dr, theta = arch["qk_rope_head_dim"], arch["rope_theta"]
    at = jnp.arange(T)
    k = _layer_norm(h @ lp["w_ik"].astype(f32), lp["ik_norm_w"].astype(f32),
                    lp["ik_norm_b"].astype(f32), eps)
    k = _rotate_head(k[:, None, :], dr, theta, at)[:, 0]        # [T, di]
    w = (h @ lp["w_iw"].astype(f32)) * (hi ** -0.5 * di ** -0.5)
    n = -(-T // q_block)
    pad = n * q_block - T
    blocks = lambda a: jnp.pad(                                # noqa: E731
        a, ((0, pad), (0, 0))).reshape(n, q_block, -1)
    kk = min(topk + 1, T)

    def block(xs):
        start, cq, wb = xs
        pos = start + jnp.arange(q_block)
        q = (cq @ lp["w_iq"].astype(f32)).reshape(q_block, hi, di)
        q = _rotate_head(q, dr, theta, pos)
        s = jnp.einsum("thd,sd->ths", q, k)
        score = jnp.einsum("ths,th->ts", jax.nn.relu(s), wb)
        live = at[None, :] <= pos[:, None]
        score = jnp.where(live, score, -jnp.inf)
        top = jax.lax.top_k(score, kk)[0]
        if kk <= topk:      # never more live keys than the selection holds
            return live, jnp.full((q_block,), jnp.inf, f32)
        last_in, first_out = top[:, topk - 1], top[:, topk]
        margin = jnp.where(jnp.isfinite(first_out),
                           (last_in - first_out)
                           / (jnp.max(jnp.abs(top), initial=0.0, axis=-1,
                                      where=jnp.isfinite(top)) + 1e-30),
                           jnp.inf)
        return live & (score >= last_in[:, None]), margin

    keep, margin = jax.lax.map(
        block, (jnp.arange(n) * q_block, blocks(c_q), blocks(w)))
    return keep.reshape(n * q_block, T)[:T], margin.reshape(-1)[:T]


def _attention(h, lp, kind, arch, q_block):
    """h [T, hidden] → (the layer's output [T, hidden], the selection's
    margin [T]: infinite for a window layer). Every position's latent is
    kept; heads are taken ``HEAD_GROUP`` at a time (their K/V rebuilt
    from all T latents), query rows ``q_block`` at a time, and a query
    sees its keys by a mask over all T."""
    T = h.shape[0]
    nh, qr, R, dn, dr, dv, theta, window = sizes(arch, kind)
    eps, f32 = arch["norm_eps"], jnp.float32
    G = math.gcd(nh, HEAD_GROUP)
    at = jnp.arange(T)
    c_q = _by_rows(lambda r: _rms(r @ lp["w_qa"].astype(f32),
                                  lp["q_a_norm_w"].astype(f32), eps),
                   h, ROW_BLOCK) * _rescale(arch, qr)
    kva = _by_rows(lambda r: r @ lp["w_kva"].astype(f32), h, ROW_BLOCK)
    c = _rms(kva[:, :R], lp["kv_a_norm_w"].astype(f32), eps) \
        * _rescale(arch, R)
    k_r = _rotary(kva[:, None, R:], theta, at)[:, 0]            # [T, rope]
    gate = jax.nn.sigmoid(h @ lp["w_g"].astype(f32)) \
        if "w_g" in lp else jnp.ones((T, nh), f32)
    if kind == "latent_sparse":
        keep, margin = _selection(h, c_q, lp, arch, q_block)
    else:
        keep = (at[None, :] <= at[:, None]) \
            & (at[None, :] > at[:, None] - window)
        margin = jnp.full((T,), jnp.inf, f32)
    n = -(-T // q_block)
    pad = n * q_block - T
    blocks = lambda a: jnp.pad(a, ((0, pad), (0, 0))).reshape(  # noqa: E731
        (n, q_block) + a.shape[1:])
    starts = jnp.arange(n) * q_block
    c_qp, keep_p = blocks(c_q), blocks(keep)

    def group(g):
        """Heads g·G … g·G + G − 1: their part of the output, through
        their rows of W_o."""
        cut = lambda w, width, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, g * G * width, G * width, axis).astype(f32)
        w_qb, w_kb = cut(lp["w_qb"], dn + dr, 1), cut(lp["w_kb"], dn, 1)
        w_vb, w_o = cut(lp["w_vb"], dv, 1), cut(lp["wo"], dv, 0)
        gate_p = blocks(jax.lax.dynamic_slice_in_dim(gate, g * G, G, 1))
        k_nope = (c @ w_kb).reshape(T, G, dn)
        v = (c @ w_vb).reshape(T, G, dv)

        def block(xs):
            start, cq, seen, gt = xs
            pos = start + jnp.arange(q_block)
            q = (cq @ w_qb).reshape(q_block, G, dn + dr)
            q_rope = _rotary(q[..., dn:], theta, pos)
            s = (jnp.einsum("tgd,sgd->gts", q[..., :dn], k_nope)
                 + jnp.einsum("tgd,sd->gts", q_rope, k_r)) \
                / math.sqrt(dn + dr)
            s = jnp.where(seen[None], s, -jnp.inf)
            # a padded query row sees nothing: its row is zeros, not NaN
            p = jnp.where(seen[None], jnp.exp(
                s - jnp.max(jnp.where(seen[None], s, -1e30), -1,
                            keepdims=True)), 0.0)
            p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
            a = jnp.einsum("gts,sgd->tgd", p, v) * gt[:, :, None]
            return a.reshape(q_block, G * dv) @ w_o

        return jax.lax.map(block, (starts, c_qp, keep_p, gate_p)).reshape(
            n * q_block, -1)[:T]

    out = jax.lax.fori_loop(
        0, nh // G, lambda g, acc: acc + group(g),
        jnp.zeros((T, lp["wo"].shape[-1]), f32))
    return out, margin


def routed_part(h, lp, arch, held=None):
    """The part of the routed sum that the experts ``held = (lo, n)`` add
    (``lp``'s expert leaves hold those n), without the shared expert."""
    return trinity.routed_part(h, lp, arch, held)


def _layer(x, lp, kind, dense, arch, q_block):
    """x [T, hidden] → (x after the layer, the margin [T] of its routing
    decision — infinite for the leading layer — and of its selection)."""
    eps, f32 = arch["norm_eps"], jnp.float32
    a, select = _attention(
        _by_rows(lambda r: _rms(r, lp["attn_norm_w"].astype(f32), eps), x,
                 ROW_BLOCK), lp, kind, arch, q_block)
    x = x + a

    def ffn(rows):
        h = _rms(rows, lp["mlp_norm_w"].astype(f32), eps)
        if dense:
            f = _dense_mlp(h, lp)
            margin = jnp.full(rows.shape[:1], jnp.inf, f32)
        else:
            f, margin = trinity._routed(h, lp, arch, _held(arch))
            f = f + shared_part(h, lp)
        return rows + f, margin

    x, route = _by_rows(ffn, x, ROW_BLOCK)
    return x, route, select


def _logits_one(params, tokens, arch, q_block):
    """tokens [T] → (float32 logits [T, vocab], the least margin [T] of
    the position's routing decisions, the least margin [T] of its
    selections)."""
    pattern = tuple(arch["layer_pattern"])
    lead = tuple(arch.get("lead_layers") or ())
    layers = params["layers"]
    x = params["embed"]["wte"][tokens].astype(jnp.float32) \
        * arch.get("embed_scale", 1.0)
    inf = jnp.full(x.shape[:1], jnp.inf, jnp.float32)
    select = inf
    for j, kind in enumerate(lead):
        x, _, s = _layer(x, jax.tree.map(lambda a: a[0], layers[f"lead{j}"]),
                         kind, True, arch, q_block)
        select = jnp.minimum(select, s)

    def period(carry, slots):
        x, route, select = carry
        for kind, lp in zip(pattern, slots):
            x, r, s = _layer(x, lp, kind, False, arch, q_block)
            route, select = jnp.minimum(route, r), jnp.minimum(select, s)
        return (x, route, select), None

    slots = tuple(layers[f"slot{i}"] for i in range(len(pattern)))
    (x, route, select), _ = jax.lax.scan(period, (x, inf, select), slots)
    w_norm = params["final_norm"]["w"].astype(jnp.float32)
    head = params["lm_head"]["w"]
    lg = _by_rows(lambda r: _rms(r, w_norm, arch["norm_eps"])
                  @ head.astype(jnp.float32), x, ROW_BLOCK)
    return lg, route, select


def logits(params, tokens, arch, q_block=Q_BLOCK):
    """Reference logits for one sequence, at the highest matmul
    precision — and no answer (NaN) at a position whose routing is
    ill-conditioned (``TIE_MARGIN``; ``trinity.logits`` says what the
    harness does with such a position)."""
    with jax.default_matmul_precision("highest"):
        lg, route, _ = _logits_one(params, tokens, arch, q_block)
    return jnp.where((route < TIE_MARGIN)[:, None], jnp.nan, lg)


def tie_margins(params, tokens, arch, q_block=Q_BLOCK):
    """(logits [T, vocab] with every position answered, the least margin
    [T] of each position's routing decisions)."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block)[:2]


def selection_margins(params, tokens, arch, q_block=Q_BLOCK):
    """The least relative margin [T] of each position's selections over
    the whole-context layers (infinite while nothing is cut)."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block)[2]


def selected_keys(params, tokens, arch, layer: str = "lead0",
                  q_block=Q_BLOCK):
    """``(keep [T, T], margin [T])`` of one whole-context layer's
    selection computed on the embedding as the layer's input — the
    leading layer's own input, so that a program's selection there can
    be held against it, set by set."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        lp = jax.tree.map(lambda a: a[0], params["layers"][layer])
        x = params["embed"]["wte"][tokens].astype(f32) \
            * arch.get("embed_scale", 1.0)
        h = _rms(x, lp["attn_norm_w"].astype(f32), arch["norm_eps"])
        c_q = _rms(h @ lp["w_qa"].astype(f32), lp["q_a_norm_w"].astype(f32),
                   arch["norm_eps"]) * _rescale(arch, arch["q_lora_rank"])
        return _selection(h, c_q, lp, arch, q_block)


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
    """{"latent_sparse": n, "latent_window": n, "lead": n, "sparse": n}
    over the layers (``sparse``: layers whose FFN is the sparse one)."""
    pattern = tuple(arch["layer_pattern"])
    lead = tuple(arch.get("lead_layers") or ())
    periods = (arch["num_layers"] - len(lead)) // len(pattern)
    count = lambda kind: (lead.count(kind)                     # noqa: E731
                          + periods * pattern.count(kind))
    return {"latent_sparse": count("latent_sparse"),
            "latent_window": count("latent_window"), "lead": len(lead),
            "sparse": arch["num_layers"] - len(lead)}


def attention_matmul_params(arch: dict, kind: str) -> int:
    """One latent mixer of ``kind``: W_qa, W_qb, W_kva, W_kb + W_vb, W_o,
    the head-wise gate; the indexer's W_Iq, W_Ik, W_Iw where it has one."""
    h = arch["hidden_size"]
    nh, qr, R, dn, dr, dv, _, _ = sizes(arch, kind)
    n = (h * qr + qr * nh * (dn + dr) + h * (R + dr) + R * nh * (dn + dv)
         + nh * dv * h)
    if arch.get("attn_gate_headwise"):
        n += h * nh
    if kind == "latent_sparse":
        hi, di = arch["index_n_heads"], arch["index_head_dim"]
        n += qr * hi * di + h * di + h * hi
    return n


def expert_matmul_params(arch: dict) -> int:
    """One expert (routed or shared): gate, up, down."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def matmul_params(arch: dict) -> float:
    """Weights a token is multiplied with once in a forward pass *here*
    (``pangu_ultra_moe.matmul_params``' rule, a mixer a kind): what a
    chunk rebuilds again and what the indexer and the attention multiply
    by the context are the cost functions' below."""
    h = arch["hidden_size"]
    kinds = layer_kinds(arch)
    held = _held(arch)[1]
    sparse = (h * arch["moe_num_experts"]
              + 3 * h * arch.get("moe_shared_intermediate_size", 0)
              + arch["moe_top_k"] * held / arch["moe_num_experts"]
              * expert_matmul_params(arch))
    return (sum(kinds[k] * attention_matmul_params(arch, k) for k in KINDS)
            + kinds["lead"] * 3 * h * arch["intermediate_size"]
            + kinds["sparse"] * sparse + h * arch["vocab_size"])


def latent_bytes(arch: dict, kind: str, el_bytes: int = 2) -> int:
    """What a token's cache row of ``kind`` must hold: ``(c, k_r)``,
    unpadded."""
    _, _, R, _, dr, _, _, _ = sizes(arch, kind)
    return (R + dr) * el_bytes


def mla_sparse_decode_cost(arch: dict, query_tokens: int, keys_selected: int,
                           el_bytes: int = 2) -> dict:
    """One whole-context layer's sparse absorbed call (kernel
    ``mla_sparse_decode``), the least work: a head multiplies ``q~ |
    q_rope`` with each of its query's selected keys and the
    probabilities with the latent; every selected row is read once a
    query position (each has its own set), plus the queries in and the
    attended latents out. ``keys_selected``: the selected keys summed
    over the query positions."""
    nh, _, R, _, dr, _, _, _ = sizes(arch, "latent_sparse")
    return {"flops": 2.0 * nh * (R + dr + R) * keys_selected,
            "bytes": latent_bytes(arch, "latent_sparse", el_bytes)
            * keys_selected + nh * (R + dr + R) * el_bytes * query_tokens}


def index_score_cost(arch: dict, query_tokens: int, kv_read_tokens: int,
                     qk_pairs: int, el_bytes: int = 2) -> dict:
    """One whole-context layer's scoring call (kernel ``index_score``),
    the least work: every index head of a query against every key it may
    see (``qk_pairs``: the causal pairs), a multiply-add a number; the
    live index keys read once a sequence, the queries and head weights
    in, a float32 score a pair out."""
    hi, di = arch["index_n_heads"], arch["index_head_dim"]
    return {"flops": 2.0 * hi * di * qk_pairs,
            "bytes": di * el_bytes * kv_read_tokens
            + hi * (di * el_bytes + 4) * query_tokens + 4.0 * qk_pairs}


def mla_window_cost(arch: dict, q_absorbed: int, keys_absorbed: int,
                    pairs_absorbed: int, q_expanded: int, keys_expanded: int,
                    pairs_expanded: int, el_bytes: int = 2) -> dict:
    """One window layer's attention calls (kernels ``mla_window_decode``
    and ``mla_window_prefill``), the least work, path by path as
    ``pangu_ultra_moe``'s two cost functions count it at this kind's
    widths: absorbed, pairs at ``2 · rank + rope`` and the live latents
    read once a sequence; expanded, pairs at ``nope + rope + v`` and the
    rebuilt K and V of the keys inside the window read once. Keys and
    pairs are the window's (``kv_g1_*`` of the program)."""
    nh, _, R, dn, dr, dv, _, _ = sizes(arch, "latent_window")
    return {"flops": 2.0 * nh * ((R + dr + R) * pairs_absorbed
                                 + (dn + dr + dv) * pairs_expanded),
            "bytes": latent_bytes(arch, "latent_window", el_bytes)
            * keys_absorbed + nh * (R + dr + R) * el_bytes * q_absorbed
            + el_bytes * ((nh * (dn + dv) + dr) * keys_expanded
                          + nh * (dn + dr + dv) * q_expanded)}


def kv_expand_flops(arch: dict, kind: str, positions: int) -> float:
    """Rebuilding the K/V heads of ``positions`` context positions, one
    layer of ``kind``."""
    nh, _, R, dn, _, dv, _, _ = sizes(arch, kind)
    return 2.0 * R * nh * (dn + dv) * positions
