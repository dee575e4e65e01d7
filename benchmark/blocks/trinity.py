"""The ``trinity`` block (Arcee Trinity, ``model_type: afmoe``): window and
full attention layers in one period behind leading dense layers, every
later layer followed by a sigmoid-routed sparse FFN — its plain reference
(forward pass and loss), its arithmetic, the scope names it adds and the
cost functions of the kernels its cell reads, found by the name a
configuration's file gives (``"block": "trinity"``; ``manifest.resolve``).

The reference is straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: attention as a masked softmax
over **all** earlier keys of the sequence, in query blocks (a window is a
mask, nothing is ever dropped — so a block the program handed back too
early shows as a disagreement), experts as a loop over the held experts,
each made float32 when its turn comes; no kernel, no cache, no state
carried from one block of rows to the next (rows are taken a block at a
time only where what is computed is a row's own, so that the check fits
beside the resident engine).
It imports nothing from ``deepspeed_tpu``; the only thing shared with the
program is the parameter tree's naming (``layers.lead<j>`` a leading dense
layer, ``layers.slot<i>`` a position of the period, stacked over the
periods). Written from the published ``config.json`` and, where it has no
key, the source's ``modeling_afmoe.py`` (the configuration's ``assumed``);
x̂ = x·rsqrt(mean x² + eps), gain w:

    x₀    = E[tokens] · embed_scale                  (√hidden: mup_enabled)
    h     = norm_in(x)
    q,k,v = h·W_q, h·W_k, h·W_v;  g = h·W_g          (no bias anywhere)
    q,k   = rmsnorm_head(q), rmsnorm_head(k)         (before any rotation)
    window layer: q,k = rope(q,k; θ, all dims); query p sees (p − W, p]
    full layer:   no rotary, no position term;   query p sees keys ≤ p
    a     = softmax(q·kᵀ/√D + mask)·v
    x     = x + norm_post_attn((a ⊙ σ(g))·W_o)
    h     = norm_pre_mlp(x)
    lead layers:  m = (silu(h·W_gate) ⊙ h·W_up)·W_down
    the others:   s = σ(h·W_r) over ALL experts (float32); S = top-k of
                  (s + b); w_e = route_scale · s_e / (Σ_{j∈S} s_j + 1e-20)
                  m = shared(h) + Σ_{e∈S, e held} w_e · expert_e(h)
    x     = x + norm_post_mlp(m)
    logits = norm_final(x)·W_head

**Only the experts the configuration holds are summed**
(``moe_held_experts = [lo, n]``): what the others would add is the other
chips' part, left out here as in the program, and that partial sum is what
goes on to the next layer. The shared expert has no gate.

``arch`` is the ``transformer_config`` group of a configuration's file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: scope names this block adds below ``layers`` (``scopes.py``): the two
#: attention kinds round ``qkv`` / ``kv_write`` / ``attend`` / ``attn_out``
#: (which stay the innermost scope of what they hold), the leading
#: layers' dense MLP, and the sparse FFN's parts inside ``mlp``
SCOPES = ("window_attn", "full_attn", "dense_mlp", "router", "experts",
          "shared_expert")
ATTN_SCOPES = {"window": "window_attn", "full": "full_attn"}

#: published key -> TransformerConfig field, for ``model.check_consistent``
#: (``num_experts`` in the file is the share held, checked by ``held``;
#: ``num_dense_layers`` is the length of ``lead_layers``, checked there)
PUBLISHED_TO_FIELD = {
    "head_dim": "head_size",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_intermediate_size",
    "route_norm": "moe_norm_topk",
    "route_scale": "moe_route_scale",
    "score_func": "moe_score_func",
}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, theta, positions):
    """x [T, heads, D] at ``positions`` [T]: rotate every head by its
    position over all D dims (rotate-half: dim i pairs with i + D/2)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _by_rows(fn, x, rows):
    """``fn`` over x [T, ...] taken ``rows`` rows at a time (what it
    computes is a row's own: a projection, an MLP): the float32
    intermediates of a long sequence are a block's and not the whole
    sequence's. ``fn`` may return several arrays, each a row's own."""
    T = x.shape[0]
    n = -(-T // rows)
    padded = jnp.pad(x, ((0, n * rows - T),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, padded.reshape((n, rows) + x.shape[1:]))
    return jax.tree.map(
        lambda o: o.reshape((n * rows,) + o.shape[2:])[:T], out)


def _attention(h, lp, kind, arch, q_block):
    """h [T, hidden] → the layer's output [T, hidden]. Every key and
    value of the sequence is kept; query rows are taken ``q_block`` at a
    time, and a query at position p sees keys ≤ p — with a window only
    those in (p − window, p] — by a mask over all T keys."""
    T = h.shape[0]
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    eps, theta = arch["norm_eps"], arch["rope_theta"]
    window = arch["sliding_window"] if kind == "window" else 0
    k = _rms((h @ lp["wk"]).reshape(T, kvh, hd), lp["k_norm_w"], eps)
    v = (h @ lp["wv"]).reshape(T, kvh, hd)
    if kind == "window":
        k = _rotary(k, theta, jnp.arange(T))
    cols = jnp.arange(T)[None, :]

    def block(xs):
        start, hq = xs                                  # [q_block, hidden]
        at = start + jnp.arange(q_block)
        q = _rms((hq @ lp["wq"]).reshape(q_block, nh, hd), lp["q_norm_w"],
                 eps)
        if kind == "window":
            q = _rotary(q, theta, at)
        keep = cols <= at[:, None]
        if window:
            keep &= cols > at[:, None] - window
        # each KV head serves heads / kv_heads query heads
        qg = q.reshape(q_block, kvh, nh // kvh, hd)
        s = jnp.einsum("tkgd,skd->kgts", qg, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
        a = jnp.einsum("kgts,skd->tkgd", p, v).reshape(q_block, nh * hd)
        return (a * jax.nn.sigmoid(hq @ lp["wg"])) @ lp["wo"]

    n = -(-T // q_block)
    hp = jnp.pad(h, ((0, n * q_block - T), (0, 0)))
    out = jax.lax.map(block, (jnp.arange(n) * q_block,
                              hp.reshape(n, q_block, -1)))
    return out.reshape(n * q_block, -1)[:T]


#: rows a position-wise part of the reference takes at a time
ROW_BLOCK = 2048


def _swiglu(h, w_gate, w_in, w_out):
    return (jax.nn.silu(h @ w_gate) * (h @ w_in)) @ w_out


def _held(arch):
    return tuple(arch.get("moe_held_experts")
                 or (0, arch["moe_num_experts"]))


#: A routing decision is **ill-conditioned** where a held expert's
#: score + bias lies within this of the selection's edge (the k-th
#: candidate if it is out, the k+1-th if it is in): a program that rounds
#: its hidden state to bfloat16 may then choose otherwise than float32
#: does, both choices are the model's, and the one expert more or less
#: moves the logits by 0.15-0.2 of their range at the published widths —
#: more than weights through fp8 move them (0.06). ``logits`` gives no
#: answer (NaN) at a position where any sparse layer's decision is so;
#: ``tie_margins`` gives the margins. The number, measured on the chip
#: (PR 34; 256 comparisons at the published widths, PERF.md section 4 and
#: the configuration's ``check._ties``): the bfloat16 program chose
#: otherwise at 11 positions, every one at a margin of 0.00014-0.00148 —
#: about a third of the positions under 0.0015, which makes the rounding
#: of a score + bias roughly 0.001 — and nowhere above; 0.004 is 2.7 x
#: the largest of them and about four such roundings.
TIE_MARGIN = 0.004


def _route(h, lp, arch, held):
    """(weights [T, k], experts [T, k], margin [T]): sigmoid scores over
    all experts, the top k of score + bias, the weights from the unbiased
    scores; and how far the nearest of the ``held = (lo, n)`` experts is
    from changing sides of the selection."""
    f32, k = jnp.float32, arch["moe_top_k"]
    lo, n_held = held
    s = jax.nn.sigmoid(h @ lp["router_wg"].astype(f32))
    chosen_by = s + lp["router_b"].astype(f32)
    edge, top_e = jax.lax.top_k(chosen_by, k + 1)
    top_e = top_e[:, :k]
    mine = chosen_by[:, lo:lo + n_held]
    # an expert that is in stays in while it beats the first one out; one
    # that is out stays out while the last one in beats it
    margin = jnp.min(jnp.where(mine >= edge[:, k - 1:k],
                               mine - edge[:, k:k + 1],
                               edge[:, k - 1:k] - mine), axis=-1)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if arch.get("moe_norm_topk"):
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return top_s * arch.get("moe_route_scale", 1.0), top_e, margin


def routed_part(h, lp, arch, held=None):
    """The part of the routed sum that the experts ``held = (lo, n)`` add
    (``lp``'s expert leaves hold those n), without the shared expert."""
    return _routed(h, lp, arch, held or _held(arch))[0]


def _routed(h, lp, arch, held):
    """``routed_part`` and the decision's margin (``_route``)."""
    f32 = jnp.float32
    lo, n_held = held
    top_w, top_e, margin = _route(h, lp, arch, held)

    def expert(e, acc):
        weight = jnp.sum(jnp.where(top_e == lo + e, top_w, 0.0), axis=-1)
        y = _swiglu(h, lp["w_gate"][e].astype(f32), lp["w_in"][e].astype(f32),
                    lp["w_out"][e].astype(f32))
        return acc + weight[:, None] * y

    return jax.lax.fori_loop(0, n_held, expert, jnp.zeros_like(h)), margin


def shared_part(h, lp):
    f32 = jnp.float32
    return _swiglu(h, lp["shared_w_gate"].astype(f32),
                   lp["shared_w_in"].astype(f32),
                   lp["shared_w_out"].astype(f32))


_FFN_LEAVES = ("router_wg", "router_b", "w_in", "w_gate", "w_out",
               "shared_w_in", "shared_w_gate", "shared_w_out")


def _layer(x, lp, kind, dense, arch, q_block):
    """x [T, hidden] → (x after the layer, the margin [T] of its routing
    decision: infinite for a leading layer, which routes nothing)."""
    eps, f32 = arch["norm_eps"], jnp.float32
    m = {k: v.astype(f32) for k, v in lp.items() if k not in _FFN_LEAVES}
    a = _attention(_rms(x, m["attn_norm_w"], eps), m, kind, arch, q_block)
    x = x + _rms(a, m["post_attn_norm_w"], eps)

    def ffn(rows):
        h = _rms(rows, m["mlp_norm_w"], eps)
        if dense:
            f = _swiglu(h, lp["w_gate"].astype(f32), lp["w_in"].astype(f32),
                        lp["w_out"].astype(f32))
            margin = jnp.full(rows.shape[:1], jnp.inf, f32)
        else:
            f, margin = _routed(h, lp, arch, _held(arch))
            f = f + shared_part(h, lp)
        return rows + _rms(f, m["post_mlp_norm_w"], eps), margin

    return _by_rows(ffn, x, ROW_BLOCK)


def _logits_one(params, tokens, arch, q_block):
    """tokens [T] → (float32 logits [T, vocab], the least margin [T] of
    the position's routing decisions over the sparse layers)."""
    pattern = tuple(arch["layer_pattern"])
    lead = tuple(arch.get("lead_layers") or ())
    layers = params["layers"]
    x = params["embed"]["wte"][tokens].astype(jnp.float32) \
        * arch.get("embed_scale", 1.0)
    for j, kind in enumerate(lead):
        x, _ = _layer(x, jax.tree.map(lambda a: a[0], layers[f"lead{j}"]),
                      kind, True, arch, q_block)

    def period(carry, slots):
        x, least = carry
        for kind, lp in zip(pattern, slots):
            x, margin = _layer(x, lp, kind, False, arch, q_block)
            least = jnp.minimum(least, margin)
        return (x, least), None

    slots = tuple(layers[f"slot{i}"] for i in range(len(pattern)))
    (x, least), _ = jax.lax.scan(
        period, (x, jnp.full(x.shape[:1], jnp.inf, jnp.float32)), slots)
    x = _rms(x, params["final_norm"]["w"].astype(jnp.float32),
             arch["norm_eps"])
    return x @ params["lm_head"]["w"].astype(jnp.float32), least


#: query rows a block of the reference's attention takes: the scores of
#: 48 heads over 12,544 keys are 2.4 MB a query row in float32, and the
#: check runs beside 12.6 GiB of resident engine
Q_BLOCK = 128


def logits(params, tokens, arch, q_block=Q_BLOCK):
    """Reference logits for one sequence, at the highest matmul
    precision — and **no answer (NaN) at a position whose routing is
    ill-conditioned** (``TIE_MARGIN``): there two answers are the
    model's, a float32 reference knows one of them, and which one a
    bfloat16 program meets is rounding's to say. Whether a position is
    so is decided here, from the float32 margins alone, before anything
    of the program is seen. ``serve_runner.check_logits`` takes a
    reference row that is NaN throughout for this mask: the position is
    not compared and is counted (``logits_check.unanswered``), and any
    other value that is not a number fails the check
    (tests/benchmark/test_trinity_block.py holds both ends)."""
    with jax.default_matmul_precision("highest"):
        lg, least = _logits_one(params, tokens, arch, q_block)
    return jnp.where((least < TIE_MARGIN)[:, None], jnp.nan, lg)


def tie_margins(params, tokens, arch, q_block=Q_BLOCK):
    """(logits [T, vocab] with every position answered, the least margin
    [T] of each position's routing decisions): what ``TIE_MARGIN`` was
    measured with."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block)


def loss(params, input_ids, arch, q_block=Q_BLOCK):
    """Mean next-token negative log-likelihood over ``input_ids``
    [B, T+1] (inputs are [:, :-1], labels [:, 1:])."""
    with jax.default_matmul_precision("highest"):
        def one(ids):
            lg, _ = _logits_one(params, ids[:-1], arch, q_block)
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, ids[1:, None], axis=-1)[:, 0]
            return jnp.mean(logz - gold)

        return jnp.mean(jax.lax.map(one, input_ids))


# -------------------------------------------------------------- arithmetic

def attention_matmul_params(arch: dict) -> int:
    """One attention mixer: q, the gate and o over all heads, k and v over
    the KV heads."""
    h, nh, hd = arch["hidden_size"], arch["num_heads"], arch["head_size"]
    return 3 * h * nh * hd + 2 * h * arch["num_kv_heads"] * hd


def expert_matmul_params(arch: dict) -> int:
    """One expert (routed or shared): gate, up, down."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def layer_kinds(arch: dict) -> dict:
    """{"window": n, "full": n, "lead": n, "sparse": n} over the layers."""
    pattern = tuple(arch["layer_pattern"])
    lead = tuple(arch.get("lead_layers") or ())
    periods = (arch["num_layers"] - len(lead)) // len(pattern)
    count = lambda kind: lead.count(kind) + periods * pattern.count(kind)  # noqa: E731
    return {"window": count("window"), "full": count("full"),
            "lead": len(lead), "sparse": arch["num_layers"] - len(lead)}


def matmul_params(arch: dict) -> float:
    """Weights a token is multiplied with once in a forward pass *here*:
    each layer's mixer, the leading layers' dense MLP, each later layer's
    router and shared expert, the output head, and of a token's top-k
    experts those this configuration holds — ``top_k · held / experts``
    of them **in expectation** (even routing); what the absent experts
    would cost is the other chips'. The embedding is a lookup and norms
    are not weight matmuls."""
    h = arch["hidden_size"]
    kinds = layer_kinds(arch)
    held = _held(arch)[1]
    sparse = (h * arch["moe_num_experts"]                       # router
              + 3 * h * arch.get("moe_shared_intermediate_size", 0)
              + arch["moe_top_k"] * held / arch["moe_num_experts"]
              * expert_matmul_params(arch))
    return (arch["num_layers"] * attention_matmul_params(arch)
            + kinds["lead"] * 3 * h * arch["intermediate_size"]
            + kinds["sparse"] * sparse + h * arch["vocab_size"])


def attention_calls(arch: dict) -> list:
    """(window, layers) of each group of attention layers whose kernel
    calls cost alike: the window layers, bounded by the window, and the
    full layers (window 0). The order is the program's
    (``TransformerConfig.kv_groups``: the whole context first)."""
    kinds = layer_kinds(arch)
    groups = [(0, kinds["full"]),
              (int(arch.get("sliding_window") or 0), kinds["window"])]
    return [g for g in groups if g[1]]


def paged_attention_cost(arch: dict, query_tokens: int, kv_read_tokens: int,
                         qk_pairs: int, kv_bytes: int = 2,
                         q_bytes: int = 2) -> dict:
    """One attention layer's paged-attention call at the *stated* head
    size (128 here, not hidden / heads = 64, which ``peaks`` would
    derive). ``kv_read_tokens`` and ``qk_pairs`` are the layer's own —
    bounded by the window on a window layer, whole on a full layer: the
    program counts them, group by group, in ``engine.last_put``
    (``kv_g<i>_read_tokens`` / ``kv_g<i>_qk_pairs``), and the reader hands
    them on (``kv_group_readers``); this module holds no second copy of
    that count. FLOPs: QKᵀ and PV over the query-key pairs.
    Bytes: the K and V of every position a sequence's queries may see,
    read once a sequence, plus q in and o out."""
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    return {"flops": 4.0 * nh * hd * qk_pairs,
            "bytes": 2.0 * kvh * hd * kv_bytes * kv_read_tokens
            + 2.0 * nh * hd * q_bytes * query_tokens}


def gmm_cost(arch: dict, valid_tokens: int, el_bytes: int = 2) -> dict:
    """The held experts' grouped matmuls (kernel ``gmm``: gate, up and
    down, every sparse layer) of ONE forward over ``valid_tokens`` tokens.
    FLOPs of the expected held (token, choice) pairs; bytes of the experts
    the forward is expected to hit, each streamed once (an expert is hit
    by a forward of t tokens with probability 1 − (1 − k/E)^t), plus the
    rows in and out of the three matmuls."""
    h, k, E = arch["hidden_size"], arch["moe_top_k"], arch["moe_num_experts"]
    m = arch["moe_intermediate_size"]
    held = _held(arch)[1]
    per_expert = expert_matmul_params(arch)
    layers = layer_kinds(arch)["sparse"]
    pairs = valid_tokens * k * held / E
    hit = held * (1.0 - (1.0 - k / E) ** valid_tokens)
    return {"flops": layers * 2.0 * per_expert * pairs,
            "bytes": layers * el_bytes
            * (hit * per_expert + pairs * (3 * h + 3 * m))}
