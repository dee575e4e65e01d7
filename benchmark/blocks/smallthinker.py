"""The ``smallthinker`` block (PowerInfer SmallThinker,
``model_name: smallthinker_21b_instruct``): one whole-context attention
layer without any position term and three rotated window layers a period,
every layer followed by softmax-routed gated-ReLU experts whose router
reads the **layer's own input**, ahead of the attention's norm — its
plain reference (forward pass and loss), its arithmetic, the scope names
it adds and the cost functions of the kernels its cell reads, found by
the name a configuration's file gives (``"block": "smallthinker"``;
``manifest.resolve``).

The reference is straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: attention as a masked softmax
over **all** earlier keys of the sequence, in query blocks (a window is a
mask, nothing is ever dropped — so a block the program handed back too
early shows as a disagreement), experts as a loop over the experts, each
made float32 when its turn comes; no kernel, no cache, nothing carried
from one block of rows to the next (rows are taken a block at a time only
where what is computed is a row's own, so that the check fits beside the
resident engine). It imports nothing from ``deepspeed_tpu``; the only
thing shared with the program is the parameter tree's naming
(``layers.slot<i>`` a position of the period, stacked over the periods).
Written from the catalog row's ``config`` and ``described_as`` (the
configuration's ``assumed`` says which is which); x the layer's input
[T, hidden], x̂ = x·rsqrt(mean x² + eps), gain w:

    r     = x·W_r                  router logits [T, 64], float32, from x
                                   ITSELF: before norm_1, before attention
    q,k,v = norm_1(x)·W_q, ·W_k, ·W_v                (no bias, no q/k norm)
    window layer: q,k = rope(q,k; θ, all dims); query p sees (p − W, p]
    full layer:   no rotary, no position term;   query p sees keys ≤ p
    a     = softmax(q·kᵀ/√D + mask)·v ·W_o           (28 heads on 4 K/V)
    h     = x + a
    u     = norm_2(h)
    S     = top-6 of r;  g = softmax(r[S])           (= softmax over all
                                   64, its top 6 divided by their sum)
    f     = Σ_{e∈S} g_e · W_down_e(relu(W_gate_e u) ⊙ W_up_e u)
    y     = h + f
    logits = norm_final(y_last)·W_head

Only the experts the configuration holds are summed (``moe_held_experts
= [lo, n]``; here all 64 — the general form is kept for the test that
ties a share to the layer).

``arch`` is the ``transformer_config`` group of a configuration's file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: scope names this block adds below ``layers`` (``scopes.py``): the two
#: attention kinds round ``qkv`` / ``kv_write`` / ``attend`` / ``attn_out``
#: (which stay the innermost scope of what they hold), the router — ahead
#: of ``attn_norm`` for this block, not inside ``mlp`` — and the experts
SCOPES = ("window_attn", "full_attn", "router", "experts")
ATTN_SCOPES = {"window": "window_attn", "full": "full_attn"}

#: published key -> TransformerConfig field, for ``model.check_consistent``
PUBLISHED_TO_FIELD = {
    "head_dim": "head_size",
    "moe_ffn_hidden_size": "moe_intermediate_size",
    "moe_num_primary_experts": "moe_num_experts",
    "moe_num_active_primary_experts": "moe_top_k",
    "norm_topk_prob": "moe_norm_topk",
    "sliding_window_size": "sliding_window",
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


def _by_rows(fn, xs, rows):
    """``fn`` over arrays [T, ...] taken ``rows`` rows at a time (what it
    computes is a row's own: a projection, an FFN): the float32
    intermediates of a long sequence are a block's and not the whole
    sequence's. ``xs`` and what ``fn`` returns may be several arrays."""
    T = jax.tree.leaves(xs)[0].shape[0]
    n = -(-T // rows)
    cut = lambda x: jnp.pad(                                    # noqa: E731
        x, ((0, n * rows - T),) + ((0, 0),) * (x.ndim - 1)
    ).reshape((n, rows) + x.shape[1:])
    out = jax.lax.map(fn, jax.tree.map(cut, xs))
    return jax.tree.map(
        lambda o: o.reshape((n * rows,) + o.shape[2:])[:T], out)


def _attention(h, lp, kind, arch, q_block):
    """h [T, hidden], the layer's normed input → (the layer's attention
    output [T, hidden], its keys — rotated where the kind rotates — and
    values [T, kv_heads, D], what a K/V cache holds of the layer). Every
    key and value of the sequence is kept; query
    rows are taken ``q_block`` at a time, and a query at position p sees
    keys ≤ p — with a window only those in (p − window, p] — by a mask
    over all T keys. A window layer rotates q and k; a full layer has no
    position term at all."""
    T = h.shape[0]
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    theta = arch["rope_theta"]
    window = arch["sliding_window"] if kind == "window" else 0
    k = (h @ lp["wk"]).reshape(T, kvh, hd)
    v = (h @ lp["wv"]).reshape(T, kvh, hd)
    if kind == "window":
        k = _rotary(k, theta, jnp.arange(T))
    cols = jnp.arange(T)[None, :]

    def block(xs):
        start, hq = xs                                  # [q_block, hidden]
        at = start + jnp.arange(q_block)
        q = (hq @ lp["wq"]).reshape(q_block, nh, hd)
        if kind == "window":
            q = _rotary(q, theta, at)
        keep = cols <= at[:, None]
        if window:
            keep &= cols > at[:, None] - window
        # each K/V head serves heads / kv_heads query heads (7 here)
        qg = q.reshape(q_block, kvh, nh // kvh, hd)
        s = jnp.einsum("tkgd,skd->kgts", qg, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
        a = jnp.einsum("kgts,skd->tkgd", p, v).reshape(q_block, nh * hd)
        return a @ lp["wo"]

    n = -(-T // q_block)
    hp = jnp.pad(h, ((0, n * q_block - T), (0, 0)))
    out = jax.lax.map(block, (jnp.arange(n) * q_block,
                              hp.reshape(n, q_block, -1)))
    return out.reshape(n * q_block, -1)[:T], k, v


#: rows a position-wise part of the reference takes at a time
ROW_BLOCK = 2048


def _held(arch):
    return tuple(arch.get("moe_held_experts")
                 or (0, arch["moe_num_experts"]))


#: A routing decision is **ill-conditioned** where the logit of the last
#: expert in (the k-th largest) and of the first one out (the k+1-th) lie
#: within this of each other, in units of the spread (the standard
#: deviation) of that position's own 64 logits: a program that rounds its
#: hidden state to bfloat16 may then choose otherwise than float32 does,
#: both choices are the model's, and one expert for another moves the
#: logits by 0.024-0.11 of their range at the published widths — more than
#: any tolerance under what weights through fp8 give (0.11) could tell
#: from a fault. ``logits`` gives no answer (NaN) at a position where any
#: layer's decision is so; ``tie_margins`` gives the margins. The unit is
#: the spread and not the logit because the router reads the *un-normed*
#: residual stream: its logits grow with depth, and so does what rounding
#: does to them, by the same factor. The number, measured on the chip
#: (PR 55; 448 + 1,071 rows at the published widths with every position
#: answered, PERF.md section 4 and the configuration's ``check._ties``):
#: the bfloat16 program chose otherwise at 269 positions (15-19%), every
#: one at a least margin of 0.0000-0.0187 spreads (mean 0.0045, so the
#: rounding of a gap is about 0.007), and nowhere above; 0.035 is 1.9 x
#: the largest of them and about five such roundings. With eight
#: decisions a position it leaves the reference answering at one
#: position in fifteen; ``replay`` makes the rows many instead.
TIE_MARGIN = 0.035


def _route(r, arch, held):
    """(weights [T, k], experts [T, k], margin [T]) of router logits r
    [T, E]: the top k of the logits and a softmax over those k — which is
    the softmax over all E, its top k divided by their sum — and how far,
    in spreads of the position's logits, the nearest of the ``held =
    (lo, n)`` experts is from changing sides of the selection (with every
    expert held: the gap between the k-th and the k+1-th logit)."""
    k = arch["moe_top_k"]
    lo, n_held = held
    edge, top_e = jax.lax.top_k(r, k + 1)
    top_r, top_e = edge[:, :k], top_e[:, :k]
    spread = jnp.std(r, axis=-1)
    # an expert that is in stays in while it beats the first one out; one
    # that is out stays out while the last one in beats it
    mine = r[:, lo:lo + n_held]
    margin = jnp.min(jnp.where(mine >= edge[:, k - 1:k],
                               mine - edge[:, k:k + 1],
                               edge[:, k - 1:k] - mine), axis=-1) / spread
    if arch.get("moe_norm_topk", True):
        weights = jax.nn.softmax(top_r, axis=-1)
    else:       # the unnormalised form: the k probabilities as they are
        weights = jnp.exp(top_r - jax.nn.logsumexp(r, -1, keepdims=True))
    return weights, top_e, margin


def routed_part(u, r, lp, arch, held=None):
    """The part of the routed sum over u [T, hidden] under router logits
    r [T, E] that the experts ``held = (lo, n)`` add (``lp``'s expert
    leaves hold those n)."""
    return _routed(u, r, lp, arch, held or _held(arch))[0]


def _routed(u, r, lp, arch, held):
    """``routed_part`` and the decision's margin (``_route``)."""
    f32 = jnp.float32
    lo, n_held = held
    top_w, top_e, margin = _route(r, arch, held)

    def expert(e, acc):
        weight = jnp.sum(jnp.where(top_e == lo + e, top_w, 0.0), axis=-1)
        gate = jax.nn.relu(u @ lp["w_gate"][e].astype(f32))
        y = (gate * (u @ lp["w_in"][e].astype(f32))) \
            @ lp["w_out"][e].astype(f32)
        return acc + weight[:, None] * y

    return jax.lax.fori_loop(0, n_held, expert, jnp.zeros_like(u)), margin


_EXPERT_LEAVES = ("router_wg", "w_in", "w_gate", "w_out")


def _layer(x, lp, kind, arch, q_block):
    """x [T, hidden] → (the layer's output, the margin [T] of its routing
    decision, its keys and values [T, 2 * kv_heads * D], a position's K
    then its V)."""
    eps, f32 = arch["norm_eps"], jnp.float32
    m = {k: v.astype(f32) for k, v in lp.items() if k not in _EXPERT_LEAVES}
    # the router reads the layer's input as it comes in
    r = _by_rows(lambda rows: rows @ lp["router_wg"].astype(f32), x,
                 ROW_BLOCK)
    a, k, v = _attention(_rms(x, m["attn_norm_w"], eps), m, kind, arch,
                         q_block)
    h = x + a

    def ffn(xs):
        rows, logits = xs
        f, margin = _routed(_rms(rows, m["mlp_norm_w"], eps), logits, lp,
                            arch, _held(arch))
        return rows + f, margin

    y, margin = _by_rows(ffn, (h, r), ROW_BLOCK)
    T = x.shape[0]
    return y, margin, jnp.concatenate([k.reshape(T, -1), v.reshape(T, -1)],
                                      axis=-1)


#: pieces the output head's matmul is taken in (each piece of the head
#: made float32 when its turn comes: 1.56 GB whole at the published
#: vocabulary, beside 2.6 GB of logits and the resident engine)
HEAD_PIECES = 8


def _head(x, w, unanswered=None, behind=None):
    """x [T, hidden] · w [hidden, vocab] in float32, the vocabulary taken
    a piece at a time into one buffer, the rows ``behind`` [R, vocab]
    after the T positions' (None: none). ``unanswered`` [T] bool: rows
    that are NaN throughout instead. Masked a piece at a time and the
    pieces unrolled, so that the result is built where it lies (a loop's
    carry is a buffer of its own: 2.8 GiB of temporaries beside 2.8 GiB
    of result, which do not fit beside the resident engine; unrolled,
    0.67 GiB — compiled for a described v5e, PR 55)."""
    T, V = x.shape[0], w.shape[1]
    pieces = HEAD_PIECES if V % HEAD_PIECES == 0 else 1
    step = V // pieces

    out = jnp.zeros((T, V), jnp.float32) if behind is None else \
        jnp.concatenate([jnp.zeros((T, V), jnp.float32), behind])
    for i in range(pieces):
        part = x @ w[:, i * step:(i + 1) * step].astype(jnp.float32)
        if unanswered is not None:
            part = jnp.where(unanswered[:, None], jnp.nan, part)
        out = jax.lax.dynamic_update_slice(out, part, (0, i * step))
    return out


#: The window group's K/V, held by ``correct`` on its own: of every
#: ``KV_STRIDE``-th position the reference's answer carries, behind the
#: logits, the keys (rotated) and values of the **first window layer** as
#: one more row — a position's K then its V, 2 x kv_heads x head_size
#: numbers, zeros up to the vocabulary's width — and ``replay`` reads the
#: same out of the engine's pool through the sequence's block table, as
#: the kernel does. Why: with weights drawn at random a query's weight
#: lies evenly on the thousands of keys of its window, a window layer's
#: output is their average, and a K/V block of the window group lost
#: moves the served logits by less than bfloat16 rounds them (0.0057-
#: 0.0064 against 0.0059 clean at the published widths; drawing the
#: attention louder or sharper either hides the fault again or trebles
#: what bfloat16 rounds: the configuration's ``check._tolerance``) — so
#: the logits alone left the hand-back path outside ``correct``. A block
#: holds every layer of its group, so one layer's rows tell a block that
#: is lost, handed back too early and written over, or misaddressed. Row
#: ``-1 - j`` (counted from the end, whatever the padded width) is
#: position ``j * KV_STRIDE``; it has no answer (NaN) where a routing
#: decision of a layer *in front of* that layer is ill-conditioned.
KV_STRIDE = 8


def _logits_one(params, tokens, arch, q_block, kv_rows: bool = False,
                tie_margin=None):
    """tokens [T] → (float32 logits [T, vocab], the margins [layers, T]
    of each layer's routing decision at each position, in the layers'
    order). With ``kv_rows`` the K/V rows stand behind the logits
    (``KV_STRIDE``), and the margins have a column for each of them too:
    the least of the decisions it depends on, under the first layer's
    name. ``tie_margin``: a row whose least margin is under it is NaN
    throughout."""
    pattern = tuple(arch["layer_pattern"])
    layers = params["layers"]
    x = params["embed"]["wte"][tokens].astype(jnp.float32)
    first_window = pattern.index("window")

    def period(x, slots):
        margins, kept = [], None
        for i, (kind, lp) in enumerate(zip(pattern, slots)):
            x, margin, kv = _layer(x, lp, kind, arch, q_block)
            margins.append(margin)
            if i == first_window:
                kept = kv[::KV_STRIDE]
        return x, (jnp.stack(margins), kept)

    slots = tuple(layers[f"slot{i}"] for i in range(len(pattern)))
    x, (margins, kv) = jax.lax.scan(period, x, slots)
    x = _rms(x, params["final_norm"]["w"].astype(jnp.float32),
             arch["norm_eps"])
    margins = margins.reshape(-1, margins.shape[-1])
    head = params["lm_head"]["w"]
    unanswered = None if tie_margin is None \
        else jnp.min(margins, axis=0) < tie_margin
    if not kv_rows:
        return _head(x, head, unanswered), margins
    kv = kv[0, ::-1]                # the first period's, last row first
    ahead = jnp.min(margins[:first_window], axis=0) if first_window \
        else jnp.full(x.shape[:1], jnp.inf)
    ahead = ahead[::KV_STRIDE][::-1]
    behind = jnp.pad(kv, ((0, 0), (0, head.shape[1] - kv.shape[1])))
    if tie_margin is not None:
        behind = jnp.where((ahead < tie_margin)[:, None], jnp.nan, behind)
    margins = jnp.concatenate([margins, jnp.full(
        (margins.shape[0], ahead.shape[0]), jnp.inf).at[0].set(ahead)], 1)
    return _head(x, head, unanswered, behind), margins


#: query rows a block of the reference's attention takes: the scores of
#: 28 heads over 4,352 keys are 0.5 MB a query row in float32, and the
#: check runs beside 10.5 GiB of resident engine
Q_BLOCK = 128


def logits(params, tokens, arch, q_block=Q_BLOCK):
    """Reference logits for one sequence, at the highest matmul
    precision — and **no answer (NaN) at a position whose routing is
    ill-conditioned** in any layer (``TIE_MARGIN``): there two answers are
    the model's, a float32 reference knows one of them, and which one a
    bfloat16 program meets is rounding's to say. Whether a position is so
    is decided here, from the float32 margins alone, before anything of
    the program is seen. ``serve_runner.check_logits`` takes a reference
    row that is NaN throughout for this mask: the position is not
    compared and is counted (``logits_check.unanswered``), and any other
    value that is not a number fails the check. Behind the T positions'
    rows stand the window group's K/V rows (``KV_STRIDE``), which
    ``replay`` reads out of the engine's pool: [T + T / 8, vocab]."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block, kv_rows=True,
                           tie_margin=TIE_MARGIN)[0]


#: prompt positions each of a replay's sequences is fed one token at a time
TAIL_ROWS = 32


def replay(engine, uid, prompt, decode_steps: int):
    """One checked request through the engine the way the cell serves it
    — as many sequences stepping together as the engine takes
    (``max_ragged_sequence_count``: the served ``[32, 1]`` step) — and
    read at a thousand positions of its *prompt*. Sequence j is the prompt
    cut ``(j + 1) * TAIL_ROWS`` tokens short, put in chunks of
    ``max_chunk_tokens`` (the chunk forward's row at its last position is
    read too); then all of them step ``TAIL_ROWS`` times together, each
    fed the prompt's own next token instead of a drawn one, each step
    giving every sequence the logits of its position; then the first one,
    which has the whole prompt by now, goes on alone for ``decode_steps``
    greedy tokens, as ``serve_runner.causal_replay`` does. One view (a
    causal row sees nothing behind it): the prompt and the greedy tokens,
    rows ``len(prompt) - S * TAIL_ROWS - 1 ... len(prompt) - 1 +
    decode_steps``, a sequence's last prefilled position twice (once from
    the chunk forward, once from its neighbour's last step). At most half
    the prompt is stepped through. The other sequences' uids are made
    from the harness's and flushed here. Last, the first sequence's live
    window-group K/V is read back out of the pool (``_window_kv``): rows
    ``-1 - j`` of the same view (``KV_STRIDE``).

    Why not the harness's own replay: the reference answers only where
    all of a position's routing decisions are well-conditioned
    (``TIE_MARGIN``: one position in fifteen), and greedy continuations of
    random weights soon cycle through a few tokens, whose positions are
    answered or not *together* — of ten prompts' 31-63 decode rows two
    prompts had none answered, and one run's three prompts compared
    nothing (PR 55, the configuration's ``check._ties``). The prompt's
    tokens are drawn independently, so its positions are too, and a
    thousand of them a prompt leave some seventy compared. With a
    4,217-token prompt the sequences stand on both sides of the 4,096
    window: some hand their first window-group block back in a chunk,
    some in a one-token step, some not at all, beside each other in one
    step, as the cell's live rows do."""
    import numpy as np

    chunk = engine.config.max_chunk_tokens
    steps = min(TAIL_ROWS, max(1, len(prompt) // 2))
    count = max(1, min(engine.config.max_ragged_sequence_count,
                       len(prompt) // 2 // steps))
    uids = [uid + (j << 21) for j in range(count)]
    heads = [len(prompt) - (j + 1) * steps for j in range(count)]
    rows, got = [], []

    def read(lg, at):
        """The put's logits, one row a sequence, as float32 rows."""
        lg = np.asarray(lg, np.float32)
        rows.extend(at)
        got.extend(lg)
        return lg

    for u, head in zip(uids, heads):
        for at in range(0, head, chunk):
            lg = engine.put([u], [prompt[at:min(at + chunk, head)]])
        read(lg, [head - 1])
    for step in range(steps):
        last = read(engine.put(uids, [[prompt[head + step]]
                                      for head in heads]),
                    [head + step for head in heads])[0]
    for u in uids[1:]:
        engine.flush(u)
    tokens = list(prompt)
    for _ in range(decode_steps):           # the first sequence: greedy
        tokens.append(int(np.argmax(last)))
        last = read(engine.put([uid], [[tokens[-1]]]), [len(tokens) - 1])[0]
    at, kv = _window_kv(engine, uid)
    rows.extend(-1 - at // KV_STRIDE)
    got.extend(np.pad(kv, ((0, 0), (0, last.shape[0] - kv.shape[1]))))
    return [(tokens, rows, got)]


def _window_kv(engine, uid):
    """(positions, rows [n, 2 * kv_heads * D] float32): the first window
    layer's K then V of every ``KV_STRIDE``-th position the sequence still
    holds in the window group's pool, read through the block table the
    kernel walks (``state_manager.table_rows``: a table entry that points
    elsewhere reads what it points at)."""
    import numpy as np

    sm = engine.state_manager
    g = next(i for i, group in enumerate(sm.groups) if group.window)
    seq, size = sm.get_sequence(uid), engine.config.kv_block_size
    table = np.array(sm.table_rows(seq)[g])
    at = np.arange(0, seq.seen_tokens, KV_STRIDE)
    at = at[table[at // size] >= 0]                 # handed back: -1
    # (the indices padded to every sampled position a table has room for:
    # one gather program, whatever the sequence's length)
    block, slot = np.zeros((2, seq.rows.shape[1] * size // KV_STRIDE),
                           np.int32)
    block[:len(at)], slot[:len(at)] = table[at // size], at % size
    name = str(g) if g else ""
    return at, np.concatenate(          # a pool: [layers, NB, KH, bs, D]
        [np.asarray(sm.kv_cache[leaf + name][0, block, :, slot],
                    np.float32)[:len(at)].reshape(len(at), -1)
         for leaf in "kv"], axis=1)


def tie_margins(params, tokens, arch, q_block=Q_BLOCK):
    """``logits`` with every row answered — the T positions' and, behind
    them, the K/V rows' (``KV_STRIDE``) — and the margins [layers, rows]
    of each layer's routing decision (a K/V row: the least of the
    decisions it depends on, under the first layer's name): what
    ``TIE_MARGIN`` was measured with."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block, kv_rows=True)


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
    """One attention layer: q and o over all heads, k and v over the K/V
    heads."""
    h, nh, hd = arch["hidden_size"], arch["num_heads"], arch["head_size"]
    return 2 * h * nh * hd + 2 * h * arch["num_kv_heads"] * hd


def expert_matmul_params(arch: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def layer_kinds(arch: dict) -> dict:
    """{"window": n, "full": n} over the layers."""
    pattern = tuple(arch["layer_pattern"])
    periods = arch["num_layers"] // len(pattern)
    return {kind: periods * pattern.count(kind)
            for kind in ("window", "full")}


def matmul_params(arch: dict) -> float:
    """Weights a token is multiplied with once in a forward pass *here*:
    each layer's attention and router and, of a token's top-k experts,
    those this configuration holds — ``top_k · held / experts`` of them in
    expectation, all six where all 64 are held — and the output head. The
    embedding is a lookup and norms are not weight matmuls."""
    h = arch["hidden_size"]
    held = _held(arch)[1]
    per_layer = (attention_matmul_params(arch)
                 + h * arch["moe_num_experts"]                  # router
                 + arch["moe_top_k"] * held / arch["moe_num_experts"]
                 * expert_matmul_params(arch))
    return arch["num_layers"] * per_layer + h * arch["vocab_size"]


def attention_calls(arch: dict) -> list:
    """(window, layers) of each group of attention layers whose kernel
    calls cost alike: the full layers (window 0) and the window layers,
    bounded by the window. The order is the program's
    (``TransformerConfig.kv_groups``: the whole context first)."""
    kinds = layer_kinds(arch)
    groups = [(0, kinds["full"]),
              (int(arch.get("sliding_window") or 0), kinds["window"])]
    return [g for g in groups if g[1]]


def paged_attention_cost(arch: dict, query_tokens: int, kv_read_tokens: int,
                         qk_pairs: int, kv_bytes: int = 2,
                         q_bytes: int = 2) -> dict:
    """One attention layer's paged-attention call at the stated head size.
    ``kv_read_tokens`` and ``qk_pairs`` are the layer's own — bounded by
    the window on a window layer, whole on a full layer: the program
    counts them, group by group, in ``engine.last_put``
    (``kv_g<i>_read_tokens`` / ``kv_g<i>_qk_pairs``), and the reader hands
    them on (``kv_group_readers``). FLOPs: QKᵀ and PV over the query-key
    pairs. Bytes: the K and V of every position a sequence's queries may
    see, read once a sequence, plus q in and o out."""
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    return {"flops": 4.0 * nh * hd * qk_pairs,
            "bytes": 2.0 * kvh * hd * kv_bytes * kv_read_tokens
            + 2.0 * nh * hd * q_bytes * query_tokens}


def experts_hit(arch: dict, valid_tokens: int) -> float:
    """Held experts a forward of ``valid_tokens`` tokens is expected to
    hit in one layer under even, independent routing: an expert is hit
    with probability 1 − (1 − k/E)^t. Even routing spreads a step's rows
    widest, so this is the most a step of that many rows hits in
    expectation; rows that route alike hit fewer."""
    k, E = arch["moe_top_k"], arch["moe_num_experts"]
    return _held(arch)[1] * (1.0 - (1.0 - k / E) ** valid_tokens)


def gmm_cost(arch: dict, valid_tokens: int, el_bytes: int = 2) -> dict:
    """The held experts' grouped matmuls (kernel ``gmm``: gate, up and
    down, every layer) of ONE forward over ``valid_tokens`` tokens. FLOPs
    of the expected held (token, choice) pairs; bytes of the experts the
    forward is expected to hit (``experts_hit``), each streamed once, plus
    the rows in and out of the three matmuls."""
    h, m = arch["hidden_size"], arch["moe_intermediate_size"]
    per_expert = expert_matmul_params(arch)
    pairs = valid_tokens * arch["moe_top_k"] * _held(arch)[1] \
        / arch["moe_num_experts"]
    return {"flops": arch["num_layers"] * 2.0 * per_expert * pairs,
            "bytes": arch["num_layers"] * el_bytes
            * (experts_hit(arch, valid_tokens) * per_expert
               + pairs * (3 * h + 3 * m))}
