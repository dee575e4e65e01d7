"""The ``phi4flash`` block: SambaY, a decoder-hybrid-decoder (Ren et al.,
arXiv:2507.06607; ``model_type: phi4flash``) — a self-decoder of Mamba (S6)
and sliding-window attention layers, one whole-context attention layer,
and a cross-decoder of gated memory units and cross attention that reads
that one layer's K/V and one state-space layer's output — its plain
reference (forward pass and loss), its arithmetic, the scope names it adds
and its published keys, found by the name a configuration's file gives
(``"block": "phi4flash"``; ``manifest.resolve``).

The reference is straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: **every layer on every
position** (the program's serving forward leaves the cross-decoder out for
the positions nobody reads; this does not), the S6 recurrence as a
``lax.scan`` over time, the conv as shifted sums, differential attention
from its definition — two softmaxes over 64-wide heads, no joined heads,
no zero padding — in blocks of query rows, the head a slice of the
vocabulary at a time. No kernel, no cache, no chunking, no exit. It imports
nothing from ``deepspeed_tpu``; what it shares with the program is the
parameter tree's naming (``layers.run<r>_slot<i>``: position ``i`` of run
``r``'s pattern, stacked over the run's periods) and ``layer_runs`` itself.

With ``N`` layers, layer ``l`` (0-based), written from the published
description as the configuration's ``assumed`` group records it:

- every layer: ``x += mixer_l(LN(x))``, then ``x += MLP(LN(x))``; LayerNorm
  with gain and bias; ``MLP(u) = (silu(u W_gate) ⊙ u W_up) W_down``, no bias.
- ``mamba1`` (S6): ``[x | z] = u W_in``; ``x ← silu(conv(x) + b)``, a
  depthwise causal conv; ``[δ | B | C] = x W_x``; ``dt = softplus(δ W_dt +
  b_dt)``; ``A = −exp(A_log)``; ``h_t = exp(dt_t ⊗ A) ⊙ h_{t−1} + (dt_t x_t)
  ⊗ B_t``; ``y_t = h_t C_t + D x_t``; out ``= (y ⊙ silu(z)) W_out``. The
  layer's **memory** is ``y``, before the gate.
- ``gmu``: out ``= (silu(u W_in) ⊙ m) W_out``, ``m`` the same token's memory
  of the latest ``mamba1`` layer in front.
- ``window`` / ``full`` / ``cross`` attention, differential: heads in
  adjacent pairs, ``a_i = softmax(q_i k_iᵀ / √D) [v1 | v2]`` (a query pair
  ``p`` reads K/V pair ``p // (pairs / kv pairs)``), ``λ = exp(λq1·λk1) −
  exp(λq2·λk2) + λ_init``, ``λ_init = 0.8 − 0.6 exp(−0.3 l)``; out ``=
  concat_p[(1 − λ_init) RMSNorm(a1 − λ a2)] W_o + b_o``; q, k, v with bias.
  ``window``: the last ``sliding_window`` keys, the query's own among
  them. ``cross``: queries of its own against the K/V of the latest
  ``full`` layer in front; no K/V weights. **No position term anywhere.**
- final LayerNorm; logits through the tied embedding.

Departures from the published model, all the configuration's ``assumed``:
the sizes and forms the published ``config.json`` does not hold are the
modelling file's as this repository's builder knew them; weights are random
from the seed.

``arch`` is the ``transformer_config`` group of a configuration's file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: scope names this block adds below ``layers`` (``scopes.py``); the S6
#: layer's are the names ``nemotron_h.py`` uses, so that ``ssm_readers``
#: reads this block too
MAMBA_SCOPES = ("mamba", "mamba_proj", "mamba_conv", "mamba_scan",
                "mamba_state_io", "mamba_out")
SCOPES = MAMBA_SCOPES + ("window_attn", "full_attn", "cross_attn", "gmu",
                         "xdec", "dense_mlp")
#: the scope round each attention kind's layers (``kv_group_readers``)
ATTN_SCOPES = {"window": "window_attn", "full": "full_attn"}

#: published key -> TransformerConfig field, for ``model.check_consistent``
PUBLISHED_TO_FIELD = {
    "sliding_window": "sliding_window",
    "layer_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

#: the vocabulary is taken this many pieces at a time in the head (a
#: float32 copy of the whole embedding is 2 GB beside the logits)
HEAD_PIECES = 8

#: The whole-context group's K/V, held by ``correct`` on its own: of every
#: ``KV_STRIDE``-th position the reference's answer carries, behind the
#: logits rows, the one writing layer's K then V (every K/V head, the
#: heads side by side), padded with zeros to the vocabulary's width; the
#: replay reads the same positions back out of the pool **through the
#: sequence's block table**. Row ``-1 - j`` of a view is position ``j *
#: KV_STRIDE``. Why: with weights drawn at random a query's weight lies
#: evenly on the thousands of keys of its context, so a block of 64 lost
#: to a sequence — its table entry pointing at a neighbour's block — moves
#: the logits by a tenth of what bf16 does, through all eight readers
#: (measured on the chip, PR 60: 0.0595 of range against 0.0539 clean),
#: and a tolerance that fp8 fails cannot see it; the pool's rows can (the
#: finding of ``smallthinker.py``'s ``KV_STRIDE``, on the other group).
KV_STRIDE = 16


def runs(arch: dict):
    """``layer_runs`` as ((pattern, periods), ...)."""
    return tuple((tuple(p), int(n)) for p, n in arch["layer_runs"])


def mb_per_layer_runs(num_layers: int, mb_per_layer: int = 2):
    """The published layout as ``layer_runs``: with ``mb_per_layer`` 2 every
    even layer is a state-space one; the self-decoder is the first half,
    layer N/2 hands on its memory, layer N/2 + 1 is the one whole-context
    attention layer, the cross-decoder the rest."""
    if mb_per_layer != 2 or num_layers % 4:
        raise ValueError("mb_per_layer 2 and a depth that is a multiple of 4")
    half = num_layers // 4
    return [[["mamba1", "window"], half], [["mamba1", "full"], 1],
            [["gmu", "cross"], half - 1]]


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


# ----------------------------------------------------------------- layers

def _softmax_attention(q, k, v, scale, window, q_block):
    """q [T, P, D], k [T, KP, D], v [T, KP, W] -> [T, P, W]: causal (and
    within ``window`` keys where it is not 0), query head p reads K/V head
    ``p // (P / KP)``. Query rows are taken ``q_block`` at a time."""
    T, P, D = q.shape
    group = P // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    cols = jnp.arange(T)[None, :]
    n_blocks = -(-T // q_block)
    q = jnp.pad(q, ((0, n_blocks * q_block - T), (0, 0), (0, 0)))

    def block(xs):
        start, qs = xs
        rows = (start + jnp.arange(q_block))[:, None]
        s = jnp.einsum("tpd,spd->pts", qs, k) * scale
        seen = cols <= rows
        if window:
            seen = seen & (rows - cols < window)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("pts,spw->tpw", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, (jnp.arange(n_blocks) * q_block,
                              q.reshape(n_blocks, q_block, P, D)))
    return out.reshape(n_blocks * q_block, P, -1)[:T]


def lambda_init(depth):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


def diff_attention(q, k, v, lp, depth, arch, window, q_block):
    """Differential attention from its definition. q [T, H, D], k / v
    [T, KH, D], heads in adjacent pairs; -> [T, H · D]."""
    T = q.shape[0]
    hd = arch["head_size"]
    scale = 1.0 / math.sqrt(hd)
    q1, q2 = q[:, 0::2], q[:, 1::2]
    k1, k2 = k[:, 0::2], k[:, 1::2]
    v12 = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)    # [v1 | v2]
    a1 = _softmax_attention(q1, k1, v12, scale, window, q_block)
    a2 = _softmax_attention(q2, k2, v12, scale, window, q_block)
    init = lambda_init(depth)
    lam = jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + init
    a = a1 - lam * a2                                       # [T, pairs, 2D]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True)
                          + arch["norm_eps"]) * lp["subln_w"]
    return (a * (1.0 - init)).reshape(T, -1)


def _heads(u, lp, name, heads, arch):
    return (u @ lp[name] + lp[name + "_b"]).reshape(
        u.shape[0], heads, arch["head_size"])


def attention(u, lp, depth, arch, window, q_block):
    """A ``full`` (window 0) or ``window`` layer: -> (out, (k, v))."""
    nh, kvh = arch["num_heads"], arch["num_kv_heads"]
    k, v = _heads(u, lp, "wk", kvh, arch), _heads(u, lp, "wv", kvh, arch)
    a = diff_attention(_heads(u, lp, "wq", nh, arch), k, v, lp, depth, arch,
                       window, q_block)
    return a @ lp["wo"] + lp["wo_b"], (k, v)


def cross_attention(u, lp, kv, depth, arch, q_block):
    """Queries of the layer's own against another layer's K and V."""
    a = diff_attention(_heads(u, lp, "wq", arch["num_heads"], arch), *kv, lp,
                       depth, arch, 0, q_block)
    return a @ lp["wo"] + lp["wo_b"]


def s6(u, lp, arch):
    """The Mamba (S6) layer: -> (out [T, H], the memory y [T, CH])."""
    T = u.shape[0]
    ch, ns = arch["mamba1_inner_size"], arch["mamba1_state_size"]
    rank, K = arch["mamba1_dt_rank"], arch["mamba1_conv_kernel"]
    xz = u @ lp["mamba1_w_in"]
    x, z = xz[:, :ch], xz[:, ch:]
    # the conv, as shifted sums: tap j reaches K-1-j steps back
    padded = jnp.concatenate([jnp.zeros((K - 1, ch)), x], 0)
    x = jax.nn.silu(sum(padded[j:j + T] * lp["mamba1_conv_w"][j]
                        for j in range(K)) + lp["mamba1_conv_b"])
    dbc = x @ lp["mamba1_w_x"]
    B, C = dbc[:, rank:rank + ns], dbc[:, rank + ns:]
    dt = jax.nn.softplus(dbc[:, :rank] @ lp["mamba1_w_dt"]
                         + lp["mamba1_dt_b"])                   # [T, CH]
    A = -jnp.exp(lp["mamba1_A_log"])                            # [S, CH]

    def token(h, xs):           # h [S, CH]
        x_t, b_t, c_t, dt_t = xs
        h = jnp.exp(dt_t * A) * h + (dt_t * x_t) * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((ns, ch)), (x, B, C, dt))
    y = y + lp["mamba1_D"] * x
    return (y * jax.nn.silu(z)) @ lp["mamba1_w_out"], y


def gmu(u, lp, memory):
    return (jax.nn.silu(u @ lp["gmu_w_in"]) * memory) @ lp["gmu_w_out"]


def mlp(u, lp):
    return (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_in"])) @ lp["w_out"]


def _layer(x, lp, kind, depth, fed, arch, q_block):
    """One layer: -> (x, what it hands on: {"memory": ...} / {"kv": ...})."""
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    eps = arch["norm_eps"]
    u = _layer_norm(x, lp["attn_norm_w"], lp["attn_norm_b"], eps)
    hands = {}
    if kind == "mamba1":
        out, hands["memory"] = s6(u, lp, arch)
    elif kind == "gmu":
        out = gmu(u, lp, fed["memory"])
    elif kind == "cross":
        out = cross_attention(u, lp, fed["kv"], depth, arch, q_block)
    else:
        out, hands["kv"] = attention(
            u, lp, depth, arch,
            arch["sliding_window"] if kind == "window" else 0, q_block)
    x = x + out
    return x + mlp(_layer_norm(x, lp["mlp_norm_w"], lp["mlp_norm_b"], eps),
                   lp), hands


def head(x, wte, behind=None):
    """x [T, hidden] against the tied embedding [vocab, hidden] in float32,
    the vocabulary a piece at a time into one buffer (unrolled, so that the
    result is built where it lies: ``smallthinker.py``'s finding), the rows
    ``behind`` [R, vocab] after the T positions' (None: none)."""
    T, V = x.shape[0], wte.shape[0]
    pieces = HEAD_PIECES if V % HEAD_PIECES == 0 else 1
    step = V // pieces
    out = jnp.zeros((T, V), jnp.float32) if behind is None else \
        jnp.concatenate([jnp.zeros((T, V), jnp.float32), behind])
    for i in range(pieces):
        part = x @ wte[i * step:(i + 1) * step].astype(jnp.float32).T
        out = jax.lax.dynamic_update_slice(out, part, (0, i * step))
    return out


def hidden(params, tokens, arch, q_block, upto=None):
    """tokens [T] -> the residual stream [T, hidden] behind the model's
    last layer, every layer on every position, and what the layers in
    front handed on. A run of several periods is a ``lax.scan`` (what such
    a run's layers hand on is not kept: the layers whose memory and K/V
    are read stand in a run of one period, inline). ``upto``: behind the
    model's first ``upto`` layers only, walked one by one (the tests')."""
    x = params["embed"]["wte"][tokens].astype(jnp.float32)
    fed, depth = {}, 0
    for r, (pattern, periods) in enumerate(runs(arch)):
        slots = tuple(params["layers"][f"run{r}_slot{i}"]
                      for i in range(len(pattern)))
        if periods == 1 or upto is not None:
            for p in range(periods):
                for i, kind in enumerate(pattern):
                    if upto is not None and depth >= upto:
                        return x, fed
                    x, hands = _layer(
                        x, jax.tree.map(lambda a: a[p], slots[i]), kind,
                        depth, fed, arch, q_block)
                    fed = {**fed, **hands}
                    depth += 1
            continue

        def period(x, xs, pattern=pattern, base=depth, fed=fed):
            lps, p = xs
            for i, kind in enumerate(pattern):
                x, _ = _layer(x, lps[i], kind, base + p * len(pattern) + i,
                              fed, arch, q_block)
            return x, None

        x, _ = jax.lax.scan(period, x, (slots, jnp.arange(periods)))
        depth += periods * len(pattern)
    return x, fed


def _logits_one(params, tokens, arch, q_block, kv_rows: bool = False):
    """tokens [T] → float32 logits [T, vocab]; with ``kv_rows`` the
    whole-context layer's K/V rows stand behind them (``KV_STRIDE``)."""
    x, fed = hidden(params, tokens, arch, q_block)
    fn = params["final_norm"]
    x = _layer_norm(x, fn["w"].astype(jnp.float32),
                    fn["b"].astype(jnp.float32), arch["norm_eps"])
    wte = params["embed"]["wte"]
    behind = None
    if kv_rows:
        T = tokens.shape[0]
        kv = jnp.concatenate([a.reshape(T, -1) for a in fed["kv"]], axis=-1)
        # position j * KV_STRIDE is row -1 - j
        kv = kv[::KV_STRIDE][::-1]
        behind = jnp.pad(kv, ((0, 0), (0, wte.shape[0] - kv.shape[1])))
    return head(x, wte, behind)


def logits(params, tokens, arch, q_block=256):
    """Reference logits for one sequence, at the highest matmul precision:
    [T, vocab] and, behind them, ``ceil(T / KV_STRIDE)`` rows that hold the
    whole-context layer's K and V (``KV_STRIDE``)."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block, kv_rows=True)


def replay(engine, uid, prompt, decode_steps: int):
    """One checked request through the engine as a causal model generates
    (``serve_runner.causal_replay``: the prompt in chunks of
    ``max_chunk_tokens`` — every chunk but the last a forward whose one
    tail row is thrown away, the last one the exit's — then
    ``decode_steps`` greedy tokens, a ``[1, 1]`` forward of all 32 layers
    each), and then the sequence's whole-context K/V read back out of the
    pool through its block table: rows ``-1 - j`` of the same view
    (``KV_STRIDE``)."""
    import numpy as np

    chunk = engine.config.max_chunk_tokens
    got, tokens = [], list(prompt)
    for at in range(0, len(prompt), chunk):
        lg = engine.put([uid], [prompt[at:at + chunk]])
    got.append(np.asarray(lg[0], np.float32))
    for _ in range(decode_steps):
        tokens.append(int(np.argmax(got[-1])))
        got.append(np.asarray(engine.put([uid], [[tokens[-1]]])[0],
                              np.float32))
    first = len(prompt) - 1
    rows = list(range(first, first + len(got)))
    at, kv = pool_kv(engine, uid)
    rows.extend(-1 - at // KV_STRIDE)
    got.extend(np.pad(kv, ((0, 0), (0, got[0].shape[0] - kv.shape[1]))))
    return [(tokens, rows, got)]


def pool_kv(engine, uid):
    """(positions, rows [n, 2 · kv width] float32): the whole-context
    group's one layer's K then V of every ``KV_STRIDE``-th position the
    sequence holds, read where its block table says they lie."""
    import numpy as np

    sm = engine.state_manager
    seq = sm.get_sequence(uid)
    size = engine.config.kv_block_size
    at = np.arange(0, seq.seen_tokens, KV_STRIDE)
    table = np.asarray(sm.table_rows(seq))[0]
    block, slot = table[at // size], at % size
    cache = sm.forward_cache
    rows = [np.asarray(cache[leaf][0, block, :, slot], np.float32
                       ).reshape(len(at), -1) for leaf in ("k", "v")]
    return at, np.concatenate(rows, axis=-1)


def loss(params, input_ids, arch, q_block=256):
    """Mean next-token negative log-likelihood over ``input_ids``
    [B, T+1] (inputs are [:, :-1], labels [:, 1:])."""
    with jax.default_matmul_precision("highest"):
        def one(ids):
            lg = _logits_one(params, ids[:-1], arch, q_block)
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, ids[1:, None], axis=-1)[:, 0]
            return jnp.mean(logz - gold)

        return jnp.mean(jax.lax.map(one, input_ids))


# -------------------------------------------------------------- arithmetic

def layer_kinds(arch: dict) -> dict:
    """Layers of each kind in the model."""
    out = {}
    for pattern, periods in runs(arch):
        for kind in pattern:
            out[kind] = out.get(kind, 0) + periods
    return out


def mixer_matmul_params(arch: dict) -> dict:
    """One mixer's weight matrices, by kind."""
    h, ch = arch["hidden_size"], arch["mamba1_inner_size"]
    qo = 2 * h * arch["num_heads"] * arch["head_size"]
    kv = 2 * h * arch["num_kv_heads"] * arch["head_size"]
    return {"mamba1": h * 2 * ch + ch * (arch["mamba1_dt_rank"]
                                        + 2 * arch["mamba1_state_size"])
            + arch["mamba1_dt_rank"] * ch + ch * h,
            "gmu": 2 * h * ch, "cross": qo, "full": qo + kv,
            "window": qo + kv}


def matmul_params(arch: dict) -> int:
    """Weights a **decode** token is multiplied with once in a forward
    pass: every layer's mixer and MLP, all of them (32 at the published
    depth), and the output head. A **prompt** token that is not its
    forward's last meets less: the layers in front of the whole-context
    attention layer, that layer's ``W_k`` and ``W_v``, and nothing behind
    — 18 layers' weights less that layer's ``W_q``, ``W_o`` and MLP —
    because the serving forward leaves the cross-decoder out for positions
    nobody reads (``prompt_matmul_params``). The embedding is a lookup;
    norms, the conv and the recurrence are not weight matmuls."""
    mixers = mixer_matmul_params(arch)
    mlp_ = 3 * arch["hidden_size"] * arch["intermediate_size"]
    return sum(n * (mixers[kind] + mlp_)
               for kind, n in layer_kinds(arch).items()) \
        + arch["hidden_size"] * arch["vocab_size"]


def prompt_matmul_params(arch: dict) -> int:
    """What a prompt position that is not read is multiplied with (see
    ``matmul_params``): no head either."""
    mixers = mixer_matmul_params(arch)
    mlp_ = 3 * arch["hidden_size"] * arch["intermediate_size"]
    total = 0
    for pattern, periods in runs(arch):
        for kind in pattern:
            if kind == "full":
                return total + 2 * arch["hidden_size"] \
                    * arch["num_kv_heads"] * arch["head_size"]
            total += periods * (mixers[kind] + mlp_)
    return total


def ssm_state_bytes(arch: dict) -> int:
    """One sequence's recurrent state in one S6 layer (float32)."""
    return arch["mamba1_inner_size"] * arch["mamba1_state_size"] * 4


def conv_tail_bytes(arch: dict, el_bytes: int = 2) -> int:
    return (arch["mamba1_conv_kernel"] - 1) * arch["mamba1_inner_size"] \
        * el_bytes


def kv_layer_token_bytes(arch: dict, el_bytes: int = 2) -> int:
    """K and V of one token in one attention layer's cache."""
    return 2 * arch["num_kv_heads"] * arch["head_size"] * el_bytes


def kv_token_bytes(arch: dict, el_bytes: int = 2) -> int:
    """K and V of one token, every layer that **writes** a cache (the
    window layers' rows live a window long): the cross layers write
    none."""
    kinds = layer_kinds(arch)
    return (kinds.get("full", 0) + kinds.get("window", 0)) \
        * kv_layer_token_bytes(arch, el_bytes)


def shared_kv_read_bytes(arch: dict, read_tokens: int,
                         el_bytes: int = 2) -> int:
    """The bytes behind ``shared_kv_read_tokens``: K/V positions the cross
    layers' walks read (the count is already every cross layer's), K and V
    of one layer each."""
    return read_tokens * kv_layer_token_bytes(arch, el_bytes)


def paged_calls(arch: dict) -> list:
    """(window, layers, one query a row) of each layer group's paged
    calls in a serving forward, in the program's order of groups
    (``TransformerConfig.kv_groups``: the whole context first): the
    whole-context group's rows are read by the layer that writes them and
    by every cross layer, each from a row's last position alone
    (``qk_pairs``); the window layers attend from every new position."""
    kinds = layer_kinds(arch)
    return [(0, kinds.get("full", 0) + kinds.get("cross", 0), True),
            (int(arch["sliding_window"]), kinds.get("window", 0), False)]


def paged_attention_cost(arch: dict, query_tokens: int, kv_read_tokens: int,
                         qk_pairs: int, kv_bytes: int = 2,
                         q_bytes: int = 2) -> dict:
    """One attention layer's paged call, the least work its definition
    asks for — not what the joined pairs at width ``2 D`` multiply (the
    zero halves of ``(q1 | 0)`` and ``(0 | q2)`` double QKᵀ there).
    FLOPs a query-key pair: every head's score over ``D`` and its
    probability times ``[v1 | v2]``, ``2 D`` wide — ``heads · (2 D + 4
    D)``. Bytes: K and V of every position a row's queries may see, once
    a row; the queries in (``D`` a head) and the two rows a pair out
    (``2 D`` a head). ``kv_read_tokens`` and ``qk_pairs`` are the layer
    group's own as the program counts them (``kv_g<i>_read_tokens`` /
    ``kv_g<i>_qk_pairs`` in ``engine.last_put``)."""
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    return {"flops": 6.0 * nh * hd * qk_pairs,
            "bytes": 2.0 * kvh * hd * kv_bytes * kv_read_tokens
            + 3.0 * nh * hd * q_bytes * query_tokens}


def qk_pairs(new, seen) -> int:
    """Query-key pairs of one forward's rows under this block's
    whole-context mask, **one layer's** (int64 arrays of each row's new
    and already-seen tokens): the serving forward attends the whole
    context from one position a row — the row's last — in the layer that
    writes the K/V and in every cross layer behind it, so a row of ``new``
    tokens is ``seen + new`` pairs whatever its width, not the causal
    ``new · seen + new (new + 1) / 2``. (The window layers attend from
    every new position, within their window: the engine counts those by
    layer group, ``kv_g1_qk_pairs``; this function is given no window.)"""
    return int(((seen + new) * (new > 0)).sum())
