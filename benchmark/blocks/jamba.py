"""The ``jamba`` block: Jamba (Lieber et al., arXiv:2403.19887; ``model_type:
jamba``) without experts — Mamba (S6) layers **with norms inside** and, one
in ``attn_layer_period``, a softmax-attention layer of few K/V heads with no
position term, every layer followed by a dense SwiGLU MLP — its plain
reference (forward pass and loss), its arithmetic, the scope names it adds
and its published keys, found by the name a configuration's file gives
(``"block": "jamba"``; ``manifest.resolve``).

The reference is straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: the S6 recurrence as a
``lax.scan`` over single tokens, the conv as shifted sums, attention in
blocks of query rows against every key, the head a slice of the vocabulary
at a time. No kernel, no cache, no chunking. It imports nothing from
``deepspeed_tpu``; what it shares with the program is the parameter tree's
naming (``layers.run<r>_slot<i>``: position ``i`` of run ``r``'s pattern,
stacked over the run's periods) and ``layer_runs`` itself.

Layer ``i`` (0-based), written from the published modelling code's
equations as the configuration's ``assumed`` group records them:

- pre-norm, sequential: ``h += mixer_i(RMSNorm(h))``, then ``h +=
  W_down(silu(W_gate n) ⊙ W_up n)``, ``n = RMSNorm(h)``; no bias anywhere
  but the conv's and the step's.
- the mixer is attention where ``i % attn_layer_period ==
  attn_layer_offset`` and S6 elsewhere (``jamba_layer_runs``); with
  ``num_experts`` 1 every MLP is the plain one.
- ``mamba1`` (S6): ``[x | z] = u W_in``; ``x ← silu(conv(x) + b)``, a
  depthwise causal conv; ``[δ | B | C] = x W_x``; **``δ, B, C ←
  RMSNorm(δ), RMSNorm(B), RMSNorm(C)``, a gain each**; ``dt = softplus(δ
  W_dt + b_dt)``; ``A = −exp(A_log)``; ``h_t = exp(dt_t ⊗ A) ⊙ h_{t−1} +
  (dt_t x_t) ⊗ B_t``; ``y_t = h_t C_t + D x_t``; out ``= (y ⊙ silu(z))
  W_out``.
- ``full`` attention: ``q = u W_q`` (heads × D), ``k, v = u W_k, u W_v``
  (K/V heads × D), causal over the whole context, scale ``D^−½``, a query
  head reads K/V head ``head // (heads / K/V heads)``; out ``= (·) W_o``.
  **No position term**: the state-space layers carry position.
- final RMSNorm; logits through the tied embedding, no scale.

Departures from the published model, all the configuration's ``assumed``:
weights are random from the seed.

``arch`` is the ``transformer_config`` group of a configuration's file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: scope names this block adds below ``layers`` (``scopes.py``); the S6
#: layer's are the names ``nemotron_h.py`` and ``phi4flash.py`` use, so
#: that ``ssm_readers`` reads this block too, and ``mamba_norm`` beside
#: them: the three norms inside the layer
MAMBA_SCOPES = ("mamba", "mamba_proj", "mamba_conv", "mamba_scan",
                "mamba_state_io", "mamba_out", "mamba_norm")
SCOPES = MAMBA_SCOPES + ("full_attn", "dense_mlp")
#: the scope round the attention layers (``kv_group_readers``)
ATTN_SCOPES = {"full": "full_attn"}

#: published key -> TransformerConfig field, for ``model.check_consistent``
PUBLISHED_TO_FIELD = {
    "mamba_d_state": "mamba1_state_size",
    "mamba_dt_rank": "mamba1_dt_rank",
    "mamba_d_conv": "mamba1_conv_kernel",
}

#: the vocabulary is taken this many pieces at a time in the head
HEAD_PIECES = 8

#: The attention layers' K/V, held by ``correct`` on its own (the finding
#: of ``phi4flash.py``'s ``KV_STRIDE``: with weights drawn at random a
#: query's weight lies evenly on the keys of its context, and a block of 64
#: lost to a sequence moves the logits by less than bf16 does): of every
#: ``KV_STRIDE``-th position the reference's answer carries, behind the
#: logits rows, every attention layer's K then V (the layers side by side),
#: padded with zeros to the vocabulary's width; the replay reads the same
#: positions back out of the pool **through the sequence's block table**.
#: Row ``-1 - j`` of a view is position ``j * KV_STRIDE``.
KV_STRIDE = 16

#: The state slots, held by ``correct`` on their own: a state that a fresh
#: row inherits from its slot's last sequence decays under ``exp(dt A)``,
#: and behind a prompt of a thousand tokens and more what is left of it
#: lies in the slowest hundredth of the (channel, state index) pairs
#: — the logits there do not show it. So the replay also runs the prompt's
#: first ``PROBE_TOKENS`` tokens (one chunk at most) as a sequence of their
#: own, in a slot of its own, and reads that row's logits — the view's last
#: row, at position ``PROBE_TOKENS - 1``, whose state is a few dozen tokens
#: old.
PROBE_TOKENS = 48

#: The widest chunk the replay feeds a prompt in (the engine's own where
#: that is narrower; the configuration's ``max_chunk_tokens`` is this too):
#: on the stratified schedule every block of sixteen requests holds the
#: same sixteen prompt lengths, and at a median of 256 (sigma 1.0) the
#: longest is 1,649 tokens. Fed in chunks of 1,024 it is two: the state
#: and the conv tail are carried across a chunk boundary in every checked
#: request, and the first chunk's one tail row is thrown away.
REPLAY_CHUNK = 1024


def runs(arch: dict):
    """``layer_runs`` as ((pattern, periods), ...)."""
    return tuple((tuple(p), int(n)) for p, n in arch["layer_runs"])


def layer_kinds_in_order(num_layers: int, attn_period: int,
                         attn_offset: int) -> list:
    """The published layout, layer by layer: attention where ``i %
    attn_layer_period == attn_layer_offset``, S6 elsewhere."""
    return ["full" if i % attn_period == attn_offset else "mamba1"
            for i in range(num_layers)]


def jamba_layer_runs(num_layers: int, attn_period: int,
                     attn_offset: int) -> list:
    """The published layout as ``layer_runs`` of one-position patterns:
    the order kept, every stretch of one kind a run (28 layers at period
    14, offset 7: S6 × 7, attention, S6 × 13, attention, S6 × 6)."""
    out = []
    for kind in layer_kinds_in_order(num_layers, attn_period, attn_offset):
        if out and out[-1][0] == [kind]:
            out[-1][1] += 1
        else:
            out.append([[kind], 1])
    return out


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# ----------------------------------------------------------------- layers

def attention(u, lp, arch, q_block):
    """A whole-context attention layer: u [T, H] -> (out [T, H], (k, v)
    [T, KH, D] each). Query rows are taken ``q_block`` at a time."""
    T = u.shape[0]
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    q = (u @ lp["wq"]).reshape(T, nh, hd)
    k = (u @ lp["wk"]).reshape(T, kvh, hd)
    v = (u @ lp["wv"]).reshape(T, kvh, hd)
    kr, vr = (jnp.repeat(a, nh // kvh, axis=1) for a in (k, v))
    cols = jnp.arange(T)[None, :]
    n_blocks = -(-T // q_block)
    qp = jnp.pad(q, ((0, n_blocks * q_block - T), (0, 0), (0, 0)))

    def block(xs):
        start, qs = xs
        rows = (start + jnp.arange(q_block))[:, None]
        s = jnp.einsum("thd,shd->hts", qs, kr) / math.sqrt(hd)
        s = jnp.where((cols <= rows)[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), vr)

    a = jax.lax.map(block, (jnp.arange(n_blocks) * q_block,
                            qp.reshape(n_blocks, q_block, nh, hd)))
    a = a.reshape(n_blocks * q_block, nh * hd)[:T]
    return a @ lp["wo"], (k, v)


def s6(u, lp, arch, inner_norm: bool = True):
    """Jamba's Mamba (S6) layer: u [T, H] -> out [T, H]."""
    T = u.shape[0]
    ch, ns = arch["mamba1_inner_size"], arch["mamba1_state_size"]
    rank, K = arch["mamba1_dt_rank"], arch["mamba1_conv_kernel"]
    eps = arch["norm_eps"]
    xz = u @ lp["mamba1_w_in"]
    x, z = xz[:, :ch], xz[:, ch:]
    # the conv, as shifted sums: tap j reaches K-1-j steps back
    padded = jnp.concatenate([jnp.zeros((K - 1, ch)), x], 0)
    x = jax.nn.silu(sum(padded[j:j + T] * lp["mamba1_conv_w"][j]
                        for j in range(K)) + lp["mamba1_conv_b"])
    dbc = x @ lp["mamba1_w_x"]
    delta, B, C = dbc[:, :rank], dbc[:, rank:rank + ns], dbc[:, rank + ns:]
    if inner_norm:
        delta = _rms_norm(delta, lp["mamba1_dt_norm"], eps)
        B = _rms_norm(B, lp["mamba1_b_norm"], eps)
        C = _rms_norm(C, lp["mamba1_c_norm"], eps)
    dt = jax.nn.softplus(delta @ lp["mamba1_w_dt"] + lp["mamba1_dt_b"])
    A = -jnp.exp(lp["mamba1_A_log"])                            # [S, CH]

    def token(h, xs):           # h [S, CH]
        x_t, b_t, c_t, dt_t = xs
        h = jnp.exp(dt_t * A) * h + (dt_t * x_t) * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((ns, ch)), (x, B, C, dt))
    y = y + lp["mamba1_D"] * x
    return (y * jax.nn.silu(z)) @ lp["mamba1_w_out"]


def mlp(u, lp):
    return (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_in"])) @ lp["w_out"]


def _layer(x, lp, kind, arch, q_block):
    """One layer: -> (x, the layer's (k, v) or None)."""
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    eps = arch["norm_eps"]
    u = _rms_norm(x, lp["attn_norm_w"], eps)
    kv = None
    if kind == "mamba1":
        out = s6(u, lp, arch, arch.get("mamba1_inner_norm", False))
    else:
        out, kv = attention(u, lp, arch, q_block)
    x = x + out
    return x + mlp(_rms_norm(x, lp["mlp_norm_w"], eps), lp), kv


def head(x, wte, behind=None):
    """x [T, hidden] against the tied embedding [vocab, hidden] in float32,
    the vocabulary a piece at a time into one buffer (unrolled, so that the
    result is built where it lies), the rows ``behind`` [R, vocab] after
    the T positions' (None: none)."""
    T, V = x.shape[0], wte.shape[0]
    pieces = HEAD_PIECES if V % HEAD_PIECES == 0 else 1
    step = V // pieces
    out = jnp.zeros((T, V), jnp.float32) if behind is None else \
        jnp.concatenate([jnp.zeros((T, V), jnp.float32), behind])
    for i in range(pieces):
        part = x @ wte[i * step:(i + 1) * step].astype(jnp.float32).T
        out = jax.lax.dynamic_update_slice(out, part, (0, i * step))
    return out


def hidden(params, tokens, arch, q_block):
    """tokens [T] -> the residual stream [T, hidden] behind the model's
    last layer, and every attention layer's (k, v) in the model's order. A
    run of several periods of S6 layers is a ``lax.scan``; attention
    layers stand in runs of one period, inline (a run of several would be
    walked one by one)."""
    x = params["embed"]["wte"][tokens].astype(jnp.float32)
    kvs = []
    for r, (pattern, periods) in enumerate(runs(arch)):
        slots = tuple(params["layers"][f"run{r}_slot{i}"]
                      for i in range(len(pattern)))
        if periods == 1 or "full" in pattern:
            for p in range(periods):
                for i, kind in enumerate(pattern):
                    x, kv = _layer(x, jax.tree.map(lambda a: a[p], slots[i]),
                                   kind, arch, q_block)
                    if kv is not None:
                        kvs.append(kv)
            continue

        def period(x, lps, pattern=pattern):
            for i, kind in enumerate(pattern):
                x, _ = _layer(x, lps[i], kind, arch, q_block)
            return x, None

        x, _ = jax.lax.scan(period, x, slots)
    return x, kvs


def _logits_one(params, tokens, arch, q_block, kv_rows: bool = False):
    """tokens [T] → float32 logits [T, vocab]; with ``kv_rows`` the
    attention layers' K/V rows stand behind them (``KV_STRIDE``)."""
    x, kvs = hidden(params, tokens, arch, q_block)
    x = _rms_norm(x, params["final_norm"]["w"].astype(jnp.float32),
                  arch["norm_eps"])
    wte = params["embed"]["wte"]
    behind = None
    if kv_rows:
        T = tokens.shape[0]
        kv = jnp.concatenate([a.reshape(T, -1) for pair in kvs
                              for a in pair], axis=-1)
        # position j * KV_STRIDE is row -1 - j
        kv = kv[::KV_STRIDE][::-1]
        behind = jnp.pad(kv, ((0, 0), (0, wte.shape[0] - kv.shape[1])))
    return head(x, wte, behind)


def logits(params, tokens, arch, q_block=256):
    """Reference logits for one sequence, at the highest matmul precision:
    [T, vocab] and, behind them, ``ceil(T / KV_STRIDE)`` rows that hold the
    attention layers' K and V (``KV_STRIDE``)."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block, kv_rows=True)


def replay(engine, uid, prompt, decode_steps: int):
    """One checked request through the engine as a causal model generates
    (``serve_runner.causal_replay``'s form: the prompt in chunks of
    ``REPLAY_CHUNK``, every chunk resumed from the state the last left
    in the sequence's slot, then ``decode_steps`` greedy tokens, a
    ``[1, 1]`` forward each), and then the sequence's K/V read back out of
    the pool through its block table: rows ``-1 - j`` of the same view
    (``KV_STRIDE``). The view's last row is the probe's (``PROBE_TOKENS``):
    the prompt's first tokens as a sequence of their own under a uid of the
    replay's, flushed before it returns — a causal model's logits behind a
    prefix are the whole sequence's at that position, so the reference's
    one forward answers it too."""
    import numpy as np

    chunk = min(engine.config.max_chunk_tokens, REPLAY_CHUNK)
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
    # the probe: a fresh row in a slot of its own, a few dozen tokens deep.
    # The model is causal, so the row is this same view's
    head_ = list(prompt[:min(PROBE_TOKENS, chunk)])
    other = uid + (1 << 19)
    try:
        rows.append(len(head_) - 1)
        got.append(np.asarray(engine.put([other], [head_])[0], np.float32))
    finally:
        engine.flush(other)
    return [(tokens, rows, got)]


def pool_kv(engine, uid):
    """(positions, rows [n, layers · 2 · kv width] float32): every
    attention layer's K then V of every ``KV_STRIDE``-th position the
    sequence holds, read where its block table says they lie."""
    import numpy as np

    sm = engine.state_manager
    seq = sm.get_sequence(uid)
    size = engine.config.kv_block_size
    at = np.arange(0, seq.seen_tokens, KV_STRIDE)
    table = np.asarray(sm.table_rows(seq))[0]
    block, slot = table[at // size], at % size
    cache = sm.forward_cache
    rows = [np.asarray(cache[leaf][layer, block, :, slot], np.float32
                       ).reshape(len(at), -1)
            for layer in range(cache["k"].shape[0]) for leaf in ("k", "v")]
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


def attention_layers(arch: dict) -> int:
    return layer_kinds(arch).get("full", 0)


def mixer_matmul_params(arch: dict) -> dict:
    """One mixer's weight matrices, by kind."""
    h, ch = arch["hidden_size"], arch["mamba1_inner_size"]
    hd = arch["head_size"]
    return {"mamba1": h * 2 * ch + ch * (arch["mamba1_dt_rank"]
                                        + 2 * arch["mamba1_state_size"])
            + arch["mamba1_dt_rank"] * ch + ch * h,
            "full": 2 * h * arch["num_heads"] * hd
            + 2 * h * arch["num_kv_heads"] * hd}


def matmul_params(arch: dict) -> int:
    """Weights a token is multiplied with once in a forward pass — dense:
    every matrix of every layer (the mixer's and the MLP's) and the tied
    head once. The embedding is a lookup; norms, the conv and the
    recurrence are not weight matmuls."""
    mixers = mixer_matmul_params(arch)
    mlp_ = 3 * arch["hidden_size"] * arch["intermediate_size"]
    return sum(n * (mixers[kind] + mlp_)
               for kind, n in layer_kinds(arch).items()) \
        + arch["hidden_size"] * arch["vocab_size"]


def ssm_state_bytes(arch: dict) -> int:
    """One sequence's recurrent state in one S6 layer (float32)."""
    return arch["mamba1_inner_size"] * arch["mamba1_state_size"] * 4


def conv_tail_bytes(arch: dict, el_bytes: int = 2) -> int:
    return (arch["mamba1_conv_kernel"] - 1) * arch["mamba1_inner_size"] \
        * el_bytes


def seat_bytes(arch: dict, el_bytes: int = 2) -> int:
    """What a sequence costs whatever it has read: every S6 layer's state
    and conv tail."""
    return layer_kinds(arch).get("mamba1", 0) * (
        ssm_state_bytes(arch) + conv_tail_bytes(arch, el_bytes))


def kv_token_bytes(arch: dict, el_bytes: int = 2) -> int:
    """K and V of one token, every attention layer."""
    return attention_layers(arch) * 2 * arch["num_kv_heads"] \
        * arch["head_size"] * el_bytes


def paged_attention_cost(arch: dict, query_tokens: int, kv_read_tokens: int,
                         qk_pairs: int, kv_bytes: int = 2,
                         q_bytes: int = 2) -> dict:
    """One attention layer's paged-attention call at the stated head
    size. FLOPs: QKᵀ and PV over the query-key pairs. Bytes: the K and V
    of every position a sequence's queries may see, read once a sequence,
    plus q in and o out."""
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    return {"flops": 4.0 * nh * hd * qk_pairs,
            "bytes": 2.0 * kvh * hd * kv_bytes * kv_read_tokens
            + 2.0 * nh * hd * q_bytes * query_tokens}
