"""The ``qwen3_next`` block: layers of two kinds in one model, every one
followed by a sparse FFN — its plain reference (forward pass and loss),
its arithmetic, the scope names it adds and the cost functions of the
kernels its cell reads, found by the name a configuration's file gives
(``"block": "qwen3_next"``; ``manifest.resolve``).

The reference is straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — the delta rule as a
``lax.scan`` over tokens, the conv as shifted sums, attention in query
blocks, experts as a loop over the held experts with masks; no kernel, no
cache, no chunking. It imports nothing from ``deepspeed_tpu``; the only
thing shared with the program is the parameter tree's naming
(``layers.slot<i>`` a position of the period, stacked over the periods).
Written from the published description of Qwen3-Next-80B-A3B
(``model_type: qwen3_next``; x̂ = x·rsqrt(mean x² + eps)):

- norms: ``x̂ ⊙ (1 + w)`` (zero-centred gain) before the mixer, before the
  FFN, at the end, and over each head of q and k; residual
  ``x += mixer(norm x); x += ffn(norm x)``.
- layer i is full attention when (i + 1) % 4 == 0, else Gated DeltaNet.
- full attention: ``[q | g] = W_q h`` a head (split inside each head),
  ``q = rope(q_norm q)``, ``k = rope(k_norm k)`` on the first
  ``rope_pct · head`` dims (rotate-half), causal softmax at scale
  head^-½ with each KV head serving heads/kv_heads query heads,
  ``o = W_o (attn ⊙ sigmoid g)``.
- Gated DeltaNet: ``[q, k, v, z] = W_qkvz h``, ``[b, a] = W_ba h``;
  ``[q | k | v]`` through a depthwise causal conv of width 4, then SiLU;
  ``β = sigmoid b``; ``g = −exp(A_log) ⊙ softplus(a + dt_bias)``; q and k
  L2-normalised, ``q ← q · dk^-½``, each key head serves hv/hk value
  heads. Per value head, S ∈ R^{dk×dv}: ``S ← e^g S``;
  ``δ = β (v − Sᵀk)``; ``S ← S + k δᵀ``; ``o = Sᵀ q``. Output
  ``W_out [(ô ⊙ w_n) ⊙ silu z]``, ô the RMS-normalised o with a plain
  gain.
- FFN: ``p = softmax(W_r h)`` over all experts, the top k renormalised to
  sum 1, expert e ``W_d (silu(W_g h) ⊙ W_u h)``, plus
  ``sigmoid(w_s · h) · shared(h)``. **Only the experts the configuration
  holds are summed** (``moe_held_experts = [lo, n]``): what the others
  would add is the other chip's part, left out here as in the program,
  and that partial sum is what goes on to the next layer.

Departures from the published model, both the configuration's ``assumed``:
no multi-token-prediction module (the published ``config`` does not size
it); ``A_log = log U(0, 16)`` and ``dt_bias = 1`` as the source's
modelling code initialises them, weights otherwise random from the seed.

``arch`` is the ``transformer_config`` group of a configuration's file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: scope names this block adds below ``layers`` (``scopes.py``)
SCOPES = ("linear_attn", "gdn_proj", "gdn_conv", "gdn_scan", "gdn_out",
          "router", "experts", "shared_expert")
GDN_SCOPES = SCOPES[:5]

#: published key -> TransformerConfig field, for ``model.check_consistent``
#: (``intermediate_size`` is the dense width no layer of this model uses;
#: ``num_experts`` in the file is the share held, checked by ``held``)
PUBLISHED_TO_FIELD = {
    "head_dim": "head_size",
    "partial_rotary_factor": "rope_pct",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_intermediate_size",
    "shared_expert_intermediate_size": "moe_shared_intermediate_size",
    "norm_topk_prob": "moe_norm_topk",
    "linear_num_key_heads": "linear_num_key_heads",
    "linear_num_value_heads": "linear_num_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel",
}


def _rms(x, w, eps, zero_centered=True):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + w) if zero_centered else y * w


def _rotary(x, rot_dim, theta):
    """x [T, heads, D]: rotate the first ``rot_dim`` dims of every head by
    position (rotate-half: dim i pairs with i + rot_dim/2)."""
    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                          / rot_dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot_dim // 2], x[..., rot_dim // 2:rot_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot_dim:]], axis=-1)


def _softmax_attention(q, k, v, q_block):
    """q [T, H, D], k/v [T, KH, D] → [T, H, D]; causal, grouped-query.
    Query rows are taken ``q_block`` at a time."""
    T, H, D = q.shape
    group = H // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    cols = jnp.arange(T)[None, :]
    n_blocks = -(-T // q_block)
    q = jnp.pad(q, ((0, n_blocks * q_block - T), (0, 0), (0, 0)))

    def block(xs):
        start, qs = xs
        rows = (start + jnp.arange(q_block))[:, None]
        s = jnp.einsum("thd,shd->hts", qs, k) / math.sqrt(D)
        s = jnp.where((cols <= rows)[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, (jnp.arange(n_blocks) * q_block,
                              q.reshape(n_blocks, q_block, H, D)))
    return out.reshape(n_blocks * q_block, H, D)[:T]


def _full_attention(h, lp, arch, q_block):
    T = h.shape[0]
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    eps = arch["norm_eps"]
    rot = int(hd * arch["rope_pct"]) // 2 * 2
    qg = (h @ lp["wq"]).reshape(T, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (h @ lp["wk"]).reshape(T, kvh, hd)
    v = (h @ lp["wv"]).reshape(T, kvh, hd)
    q = _rotary(_rms(q, lp["q_norm_w"], eps), rot, arch["rope_theta"])
    k = _rotary(_rms(k, lp["k_norm_w"], eps), rot, arch["rope_theta"])
    attn = _softmax_attention(q, k, v, q_block) * jax.nn.sigmoid(gate)
    return attn.reshape(T, nh * hd) @ lp["wo"]


def _gated_deltanet(h, lp, arch):
    T = h.shape[0]
    hk, hv = arch["linear_num_key_heads"], arch["linear_num_value_heads"]
    dk, dv = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    K = arch["linear_conv_kernel"]
    n_qk = hk * dk
    qkvz = h @ lp["w_qkvz"]
    qkv, z = qkvz[:, :2 * n_qk + hv * dv], qkvz[:, 2 * n_qk + hv * dv:]
    ba = h @ lp["w_ba"]
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, hv:] + lp["dt_bias"])
    # the conv, as shifted sums: tap j reaches K-1-j steps back
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1])), qkv], 0)
    conv = sum(padded[j:j + T] * lp["conv_w"][j] for j in range(K))
    conv = jax.nn.silu(conv)
    q = conv[:, :n_qk].reshape(T, hk, dk)
    k = conv[:, n_qk:2 * n_qk].reshape(T, hk, dk)
    v = conv[:, 2 * n_qk:].reshape(T, hv, dv)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)   # noqa: E731
                                  + 1e-6)
    q = jnp.repeat(unit(q) / math.sqrt(dk), hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)

    def token(S, xs):           # S [hv, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, None, None] * S
        delta = b_t[:, None] * (v_t - jnp.einsum("hkd,hk->hd", S, k_t))
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkd,hk->hd", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv)), (q, k, v, g, beta))
    o = _rms(o, lp["gdn_norm_w"], arch["norm_eps"], zero_centered=False)
    o = o * jax.nn.silu(z.reshape(T, hv, dv))
    return o.reshape(T, hv * dv) @ lp["w_gdn_out"]


def _swiglu(h, w_gate, w_in, w_out):
    return (jax.nn.silu(h @ w_gate) * (h @ w_in)) @ w_out


def _sparse_ffn(h, lp, arch):
    """The held experts' part of the top-k sum, plus the shared expert.
    ``lp`` holds the experts' weights as stored (any float type): each is
    made float32 when its turn in the loop comes."""
    f32 = jnp.float32
    lo, n_held = arch.get("moe_held_experts") or (0, arch["moe_num_experts"])
    p = jax.nn.softmax(h @ lp["router_wg"].astype(f32), axis=-1)
    top_p, top_e = jax.lax.top_k(p, arch["moe_top_k"])
    if arch.get("moe_norm_topk"):
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)

    def expert(e, acc):
        weight = jnp.sum(jnp.where(top_e == lo + e, top_p, 0.0), axis=-1)
        y = _swiglu(h, lp["w_gate"][e].astype(f32), lp["w_in"][e].astype(f32),
                    lp["w_out"][e].astype(f32))
        return acc + weight[:, None] * y

    y = jax.lax.fori_loop(0, n_held, expert, jnp.zeros_like(h))
    if arch.get("moe_shared_intermediate_size"):
        shared = _swiglu(h, lp["shared_w_gate"].astype(f32),
                         lp["shared_w_in"].astype(f32),
                         lp["shared_w_out"].astype(f32))
        y = y + jax.nn.sigmoid(h @ lp["shared_gate_w"].astype(f32)) * shared
    return y


_EXPERT_LEAVES = ("router_wg", "w_in", "w_gate", "w_out", "shared_w_in",
                  "shared_w_gate", "shared_w_out", "shared_gate_w")


def _layer(x, lp, kind, arch, q_block):
    f32 = lambda t: {k: v.astype(jnp.float32) for k, v in t.items()   # noqa: E731
                     if k not in _EXPERT_LEAVES}
    eps = arch["norm_eps"]
    mixer = f32(lp)
    h = _rms(x, mixer["attn_norm_w"], eps)
    x = x + (_full_attention(h, mixer, arch, q_block) if kind == "full"
             else _gated_deltanet(h, mixer, arch))
    return x + _sparse_ffn(_rms(x, mixer["mlp_norm_w"], eps), lp, arch)


def _logits_one(params, tokens, arch, q_block):
    """tokens [T] → float32 logits [T, vocab]."""
    pattern = tuple(arch["layer_pattern"])
    x = params["embed"]["wte"][tokens].astype(jnp.float32)

    def period(x, slots):
        for kind, lp in zip(pattern, slots):
            x = _layer(x, lp, kind, arch, q_block)
        return x, None

    slots = tuple(params["layers"][f"slot{i}"] for i in range(len(pattern)))
    x, _ = jax.lax.scan(period, x, slots)
    x = _rms(x, params["final_norm"]["w"].astype(jnp.float32),
             arch["norm_eps"])
    return x @ params["lm_head"]["w"].astype(jnp.float32)


def logits(params, tokens, arch, q_block=1024):
    """Reference logits for one sequence, at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block)


def loss(params, input_ids, arch, q_block=1024):
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

def deltanet_matmul_params(arch: dict) -> int:
    """One Gated DeltaNet mixer: the qkvz, ba and output projections."""
    h = arch["hidden_size"]
    hk, hv = arch["linear_num_key_heads"], arch["linear_num_value_heads"]
    dk, dv = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    return h * (2 * hk * dk + 2 * hv * dv) + h * 2 * hv + hv * dv * h


def attention_matmul_params(arch: dict) -> int:
    """One gated attention mixer: q (twice as wide: the gate), k, v, o."""
    h, nh, hd = arch["hidden_size"], arch["num_heads"], arch["head_size"]
    return h * 2 * nh * hd + 2 * h * arch["num_kv_heads"] * hd + nh * hd * h


def expert_matmul_params(arch: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def matmul_params(arch: dict) -> float:
    """Weights a token is multiplied with once in a forward pass *here*:
    each layer's mixer, router and shared expert, the output head, and of
    its top-k experts those this configuration holds — ``top_k · held /
    experts`` of them **in expectation** (even routing); what the absent
    experts would cost is the other chip's. The embedding is a lookup,
    norms, the conv and the delta rule are not weight matmuls."""
    h = arch["hidden_size"]
    pattern = tuple(arch["layer_pattern"])
    periods = arch["num_layers"] // len(pattern)
    mixers = periods * (pattern.count("linear") * deltanet_matmul_params(arch)
                        + pattern.count("full")
                        * attention_matmul_params(arch))
    held = (arch.get("moe_held_experts") or (0, arch["moe_num_experts"]))[1]
    ffn = (h * arch["moe_num_experts"]                          # router
           + 3 * h * arch.get("moe_shared_intermediate_size", 0) + h
           + arch["moe_top_k"] * held / arch["moe_num_experts"]
           * expert_matmul_params(arch))
    return mixers + arch["num_layers"] * ffn + h * arch["vocab_size"]


def attention_layers(arch: dict) -> int:
    pattern = tuple(arch["layer_pattern"])
    return arch["num_layers"] // len(pattern) * pattern.count("full")


def paged_attention_cost(arch: dict, query_tokens: int, kv_read_tokens: int,
                         qk_pairs: int, kv_bytes: int = 2,
                         q_bytes: int = 2) -> dict:
    """One attention layer's paged-attention call at the *stated* head
    size (``peaks.paged_attention_cost`` derives it as hidden/heads).
    FLOPs: QKᵀ and PV over the query-key pairs. Bytes: the K and V of
    every position a sequence's queries may see, read once a sequence,
    plus q in and o out."""
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    return {"flops": 4.0 * nh * hd * qk_pairs,
            "bytes": 2.0 * kvh * hd * kv_bytes * kv_read_tokens
            + 2.0 * nh * hd * q_bytes * query_tokens}


def gmm_cost(arch: dict, valid_tokens: int, el_bytes: int = 2) -> dict:
    """The held experts' grouped matmuls (kernel ``gmm``: gate, up and
    down, every layer) of ONE forward over ``valid_tokens`` tokens. FLOPs
    of the expected held (token, choice) pairs; bytes of the experts the
    forward is expected to hit, each streamed once (an expert is hit by a
    forward of t tokens with probability 1 − (1 − k/E)^t), plus the rows
    in and out of the three matmuls."""
    h, k, E = arch["hidden_size"], arch["moe_top_k"], arch["moe_num_experts"]
    m = arch["moe_intermediate_size"]
    held = (arch.get("moe_held_experts") or (0, E))[1]
    per_expert = expert_matmul_params(arch)
    pairs = valid_tokens * k * held / E
    hit = held * (1.0 - (1.0 - k / E) ** valid_tokens)
    return {"flops": arch["num_layers"] * 2.0 * per_expert * pairs,
            "bytes": arch["num_layers"] * el_bytes
            * (hit * per_expert + pairs * (3 * h + 3 * m))}
