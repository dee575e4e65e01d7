"""The ``nemotron_h`` block: a model whose layers are a mixer alone or an
FFN alone — Mamba-2 state-space layers, a few attention layers without
any position term, and LatentMoE FFNs (ungated ``relu2`` experts in a
latent narrower than the model, beside a shared expert on the full
width) — its plain reference (forward pass and loss), its arithmetic, the
scope names it adds and its published keys, found by the name a
configuration's file gives (``"block": "nemotron_h"``;
``manifest.resolve``).

The reference is straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — the state-space recurrence
as a ``lax.scan`` over tokens, the conv as shifted sums, attention in
query blocks, experts as a loop over the held experts with masks; no
kernel, no cache, no chunking. It imports nothing from ``deepspeed_tpu``;
the only thing shared with the program is the parameter tree's naming
(``layers.slot<i>`` a position of the period, stacked over the periods)
and the two lists that say what a position is (``layer_pattern``: its
mixer's kind or null; ``layer_ffn``: whether it carries an FFN). Written
from the published description of NVIDIA-Nemotron-3-Super-120B-A12B
(``model_type: nemotron_h``; ``u = x·rsqrt(mean x² + eps) ⊙ w``, a plain
gain; every layer ``x ← x + f(u)``; no bias but the conv's; the
embedding unscaled, a final norm, an untied head):

- ``M``, Mamba-2: ``[z | xBC | dt] = u W_in`` (widths inner | inner +
  2·groups·state | heads); ``xBC ← silu(conv(xBC) + b)``, a depthwise
  causal conv of 4 taps; ``x`` [heads × P], ``B``, ``C`` [groups × S],
  head h reads group h // (heads / groups); ``dt ← softplus(dt +
  dt_bias)``, ``A = −exp(A_log)``; per head a state h ∈ R^{P×S}:
  ``h ← exp(dt·A) h + dt·x ⊗ B``, ``y = h C + D·x``; then the gate first
  and the norm by group: ``y ← RMSNorm_groups(y · silu(z)) ⊙ w`` (the
  mean square over each group's inner/groups channels); ``out = y W_out``.
- ``*``, attention: grouped-query, q/k/v/o without bias, causal softmax at
  head^-½, **no rotary** (the source's modelling code applies none: the
  state-space layers carry position).
- ``E``, LatentMoE: ``s = sigmoid(u W_g)`` in float32 over all experts;
  the top k of ``s + b`` (the selection bias), weights ``s_e / (Σ_chosen
  s + 1e-20)`` × the routed scale; ``ℓ = u W_l1``, ``r = Σ_e w_e ·
  relu(ℓ W_up,e)² W_down,e``, ``routed = r W_l2``; shared, on the full
  width: ``relu(u W_su)² W_sd``; ``out = routed + shared``. **Only the
  experts the configuration holds are summed** (``moe_held_experts =
  [lo, n]``): what the others would add is the other chips' part, left out
  here as in the program; ``W_l2`` is applied to that partial sum (it is
  linear: the shares of all holders, each through ``W_l2``, add up to the
  whole — ``routed_part`` and ``shared_part`` are apart for the test that
  says so).

Departures from the published model, all the configuration's ``assumed``:
no multi-token-prediction module; ``A_log``, ``D``, ``dt_bias``, the conv's
taps and bias as the source's modelling code initialises them, weights
otherwise random from the seed.

``arch`` is the ``transformer_config`` group of a configuration's file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: scope names this block adds below ``layers`` (``scopes.py``)
MAMBA_SCOPES = ("mamba", "mamba_proj", "mamba_conv", "mamba_scan",
                "mamba_state_io", "mamba_out")
SCOPES = MAMBA_SCOPES + ("router", "latent_proj", "experts",
                         "shared_expert")

#: published key -> TransformerConfig field, for ``model.check_consistent``
#: (``n_routed_experts`` in the file is the share held, checked by the
#: block's test against ``moe_held_experts``; ``intermediate_size`` is a
#: width no layer of the cut uses beside ``moe_intermediate_size``)
PUBLISHED_TO_FIELD = {
    "head_dim": "head_size",
    "layer_norm_epsilon": "norm_eps",
    "norm_eps": "norm_eps",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_intermediate_size",
    "moe_shared_expert_intermediate_size": "moe_shared_intermediate_size",
    "moe_latent_size": "moe_latent_size",
    "norm_topk_prob": "moe_norm_topk",
    "routed_scaling_factor": "moe_route_scale",
    "mlp_hidden_act": "moe_activation",
    "mamba_num_heads": "mamba_num_heads",
    "mamba_head_dim": "mamba_head_dim",
    "ssm_state_size": "mamba_state_size",
    "n_groups": "mamba_n_groups",
    "conv_kernel": "mamba_conv_kernel",
    "chunk_size": "mamba_chunk_size",
}

#: the published pattern's characters -> (mixer kind, carries an FFN)
PATTERN = {"M": ("mamba2", False), "*": ("full", False), "E": (None, True)}


def positions(pattern: str):
    """A published ``hybrid_override_pattern`` as the two lists of a
    ``transformer_config``: (layer_pattern, layer_ffn)."""
    kinds, ffn = zip(*(PATTERN[c] for c in pattern))
    return list(kinds), list(ffn)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# ----------------------------------------------------------------- layers

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


def _attention(u, lp, arch, q_block):
    """No rotary, no gate, no q/k norm."""
    T = u.shape[0]
    nh, kvh, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_size"]
    q = (u @ lp["wq"]).reshape(T, nh, hd)
    k = (u @ lp["wk"]).reshape(T, kvh, hd)
    v = (u @ lp["wv"]).reshape(T, kvh, hd)
    return _softmax_attention(q, k, v, q_block).reshape(T, nh * hd) @ lp["wo"]


def mamba_dims(arch: dict):
    """(heads, head channels P, state size S, groups, inner width, conv
    channels)."""
    nh, hd = arch["mamba_num_heads"], arch["mamba_head_dim"]
    ns, g = arch["mamba_state_size"], arch["mamba_n_groups"]
    return nh, hd, ns, g, nh * hd, nh * hd + 2 * g * ns


def _mamba2(u, lp, arch):
    T = u.shape[0]
    nh, hd, ns, g, inner, ch = mamba_dims(arch)
    K = arch["mamba_conv_kernel"]
    zxd = u @ lp["mamba_w_in"]
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + ch], zxd[:, inner + ch:]
    # the conv, as shifted sums: tap j reaches K-1-j steps back
    padded = jnp.concatenate([jnp.zeros((K - 1, ch)), xbc], 0)
    xbc = jax.nn.silu(sum(padded[j:j + T] * lp["mamba_conv_w"][j]
                          for j in range(K)) + lp["mamba_conv_b"])
    x = xbc[:, :inner].reshape(T, nh, hd)
    # head h reads group h // (heads / groups)
    B = jnp.repeat(xbc[:, inner:inner + g * ns].reshape(T, g, ns), nh // g, 1)
    C = jnp.repeat(xbc[:, inner + g * ns:].reshape(T, g, ns), nh // g, 1)
    dt = jax.nn.softplus(dt + lp["mamba_dt_bias"])              # [T, heads]
    A = -jnp.exp(lp["mamba_A_log"])

    def token(h, xs):           # h [heads, P, S]
        x_t, b_t, c_t, dt_t = xs
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hps,hs->hp", h, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((nh, hd, ns)), (x, B, C, dt))
    y = y + lp["mamba_D"][None, :, None] * x
    # the gate first, then the norm over each group's channels
    y = y.reshape(T, inner) * jax.nn.silu(z)
    yg = y.reshape(T, g, inner // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + arch["norm_eps"])
    return (yg.reshape(T, inner) * lp["mamba_norm_w"]) @ lp["mamba_w_out"]


def route(u, lp, arch):
    """(weights [T, k], experts [T, k]) over ALL experts, float32."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(u @ lp["router_wg"].astype(f32))
    _, top_e = jax.lax.top_k(s + lp["router_b"].astype(f32),
                             arch["moe_top_k"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if arch.get("moe_norm_topk"):
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return top_s * arch.get("moe_route_scale", 1.0), top_e


def routed_part(u, lp, arch):
    """The held experts' part of the top-k sum, in the latent and through
    ``W_l2``. ``lp`` holds the experts' weights as stored (any float
    type): each is made float32 when its turn in the loop comes."""
    f32 = jnp.float32
    lo, n_held = arch.get("moe_held_experts") or (0, arch["moe_num_experts"])
    top_w, top_e = route(u, lp, arch)
    lat = u @ lp["latent_w_in"].astype(f32)

    def expert(e, acc):
        weight = jnp.sum(jnp.where(top_e == lo + e, top_w, 0.0), axis=-1)
        y = _relu2(lat @ lp["w_in"][e].astype(f32)) @ lp["w_out"][e].astype(
            f32)
        return acc + weight[:, None] * y

    r = jax.lax.fori_loop(0, n_held, expert, jnp.zeros_like(lat))
    return r @ lp["latent_w_out"].astype(f32)


def shared_part(u, lp):
    f32 = jnp.float32
    return _relu2(u @ lp["shared_w_in"].astype(f32)) \
        @ lp["shared_w_out"].astype(f32)


def latent_moe(u, lp, arch):
    return routed_part(u, lp, arch) + shared_part(u, lp)


_EXPERT_LEAVES = ("router_wg", "router_b", "w_in", "w_out", "latent_w_in",
                  "latent_w_out", "shared_w_in", "shared_w_out")


def _position(x, lp, kind, ffn, arch, q_block):
    """One position of the period: its mixer (if it has one), then its
    FFN (if it has one), a norm and a residual add each."""
    small = {k: v.astype(jnp.float32) for k, v in lp.items()
             if k not in _EXPERT_LEAVES}
    eps = arch["norm_eps"]
    if kind is not None:
        u = _rms(x, small["attn_norm_w"], eps)
        x = x + (_attention(u, small, arch, q_block) if kind == "full"
                 else _mamba2(u, small, arch))
    if ffn:
        x = x + latent_moe(_rms(x, small["mlp_norm_w"], eps), lp, arch)
    return x


def _logits_one(params, tokens, arch, q_block):
    """tokens [T] → float32 logits [T, vocab]."""
    pattern = tuple(arch["layer_pattern"])
    ffns = tuple(arch.get("layer_ffn") or (True,) * len(pattern))
    x = params["embed"]["wte"][tokens].astype(jnp.float32)

    def period(x, slots):
        for kind, ffn, lp in zip(pattern, ffns, slots):
            x = _position(x, lp, kind, ffn, arch, q_block)
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

def layer_kinds(arch: dict) -> dict:
    """Layers of each sort in the model: its mixers by kind, and
    ``"ffn"``, the positions that carry an FFN."""
    pattern = tuple(arch["layer_pattern"])
    ffns = tuple(arch.get("layer_ffn") or (True,) * len(pattern))
    periods = arch["num_layers"] // len(pattern)
    out = {kind: periods * pattern.count(kind)
           for kind in dict.fromkeys(pattern) if kind is not None}
    out["ffn"] = periods * sum(map(bool, ffns))
    return out


def mamba_matmul_params(arch: dict) -> int:
    """One Mamba-2 mixer: the input and the output projection."""
    nh, hd, ns, g, inner, ch = mamba_dims(arch)
    h = arch["hidden_size"]
    return h * (inner + ch + nh) + inner * h


def attention_matmul_params(arch: dict) -> int:
    h, nh, hd = arch["hidden_size"], arch["num_heads"], arch["head_size"]
    return 2 * h * nh * hd + 2 * h * arch["num_kv_heads"] * hd


def expert_matmul_params(arch: dict) -> int:
    """One expert: up and down, in the latent."""
    return 2 * arch["moe_latent_size"] * arch["moe_intermediate_size"]


def ffn_fixed_matmul_params(arch: dict) -> int:
    """What every token passes in a LatentMoE layer whatever it is routed
    to: the router, both latent projections, the shared expert."""
    h = arch["hidden_size"]
    return (h * arch["moe_num_experts"] + 2 * h * arch["moe_latent_size"]
            + 2 * h * arch["moe_shared_intermediate_size"])


def matmul_params(arch: dict) -> float:
    """Weights a token is multiplied with once in a forward pass *here*:
    each mixer, each FFN's router, latent projections and shared expert,
    the output head, and of its top-k experts those this configuration
    holds — ``top_k · held / experts`` of them **in expectation** (even
    routing: 5.5 of the 22 at 128 of 512 held); what the absent experts
    would cost is the other chips'. The embedding is a lookup; norms, the
    conv and the recurrence are not weight matmuls."""
    kinds = layer_kinds(arch)
    held = (arch.get("moe_held_experts") or (0, arch["moe_num_experts"]))[1]
    ffn = ffn_fixed_matmul_params(arch) + arch["moe_top_k"] * held \
        / arch["moe_num_experts"] * expert_matmul_params(arch)
    return (kinds.get("mamba2", 0) * mamba_matmul_params(arch)
            + kinds.get("full", 0) * attention_matmul_params(arch)
            + kinds["ffn"] * ffn
            + arch["hidden_size"] * arch["vocab_size"])


def ssm_state_bytes(arch: dict) -> int:
    """One sequence's recurrent state in one Mamba-2 layer (float32)."""
    nh, hd, ns = mamba_dims(arch)[:3]
    return nh * hd * ns * 4


def conv_tail_bytes(arch: dict, el_bytes: int = 2) -> int:
    return (arch["mamba_conv_kernel"] - 1) * mamba_dims(arch)[5] * el_bytes


def kv_token_bytes(arch: dict, el_bytes: int = 2) -> int:
    """K and V of one token, all attention layers."""
    return layer_kinds(arch).get("full", 0) * 2 * arch["num_kv_heads"] \
        * arch["head_size"] * el_bytes
