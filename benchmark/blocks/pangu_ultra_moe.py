"""The ``pangu_ultra_moe`` block (openPangu-Ultra-MoE, ``model_type:
pangu_ultra_moe``): latent attention (MLA) in every layer, leading dense
layers, every later layer followed by a sigmoid-routed sparse FFN with
one ungated shared expert, four norms a layer — its plain reference
(forward pass and loss), its arithmetic, the scope names it adds and the
cost functions of the two kernels its cell reads, found by the name a
configuration's file gives (``"block": "pangu_ultra_moe"``).

The reference is straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, in the **expanded** form:
keys and values of every head are rebuilt from the latents of **all**
earlier positions and attended as plain multi-head attention — no cache,
no absorbed product, no kernel. It imports nothing from
``deepspeed_tpu``; what it shares with the program is the parameter
tree's naming (``layers.lead<j>``, ``layers.slot<i>`` stacked over the
periods). What is computed is taken a piece at a time only where the
piece is independent of the rest — query rows a block at a time, heads a
group at a time (a head's K/V of 12,544 positions is 12.8 MB in float32,
all 128 heads' 1.6 GB), the dense MLP's 18,432 columns a slab at a time,
the held experts one after the other — so that the check fits beside the
resident engine. Written from the published ``config.json`` and, where it
has no key, the family's modelling code, a DeepSeek-V3-shaped layer (the
configuration's ``assumed.from_the_modelling_code``);
x̂ = x·rsqrt(mean x² + eps), gain w:

    x₀      = E[tokens]
    h       = norm_in(x)
    c_q     = norm_q(h·W_qa)                          (q_lora_rank)
    q       = c_q·W_qb  → heads of [q_nope | q_rope]  (nope + rope a head)
    [c_kv | k_r] = h·W_kva                            (kv_lora_rank + rope)
    c       = norm_kv(c_kv);  k_r = rope(k_r; θ)      (one k_r for all heads)
    q_rope  = rope(q_rope; θ)                         (rotate-half pairs)
    k_nope_h = c·W_kb_h;  v_h = c·W_vb_h              (rebuilt a head)
    s_h(i,j) = (q_nope_h(i)·k_nope_h(j) + q_rope_h(i)·k_r(j)) / √(nope + rope)
    a_h     = softmax_j≤i(s_h)·v_h
    x       = x + norm_post_attn(concat_h(a_h)·W_o)   (no bias anywhere)
    h       = norm_pre_mlp(x)
    lead layers:  m = (silu(h·W_gate) ⊙ h·W_up)·W_down
    the others:   s = σ(h·W_r) over ALL experts (float32); S = top-k of s;
                  w_e = route_scale · s_e / (Σ_{j∈S} s_j + 1e-20)
                  m = shared(h) + Σ_{e∈S, e held} w_e · expert_e(h)
    x       = x + norm_post_mlp(m)
    logits  = norm_final(x)·W_head

**Only the experts the configuration holds are summed**
(``moe_held_experts = [lo, n]``), as in ``blocks/trinity.py``, whose
sparse FFN this is but for the selection bias (none here): its routing,
its held share, its shared expert and its rule for ill-conditioned
decisions are imported from there, not copied.

``arch`` is the ``transformer_config`` group of a configuration's file.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.blocks import trinity

#: scope names this block adds below ``layers`` (``scopes.py``): the
#: latent layer round ``qkv`` / ``kv_write`` / ``attend`` / ``attn_out``,
#: the rebuilding of K/V heads for a chunk inside it, the leading layers'
#: dense MLP, and the sparse FFN's parts inside ``mlp``
SCOPES = ("latent_attn", "kv_expand", "dense_mlp", "router", "experts",
          "shared_expert")
ATTN_SCOPES = {"latent": "latent_attn"}

#: published key -> TransformerConfig field, for ``model.check_consistent``
#: (``n_routed_experts`` in the file is the share held and
#: ``first_k_dense_replace`` the length of ``lead_layers``)
PUBLISHED_TO_FIELD = {
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_intermediate_size",
    "norm_topk_prob": "moe_norm_topk",
    "routed_scaling_factor": "moe_route_scale",
    "sandwich_norm": "sandwich_norm",
}

_rms, _rotary, _by_rows = trinity._rms, trinity._rotary, trinity._by_rows
_held, shared_part, ROW_BLOCK = trinity._held, trinity.shared_part, \
    trinity.ROW_BLOCK

#: heads whose K/V the reference rebuilds at a time, and columns of the
#: dense MLP it multiplies at a time
HEAD_GROUP = 8
MLP_SLAB = 2048
#: query rows a block of the reference's attention takes: the scores of
#: 8 heads over 12,544 keys are 0.4 MB a query row in float32
Q_BLOCK = 128

#: A routing decision within this of the selection's edge is
#: ill-conditioned and its position gets no answer (``trinity.TIE_MARGIN``
#: says why). Measured on the chip (PR 36, the configuration's
#: ``check._ties``; 176 comparisons at the published widths): the
#: bfloat16 program chose otherwise at 2 positions, at margins of 0.00032
#: and 0.00034, and nowhere above; 19% of the positions lie under 0.004.
TIE_MARGIN = 0.004


def _widths(arch):
    return (arch["num_heads"], arch["qk_nope_head_dim"],
            arch["qk_rope_head_dim"], arch["v_head_dim"],
            arch["kv_lora_rank"])


def _attention(h, lp, arch, q_block):
    """h [T, hidden] → the layer's output [T, hidden], expanded form.
    Every position's latent is kept; heads are taken ``HEAD_GROUP`` at a
    time (their K/V rebuilt from all T latents), query rows ``q_block``
    at a time, and a query at position p sees keys ≤ p by a mask over all
    T keys."""
    T = h.shape[0]
    nh, dn, dr, dv, R = _widths(arch)
    eps, theta, f32 = arch["norm_eps"], arch["rope_theta"], jnp.float32
    G = math.gcd(nh, HEAD_GROUP)
    at = jnp.arange(T)
    c_q = _by_rows(lambda r: _rms(r @ lp["w_qa"].astype(f32),
                                  lp["q_a_norm_w"].astype(f32), eps),
                   h, ROW_BLOCK)
    kva = _by_rows(lambda r: r @ lp["w_kva"].astype(f32), h, ROW_BLOCK)
    c = _rms(kva[:, :R], lp["kv_a_norm_w"].astype(f32), eps)
    k_r = _rotary(kva[:, None, R:], theta, at)[:, 0]            # [T, rope]
    n = -(-T // q_block)
    pad = n * q_block - T
    c_qp = jnp.pad(c_q, ((0, pad), (0, 0))).reshape(n, q_block, -1)
    starts = jnp.arange(n) * q_block
    cols = at[None, :]

    def group(g):
        """Heads g·G … g·G + G − 1: their part of the output, through
        their rows of W_o."""
        w_qb = jax.lax.dynamic_slice_in_dim(
            lp["w_qb"], g * G * (dn + dr), G * (dn + dr), 1).astype(f32)
        w_kb = jax.lax.dynamic_slice_in_dim(
            lp["w_kb"], g * G * dn, G * dn, 1).astype(f32)
        w_vb = jax.lax.dynamic_slice_in_dim(
            lp["w_vb"], g * G * dv, G * dv, 1).astype(f32)
        w_o = jax.lax.dynamic_slice_in_dim(
            lp["wo"], g * G * dv, G * dv, 0).astype(f32)
        k_nope = (c @ w_kb).reshape(T, G, dn)
        v = (c @ w_vb).reshape(T, G, dv)

        def block(xs):
            start, cq = xs
            pos = start + jnp.arange(q_block)
            q = (cq @ w_qb).reshape(q_block, G, dn + dr)
            q_rope = _rotary(q[..., dn:], theta, pos)
            s = (jnp.einsum("tgd,sgd->gts", q[..., :dn], k_nope)
                 + jnp.einsum("tgd,sd->gts", q_rope, k_r)) \
                / math.sqrt(dn + dr)
            keep = cols <= pos[:, None]
            p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
            a = jnp.einsum("gts,sgd->tgd", p, v).reshape(q_block, G * dv)
            return a @ w_o

        return jax.lax.map(block, (starts, c_qp)).reshape(
            n * q_block, -1)[:T]

    return jax.lax.fori_loop(
        0, nh // G, lambda g, acc: acc + group(g),
        jnp.zeros((T, lp["wo"].shape[-1]), f32))


def _dense_mlp(h, lp):
    """The leading layers' SwiGLU, ``MLP_SLAB`` of its columns at a time
    (each slab made float32 when its turn comes)."""
    f32 = jnp.float32
    m = lp["w_in"].shape[-1]
    slab = math.gcd(m, MLP_SLAB)

    def part(i, acc):
        cut = lambda w, axis: jax.lax.dynamic_slice_in_dim(    # noqa: E731
            w, i * slab, slab, axis).astype(f32)
        return acc + (jax.nn.silu(h @ cut(lp["w_gate"], 1))
                      * (h @ cut(lp["w_in"], 1))) @ cut(lp["w_out"], 0)

    return jax.lax.fori_loop(0, m // slab, part, jnp.zeros_like(h))


def _route_leaves(lp, arch):
    """``lp`` as ``trinity``'s routing reads it: this model selects by
    the scores alone, which is a selection bias of zero."""
    return dict(lp, router_b=jnp.zeros((arch["moe_num_experts"],),
                                       jnp.float32))


def routed_part(h, lp, arch, held=None):
    """The part of the routed sum that the experts ``held = (lo, n)`` add
    (``lp``'s expert leaves hold those n), without the shared expert."""
    return trinity.routed_part(h, _route_leaves(lp, arch), arch, held)


_NORMS = ("attn_norm_w", "post_attn_norm_w", "mlp_norm_w", "post_mlp_norm_w")


def _layer(x, lp, dense, arch, q_block):
    """x [T, hidden] → (x after the layer, the margin [T] of its routing
    decision: infinite for a leading layer, which routes nothing)."""
    eps, f32 = arch["norm_eps"], jnp.float32
    m = {k: lp[k].astype(f32) for k in _NORMS}
    a = _attention(_by_rows(lambda r: _rms(r, m["attn_norm_w"], eps), x,
                            ROW_BLOCK), lp, arch, q_block)
    x = x + _rms(a, m["post_attn_norm_w"], eps)

    def ffn(rows):
        h = _rms(rows, m["mlp_norm_w"], eps)
        if dense:
            f = _dense_mlp(h, lp)
            margin = jnp.full(rows.shape[:1], jnp.inf, f32)
        else:
            f, margin = trinity._routed(h, _route_leaves(lp, arch), arch,
                                        _held(arch))
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
    for j in range(len(lead)):
        x, _ = _layer(x, jax.tree.map(lambda a: a[0], layers[f"lead{j}"]),
                      True, arch, q_block)

    def period(carry, slots):
        x, least = carry
        for lp in slots:
            x, margin = _layer(x, lp, False, arch, q_block)
            least = jnp.minimum(least, margin)
        return (x, least), None

    slots = tuple(layers[f"slot{i}"] for i in range(len(pattern)))
    (x, least), _ = jax.lax.scan(
        period, (x, jnp.full(x.shape[:1], jnp.inf, jnp.float32)), slots)
    w_norm = params["final_norm"]["w"].astype(jnp.float32)
    head = params["lm_head"]["w"]
    return _by_rows(lambda r: _rms(r, w_norm, arch["norm_eps"])
                    @ head.astype(jnp.float32), x, ROW_BLOCK), least


def logits(params, tokens, arch, q_block=Q_BLOCK):
    """Reference logits for one sequence, at the highest matmul
    precision — and no answer (NaN) at a position whose routing is
    ill-conditioned (``TIE_MARGIN``; ``trinity.logits`` says what the
    harness does with such a position)."""
    with jax.default_matmul_precision("highest"):
        lg, least = _logits_one(params, tokens, arch, q_block)
    return jnp.where((least < TIE_MARGIN)[:, None], jnp.nan, lg)


def tie_margins(params, tokens, arch, q_block=Q_BLOCK):
    """(logits [T, vocab] with every position answered, the least margin
    [T] of each position's routing decisions)."""
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
    """One latent mixer: W_qa, W_qb, W_kva, W_kb + W_vb, W_o."""
    h = arch["hidden_size"]
    nh, dn, dr, dv, R = _widths(arch)
    qr = arch["q_lora_rank"]
    return (h * qr + qr * nh * (dn + dr) + h * (R + dr)
            + R * nh * (dn + dv) + nh * dv * h)


def expert_matmul_params(arch: dict) -> int:
    """One expert (routed or shared): gate, up, down."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def layer_kinds(arch: dict) -> dict:
    lead = tuple(arch.get("lead_layers") or ())
    return {"latent": arch["num_layers"], "lead": len(lead),
            "sparse": arch["num_layers"] - len(lead)}


def matmul_params(arch: dict) -> float:
    """Weights a token is multiplied with once in a forward pass *here*:
    each layer's mixer (``W_kb`` / ``W_vb`` counted once a token, as the
    absorbed form and a prompt's first chunk multiply them; what a later
    chunk rebuilds again is ``kv_expand_cost``'s), the leading layers'
    dense MLP, each later layer's router and shared expert, the output
    head, and of a token's top-k experts those this configuration holds —
    ``top_k · held / experts`` of them in expectation. The embedding is a
    lookup and norms are not weight matmuls."""
    h = arch["hidden_size"]
    kinds = layer_kinds(arch)
    held = _held(arch)[1]
    sparse = (h * arch["moe_num_experts"]
              + 3 * h * arch.get("moe_shared_intermediate_size", 0)
              + arch["moe_top_k"] * held / arch["moe_num_experts"]
              * expert_matmul_params(arch))
    return (arch["num_layers"] * attention_matmul_params(arch)
            + kinds["lead"] * 3 * h * arch["intermediate_size"]
            + kinds["sparse"] * sparse + h * arch["vocab_size"])


def latent_bytes(arch: dict, el_bytes: int = 2) -> int:
    """What a token's cache row must hold: ``(c, k_r)``, unpadded."""
    return (arch["kv_lora_rank"] + arch["qk_rope_head_dim"]) * el_bytes


def mla_decode_cost(arch: dict, query_tokens: int, kv_read_tokens: int,
                    qk_pairs: int, el_bytes: int = 2) -> dict:
    """One layer's absorbed call (kernel ``mla_decode``), the least work:
    a head multiplies ``q~ | q_rope`` (rank + rope) with each key its
    query may see and the probabilities with the latent (rank); every
    live latent is read once a sequence — not once a head, not once a
    query row of a narrow chunk — plus the queries in and the attended
    latents out."""
    nh, dn, dr, dv, R = _widths(arch)
    return {"flops": 2.0 * nh * (R + dr + R) * qk_pairs,
            "bytes": latent_bytes(arch, el_bytes) * kv_read_tokens
            + nh * (R + dr + R) * el_bytes * query_tokens}


def mla_prefill_cost(arch: dict, query_tokens: int, kv_read_tokens: int,
                     qk_pairs: int, el_bytes: int = 2) -> dict:
    """One layer's expanded call (kernel ``mla_prefill``), the least
    work: q·k at nope + rope and p·v at v over the pairs under the causal
    mask; the rebuilt K and V of every key the chunk may see and its k_r
    read once, the queries in and the output out (the carry between the
    tiles of a long context is the implementation's, not needed work)."""
    nh, dn, dr, dv, R = _widths(arch)
    return {"flops": 2.0 * nh * (dn + dr + dv) * qk_pairs,
            "bytes": el_bytes * ((nh * (dn + dv) + dr) * kv_read_tokens
                                 + nh * (dn + dr + dv) * query_tokens)}


def kv_expand_flops(arch: dict, positions: int) -> float:
    """Rebuilding the K/V heads of ``positions`` context positions, one
    layer: each latent through ``W_kb`` and ``W_vb``."""
    nh, dn, dr, dv, R = _widths(arch)
    return 2.0 * R * nh * (dn + dv) * positions
