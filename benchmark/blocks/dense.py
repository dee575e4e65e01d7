"""The ``dense`` block: a decoder layer of attention and one MLP that
every token passes through — its plain reference (forward pass and loss)
and its arithmetic, found by the name a configuration's file gives
(``"block": "dense"``; ``manifest.resolve``).

The reference is straightforward float32 ``jax.numpy`` — no kernel, no
cache, no batching tricks, independent of
``deepspeed_tpu/models/transformer.py``. Written from the published
descriptions:

- GPT-NeoX (Pythia): LayerNorm with bias, rotary on the first
  ``rotary_pct·head_dim`` dims (rotate-half pairing), exact GELU, biases
  everywhere, parallel residual ``x + attn(ln1 x) + mlp(ln2 x)``, untied
  output head.
- Mistral: RMSNorm, full rotary, grouped-query attention (each KV head
  serves ``heads/kv_heads`` query heads), sliding window (a query at i
  sees keys j with i − window < j ≤ i), SwiGLU ``down(silu(gate x)·up x)``,
  no biases, sequential residual.

``arch`` is the ``transformer_config`` group of a configuration's file.
The only thing shared with the program is the parameter tree's naming
(``embed.wte``, ``layers.{wq,wk,wv,wo,w_in,w_out,w_gate,…}`` stacked over
the layer dim, ``final_norm``, ``lm_head.w``). On a TPU a float32 matmul
runs in lower precision unless told otherwise, so everything here runs
under ``jax.default_matmul_precision("highest")``.

A block whose layer differs from this one only in what stands in the
MLP's place (sparse experts) is a file of its own beside this one that
passes its own ``mlp(h, lp, arch)`` to ``logits`` and ``loss`` here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _norm(x, w, b, kind, eps):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rotary(x, rot_dim, theta):
    """x [T, heads, D]: rotate the first ``rot_dim`` dims of every head by
    position (rotate-half: dim i pairs with i + rot_dim/2)."""
    if rot_dim == 0:
        return x
    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                          / rot_dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot_dim // 2], x[..., rot_dim // 2:rot_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot_dim:]], axis=-1)


def _attention(q, k, v, window, q_block):
    """q [T, H, D], k/v [T, KH, D] → [T, H·D]; causal, windowed, GQA.
    Query rows are taken ``q_block`` at a time so the score matrix of a
    long sequence need not be held whole."""
    T, H, D = q.shape
    group = H // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    cols = jnp.arange(T)[None, :]
    out = []
    for start in range(0, T, q_block):
        qs = q[start:start + q_block]
        rows = (start + jnp.arange(qs.shape[0]))[:, None]
        keep = cols <= rows
        if window:
            keep &= cols > rows - window
        s = jnp.einsum("thd,shd->hts", qs, k) / math.sqrt(D)
        s = jnp.where(keep[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("hts,shd->thd", p, v))
    return jnp.concatenate(out, axis=0).reshape(T, H * D)


def _lin(y, lp, name):
    y = y @ lp[name]
    return y + lp[name + "_b"] if name + "_b" in lp else y


def mlp(h, lp, arch):
    """The layer's MLP on its normed input [T, hidden]."""
    if arch["activation"] == "silu":
        y = jax.nn.silu(_lin(h, lp, "w_gate")) * _lin(h, lp, "w_in")
    elif arch["activation"] == "gelu_exact":
        y = jax.nn.gelu(_lin(h, lp, "w_in"), approximate=False)
    else:
        raise ValueError(f"no reference for activation "
                         f"{arch['activation']!r}")
    return _lin(y, lp, "w_out")


def _layer(x, lp, arch, q_block, mlp):
    nh = arch["num_heads"]
    kvh = arch.get("num_kv_heads") or nh
    hd = arch["hidden_size"] // nh
    T = x.shape[0]
    kind, eps = arch["norm"], arch["norm_eps"]
    rot = int(hd * arch.get("rope_pct", 1.0)) // 2 * 2
    h1 = _norm(x, lp["attn_norm_w"], lp.get("attn_norm_b"), kind, eps)
    theta = arch["rope_theta"]
    q = _rotary(_lin(h1, lp, "wq").reshape(T, nh, hd), rot, theta)
    k = _rotary(_lin(h1, lp, "wk").reshape(T, kvh, hd), rot, theta)
    v = _lin(h1, lp, "wv").reshape(T, kvh, hd)
    attn = _lin(_attention(q, k, v, arch.get("sliding_window") or 0,
                           q_block), lp, "wo")
    mlp_in = x if arch.get("parallel_residual") else x + attn
    h2 = _norm(mlp_in, lp["mlp_norm_w"], lp.get("mlp_norm_b"), kind, eps)
    return x + attn + mlp(h2, lp, arch)


def _logits_one(params, tokens, arch, q_block, mlp):
    """tokens [T] → float32 logits [T, vocab]."""
    if arch.get("position") != "rope":
        raise ValueError("the reference covers rotary models only")
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = params["embed"]["wte"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _layer(x, f32(lp), arch, q_block, mlp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    fn = f32(params["final_norm"])
    x = _norm(x, fn["w"], fn.get("b"), arch["norm"], arch["norm_eps"])
    if arch.get("tie_embeddings"):
        return x @ params["embed"]["wte"].astype(jnp.float32).T
    return x @ params["lm_head"]["w"].astype(jnp.float32)


def logits(params, tokens, arch, q_block=1024, mlp=mlp):
    """Reference logits for one sequence, at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        return _logits_one(params, tokens, arch, q_block, mlp)


def loss(params, input_ids, arch, q_block=1024, mlp=mlp):
    """Mean next-token negative log-likelihood over ``input_ids``
    [B, T+1] (inputs are [:, :-1], labels [:, 1:])."""
    with jax.default_matmul_precision("highest"):
        def one(ids):
            lg = _logits_one(params, ids[:-1], arch, q_block, mlp)
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, ids[1:, None], axis=-1)[:, 0]
            return jnp.mean(logz - gold)

        return jnp.mean(jax.lax.map(one, input_ids))


def attention_matmul_params(arch: dict) -> int:
    """One layer's q, k, v and o weights."""
    h, nh = arch["hidden_size"], arch["num_heads"]
    kvh = arch.get("num_kv_heads") or nh
    hd = h // nh
    return h * nh * hd + 2 * h * kvh * hd + nh * hd * h


def matmul_params(arch: dict) -> int:
    """Weights that a token is multiplied with once in a forward pass:
    q, k, v, o, the MLP (three matrices when gated) and the output head.
    The embedding is a lookup, norms and biases are not matmuls."""
    h, m = arch["hidden_size"], arch["intermediate_size"]
    per_layer = attention_matmul_params(arch) \
        + (3 if arch["activation"] == "silu" else 2) * h * m
    return arch["num_layers"] * per_layer + h * arch["vocab_size"]
