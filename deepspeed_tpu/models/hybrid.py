"""The parts of a hybrid block: layers of more than one kind in one model
(``TransformerConfig.layer_pattern``: one period of mixer kinds, scanned
one period an iteration — in serving too, however few the periods: two
periods laid out inline ran slower than the loop in every program
measured, PERF.md section 6, PR 46), each followed by the same sparse
FFN.

- ``"full"``: gated softmax attention — a stated head size, per-head
  q/k RMSNorm, an output gate read off a ``wq`` twice as wide
  (``[q | g]`` within each head) or off a projection of its own
  (``attn_gate_proj``: ``wg``), partial rotary or none
  (``rope_kinds``).
- ``"window"``: the same layer over the last ``sliding_window``
  positions; its K/V is a group of its own in serving, with a pool and a
  block table whose blocks behind the window go back while the sequence
  lives.
- ``"latent"``: latent attention (MLA) — queries through a low-rank
  bottleneck (``w_qa``, a norm, ``w_qb``), keys and values rebuilt from
  one normed latent a token (``w_kva`` -> ``[c | k_r]``; ``w_kb`` /
  ``w_vb``), a head's query and key ``[nope | rope]`` wide with the
  key's rope part one for all heads. Its cache is ``(c, k_r)``, no head
  axis; the same numbers come out of the *expanded* form (K/V rebuilt,
  MHA) and the *absorbed* one (``w_kb`` folded into the query, ``w_vb``
  applied to the attended latents): ``latent_*`` below.
- ``"latent_sparse"``: that layer behind a learned selection (the
  *indexer*): ``index_n_heads`` small query heads off the query latent
  (``w_iq``), one key a token (``w_ik``, a LayerNorm) and a weight a head
  (``w_iw``) score every earlier position, ``I = Σ_h w_h · relu(q_h ·
  k)``; the layer attends the ``index_topk`` positions of largest score
  and no other (``index_*`` below). Its cache is the latent row and the
  indexer's key beside it.
- ``"latent_window"``: latent attention over the last ``sliding_window``
  positions at sizes of its own (``swa_*``: ``cfg.latent_sizes(kind)``
  gives every latent kind's); its latents are a layer group of their
  own, as a ``"window"`` layer's K/V.
- ``"lightning"``: linear attention under one scalar decay a head
  (ops/lightning_attention.py): q/k RMSNorm a head and rotary, a float32
  state ``[heads, D, D]`` a sequence in place of any per-token cache, an
  RMSNorm over the joined heads' output and a sigmoid gate of the
  layer's input in front of ``wo`` (``lightning_mixer``).
- ``"block_sparse"``: a ``"full"`` layer (its projections, norms and
  gate) whose query, from position ``block_dense_len`` on, attends whole
  blocks of keys only — the first, those its window reaches, and the
  ``block_topk`` others of largest score, chosen a K/V head with **no
  weights**: the head's queries against the means of overlapping runs of
  its keys (``block_*`` below). Its cache is ``k`` / ``v`` and the
  compressed keys ``kc`` beside them.
- ``"linear"``: Gated DeltaNet (``ops/gated_delta.py``) — one projection
  to ``[q | k | v | z]`` and one to ``[b | a]``, a depthwise causal conv
  over ``[q | k | v]``, the gated delta rule over a float32 state, a
  gated RMSNorm and the output projection. Its cache is the state and the
  conv's last inputs, not per-token K/V.
- the FFN: dropless top-k experts over a held range plus a shared expert
  (``moe/grouped.py``): softmax or sigmoid scores, a selection bias
  outside the weights, the shared expert under a sigmoid gate or bare.
  ``lead_layers`` run before the scanned periods with a dense MLP in its
  place, and so does every layer of a model without experts
  (``moe_num_experts`` 0); ``sandwich_norm`` puts a norm behind the mixer
  and the FFN too; ``residual_scale`` multiplies both before their adds.

``CausalLM`` (training, the reference path) and ``PagedCausalLM``
(serving) both call these; only where the mixer's cache lives differs —
and that serving holds some projections' outputs to the layout their
matmul writes (``full_qkv``'s and ``lightning_mixer``'s ``hold``), so
that the weight is multiplied where it lies in its stack.
Scopes follow ``docs/OBSERVABILITY.md``: ``linear_attn`` ⊃ ``gdn_proj``,
``gdn_conv``, ``gdn_scan``, ``gdn_out``; ``full_attn`` / ``window_attn`` /
``latent_attn`` / ``window_latent_attn`` round an attention layer's
``qkv``, ``kv_write``, ``attend`` and ``attn_out`` (a latent layer's
chunk forward adds ``kv_expand``; a sparse one ``index`` ⊃
``index_proj``, ``index_score``, ``index_select``, and ``index_write``
inside ``kv_write``); ``router``, ``experts``, ``shared_expert`` or
``dense_mlp`` inside ``mlp``; ``lightning_attn`` ⊃ ``lightning_proj``,
``lightning_scan``, ``lightning_out``; ``sparse_attn`` rounds a
block-sparse layer's and adds ``block_compress``, ``block_score`` and
``block_select``.
"""

from __future__ import annotations

import contextlib
import itertools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import gated_delta as gd
from ..ops import lightning_attention as la
from ..parallel.sharding import spec

KINDS = ("full", "linear", "window", "latent", "latent_sparse",
         "latent_window", "lightning", "block_sparse")
#: the kinds whose cache is a latent row a token, no head axis
LATENT_KINDS = ("latent", "latent_sparse", "latent_window")
#: the kinds that keep a per-token cache, and the scope round each one's
#: layer
ATTN_SCOPE = {"full": "full_attn", "window": "window_attn",
              "latent": "latent_attn", "latent_sparse": "latent_attn",
              "latent_window": "window_latent_attn",
              "block_sparse": "sparse_attn"}


class RecurrentStateUnsupported(NotImplementedError):
    """Raised where a feature that assumes per-token KV (rollback, prefix
    sharing, the KV tier, head-split TP) meets a model with recurrent
    layers: their state cannot be cut at a token or shared by prefix."""


class ReleasedKVUnsupported(NotImplementedError):
    """Raised where a feature that assumes a sequence's whole context is
    resident in one pool (the prefix cache and the KV tier over several
    layer groups, export / import and the preemption stash, a rollback
    past a released block, quantized pools of several groups) meets K/V
    kept by layer group, whose window groups hand blocks back while the
    sequence lives (inference/v2/ragged/manager.py)."""


class LatentKVUnsupported(NotImplementedError):
    """Raised where a feature that assumes K/V kept by kv-head (quantized
    pools, whose scales are one a block a kv-head; the KV tier; TP
    serving, which splits the pool and the attention by head) meets
    latent attention, whose cache is one row a token shared by every
    head (inference/v2/ragged/manager.py)."""


class CompressedKeysUnsupported(NotImplementedError):
    """Raised where a feature that assumes a pool block holds its own
    tokens' K/V and nothing else (the prefix cache, which shares blocks;
    quantized pools and TP serving, which scale and split by kv-head; the
    KV tier) meets a block-sparse layer's compressed keys, kept beside
    k / v in the same blocks (inference/v2/ragged/manager.py)."""


def rms(x, w, eps, zero_centered):
    """RMSNorm over the last dim in float32; ``zero_centered``: the gain
    is ``1 + w``."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    w32 = w.astype(jnp.float32)
    return (y * (1.0 + w32 if zero_centered else w32)).astype(dt)


def block_norm(cfg, x, w):
    return rms(x, w, cfg.norm_eps, cfg.norm_zero_centered)


# ------------------------------------------------------------------ sizes

def gdn_dims(cfg):
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    return hk, hv, dk, dv, 2 * hk * dk + hv * dv     # conv channels


def state_shapes(cfg, slots: int):
    """The recurrent cache for ``slots`` sequences, by the kinds the
    model has: the delta rule's state (float32 whatever the served type)
    and the conv's tail; a lightning layer's state, as wide as its head
    on both sides."""
    shapes = {}
    if cfg.layers_of("linear"):
        hk, hv, dk, dv, ch = gdn_dims(cfg)
        L = cfg.layers_of("linear")
        shapes.update(
            ssm=((L, slots, hv, dk, dv), jnp.float32),
            conv=((L, slots, cfg.linear_conv_kernel - 1, ch), cfg.dtype))
    if cfg.layers_of("lightning"):
        nh, hd = cfg.lightning_num_heads, cfg.lightning_head_dim
        shapes["lightning"] = ((cfg.layers_of("lightning"), slots, nh, hd,
                                hd), jnp.float32)
    return shapes


# ------------------------------------------------------------------- init

def init_slot(cfg, kind: str, key, periods: int, dense: bool = False):
    """One position of the period: its mixer's and its FFN's weights,
    stacked over the periods. ``dense``: a lead layer, whose FFN is the
    dense MLP."""
    h, hd, nh, kvh = (cfg.hidden_size, cfg.head_dim, cfg.num_heads,
                      cfg.kv_heads)
    P, std = periods, 0.02
    out_std = std / math.sqrt(2 * cfg.num_layers)
    # (a kind with more leaves than sixteen draws on from a second split:
    # the first sixteen are what they were)
    ks = itertools.chain(jax.random.split(key, 16),
                         jax.random.split(jax.random.fold_in(key, 1), 16))

    def w(shape, scale=std):
        return (scale * jax.random.normal(next(ks), (P,) + shape)
                ).astype(jnp.float32)

    gain = (jnp.zeros if cfg.norm_zero_centered else jnp.ones)
    lp = {"attn_norm_w": gain((P, h), jnp.float32),
          "mlp_norm_w": gain((P, h), jnp.float32)}
    if cfg.sandwich_norm:
        lp.update(post_attn_norm_w=gain((P, h), jnp.float32),
                  post_mlp_norm_w=gain((P, h), jnp.float32))
    if kind in LATENT_KINDS:
        z = cfg.latent_sizes(kind)
        nh, qr, kvr, dr, dn, dv = (z.heads, z.q_rank, z.kv_rank, z.rope,
                                   z.nope, z.v)
        # under the rescale a latent reaches its projections sqrt(hidden /
        # rank) times a normed one: they are drawn that much smaller, so
        # that a model of random weights attends as one without the
        # rescale does — nearly evenly. (At 0.02 the logits' spread is 2,
        # a query's weight lies on some thirty keys, and which keys a
        # bf16 indexer keeps at its selection's edge moves the logits by
        # tenths of their range: no check could tell that from a fault.)
        rq = _rescale(cfg, qr) if cfg.latent_rescale else 1.0
        rkv = _rescale(cfg, kvr) if cfg.latent_rescale else 1.0
        lp.update(w_qa=w((h, qr)), w_qb=w((qr, nh * (dn + dr)), std / rq),
                  w_kva=w((h, kvr + dr)), w_kb=w((kvr, nh * dn), std / rkv),
                  w_vb=w((kvr, nh * dv), std / rkv),
                  wo=w((nh * dv, h), out_std),
                  q_a_norm_w=gain((P, qr), jnp.float32),
                  kv_a_norm_w=gain((P, kvr), jnp.float32))
        if cfg.attn_gate_headwise:
            lp["w_g"] = w((h, nh))
        if kind == "latent_sparse":
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            lp.update(w_iq=w((qr, hi * di)), w_ik=w((h, di)),
                      w_iw=w((h, hi)),
                      ik_norm_w=jnp.ones((P, di), jnp.float32),
                      ik_norm_b=jnp.zeros((P, di), jnp.float32))
    elif kind == "lightning":
        width = cfg.lightning_num_heads * cfg.lightning_head_dim
        lp.update(wq=w((h, width)), wk=w((h, width)), wv=w((h, width)),
                  wg=w((h, width)), wo=w((width, h), out_std),
                  out_norm_w=jnp.ones((P, width), jnp.float32))
        if cfg.qk_norm:
            lp["q_norm_w"] = gain((P, cfg.lightning_head_dim), jnp.float32)
            lp["k_norm_w"] = gain((P, cfg.lightning_head_dim), jnp.float32)
    elif kind in ATTN_SCOPE:
        own_gate = cfg.attn_output_gate and cfg.attn_gate_proj
        q_out = nh * hd * (2 if cfg.attn_output_gate and not own_gate else 1)
        lp.update(wq=w((h, q_out)), wk=w((h, kvh * hd)), wv=w((h, kvh * hd)),
                  wo=w((nh * hd, h), out_std))
        if own_gate:
            lp["wg"] = w((h, nh * hd))
        if cfg.qk_norm:
            lp["q_norm_w"] = gain((P, hd), jnp.float32)
            lp["k_norm_w"] = gain((P, hd), jnp.float32)
    else:
        hk, hv, dk, dv, ch = gdn_dims(cfg)
        lp.update(
            w_qkvz=w((h, ch + hv * dv)), w_ba=w((h, 2 * hv)),
            conv_w=w((cfg.linear_conv_kernel, ch), 0.5),
            # A = U(0, 16), dt_bias = 1: the source's modelling code
            A_log=jnp.log(jax.random.uniform(next(ks), (P, hv), jnp.float32,
                                             1e-3, 16.0)),
            dt_bias=jnp.ones((P, hv), jnp.float32),
            gdn_norm_w=jnp.ones((P, dv), jnp.float32),
            w_gdn_out=w((hv * dv, h), out_std))
    if dense or not cfg.moe_num_experts:
        m = cfg.intermediate_size
        lp.update(w_in=w((h, m)), w_gate=w((h, m)), w_out=w((m, h), out_std))
        return lp
    n_held = cfg.moe_held_experts[1] if cfg.moe_held_experts \
        else cfg.moe_num_experts
    m = cfg.moe_intermediate_size or cfg.intermediate_size
    lp.update(router_wg=w((h, cfg.moe_num_experts), 1.0 / math.sqrt(h)),
              w_in=w((n_held, h, m)), w_gate=w((n_held, h, m)),
              w_out=w((n_held, m, h), out_std))
    if cfg.moe_select_bias:
        lp["router_b"] = jnp.zeros((P, cfg.moe_num_experts), jnp.float32)
    ms = cfg.moe_shared_intermediate_size
    if ms:
        lp.update(shared_w_in=w((h, ms)), shared_w_gate=w((h, ms)),
                  shared_w_out=w((ms, h), out_std))
        if cfg.moe_shared_gate:
            lp["shared_gate_w"] = w((h, 1), 1.0 / math.sqrt(h))
    return lp


def slot_specs(cfg, kind: str, dense: bool = False):
    """Logical sharding axes of ``init_slot``'s tree."""
    lp = {"attn_norm_w": spec("layers", "embed"),
          "mlp_norm_w": spec("layers", "embed")}
    if cfg.sandwich_norm:
        lp.update(post_attn_norm_w=spec("layers", "embed"),
                  post_mlp_norm_w=spec("layers", "embed"))
    if kind in LATENT_KINDS:
        lp.update(w_qa=spec("layers", "embed", None),
                  w_qb=spec("layers", None, "heads"),
                  w_kva=spec("layers", "embed", None),
                  w_kb=spec("layers", None, "heads"),
                  w_vb=spec("layers", None, "heads"),
                  wo=spec("layers", "heads", "embed"),
                  q_a_norm_w=spec("layers", None),
                  kv_a_norm_w=spec("layers", None))
        if cfg.attn_gate_headwise:
            lp["w_g"] = spec("layers", "embed", None)
        if kind == "latent_sparse":
            lp.update(w_iq=spec("layers", None, None),
                      w_ik=spec("layers", "embed", None),
                      w_iw=spec("layers", "embed", None),
                      ik_norm_w=spec("layers", None),
                      ik_norm_b=spec("layers", None))
    elif kind == "lightning":
        lp.update({name: spec("layers", "embed", "heads")
                   for name in ("wq", "wk", "wv", "wg")},
                  wo=spec("layers", "heads", "embed"),
                  out_norm_w=spec("layers", None))
        if cfg.qk_norm:
            lp["q_norm_w"] = spec("layers", None)
            lp["k_norm_w"] = spec("layers", None)
    elif kind in ATTN_SCOPE:
        lp.update(wq=spec("layers", "embed", "heads"),
                  wk=spec("layers", "embed", "kv_heads"),
                  wv=spec("layers", "embed", "kv_heads"),
                  wo=spec("layers", "heads", "embed"))
        if cfg.attn_output_gate and cfg.attn_gate_proj:
            lp["wg"] = spec("layers", "embed", "heads")
        if cfg.qk_norm:
            lp["q_norm_w"] = spec("layers", None)
            lp["k_norm_w"] = spec("layers", None)
    else:
        lp.update(w_qkvz=spec("layers", "embed", None),
                  w_ba=spec("layers", "embed", None),
                  conv_w=spec("layers", None, None),
                  A_log=spec("layers", None), dt_bias=spec("layers", None),
                  gdn_norm_w=spec("layers", None),
                  w_gdn_out=spec("layers", None, "embed"))
    if dense or not cfg.moe_num_experts:
        lp.update(w_in=spec("layers", "embed", "mlp"),
                  w_gate=spec("layers", "embed", "mlp"),
                  w_out=spec("layers", "mlp", "embed"))
        return lp
    lp.update(router_wg=spec("layers", "embed", None),
              w_in=spec("layers", "expert", "embed", "mlp"),
              w_gate=spec("layers", "expert", "embed", "mlp"),
              w_out=spec("layers", "expert", "mlp", "embed"))
    if cfg.moe_select_bias:
        lp["router_b"] = spec("layers", None)
    if cfg.moe_shared_intermediate_size:
        lp.update(shared_w_in=spec("layers", "embed", "mlp"),
                  shared_w_gate=spec("layers", "embed", "mlp"),
                  shared_w_out=spec("layers", "mlp", "embed"))
        if cfg.moe_shared_gate:
            lp["shared_gate_w"] = spec("layers", "embed", None)
    return lp


# ----------------------------------------------------------------- mixers

def full_qkv(cfg, h1, lp, rope, hold=None):
    """The gated attention layer's projections on its normed input
    [B, T, H]: (q, k, v, gate) with q/k normed per head and rotated
    (``rope``: q or k [B, T, heads, D] -> the same, rotated; the
    identity for a kind that is not rotated, ``rotates``). ``hold``:
    ``(name, y) -> y``, what the output [B, T, out] of the projection
    ``"q"``, ``"k"``, ``"v"`` or ``"g"`` goes through before it is cut
    into heads (serving's hold on its layout; None: nothing)."""
    from .transformer import _linear

    B, T, _ = h1.shape
    nh, kvh, hd, dt = cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.dtype
    hold = hold or (lambda name, y: y)
    q = hold("q", _linear(h1, lp["wq"], None, dt))
    gate = None
    if cfg.attn_output_gate and cfg.attn_gate_proj:
        gate = hold("g", _linear(h1, lp["wg"], None, dt)
                    ).reshape(B, T, nh, hd)
    elif cfg.attn_output_gate:
        q, gate = jnp.split(q.reshape(B, T, nh, 2 * hd), 2, axis=-1)
    q = q.reshape(B, T, nh, hd)
    k = hold("k", _linear(h1, lp["wk"], None, dt)).reshape(B, T, kvh, hd)
    v = hold("v", _linear(h1, lp["wv"], None, dt)).reshape(B, T, kvh, hd)
    if cfg.qk_norm:
        q = block_norm(cfg, q, lp["q_norm_w"])
        k = block_norm(cfg, k, lp["k_norm_w"])
    return rope(q), rope(k), v, gate


def rotates(cfg, kind: str) -> bool:
    return cfg.rope_kinds is None or kind in cfg.rope_kinds


def full_out(cfg, attn, gate, lp):
    """[B, T, heads, D] attention output -> the layer's output: under the
    sigmoid of its gate, through ``wo``."""
    from .transformer import _linear

    B, T = attn.shape[:2]
    if gate is not None:
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)
                                     ).astype(attn.dtype)
    return _linear(attn.reshape(B, T, -1), lp["wo"], None, cfg.dtype)


def _rescale(cfg, rank: int) -> float:
    """``latent_rescale``: what a latent is multiplied by behind its
    norm, sqrt(hidden / rank)."""
    return math.sqrt(cfg.hidden_size / rank)


def latent_cq(cfg, h1, lp, kind="latent"):
    """The query latent of a latent layer's normed input [B, T, H]:
    [B, T, q_rank], normed (and rescaled, ``latent_rescale``). A sparse
    layer's indexer reads it too."""
    from .transformer import _linear

    c_q = block_norm(cfg, _linear(h1, lp["w_qa"], None, cfg.dtype),
                     lp["q_a_norm_w"])
    if cfg.latent_rescale:
        c_q = c_q * jnp.asarray(
            _rescale(cfg, cfg.latent_sizes(kind).q_rank), cfg.dtype)
    return c_q


def latent_qkv(cfg, h1, lp, rope, kind="latent", c_q=None):
    """A latent layer's projections on its normed input [B, T, H]:
    ``(q_nope [B, T, heads, nope], q_rope [B, T, heads, rope], c
    [B, T, kv_rank], k_r [B, T, rope])`` at ``kind``'s sizes — ``c``
    normed, ``q_rope`` and ``k_r`` rotated (``rope``: [B, T, heads, rope]
    -> the same, rotated at the kind's base). ``(c, k_r)`` is what the
    cache holds. ``c_q``: the query latent where the caller has it."""
    from .transformer import _linear

    B, T, _ = h1.shape
    z, dt = cfg.latent_sizes(kind), cfg.dtype
    nh, dn, dr, kvr = z.heads, z.nope, z.rope, z.kv_rank
    if c_q is None:
        c_q = latent_cq(cfg, h1, lp, kind)
    q = _linear(c_q, lp["w_qb"], None, dt).reshape(B, T, nh, dn + dr)
    kva = _linear(h1, lp["w_kva"], None, dt)
    c = block_norm(cfg, kva[..., :kvr], lp["kv_a_norm_w"])
    if cfg.latent_rescale:
        c = c * jnp.asarray(_rescale(cfg, kvr), dt)
    k_r = rope(kva[..., None, kvr:])[:, :, 0]
    return q[..., :dn], rope(q[..., dn:]), c, k_r


def latent_expand(cfg, c, lp, kind="latent"):
    """K/V heads rebuilt from latents ``c`` [..., kv_rank]: ``(k_nope
    [..., heads, nope], v [..., heads, v])``."""
    from .transformer import _linear

    z, dt = cfg.latent_sizes(kind), cfg.dtype
    return (_linear(c, lp["w_kb"], None, dt).reshape(
                c.shape[:-1] + (z.heads, z.nope)),
            _linear(c, lp["w_vb"], None, dt).reshape(
                c.shape[:-1] + (z.heads, z.v)))


def latent_absorb(cfg, q_nope, lp, kind="latent"):
    """The absorbed form's query: ``q_nope`` [..., heads, nope] through
    each head's ``w_kb`` transposed -> [..., heads, kv_rank], to be
    multiplied with the latents themselves."""
    z = cfg.latent_sizes(kind)
    w = lp["w_kb"].astype(cfg.dtype).reshape(z.kv_rank, z.heads, z.nope)
    return jnp.einsum("...hd,chd->...hc", q_nope, w,
                      preferred_element_type=jnp.float32).astype(cfg.dtype)


def latent_unabsorb(cfg, o_lat, lp, kind="latent"):
    """The absorbed form's output: attended latents ``o_lat`` [...,
    heads, kv_rank] through each head's ``w_vb`` -> [..., heads, v]."""
    z = cfg.latent_sizes(kind)
    w = lp["w_vb"].astype(cfg.dtype).reshape(z.kv_rank, z.heads, z.v)
    return jnp.einsum("...hc,chd->...hd", o_lat.astype(cfg.dtype), w,
                      preferred_element_type=jnp.float32).astype(cfg.dtype)


def latent_scale(cfg, kind="latent") -> float:
    z = cfg.latent_sizes(kind)
    return cfg.attn_scale or 1.0 / math.sqrt(z.nope + z.rope)


def absorb_limit(cfg, kind: str) -> int:
    """The widest chunk of ``kind`` whose positions run absorbed, each a
    one-position row (ops/latent_attention.py "Where the two cross")."""
    from ..ops import latent_attention as la

    z = cfg.latent_sizes(kind)
    if not z.window:        # 171 at R 512: both published sets of widths
        return la.ABSORB_MAX_QUERIES
    return la.absorb_max_queries(z.kv_rank, z.nope, z.rope, z.v, z.window)


def latent_attend_dense(cfg, q_nope, q_rope, k_nope, k_r, v, kind="latent",
                        keep=None):
    """Causal attention of the expanded form over one whole sequence,
    plain XLA (no cache: training and the reference path; the flash
    kernels take one width for q·k and v): q [B, T, heads, ·], k_nope / v
    [B, T, heads, ·], k_r [B, T, rope] -> [B, T, heads, v]. A window
    kind sees its last ``window`` positions; ``keep`` [B, T, T]: the
    keys a sparse layer's queries selected."""
    T = q_nope.shape[1]
    s = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bthd,bsd->bhts", q_rope, k_r,
                      preferred_element_type=jnp.float32)) \
        * latent_scale(cfg, kind)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    window = cfg.latent_sizes(kind).window
    if window:
        causal &= jnp.arange(T)[:, None] - jnp.arange(T)[None, :] < window
    if keep is not None:
        causal = causal[None, None] & keep[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def latent_gate(cfg, h1, lp):
    """The head-wise output gate of a latent layer's normed input:
    [B, T, heads] before the sigmoid, or None (``attn_gate_headwise``)."""
    from .transformer import _linear

    if not cfg.attn_gate_headwise:
        return None
    return _linear(h1, lp["w_g"], None, cfg.dtype)


def latent_out(cfg, attn, lp, gate=None):
    """[B, T, heads, v] attention output -> the layer's: each head under
    the sigmoid of its ``gate`` [B, T, heads], through ``wo``."""
    from .transformer import _linear

    B, T = attn.shape[:2]
    if gate is not None:
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)
                                     ).astype(attn.dtype)[..., None]
    return _linear(attn.reshape(B, T, -1), lp["wo"], None, cfg.dtype)


# ---------------------------------------------------------------- indexer

def index_qk(cfg, h1, c_q, lp, rope):
    """A sparse layer's indexer projections (under ``index_proj``) on the
    layer's normed input ``h1`` [B, T, H] and query latent ``c_q``: ``(q
    [B, T, index heads, index dim], k [B, T, index dim], w [B, T, index
    heads] float32)`` — the key through a LayerNorm, the first
    ``qk_rope_head_dim`` numbers of query and key rotated (``rope``), the
    weights scaled by heads^-1/2 · dim^-1/2. ``k`` is what the index
    pool holds."""
    from .transformer import _linear

    B, T, _ = h1.shape
    hi, di, dr, dt = (cfg.index_n_heads, cfg.index_head_dim,
                      cfg.qk_rope_head_dim, cfg.dtype)
    q = _linear(c_q, lp["w_iq"], None, dt).reshape(B, T, hi, di)
    q = jnp.concatenate([rope(q[..., :dr]), q[..., dr:]], axis=-1)
    k = _linear(h1, lp["w_ik"], None, dt).astype(jnp.float32)
    k = k - jnp.mean(k, -1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(jnp.square(k), -1, keepdims=True)
                      + cfg.norm_eps)
    k = (k * lp["ik_norm_w"].astype(jnp.float32)
         + lp["ik_norm_b"].astype(jnp.float32)).astype(dt)
    k = jnp.concatenate([rope(k[..., None, :dr])[:, :, 0], k[..., dr:]],
                        axis=-1)
    w = _linear(h1, lp["w_iw"], None, dt).astype(jnp.float32) \
        * (hi ** -0.5 * di ** -0.5)
    return q, k, w


def index_scores(q, k, w):
    """The indexer's scores of queries against keys, plain XLA: q
    [..., Q, heads, D], k [..., S, D], w [..., Q, heads] -> [..., Q, S]
    float32, ``Σ_h w_h · relu(q_h · k_s)``."""
    s = jnp.einsum("...qhd,...sd->...qhs", q, k,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...qhs,...qh->...qs", jax.nn.relu(s), w,
                      preferred_element_type=jnp.float32,
                      precision=lax.Precision.HIGHEST)


SELECT_BLOCK = 256      # positions a block of ``index_select``'s compaction


def _order_keys(scores, live):
    """Each score's bits in an order-preserving unsigned form [..., S]
    uint32: a larger score is a larger key, and a key that is not
    ``live`` is 0, below every score's."""
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    top = jnp.uint32(1 << 31)
    return jnp.where(live, jnp.where(bits >= top, ~bits, bits | top),
                     jnp.uint32(0))


def _kth_largest(key, k: int, axes: int = 1):
    """The ``k``-th largest of the keys over the last ``axes`` axes of
    ``key`` (uint32) -> [...], found bit by bit from the top: 32 passes
    that each count the keys at or above a candidate (a compare and a
    sum, fused) and keep the bit while ``k`` of them are. Exact, and no
    sort. (Four bits a pass, 15 candidates at once, is no faster for one
    row: a pass is its compares, 66,560 of them a candidate.)"""
    over = tuple(range(-axes, 0))
    top = jnp.uint32(1 << 31)

    def refine(i, kth):
        cand = kth | (top >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= cand[(...,) + (None,) * axes], axis=over,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, kth)

    return lax.fori_loop(0, 32, refine,
                         jnp.zeros(key.shape[:key.ndim - axes], jnp.uint32))


def _running_count(mask):
    """mask [..., blocks, B] -> the count of set positions up to and
    including each, inside its block: one product with a triangle of
    ones on the matrix unit (0/1 in bfloat16, summed in float32: exact)."""
    B = mask.shape[-1]
    tri = (jnp.arange(B)[:, None] <= jnp.arange(B)[None, :])
    return jnp.einsum("...b,bc->...c", mask.astype(jnp.bfloat16),
                      tri.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def index_select(scores, live, topk: int):
    """The selection, exact: ``scores`` [..., S] float32 with ``live``
    [..., S] saying which keys a query may see -> ``(idx [..., k], n
    [...])``, ``k = min(topk, S)``: the ``n = min(live keys, k)`` live
    positions of largest score — of equal scores at the edge the earlier
    positions, ``lax.top_k``'s own rule, so the set is ``lax.top_k``'s —
    in ascending order of position (the gather behind it walks a table's
    blocks in order; no caller reads an order by score). ``idx[..., n:]``
    is 0 and is not to be read.

    No sort: ``lax.top_k`` at k = 2,048 sorts a row whole on the TPU,
    and a sort of one row costs what its dependent stages cost, whatever
    the rows (v5e: 0.64 ms for one row of 66,560 scores and 9.4 ms for
    128, where this takes 0.09 and 1.2). Instead (1) the ``k``-th
    largest live score is counted out (``_kth_largest``, shared with
    ``index_keep``); (2) every key above it is in, ``g < k`` of them,
    and of the keys equal to it the first ``k − g`` by position (a
    running count over the equal ones); (3) the kept positions are
    compacted in two levels over blocks of ``SELECT_BLOCK``: an output
    slot finds its block in the blocks' running totals ([k x blocks]
    compares), then its place in the block's running count ([k x
    block]), which a one-hot product brings to the slot — no scatter
    and no gather over the width."""
    S = scores.shape[-1]
    k, B = min(int(topk), S), SELECT_BLOCK
    kept = _kept_blocks(scores, live, k)
    nb = kept.shape[-2]
    run, each, before = _block_totals(kept)
    until = before + each                                     # [..., nb]
    n = until[..., -1]
    slot = jnp.arange(k, dtype=jnp.int32)
    past = until[..., None, :] <= slot[:, None]               # [..., k, nb]
    block = jnp.sum(past, axis=-1, dtype=jnp.int32)           # [..., k]
    rank = slot - jnp.max(jnp.where(past, until[..., None, :], 0), axis=-1)
    mine = block[..., None] == jnp.arange(nb, dtype=jnp.int32)
    run = jnp.einsum("...kn,...nb->...kb", mine.astype(jnp.bfloat16),
                     run.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)      # [..., k, B]
    place = jnp.sum(run <= rank[..., None].astype(jnp.float32), axis=-1,
                    dtype=jnp.int32)
    idx = jnp.where(slot < n[..., None], block * B + place, 0)
    return idx, n


def _block_totals(mask):
    """mask [..., nb, B] -> (the running count inside each block, each
    block's count, the count of the blocks before it)."""
    nb = mask.shape[-2]
    earlier = jnp.arange(nb)[None, :] < jnp.arange(nb)[:, None]
    run = _running_count(mask)
    each = run[..., -1].astype(jnp.int32)                     # [..., nb]
    return run, each, jnp.sum(jnp.where(earlier, each[..., None, :], 0),
                              axis=-1)


def _kept_blocks(scores, live, k: int):
    """``index_select``'s set as a mask over the keys in blocks of
    ``SELECT_BLOCK`` [..., nb, B]: every live key above the ``k``-th
    largest live score and, of the keys equal to it, the first by
    position, to ``k`` exactly."""
    S, B = scores.shape[-1], SELECT_BLOCK
    lead, nb = scores.shape[:-1], -(-S // B)
    key = jnp.pad(_order_keys(scores, live),
                  [(0, 0)] * len(lead) + [(0, nb * B - S)]
                  ).reshape(lead + (nb, B))
    kth = _kth_largest(key, k, axes=2)[..., None, None]
    above, equal = key > kth, key == kth
    # the edge: of the equal keys, the first (k - above) by position
    room = k - jnp.sum(above, axis=(-2, -1), dtype=jnp.int32)
    run, _, before = _block_totals(equal)
    return (above | (equal & (run + before[..., None].astype(jnp.float32)
                              <= room[..., None, None].astype(jnp.float32)))
            ) & (key > 0)


def index_kept(scores, live, topk: int):
    """``index_select``'s set as a mask [..., S] over the keys: ties at
    the edge go to the earlier positions, to ``topk`` exactly (where
    ``index_keep`` keeps every key equal to the edge)."""
    S = scores.shape[-1]
    kept = _kept_blocks(scores, live, min(int(topk), S))
    return kept.reshape(kept.shape[:-2] + (-1,))[..., :S]


def index_keep(scores, live, topk: int):
    """The selection as a mask [..., S] over the keys (what a chunk's
    expanded form attends under): a live key whose score is at least the
    ``topk``-th largest live one's; every live key while there are no
    more than ``topk``. Exact, and without a sort — ``lax.top_k`` at
    k = 2,048 sorts each row whole on the TPU, 0.08 ms a row of 66,560
    scores, 79% of a chunk forward: the ``topk``-th largest score is
    counted out instead (``_kth_largest``). Keys equal to it are all
    kept (``index_select`` keeps the earlier ones, to ``topk`` exactly)."""
    key = _order_keys(scores, live)
    kth = _kth_largest(key, min(int(topk), scores.shape[-1]))
    return live & (key >= kth[..., None])


def gdn_mixer(cfg, h1, lp, tail, state, n_tokens):
    """The Gated DeltaNet layer on its normed input [B, T, H], resumed
    from ``tail`` [B, K-1, CH] and ``state`` [B, HV, DK, DV] (float32).
    Positions at or beyond a row's ``n_tokens`` change neither. Returns
    (y [B, T, H], new tail, new state)."""
    from .transformer import _linear

    B, T, _ = h1.shape
    hk, hv, dk, dv, ch = gdn_dims(cfg)
    dt, f32 = cfg.dtype, jnp.float32
    with jax.named_scope("gdn_proj"):
        qkvz = _linear(h1, lp["w_qkvz"], None, dt)
        qkv, z = qkvz[..., :ch], qkvz[..., ch:]
        ba = _linear(h1, lp["w_ba"], None, dt).astype(f32)
        keep = (jnp.arange(T)[None, :] < n_tokens[:, None])[..., None]
        beta = jnp.where(keep, jax.nn.sigmoid(ba[..., :hv]), 0.0)
        g = jnp.where(keep, -jnp.exp(lp["A_log"].astype(f32))
                      * jax.nn.softplus(ba[..., hv:]
                                        + lp["dt_bias"].astype(f32)), 0.0)
    with jax.named_scope("gdn_conv"):
        qkv, tail = gd.causal_conv(qkv, tail, lp["conv_w"], n_tokens)
        qkv = jax.nn.silu(qkv)
        q = qkv[..., :hk * dk].reshape(B, T, hk, dk).astype(f32)
        k = qkv[..., hk * dk:2 * hk * dk].reshape(B, T, hk, dk).astype(f32)
        v = qkv[..., 2 * hk * dk:].reshape(B, T, hv, dv)
        l2 = lambda x: x * lax.rsqrt(                           # noqa: E731
            jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        q, k = l2(q) * dk ** -0.5, l2(k)
    with jax.named_scope("gdn_scan"):
        if T == 1:
            o, state = gd.gated_delta_step(q[:, 0], k[:, 0], v[:, 0],
                                           g[:, 0], beta[:, 0], state)
            o = o[:, None]
        else:
            o, state = gd.gated_delta_chunked(q, k, v, g, beta, state)
    with jax.named_scope("gdn_out"):
        o = rms(o, lp["gdn_norm_w"], cfg.norm_eps, False)
        o = o * jax.nn.silu(z.reshape(B, T, hv, dv).astype(f32))
        y = _linear(o.astype(dt).reshape(B, T, hv * dv), lp["w_gdn_out"],
                    None, dt)
    return y, tail, state


def lightning_mixer(cfg, h1, lp, rope, state, n_tokens, hold=None):
    """The lightning layer on its normed input [B, T, H], resumed from
    ``state`` [B, heads, D, D] (float32). Positions at or beyond a row's
    ``n_tokens`` leave it as it was. ``rope``: q or k [B, T, heads, D] ->
    the same, rotated (the identity where the kind is not rotated).
    ``hold``: as ``full_qkv``'s. Returns (y [B, T, H], new state)."""
    from .transformer import _linear

    B, T, _ = h1.shape
    nh, hd, dt = cfg.lightning_num_heads, cfg.lightning_head_dim, cfg.dtype
    hold = hold or (lambda name, y: y)
    with jax.named_scope("lightning_proj"):
        q, k, v, gate = (hold(name, _linear(h1, lp["w" + name], None, dt))
                         for name in "qkvg")
        q, k, v = (a.reshape(B, T, nh, hd) for a in (q, k, v))
        if cfg.qk_norm:
            q = block_norm(cfg, q, lp["q_norm_w"])
            k = block_norm(cfg, k, lp["k_norm_w"])
        q, k = rope(q), rope(k)
    with jax.named_scope("lightning_scan"):
        slope = la.slopes(nh)
        if T == 1:
            o, new = la.lightning_step(q[:, 0], k[:, 0], v[:, 0], slope,
                                       state)
            o = o[:, None]
            state = jnp.where((n_tokens > 0)[:, None, None, None], new,
                              state)
        else:
            o, state = la.lightning_chunked(q, k, v, slope, state, n_tokens)
        o = o * (cfg.attn_scale or hd ** -0.5)
    with jax.named_scope("lightning_out"):
        o = rms(o.reshape(B, T, nh * hd), lp["out_norm_w"], cfg.norm_eps,
                cfg.norm_zero_centered)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
        y = _linear(o.astype(dt), lp["wo"], None, dt)
    return y, state


# ---------------------------------------------------------- block selection

class BlockSizes(NamedTuple):
    """A block-sparse layer's sizes: a compressed key is the mean of
    ``kernel`` keys, one every ``stride``; a ``block`` of keys is
    attended whole; ``per`` kernels begin in a block and each spans
    ``ratio`` strides."""
    kernel: int
    stride: int
    block: int
    topk: int
    init: int
    window: int
    dense_len: int

    @property
    def per(self) -> int:
        return self.block // self.stride

    @property
    def ratio(self) -> int:
        return self.kernel // self.stride

    @property
    def table_width(self) -> int:
        """The most blocks a one-token row attends: a selecting query's
        initial blocks, its ``topk`` and the blocks its window reaches,
        or every block of a context short of ``dense_len``."""
        return max(self.init + self.topk + self.window // self.block + 1,
                   -(-self.dense_len // self.block))


def block_sizes(cfg) -> BlockSizes:
    return BlockSizes(cfg.block_kernel_size, cfg.block_kernel_stride,
                      cfg.block_select_size, cfg.block_topk,
                      cfg.block_init_blocks, cfg.block_window,
                      cfg.block_dense_len)


def block_compress(z: BlockSizes, k):
    """Compressed keys of a run of keys ``k`` [..., W, KH, D] that begins
    at a whole stride, ``W = stride · (n + ratio − 1)``: the ``n`` means
    [..., n, KH, D] of ``kernel`` consecutive keys, one a stride — each
    the mean of its ``ratio`` strides' means, in float32, returned in
    ``k``'s type."""
    lead, (W, KH, D) = k.shape[:-3], k.shape[-3:]
    n = W // z.stride - (z.ratio - 1)
    strides = jnp.mean(k.astype(jnp.float32).reshape(
        lead + (W // z.stride, z.stride, KH, D)), axis=-3)
    return (sum(strides[..., i:i + n, :, :] for i in range(z.ratio))
            / z.ratio).astype(k.dtype)


def block_visible(z: BlockSizes, t):
    """The kernels wholly in the causal past of position ``t``: those j
    with ``stride · j + kernel <= t + 1``."""
    return jnp.maximum((t + 1 - z.kernel) // z.stride + 1, 0)


def block_scores(cfg, q, kc, t):
    """Each block's score for the queries ``q`` [..., C, H, D] at
    positions ``t`` [..., C], against the compressed keys ``kc``
    [..., J, KH, D] (J a whole number of blocks' kernels, kernel j at
    row j): a head's softmax over the kernels it may see, summed over
    the K/V head's queries, and for a block the largest over the kernels
    that overlap it -> [..., C, KH, J / per] float32."""
    z = block_sizes(cfg)
    lead, (C, H, D) = q.shape[:-3], q.shape[-3:]
    J, KH = kc.shape[-3], kc.shape[-2]
    s = jnp.einsum("...ckgd,...jkd->...ckgj",
                   q.reshape(lead + (C, KH, H // KH, D)), kc,
                   preferred_element_type=jnp.float32) \
        * (cfg.attn_scale or 1.0 / math.sqrt(D))
    live = (jnp.arange(J) < block_visible(z, t)[..., None]
            )[..., None, None, :]
    s = jnp.where(live, s, -1e30)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = jnp.sum(p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30),
                axis=-2)                                  # [..., C, KH, J]
    blocks = p.shape[:-1] + (J // z.per, z.per)
    score = jnp.max(p.reshape(blocks), axis=-1)
    for i in range(1, z.ratio):     # the kernels that began a block before
        before = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(i, 0)])[..., :J]
        score = jnp.maximum(score, before.reshape(blocks)[..., 0])
    return score


def _block_parts(z: BlockSizes, t, blocks: int):
    """For queries at positions ``t`` [...] over ``blocks`` blocks:
    ``(forced, candidates, first_w, cur)`` — the blocks a selecting
    query attends whatever their score (the initial ones and those that
    hold one of its last ``window`` positions), those it chooses among
    (the rest of its past), its window's first block and its own."""
    b = jnp.arange(blocks)
    first_w = (jnp.maximum(t - z.window + 1, 0) // z.block)[..., None]
    cur = (t // z.block)[..., None]
    forced = ((b >= first_w) | (b < z.init)) & (b <= cur)
    return forced, (b >= z.init) & (b < first_w), first_w[..., 0], \
        cur[..., 0]


def block_keep(cfg, scores, t):
    """The blocks each query attends, as a mask: ``scores``
    [..., C, KH, blocks] (``block_scores``), ``t`` [..., C] ->
    [..., C, KH, blocks] bool. A query short of ``dense_len`` attends
    every block of its past; another its forced blocks and the ``topk``
    candidates of largest score, ties to the lower block."""
    z = block_sizes(cfg)
    forced, cand, _, cur = _block_parts(z, t, scores.shape[-1])
    kept = index_kept(scores, jnp.broadcast_to(
        cand[..., None, :], scores.shape), z.topk)
    causal = jnp.arange(scores.shape[-1]) <= cur[..., None]
    return jnp.where((t < z.dense_len)[..., None, None],
                     causal[..., None, :], forced[..., None, :] | kept)


def block_select(cfg, scores, t):
    """The same selection as a table, for one-token rows: ``scores``
    [N, KH, blocks], ``t`` [N] -> ``(table [N, KH, width] int32, n
    [N])``: the ``n`` blocks each row attends in ascending order, its
    own block last (``width``: ``BlockSizes.table_width``; entries past
    ``n`` are 0 and not to be read). The count is one for a row's K/V
    heads: it follows from the position alone."""
    z = block_sizes(cfg)
    _, cand, first_w, cur = _block_parts(z, t, scores.shape[-1])
    idx, n = index_select(scores, jnp.broadcast_to(
        cand[:, None, :], scores.shape), z.topk)        # [N, KH, k]
    n = n[:, :1]                                         # [N, 1]
    slot = jnp.arange(z.table_width, dtype=jnp.int32)
    chosen = jnp.take_along_axis(
        idx, jnp.broadcast_to(jnp.clip(slot - z.init, 0, idx.shape[-1] - 1),
                              idx.shape[:-1] + slot.shape), axis=-1)
    window = first_w[:, None] + slot - z.init - n        # [N, width]
    picked = jnp.where(slot < z.init, slot,
                       jnp.where(slot < z.init + n[..., None], chosen,
                                 window[:, None, :]))
    dense = t < z.dense_len
    count = jnp.where(dense, cur + 1, z.init + n[:, 0] + cur - first_w + 1)
    table = jnp.where(dense[:, None, None], slot, picked)
    return jnp.where(slot < count[:, None, None], table, 0).astype(
        jnp.int32), count.astype(jnp.int32)


def block_keep_dense(cfg, q, k):
    """A whole sequence's selection, plain XLA (no cache: training and
    the reference path): q [B, T, H, D], k [B, T, KH, D] -> the keys each
    query attends, [B, T, KH, T] bool (causal)."""
    z = block_sizes(cfg)
    T = q.shape[1]
    blocks = -(-T // z.block)
    at = jnp.arange(T)
    with jax.named_scope("block_compress"):
        width = z.stride * (blocks * z.per + z.ratio - 1)
        kc = block_compress(z, jnp.pad(
            k, ((0, 0), (0, width - T), (0, 0), (0, 0))))
    with jax.named_scope("block_score"):
        scores = block_scores(cfg, q, kc, at[None, :])
    with jax.named_scope("block_select"):
        keep = jnp.repeat(block_keep(cfg, scores, at[None, :]), z.block,
                          axis=-1)[..., :T]
    return keep & (at[None, :] <= at[:, None])[None, :, None, :]


def block_attend_dense(cfg, q, k, v, keep):
    """Attention of q [B, T, H, D] over k, v [B, T, KH, D] under ``keep``
    [B, T, KH, T], plain XLA -> [B, T, H, D]."""
    B, T, H, D = q.shape
    KH = k.shape[2]
    s = jnp.einsum("btkgd,bskd->bkgts", q.reshape(B, T, KH, H // KH, D), k,
                   preferred_element_type=jnp.float32) \
        * (cfg.attn_scale or 1.0 / math.sqrt(D))
    p = jax.nn.softmax(jnp.where(keep.transpose(0, 2, 1, 3)[:, :, None], s,
                                 -1e30), axis=-1)
    return jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32
                      ).astype(v.dtype).reshape(B, T, H, D)


# ----------------------------------------------------------------- period

def run_period(cfg, x, slots, mixers, kinds=None, dense=False, valid=None,
               max_rows=None, transform=None):
    """A run of layers on x [B, T, H] — one period (``kinds`` None: the
    pattern) or the lead layers (``dense``) — each position's norm, its
    mixer, the FFN and the residual adds. ``slots``: the weights, one
    tree a position. ``mixers[kind](h1, lp, i)`` -> the mixer's output
    for the i-th layer of its kind in the run: where the cache lives is
    the caller's (training keeps none, serving paged pools and state
    slots). Returns (x, summed aux loss)."""
    scope = jax.named_scope
    aux = jnp.zeros((), jnp.float32)
    kinds = cfg.layer_pattern if kinds is None else kinds
    seen = {kind: 0 for kind in KINDS}
    scaled = (lambda y: y) if cfg.residual_scale == 1.0 else (
        lambda y: y * jnp.asarray(cfg.residual_scale, y.dtype))
    for kind, lp in zip(kinds, slots):
        if transform is not None:
            lp = transform(lp)
        with scope("attn_norm"):
            h1 = block_norm(cfg, x, lp["attn_norm_w"])
        with scope(ATTN_SCOPE[kind]) if kind in ATTN_SCOPE \
                else contextlib.nullcontext():
            y = mixers[kind](h1, lp, seen[kind])
        seen[kind] += 1
        with scope("mlp"):      # norms, FFN and the residual adds
            if cfg.sandwich_norm:
                y = block_norm(cfg, y, lp["post_attn_norm_w"])
            x = x + scaled(y)
            h2 = block_norm(cfg, x, lp["mlp_norm_w"])
            if dense or not cfg.moe_num_experts:
                f, a = dense_ffn(cfg, h2, lp), 0.0
            else:
                f, a = moe_ffn(cfg, h2, lp, valid=valid, max_rows=max_rows)
            if cfg.sandwich_norm:
                f = block_norm(cfg, f, lp["post_mlp_norm_w"])
            x = x + scaled(f)
        aux = aux + a
    return x, aux


def lead_slots(cfg, params):
    """The lead layers' trees, in order (each is stacked over one
    "period", like a slot: the leading dim is dropped)."""
    return tuple(jax.tree.map(lambda a: a[0], params["layers"][f"lead{j}"])
                 for j in range(len(cfg.lead_layers)))


# -------------------------------------------------------------------- FFN

def dense_ffn(cfg, h2, lp):
    """The dense gated MLP on its normed input [B, T, H]: a lead layer's,
    and every layer's of a model without experts."""
    from .transformer import _linear

    dt = cfg.dtype
    with jax.named_scope("dense_mlp"):
        return _linear(jax.nn.silu(_linear(h2, lp["w_gate"], None, dt))
                       * _linear(h2, lp["w_in"], None, dt),
                       lp["w_out"], None, dt)


def moe_ffn(cfg, h2, lp, valid=None, max_rows=None):
    """The sparse FFN on its normed input [B, T, H]: the held experts'
    part of the top-k sum plus the shared expert. ``valid`` [B, T]:
    padding reaches no expert; ``max_rows`` bounds the valid rows.
    Returns (y, aux_loss)."""
    from ..moe.grouped import dropless_moe_mlp
    from .transformer import _linear

    B, T, H = h2.shape
    dt = cfg.dtype
    rows = h2.reshape(B * T, H)
    flat_valid = None if valid is None else valid.reshape(B * T)
    with jax.named_scope("router"):
        logits = rows.astype(jnp.float32) \
            @ lp["router_wg"].astype(jnp.float32)
    with jax.named_scope("experts"):
        y, l_aux = dropless_moe_mlp(
            rows, logits, lp["w_in"], lp["w_out"], lp["w_gate"],
            activation="silu", dtype=dt, top_k=cfg.moe_top_k,
            renormalize=cfg.moe_norm_topk, held=cfg.moe_held_experts,
            valid=flat_valid, max_rows=max_rows,
            score_func=cfg.moe_score_func, select_bias=lp.get("router_b"),
            route_scale=cfg.moe_route_scale)
    if cfg.moe_shared_intermediate_size:
        with jax.named_scope("shared_expert"):
            s = jax.nn.silu(_linear(rows, lp["shared_w_gate"], None, dt)) \
                * _linear(rows, lp["shared_w_in"], None, dt)
            s = _linear(s, lp["shared_w_out"], None, dt)
            if cfg.moe_shared_gate:
                gate = jax.nn.sigmoid(_linear(rows, lp["shared_gate_w"],
                                              None, dt).astype(jnp.float32))
                s = s * gate.astype(dt)
            y = y + s
    return y.reshape(B, T, H), l_aux
