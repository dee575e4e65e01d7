"""``"full"``: gated softmax attention — a stated head size, per-head
q/k RMSNorm, an output gate read off a ``wq`` twice as wide (``[q | g]``
within each head) or off a projection of its own (``attn_gate_proj``:
``wg``), partial rotary or none (``rope_kinds``), biases on q, k, v and on the output where the model has
them (``qkv_bias``, ``o_bias``). ``"window"``: the same layer over the
last ``sliding_window`` positions.

``diff_attn``: the differential form (Ye et al. 2024, as SambaY's
decoder takes it). Heads go in adjacent pairs, ``q -> (q1, q2)``,
``k -> (k1, k2)``, ``v -> (v1, v2)``; ``a1 = softmax(q1 k1ᵀ s)[v1 | v2]``,
``a2 = softmax(q2 k2ᵀ s)[v1 | v2]``; the pair's output is ``(1 − λ_init)
· RMSNorm(a1 − λ a2)`` with ``λ = exp(λq1·λk1) − exp(λq2·λk2) + λ_init``
and ``λ_init = 0.8 − 0.6 exp(−0.3 l)`` at the model's layer ``l``. It
runs **exactly** on the attention that is there, at twice the head size
over half the K/V heads: a K/V pair's ``[k1 | k2]`` and ``[v1 | v2]`` are
one head of ``2D`` (its two heads as they lie side by side), the queries
``(q1 | 0)`` and ``(0 | q2)`` — the zeros add nothing to a score, and a
row's output is its softmax over both values (``diff_queries``,
``diff_combine``; ``cfg.paged_heads()`` is the geometry the pool and the
kernel then see). The softmax scale is the model's stated one
(``attn_scale``: the kernel's default would follow the doubled width).

In serving the layer's K/V is paged, one pool a group of layers whose
K/V has one lifetime (``cfg.kv_groups()``): ``k`` / ``v``
[L_0, NB_0, KH, bs, D] the first group's, ``k1`` / ``v1`` the second's —
the layers of a window, whose blocks behind it the manager hands back
while the sequence lives. ``layer`` counts a group's own layers, and a
group's write plan and its kernel's walk read its own table.

In a model of several runs of layers the whole-context layer hands its
K and V on to the layers that read them (``"cross"``, cross.py): in
serving they are its pool rows, without a cache the pair itself
(``Fwd.carry["kv"]``). Where a serving forward's rows leave behind the
last layer that writes (``Fwd.exit``), that layer writes its K/V from
every position and attends from each row's last alone.

Scopes (docs/OBSERVABILITY.md): ``full_attn`` / ``window_attn`` round the
layer's ``qkv``, ``kv_write``, ``attend`` and ``attn_out``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...parallel.sharding import spec
from ..transformer import _attention, _linear
from .base import Mixer, block_norm, held, rms

scope = jax.named_scope

#: the differential form's leaves beside the projections: the four
#: vectors ``λ`` is made of and the gain of the norm over a pair's output
DIFF_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def check(cfg):
    if cfg.diff_attn and (cfg.num_heads % 2 or cfg.kv_heads % 2
                          or cfg.attn_scale is None
                          or cfg.attn_output_gate or cfg.qk_norm
                          or cfg.layer_runs is None):
        raise ValueError(
            "diff_attn takes heads and K/V heads in pairs, a stated "
            "attn_scale (the kernel's default follows the doubled head), "
            "no output gate and no q/k norm, in a model of layer_runs "
            "(λ_init follows the layer's depth)")


def init_diff(cfg, w, gain):
    """The differential form's own leaves: ``λ``'s vectors N(0, 0.1), the
    pair norm's gain 1."""
    hd = cfg.head_dim
    return dict({name: w((hd,), 0.1) for name in DIFF_LAMBDAS},
                subln_w=jnp.ones((w.periods, 2 * hd), jnp.float32))


def biased(cfg, names):
    """Of the projections ``names`` (``wq`` ...), those that carry a
    bias in this model."""
    return [name for name in names
            if (cfg.resolved_o_bias if name == "wo" else cfg.qkv_bias)]


def init_biases(cfg, w, names):
    """Zero biases of the projections ``names``, where the model has
    them."""
    widths = dict(wq=cfg.num_heads * cfg.head_dim,
                  wk=cfg.kv_heads * cfg.head_dim,
                  wv=cfg.kv_heads * cfg.head_dim, wo=cfg.hidden_size)
    return {name + "_b": jnp.zeros((w.periods, widths[name]), jnp.float32)
            for name in biased(cfg, names)}


def init(cfg, w, gain):
    h, hd, nh, kvh = (cfg.hidden_size, cfg.head_dim, cfg.num_heads,
                      cfg.kv_heads)
    own_gate = cfg.attn_output_gate and cfg.attn_gate_proj
    q_out = nh * hd * (2 if cfg.attn_output_gate and not own_gate else 1)
    lp = dict(wq=w((h, q_out)), wk=w((h, kvh * hd)), wv=w((h, kvh * hd)),
              wo=w((nh * hd, h), w.out_std))
    if own_gate:
        lp["wg"] = w((h, nh * hd))
    if cfg.qk_norm:
        lp["q_norm_w"] = gain(hd)
        lp["k_norm_w"] = gain(hd)
    lp.update(init_biases(cfg, w, ("wq", "wk", "wv", "wo")))
    if cfg.diff_attn:
        lp.update(init_diff(cfg, w, gain))
    return lp


def spec_extras(cfg, names):
    """Logical axes of ``init_biases``' and ``init_diff``'s leaves."""
    axes = dict(wq="heads", wk="kv_heads", wv="kv_heads", wo="embed")
    lp = {name + "_b": spec("layers", axes[name])
          for name in biased(cfg, names)}
    if cfg.diff_attn:
        lp.update({name: spec("layers", None)
                   for name in DIFF_LAMBDAS + ("subln_w",)})
    return lp


def specs(cfg):
    lp = dict(wq=spec("layers", "embed", "heads"),
              wk=spec("layers", "embed", "kv_heads"),
              wv=spec("layers", "embed", "kv_heads"),
              wo=spec("layers", "heads", "embed"))
    if cfg.attn_output_gate and cfg.attn_gate_proj:
        lp["wg"] = spec("layers", "embed", "heads")
    if cfg.qk_norm:
        lp["q_norm_w"] = spec("layers", None)
        lp["k_norm_w"] = spec("layers", None)
    lp.update(spec_extras(cfg, ("wq", "wk", "wv", "wo")))
    return lp


def pool(cfg, block_size: int):
    _, kvh, hd = cfg.paged_heads()
    block = (kvh, block_size, hd)
    return {"k": block, "v": block}


def diff_queries(q):
    """The differential form's queries [B, T, H, D], heads in adjacent
    pairs ``(q1, q2)``, as the attention over the joined K/V heads takes
    them: [B, T, H, 2D], ``(q1 | 0)`` then ``(0 | q2)``."""
    B, T, H, D = q.shape
    pairs = q.reshape(B, T, H // 2, 2, D)
    zero = jnp.zeros_like(pairs[..., 0, :])
    return jnp.stack(
        [jnp.concatenate([pairs[..., 0, :], zero], -1),
         jnp.concatenate([zero, pairs[..., 1, :]], -1)],
        axis=3).reshape(B, T, H, 2 * D)


def diff_keys(kv):
    """K or V [B, T, KH, D] -> [B, T, KH / 2, 2D]: a pair's two heads as
    they lie, side by side."""
    B, T, KH, D = kv.shape
    return kv.reshape(B, T, KH // 2, 2 * D)


def diff_combine(cfg, attn, lp, depth):
    """The attention's rows [B, T, H, 2D] — ``a1`` then ``a2`` of each
    pair — to the pairs' outputs [B, T, H / 2, 2D]: ``(1 − λ_init) ·
    RMSNorm(a1 − λ a2)``, in float32. ``depth``: the layer's index in the
    model (an int or a traced one)."""
    f32 = jnp.float32
    B, T, H, W = attn.shape
    a = attn.reshape(B, T, H // 2, 2, W).astype(f32)
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, f32))
    lq1, lk1, lq2, lk2 = (lp[name].astype(f32) for name in DIFF_LAMBDAS)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
        + lam_init
    out = rms(a[..., 0, :] - lam * a[..., 1, :], lp["subln_w"],
              cfg.norm_eps, False)
    return (out * (1.0 - lam_init)).astype(attn.dtype)


def full_qkv(cfg, h1, lp, rope, hold=None, q_of=None):
    """The gated attention layer's projections on its normed input
    [B, T, H]: (q, k, v, gate) with q/k normed per head and rotated
    (``rope``: q or k [B, T, heads, D] -> the same, rotated; the
    identity for a kind that is not rotated, ``Fwd.rope``). ``hold``:
    ``(name, y) -> y``, what the output [B, T, out] of the projection
    ``"q"``, ``"k"``, ``"v"`` or ``"g"`` goes through before it is cut
    into heads (serving's hold on its layout; None: nothing). ``q_of``:
    [B, T, H] -> the positions the queries (and their gate) are taken
    at, where that is not every one (``Fwd.narrow``). Under
    ``diff_attn`` all three come back as the attention over joined pairs
    takes them (``diff_queries``, ``diff_keys``)."""
    B, T, _ = h1.shape
    nh, kvh, hd, dt = cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.dtype
    hold = hold or (lambda name, y: y)
    hq = h1 if q_of is None else q_of(h1)
    Tq = hq.shape[1]
    q = hold("q", _linear(hq, lp["wq"], lp.get("wq_b"), dt))
    gate = None
    if cfg.attn_output_gate and cfg.attn_gate_proj:
        gate = hold("g", _linear(hq, lp["wg"], None, dt)
                    ).reshape(B, Tq, nh, hd)
    elif cfg.attn_output_gate:
        q, gate = jnp.split(q.reshape(B, Tq, nh, 2 * hd), 2, axis=-1)
    q = q.reshape(B, Tq, nh, hd)
    k = hold("k", _linear(h1, lp["wk"], lp.get("wk_b"), dt)
             ).reshape(B, T, kvh, hd)
    v = hold("v", _linear(h1, lp["wv"], lp.get("wv_b"), dt)
             ).reshape(B, T, kvh, hd)
    if cfg.qk_norm:
        q = block_norm(cfg, q, lp["q_norm_w"])
        k = block_norm(cfg, k, lp["k_norm_w"])
    if cfg.diff_attn:
        return diff_queries(rope(q)), diff_keys(rope(k)), diff_keys(v), gate
    return rope(q), rope(k), v, gate


def full_out(cfg, attn, gate, lp, depth=None):
    """[B, T, heads, D] attention output -> the layer's output: under the
    sigmoid of its gate, through ``wo``. Under ``diff_attn`` the pairs'
    rows are combined first (``diff_combine``, at the layer's
    ``depth``)."""
    B, T = attn.shape[:2]
    if cfg.diff_attn:
        attn = diff_combine(cfg, attn, lp, depth)
    if gate is not None:
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)
                                     ).astype(attn.dtype)
    return _linear(attn.reshape(B, T, -1), lp["wo"], lp.get("wo_b"),
                   cfg.dtype)


def write_kv(cfg, fwd, kind: str, k, v, layer):
    """A layer's K and V [N, C, KH, D] into its group's pools (under
    ``kv_write``)."""
    _, kvh, hd = cfg.paged_heads()
    for name, rows in (("k", k), ("v", v)):
        fwd.write(kind, name, rows.reshape(-1, kvh, hd), layer)


def _describe(kind: str, name: str, windowed: bool) -> Mixer:
    def window(cfg):
        return cfg.sliding_window if windowed else 0

    def reference(cfg, fwd):
        turn = fwd.rope(cfg, kind)

        def mixer(h1, lp, i):
            with scope("qkv"):
                q, k, v, gate = full_qkv(cfg, h1, lp, turn)
                if "kv" in fwd.hand:
                    fwd.carry["kv"] = (k, v)
            with scope("attend"):
                attn = _attention(q, k, v, cfg, causal=True,
                                  window=window(cfg))
            with scope("attn_out"):
                return full_out(cfg, attn, gate, lp, fwd.depth(kind, i))
        return mixer

    def paged(cfg, fwd):
        turn = fwd.rope(cfg, kind)
        pools, table = fwd.pools, fwd.tables[fwd.group_of[kind]]
        k_name, v_name = fwd.leaf(kind, "k"), fwd.leaf(kind, "v")

        # the rows that attend: every position, or at the exit each row's
        # last alone (the K/V is written from every one either way)
        at = fwd if fwd.exit is None else fwd.exit
        q_of = None if fwd.exit is None else fwd.exit.narrow

        def mixer(h1, lp, i):
            layer = fwd.layer(kind, i)
            # held to rows in the narrow buckets (``base.held``): left
            # free, a one-token step copies each weight transposed first
            with scope("qkv"):
                q, k, v, gate = full_qkv(cfg, h1, lp, turn, held(cfg), q_of)
            with scope("kv_write"):
                write_kv(cfg, fwd, kind, k, v, layer)
            with scope("attend"):
                attn = fwd.attend(
                    q, {"k": pools[k_name], "v": pools[v_name],
                        **({"k_scale": pools["k_scale"],
                            "v_scale": pools["v_scale"]}
                           if fwd.quant else {})},
                    layer, table, at.start_pos, at.n_tokens, None,
                    window=window(cfg))
            with scope("attn_out"):
                return full_out(cfg, attn, gate, lp, fwd.depth(kind, i))
        return mixer

    return Mixer(init=init, specs=specs, reference=reference, paged=paged,
                 scope=name, check=check, pool=pool, windowed=windowed,
                 paged_walk=True, hands=() if windowed else ("kv",),
                 exits=True, holds=True)


FULL = _describe("full", "full_attn", False)
WINDOW = _describe("window", "window_attn", True)
