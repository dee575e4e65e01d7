"""``"full"``: gated softmax attention — a stated head size, per-head
q/k RMSNorm, an output gate read off a ``wq`` twice as wide (``[q | g]``
within each head) or off a projection of its own (``attn_gate_proj``:
``wg``), partial rotary or none (``rope_kinds``). ``"window"``: the same
layer over the last ``sliding_window`` positions.

In serving the layer's K/V is paged, one pool a group of layers whose
K/V has one lifetime (``cfg.kv_groups()``): ``k`` / ``v``
[L_0, NB_0, KH, bs, D] the first group's, ``k1`` / ``v1`` the second's —
the layers of a window, whose blocks behind it the manager hands back
while the sequence lives. ``layer`` counts a group's own layers, and a
group's write plan and its kernel's walk read its own table.

Scopes (docs/OBSERVABILITY.md): ``full_attn`` / ``window_attn`` round the
layer's ``qkv``, ``kv_write``, ``attend`` and ``attn_out``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...parallel.sharding import spec
from ..transformer import _attention, _linear
from .base import Mixer, block_norm

scope = jax.named_scope


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
    return lp


def pool(cfg, block_size: int):
    block = (cfg.kv_heads, block_size, cfg.head_dim)
    return {"k": block, "v": block}


def full_qkv(cfg, h1, lp, rope, hold=None):
    """The gated attention layer's projections on its normed input
    [B, T, H]: (q, k, v, gate) with q/k normed per head and rotated
    (``rope``: q or k [B, T, heads, D] -> the same, rotated; the
    identity for a kind that is not rotated, ``Fwd.rope``). ``hold``:
    ``(name, y) -> y``, what the output [B, T, out] of the projection
    ``"q"``, ``"k"``, ``"v"`` or ``"g"`` goes through before it is cut
    into heads (serving's hold on its layout; None: nothing)."""
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


def full_out(cfg, attn, gate, lp):
    """[B, T, heads, D] attention output -> the layer's output: under the
    sigmoid of its gate, through ``wo``."""
    B, T = attn.shape[:2]
    if gate is not None:
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)
                                     ).astype(attn.dtype)
    return _linear(attn.reshape(B, T, -1), lp["wo"], None, cfg.dtype)


def write_kv(cfg, fwd, kind: str, k, v, layer):
    """A layer's K and V [N, C, KH, D] into its group's pools (under
    ``kv_write``)."""
    for name, rows in (("k", k), ("v", v)):
        fwd.write(kind, name, rows.reshape(-1, cfg.kv_heads, cfg.head_dim),
                  layer)


def _describe(kind: str, name: str, windowed: bool) -> Mixer:
    def window(cfg):
        return cfg.sliding_window if windowed else 0

    def reference(cfg, fwd):
        turn = fwd.rope(cfg, kind)

        def mixer(h1, lp, _):
            with scope("qkv"):
                q, k, v, gate = full_qkv(cfg, h1, lp, turn)
            with scope("attend"):
                attn = _attention(q, k, v, cfg, causal=True,
                                  window=window(cfg))
            with scope("attn_out"):
                return full_out(cfg, attn, gate, lp)
        return mixer

    def paged(cfg, fwd):
        turn = fwd.rope(cfg, kind)
        pools, table = fwd.pools, fwd.tables[fwd.group_of[kind]]
        k_name, v_name = fwd.leaf(kind, "k"), fwd.leaf(kind, "v")

        def mixer(h1, lp, i):
            layer = fwd.layer(kind, i)
            # no hold: the queries go to a kernel, whose operands are
            # laid out as they are given
            with scope("qkv"):
                q, k, v, gate = full_qkv(cfg, h1, lp, turn)
            with scope("kv_write"):
                write_kv(cfg, fwd, kind, k, v, layer)
            with scope("attend"):
                attn = fwd.attend(
                    q, {"k": pools[k_name], "v": pools[v_name],
                        **({"k_scale": pools["k_scale"],
                            "v_scale": pools["v_scale"]}
                           if fwd.quant else {})},
                    layer, table, fwd.start_pos, fwd.n_tokens, None,
                    window=window(cfg))
            with scope("attn_out"):
                return full_out(cfg, attn, gate, lp)
        return mixer

    return Mixer(init=init, specs=specs, reference=reference, paged=paged,
                 scope=name, pool=pool, windowed=windowed, paged_walk=True)


FULL = _describe("full", "full_attn", False)
WINDOW = _describe("window", "window_attn", True)
