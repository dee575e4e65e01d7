"""``"lightning"``: linear attention under one scalar decay a head
(ops/lightning_attention.py): q/k RMSNorm a head and rotary, a float32
state ``[heads, D, D]`` a sequence in place of any per-token cache, an
RMSNorm over the joined heads' output and a sigmoid gate of the layer's
input in front of ``wo``.

Its cache is the leaf ``lightning`` [L_lgt, slots + 1, heads, D, D]
float32, one slot a sequence, by a Gated DeltaNet layer's rules
(``gdn.py``): a row at ``start_pos`` 0 starts from zero, positions at or
beyond ``n_tokens`` leave the state as it was. Serving holds its
projections' outputs to the layout their matmul writes (``base.held``),
so that the weight is multiplied where it lies in its stack.

Scopes (docs/OBSERVABILITY.md): ``lightning_attn`` ⊃ ``lightning_proj``,
``lightning_scan``, ``lightning_out``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops import lightning_attention as la
from ...parallel.sharding import spec
from ..transformer import _linear
from .base import Mixer, block_norm, held, rms

KIND = "lightning"
scope = jax.named_scope


def check(cfg):
    if cfg.lightning_num_heads <= 0 \
            or cfg.lightning_head_dim != cfg.head_dim:
        raise ValueError(
            "\"lightning\" layers need lightning_num_heads > 0 "
            "and the model's head size (one rotary table, one "
            "q/k norm width)")


def init(cfg, w, gain):
    h = cfg.hidden_size
    width = cfg.lightning_num_heads * cfg.lightning_head_dim
    lp = dict(wq=w((h, width)), wk=w((h, width)), wv=w((h, width)),
              wg=w((h, width)), wo=w((width, h), w.out_std),
              out_norm_w=jnp.ones((w.periods, width), jnp.float32))
    if cfg.qk_norm:
        lp["q_norm_w"] = gain(cfg.lightning_head_dim)
        lp["k_norm_w"] = gain(cfg.lightning_head_dim)
    return lp


def specs(cfg):
    lp = dict({name: spec("layers", "embed", "heads")
               for name in ("wq", "wk", "wv", "wg")},
              wo=spec("layers", "heads", "embed"),
              out_norm_w=spec("layers", None))
    if cfg.qk_norm:
        lp["q_norm_w"] = spec("layers", None)
        lp["k_norm_w"] = spec("layers", None)
    return lp


def state(cfg, slots: int):
    """The layer's state, as wide as its head on both sides."""
    nh, hd = cfg.lightning_num_heads, cfg.lightning_head_dim
    return {KIND: ((cfg.layers_of(KIND), slots, nh, hd, hd), jnp.float32)}


def lightning_mixer(cfg, h1, lp, rope, state, n_tokens, hold=None):
    """The lightning layer on its normed input [B, T, H], resumed from
    ``state`` [B, heads, D, D] (float32). Positions at or beyond a row's
    ``n_tokens`` leave it as it was. ``rope``: q or k [B, T, heads, D] ->
    the same, rotated (the identity where the kind is not rotated).
    ``hold``: as ``attention.full_qkv``'s. Returns (y [B, T, H], new
    state)."""
    B, T, _ = h1.shape
    nh, hd, dt = cfg.lightning_num_heads, cfg.lightning_head_dim, cfg.dtype
    hold = hold or (lambda name, y: y)
    with scope("lightning_proj"):
        q, k, v, gate = (hold(name, _linear(h1, lp["w" + name], None, dt))
                         for name in "qkvg")
        q, k, v = (a.reshape(B, T, nh, hd) for a in (q, k, v))
        if cfg.qk_norm:
            q = block_norm(cfg, q, lp["q_norm_w"])
            k = block_norm(cfg, k, lp["k_norm_w"])
        q, k = rope(q), rope(k)
    with scope("lightning_scan"):
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
    with scope("lightning_out"):
        o = rms(o.reshape(B, T, nh * hd), lp["out_norm_w"], cfg.norm_eps,
                cfg.norm_zero_centered)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
        y = _linear(o.astype(dt), lp["wo"], None, dt)
    return y, state


def reference(cfg, fwd):
    B = fwd.shape[0]
    (shape, dt), = state(cfg, B).values()
    zero = jnp.zeros((B,) + shape[2:], dt)
    turn = fwd.rope(cfg, KIND)

    def mixer(h1, lp, _):
        with scope("lightning_attn"):
            return lightning_mixer(cfg, h1, lp, turn, zero, fwd.n_tokens)[0]
    return mixer


def paged(cfg, fwd):
    pools, slots, fresh = fwd.pools, fwd.state_slots, fwd.fresh
    turn = fwd.rope(cfg, KIND)

    def mixer(h1, lp, i):
        layer = fwd.layer(KIND, i)
        with scope("lightning_attn"):
            state = pools[KIND][layer, slots]
            state = jnp.where(fresh[:, None, None, None], 0, state)
            y, state = lightning_mixer(
                cfg, h1, lp, turn, state, fwd.n_tokens,
                hold=held(cfg, always="qkv"))
            pools[KIND] = pools[KIND].at[layer, slots].set(state)
            return y
    return mixer


def count(cfg, staged, bucket_chunk: int, block_size: int):
    """``lightning_rows``: the positions through the recurrence."""
    return {"lightning_rows": sum(len(toks) for _, toks in staged)}


LIGHTNING = Mixer(init=init, specs=specs, reference=reference, paged=paged,
                  check=check, state=state, totals=("lightning_rows",),
                  record=("lightning_rows",), count=count, holds=True)
