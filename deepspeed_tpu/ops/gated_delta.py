"""The gated delta rule (Gated DeltaNet; Yang, Kautz & Hatamizadeh 2024) —
the recurrence of a linear-attention layer whose per-sequence state is a
fixed-size matrix instead of per-token K/V.

Per value head, with a state ``S`` [DK, DV] kept in float32::

    S   <- exp(g_t) * S                      (g_t <= 0: the gate's decay)
    d_t  = beta_t * (v_t - S^T k_t)          (the delta: what k_t misses)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

Two forms, both taking and returning the state:

- ``gated_delta_step``: one token a sequence (a decode step).
- ``gated_delta_chunked``: a chunk of tokens a sequence (prefill). Tokens
  are taken ``chunk`` (64) at a time; inside one such tile the
  recurrence is solved in closed form with matmuls (the WY
  representation: the tile's deltas are ``(I + M)^-1`` applied to the
  gated values, ``M`` the strictly lower part of ``(beta k) k^T`` under
  the decay), tiles are linked by a ``lax.scan`` that carries ``S``. The
  tile's work is done inside the scan body, so what is live at once is
  one tile's worth whatever the chunk's length.

A position with ``g = 0`` and ``beta = 0`` leaves the state exactly as it
was (decay 1, delta 0): that is how callers mask padding.

Plain XLA: the tile solve is the inverse of a unit lower triangular
matrix by halves (block forward substitution in matmuls);
``precision`` is that of the float32 matmuls in here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

TILE = 64


def _expand(x, heads: int):
    """[.., HK, D] -> [.., HV, D]: key head j serves value heads
    j*r .. j*r + r - 1."""
    r = heads // x.shape[-2]
    return x if r == 1 else jnp.repeat(x, r, axis=-2)


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row. q, k [N, HK, DK] (k L2-normalised, q normalised
    and scaled by the caller); v [N, HV, DV]; g, beta [N, HV]; state
    [N, HV, DK, DV] float32. Returns (o [N, HV, DV] float32, state)."""
    f32 = jnp.float32
    hv = v.shape[-2]
    q, k = _expand(q.astype(f32), hv), _expand(k.astype(f32), hv)
    v = v.astype(f32)
    state = state * jnp.exp(g.astype(f32))[..., None, None]
    kv = jnp.sum(state * k[..., :, None], axis=-2)            # S^T k
    delta = beta.astype(f32)[..., None] * (v - kv)
    state = state + k[..., :, None] * delta[..., None, :]
    o = jnp.sum(state * q[..., :, None], axis=-2)             # S^T q
    return o, state


def _unit_lower_inverse(m, precision):
    """(I + M)^-1 for strictly lower triangular M [.., c, c], by halves:
    with I + M = [[A, 0], [C, D]], the inverse is [[A^-1, 0],
    [-D^-1 C A^-1, D^-1]] — block forward substitution, as stable as the
    token-by-token one, in matmuls. Blocks of 8 or fewer are inverted as
    the product (I + N)(I + N^2)(I + N^4) with N = -M (N^8 = 0): at that
    size its terms cannot grow. (The same product over a whole tile of 64
    sums terms of 1e16 to an answer of 1 when the keys of a sequence
    point the same way, and float32 loses it: measured as non-finite
    logits on the chip.)"""
    c = m.shape[-1]
    mm = lambda a, b: jnp.matmul(a, b, precision=precision)    # noqa: E731
    if c <= 8:
        eye = jnp.eye(c, dtype=m.dtype)
        power = -m
        out = eye + power
        span = 2
        while span < c:
            power = mm(power, power)
            out = mm(out, eye + power)
            span *= 2
        return out
    h = c // 2
    a = _unit_lower_inverse(m[..., :h, :h], precision)
    d = _unit_lower_inverse(m[..., h:, h:], precision)
    low = -mm(mm(d, m[..., h:, :h]), a)
    top = jnp.concatenate([a, jnp.zeros_like(m[..., :h, h:])], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([low, d], axis=-1)],
                           axis=-2)


def _tile(q, k, v, g, beta, state, precision):
    """One tile of c tokens. q, k [N, c, HV, DK]; v [N, c, HV, DV];
    g, beta [N, c, HV]; state [N, HV, DK, DV]. All float32."""
    ein = lambda s, *a: jnp.einsum(s, *a, precision=precision)  # noqa: E731
    c = q.shape[1]
    gc = jnp.cumsum(g, axis=1)                                  # [N, c, HV]
    gh = gc.transpose(0, 2, 1)                                  # [N, HV, c]
    rows = jnp.arange(c)
    lower = rows[:, None] >= rows[None, :]
    # exp(gc_i - gc_j) for i >= j (<= 1); the upper part is masked before
    # the exponential so that it cannot overflow
    decay = jnp.exp(jnp.where(lower, gh[..., :, None] - gh[..., None, :],
                              -jnp.inf))                        # [N,HV,c,c]
    kb = k * beta[..., None]
    vb = v * beta[..., None]
    m = ein("nihd,njhd->nhij", kb, k) * decay
    m = jnp.where(rows[:, None] > rows[None, :], m, 0.0)
    t = _unit_lower_inverse(m, precision)                       # [N,HV,c,c]
    u = ein("nhij,njhd->nihd", t, vb)
    w = ein("nhij,njhd->nihd", t, kb * jnp.exp(gc)[..., None])
    v_new = u - ein("nihk,nhkd->nihd", w, state)
    local = ein("nihd,njhd->nhij", q, k) * decay
    o = ein("nihk,nhkd->nihd", q * jnp.exp(gc)[..., None], state) \
        + ein("nhij,njhd->nihd", local, v_new)
    last = gc[:, -1]                                            # [N, HV]
    k_tail = k * jnp.exp(last[:, None] - gc)[..., None]
    state = state * jnp.exp(last)[..., None, None] \
        + ein("nihk,nihd->nhkd", k_tail, v_new)
    return o, state


def gated_delta_chunked(q, k, v, g, beta, state, tile: int = TILE,
                        precision=lax.Precision.HIGHEST):
    """A chunk of C tokens a row. q, k [N, C, HK, DK]; v [N, C, HV, DV];
    g, beta [N, C, HV]; state [N, HV, DK, DV] float32. A C that is no
    multiple of ``tile`` is padded to one with positions that change
    nothing. Returns (o [N, C, HV, DV] float32, state)."""
    f32 = jnp.float32
    N, C, hv = v.shape[0], v.shape[1], v.shape[2]
    c = min(tile, C)
    if C % c:
        pad = lambda x: jnp.pad(                                # noqa: E731
            x, [(0, 0), (0, c - C % c)] + [(0, 0)] * (x.ndim - 2))
        o, state = gated_delta_chunked(pad(q), pad(k), pad(v), pad(g),
                                       pad(beta), state, tile, precision)
        return o[:, :C], state
    n_tiles = C // c

    def tiles(x):       # [N, C, ...] -> [n_tiles, N, c, ...]
        return jnp.moveaxis(x.reshape((N, n_tiles, c) + x.shape[2:]), 1, 0)

    def body(s, xs):
        qt, kt, vt, gt, bt = xs
        o, s = _tile(_expand(qt.astype(f32), hv), _expand(kt.astype(f32), hv),
                     vt.astype(f32), gt.astype(f32), bt.astype(f32), s,
                     precision)
        return s, o

    xs = tuple(tiles(x) for x in (q, k, v, g, beta))
    if n_tiles == 1:
        state, o = body(state, tuple(x[0] for x in xs))
        return o, state
    state, o = lax.scan(body, state, xs)
    return jnp.moveaxis(o, 0, 1).reshape(N, C, hv, -1), state


def causal_conv(x, tail, w, n_tokens):
    """Depthwise causal convolution of width K over time, resumed from a
    tail. x [N, C, CH] (this chunk's inputs); tail [N, K-1, CH] (the last
    K-1 inputs before it; zeros at a sequence's start); w [K, CH]
    (``w[j]`` multiplies the input K-1-j steps back); n_tokens [N] (valid
    width of each row). Returns (y [N, C, CH], new tail: the last K-1
    inputs up to each row's valid end, so a row of no tokens keeps its)."""
    K = w.shape[0]
    C = x.shape[1]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)   # [N, C+K-1, CH]
    y = sum(xp[:, j:j + C] * w[j].astype(x.dtype) for j in range(K))
    idx = n_tokens[:, None] + jnp.arange(K - 1)[None, :]      # [N, K-1]
    new_tail = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    return y, new_tail.astype(tail.dtype)
