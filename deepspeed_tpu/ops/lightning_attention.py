"""Lightning attention (Qin et al. 2024): linear attention under a decay
that is one scalar a head — the recurrence of a layer whose per-sequence
state is a fixed-size matrix instead of per-token K/V, beside
``gated_delta.py``'s: no delta, no convolution, no data-dependent gate,
and a state as wide as the head is on both sides.

Per head h, with a state ``S`` [DK, DV] kept in float32 and a decay
``λ_h = exp(-slope_h)`` in (0, 1)::

    S   <- λ_h * S + k_t v_t^T
    o_t  = S^T q_t            (the caller's scale is folded into q)

Two forms, both taking and returning the state:

- ``lightning_step``: one token a sequence (a decode step).
- ``lightning_chunked``: a chunk of tokens a sequence (prefill). Tokens
  are taken ``tile`` (64) at a time; inside a tile of B positions the
  recurrence is its closed form in matmuls, ``O = ((Q K^T) ⊙ D) V +
  diag(λ^i) Q S_prev`` and ``S_next = λ^B S_prev + (diag(λ^(B-i)) K)^T
  V`` with ``D_ij = λ^(i-j)`` for ``i >= j`` and 0 above; tiles are
  linked by a ``lax.scan`` that carries ``S``, the tile's work inside the
  body, so what is live at once is one tile's worth whatever the chunk's
  length. Only non-negative powers of λ are taken (λ_0 = 0.43 for 32
  heads: ``λ^-64`` overflows float32, so nothing divides by ``λ^i``).

A position at or beyond a row's ``n_tokens`` changes nothing: its key is
dropped and the tile's decay runs over the valid positions only, so a
padded row hands back the state it was given, bit for bit.

Plain XLA; ``precision`` is that of the float32 matmuls in here.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

TILE = 64


def slopes(heads: int):
    """The decay exponents [heads] float32, ``2^(-8 (h + 1) / heads)``:
    ALiBi's geometric slopes, as Lightning Attention uses them — head 0
    forgets fastest (λ = exp(-0.84) at 32 heads), the last one keeps
    (λ = exp(-1/256))."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / heads)


def lightning_step(q, k, v, slope, state):
    """One token a row. q, k [N, H, DK]; v [N, H, DV]; slope [H]; state
    [N, H, DK, DV] float32. Returns (o [N, H, DV] float32, state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    state = state * jnp.exp(-slope.astype(f32))[:, None, None] \
        + k[..., :, None] * v[..., None, :]
    return jnp.sum(state * q[..., :, None], axis=-2), state


def _tile(q, k, v, slope, n, state, precision):
    """One tile of B tokens of which each row's first ``n`` [N] are
    valid. q, k [N, B, H, DK]; v [N, B, H, DV]; state [N, H, DK, DV]; all
    float32."""
    ein = lambda s, *a: jnp.einsum(s, *a, precision=precision)  # noqa: E731
    B = q.shape[1]
    at = jnp.arange(B)
    s = slope[:, None, None]                                    # [H, 1, 1]
    # D_ij = λ^(i - j) below and on the diagonal; masked before the
    # exponential, so that the part above cannot overflow
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                              -s * (at[:, None] - at[None, :]), -jnp.inf))
    valid = at[None, :] < n[:, None]                            # [N, B]
    k = jnp.where(valid[..., None, None], k, 0.0)
    local = ein("nihd,njhd->nhij", q, k) * decay
    carried = jnp.exp(-slope[None, :] * (at[:, None] + 1.0))    # λ^(i+1)
    o = ein("nhij,njhd->nihd", local, v) \
        + ein("nihk,nhkd->nihd", q * carried[None, :, :, None], state)
    # what is left of position j at the row's last valid one: λ^(n-1-j)
    left = jnp.exp(-slope[None, None, :] * jnp.maximum(
        n[:, None] - 1 - at[None, :], 0).astype(jnp.float32)[..., None])
    state = state * jnp.exp(-slope[None, :] * n[:, None].astype(
        jnp.float32))[..., None, None] \
        + ein("nihk,nihd->nhkd", k * left[..., None], v)
    return o, state


def lightning_chunked(q, k, v, slope, state, n_tokens=None,
                      tile: int = TILE, precision=lax.Precision.HIGHEST):
    """A chunk of C tokens a row. q, k [N, C, H, DK]; v [N, C, H, DV];
    slope [H]; state [N, H, DK, DV] float32; n_tokens [N]: each row's
    valid width (None: all C). A C that is no multiple of ``tile`` is
    padded to one with positions that change nothing. Returns (o
    [N, C, H, DV] float32, state)."""
    f32 = jnp.float32
    N, C, H = v.shape[0], v.shape[1], v.shape[2]
    if n_tokens is None:
        n_tokens = jnp.full((N,), C, jnp.int32)
    c = min(tile, C)
    if C % c:
        pad = lambda x: jnp.pad(                                # noqa: E731
            x, [(0, 0), (0, c - C % c)] + [(0, 0)] * (x.ndim - 2))
        o, state = lightning_chunked(pad(q), pad(k), pad(v), slope, state,
                                     n_tokens, tile, precision)
        return o[:, :C], state
    n_tiles = C // c
    slope = slope.astype(f32)

    def tiles(x):       # [N, C, ...] -> [n_tiles, N, c, ...]
        return jnp.moveaxis(x.reshape((N, n_tiles, c) + x.shape[2:]), 1, 0)

    def body(s, xs):
        qt, kt, vt, start = xs
        o, s = _tile(qt.astype(f32), kt.astype(f32), vt.astype(f32), slope,
                     jnp.clip(n_tokens - start, 0, c), s, precision)
        return s, o

    xs = tuple(tiles(x) for x in (q, k, v)) \
        + (jnp.arange(n_tiles, dtype=jnp.int32) * c,)
    if n_tiles == 1:
        state, o = body(state, tuple(x[0] for x in xs))
        return o, state
    state, o = lax.scan(body, state, xs)
    return jnp.moveaxis(o, 0, 1).reshape(N, C, H, -1), state
