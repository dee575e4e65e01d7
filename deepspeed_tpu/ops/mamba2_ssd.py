"""The Mamba-2 recurrence (state-space duality; Dao & Gu 2024) — the
scan of a state-space layer whose per-sequence state is a fixed-size
matrix instead of per-token K/V, beside ``gated_delta.py``'s and
``lightning_attention.py``'s: a decay that depends on the token, no delta,
and a state wider on one side than the head (``[heads, P, S]``: P the
head's channels, S the state size), B and C shared by the heads of a
group.

Per head h of group g, with a state ``S`` [P, S] kept in float32::

    S   <- exp(dt_t A_h) * S + (dt_t x_t) B_t^T     (A_h < 0, dt_t >= 0)
    y_t  = S C_t + D_h x_t

Two forms, both taking and returning the state:

- ``ssd_step``: one token a sequence (a decode step).
- ``ssd_chunked``: a chunk of tokens a sequence (prefill). Tokens are
  taken ``chunk`` (the published 128) at a time; inside one such tile the
  recurrence is its closed form in matmuls, ``Y = ((C B^T) ⊙ L)(dt X) +
  diag(exp(cum)) C S_prev`` and ``S_next = exp(cum_last) S_prev +
  (diag(exp(cum_last - cum)) dt X)^T B`` with ``cum`` the running sum of
  ``dt A`` and ``L_ij = exp(cum_i - cum_j)`` for ``i >= j``, 0 above;
  tiles are linked by a ``lax.scan`` that carries ``S``, the tile's work
  inside the body, so what is live at once is one tile's worth whatever
  the chunk's length. Only differences ``cum_i - cum_j <= 0`` are
  exponentiated, so nothing overflows however fast a head forgets.

A position with ``dt = 0`` leaves the state exactly as it was (decay 1,
nothing added): that is how callers mask padding.

Plain XLA; ``precision`` is that of the float32 matmuls in here.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

CHUNK = 128


def ssd_step(x, dt, A, B, C, D, state):
    """One token a row. x [N, H, P]; dt [N, H] (after softplus; 0 for a
    row that must not move); A, D [H]; B, C [N, G, S]; state [N, H, P, S]
    float32. Returns (y [N, H, P] float32, state)."""
    f32 = jnp.float32
    N, H, P = x.shape
    G = B.shape[1]
    x, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))                         # [N, H]
    # heads by group, so that B and C are broadcast and never repeated
    s = state.reshape(N, G, H // G, P, -1)
    xdt = (x * dt[..., None]).reshape(N, G, H // G, P)
    s = s * decay.reshape(N, G, H // G, 1, 1) \
        + xdt[..., None] * B.astype(f32)[:, :, None, None, :]
    y = jnp.sum(s * C.astype(f32)[:, :, None, None, :], axis=-1)
    y = y.reshape(N, H, P) + D.astype(f32)[:, None] * x
    return y, s.reshape(state.shape)


def _tile(x, dt, A, B, C, state, precision):
    """One tile of c tokens. x [N, c, G, R, P]; dt [N, c, G, R]; A [G, R];
    B, C [N, c, G, S]; state [N, G, R, P, S]. All float32; the ``D x``
    term is the caller's."""
    ein = lambda s, *a: jnp.einsum(s, *a, precision=precision)  # noqa: E731
    c = x.shape[1]
    cum = jnp.cumsum(dt * A, axis=1)                            # [N,c,G,R]
    ch = jnp.moveaxis(cum, 1, -1)                               # [N,G,R,c]
    at = jnp.arange(c)
    # exp(cum_i - cum_j) for i >= j (<= 1); the upper part is masked
    # before the exponential so that it cannot overflow
    L = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                          ch[..., :, None] - ch[..., None, :], -jnp.inf))
    xdt = x * dt[..., None]
    cb = ein("nigs,njgs->ngij", C, B)                           # [N,G,c,c]
    y = ein("ngrij,njgrp->nigrp", cb[:, :, None] * L, xdt) \
        + ein("nigs,ngrps->nigrp", C, state) * jnp.exp(cum)[..., None]
    left = jnp.exp(cum[:, -1:] - cum)                           # [N,c,G,R]
    state = state * jnp.exp(cum[:, -1])[..., None, None] \
        + ein("njgrp,njgs->ngrps", xdt * left[..., None], B)
    return y, state


def ssd_chunked(x, dt, A, B, C, D, state, chunk: int = CHUNK,
                precision=lax.Precision.HIGHEST):
    """A chunk of T tokens a row. x [N, T, H, P]; dt [N, T, H] (after
    softplus; 0 at positions that must change nothing); A, D [H]; B, C
    [N, T, G, S]; state [N, H, P, S] float32. A T that is no multiple of
    ``chunk`` is padded to one with positions that change nothing.
    Returns (y [N, T, H, P] float32, state)."""
    f32 = jnp.float32
    N, T, H, P = x.shape
    G = B.shape[2]
    c = min(chunk, T)
    if T % c:
        pad = lambda a: jnp.pad(                                # noqa: E731
            a, [(0, 0), (0, c - T % c)] + [(0, 0)] * (a.ndim - 2))
        y, state = ssd_chunked(pad(x), pad(dt), A, pad(B), pad(C), D, state,
                               chunk, precision)
        return y[:, :T], state
    n_tiles = T // c
    R = H // G
    A = A.astype(f32).reshape(G, R)

    def tiles(a):       # [N, T, ...] -> [n_tiles, N, c, ...]
        return jnp.moveaxis(a.reshape((N, n_tiles, c) + a.shape[2:]), 1, 0)

    def body(s, xs):
        xt, dtt, bt, ct = xs
        y, s = _tile(xt.astype(f32).reshape(N, c, G, R, P),
                     dtt.astype(f32).reshape(N, c, G, R), A,
                     bt.astype(f32), ct.astype(f32), s, precision)
        return s, y.reshape(N, c, H, P)

    xs = tuple(tiles(a) for a in (x, dt, B, C))
    s0 = state.reshape(N, G, R, P, -1)
    if n_tiles == 1:
        s, y = body(s0, tuple(a[0] for a in xs))
    else:
        s, y = lax.scan(body, s0, xs)
        y = jnp.moveaxis(y, 0, 1).reshape(N, T, H, P)
    y = y + D.astype(f32)[:, None] * x.astype(f32)
    return y, s.reshape(state.shape)
