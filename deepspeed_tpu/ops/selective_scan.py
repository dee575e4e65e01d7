"""The S6 recurrence (the selective scan of Mamba; Gu & Dao 2023) — the
scan of a state-space layer whose transition is diagonal with **a decay
of its own for every channel and every state index**, beside
``mamba2_ssd.py``'s one scalar a head: its closed form in matmuls does not
apply (the decay does not factor out of the channel), so the chunked form
here is the recurrence itself, taken a sub-chunk at a time.

Per channel c, with a state ``h`` [S] kept in float32::

    h   <- exp(dt_t[c] A[:, c]) * h + (dt_t[c] x_t[c]) B_t     (A < 0)
    y_t[c] = h · C_t + D[c] x_t[c]

``B_t`` and ``C_t`` [S] are one for all channels. The state is laid out
``[S, channels]``, the state index first: the channels (thousands) lie
along the lanes and the state indices (sixteen) down the sublanes, where
``[channels, S]`` would pad sixteen numbers to a lane row of 128.

- ``s6_step``: one token a row (a decode step; the tests' oracle is this
  under a ``lax.scan`` over time).
- ``s6_chunked``: a chunk of tokens a row (prefill). Tokens are taken
  ``sub`` at a time by a ``lax.scan`` that carries the state; inside a
  sub-chunk the decays ``exp(dt ⊗ A)`` and the inputs ``(dt x) ⊗ B`` of
  its ``sub`` steps are made at once, the steps themselves run one after
  another (each a multiply-add over ``[S, channels]``), and ``y`` is read
  off the ``sub`` states together. What is live at once is a sub-chunk's
  ``[sub, S, channels]`` float32 — 2.5 MiB at 8 × 16 × 5,120 — whatever
  the chunk's length; a chunk's states all at once would be 671 MB at
  2,048 tokens. Measured on a v5e at 2,048 × 5,120 × 16 (PERF.md section
  6, PR 60): 1.3 ms a layer at 8 tokens a sub-chunk, 6.9 ms at 16 and at
  32 (a sub-chunk's intermediates no longer stay on the core), 7.4 at 64.

A position with ``dt = 0`` leaves the state exactly as it was (decay 1,
nothing added): that is how callers mask padding. Plain XLA throughout.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

#: the tokens of one sub-chunk of ``s6_chunked``
SUB = 8


def s6_step(x, dt, A, B, C, D, state):
    """One token a row. x, dt [N, CH] (dt after softplus; 0 where the row
    must change nothing); A [S, CH]; B, C [N, S]; D [CH]; state
    [N, S, CH] float32. Returns (y [N, CH] float32, state)."""
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    state = jnp.exp(dt[:, None, :] * A.astype(f32)) * state \
        + (dt * x)[:, None, :] * B.astype(f32)[:, :, None]
    y = jnp.sum(state * C.astype(f32)[:, :, None], axis=1)
    return y + D.astype(f32) * x, state


def s6_chunked(x, dt, A, B, C, D, state, sub: int = SUB):
    """A chunk of T tokens a row. x, dt [N, T, CH]; A [S, CH]; B, C
    [N, T, S]; D [CH]; state [N, S, CH] float32. A T that is no multiple
    of ``sub`` is padded to one with positions that change nothing.
    Returns (y [N, T, CH] float32, state)."""
    f32 = jnp.float32
    N, T, CH = x.shape
    c = min(sub, T)
    if T % c:
        pad = lambda a: jnp.pad(                                # noqa: E731
            a, [(0, 0), (0, c - T % c), (0, 0)])
        y, state = s6_chunked(pad(x), pad(dt), A, pad(B), pad(C), D, state,
                              sub)
        return y[:, :T], state
    n_subs = T // c
    A = A.astype(f32)

    def subs(a):        # [N, T, w] -> [n_subs, N, c, w]
        return jnp.moveaxis(a.reshape(N, n_subs, c, a.shape[-1]), 1, 0)

    def body(h, xs):
        xt, dtt, bt, ct = (a.astype(f32) for a in xs)
        decay = jnp.exp(dtt[:, :, None, :] * A)             # [N, c, S, CH]
        fed = (dtt * xt)[:, :, None, :] * bt[..., None]
        hs = []
        for t in range(c):
            h = decay[:, t] * h + fed[:, t]
            hs.append(h)
        y = jnp.sum(jnp.stack(hs, axis=1) * ct[..., None], axis=2)
        return h, y

    xs = tuple(subs(a) for a in (x, dt, B, C))
    if n_subs == 1:
        state, y = body(state, tuple(a[0] for a in xs))
    else:
        state, y = lax.scan(body, state, xs)
        y = jnp.moveaxis(y, 0, 1).reshape(N, T, CH)
    return y + D.astype(f32) * x.astype(f32), state
