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

Three forms. Two take and return the state:

- ``ssd_step``: one token a sequence (a decode step), the plain form: the
  model without a cache, the tests' oracle, a backend without the kernel.
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

The third steps the state where a server keeps it:

- ``ssd_step_slots``: ``ssd_step``'s arithmetic on the rows of a decode
  step, each row's state at ``[layer, slot]`` of the slots' leaf ``[L,
  slots, heads, P, S]``. On the chip one Pallas kernel, ``mamba2_step``:
  the leaf is aliased in and out and stays in HBM but for the blocks the
  grid names -- ``(row, tile of STEP_GROUPS groups' heads)`` -> ``[layer,
  slots[row], tile]``, layer and slots in scalar memory --, so a live
  row's state is read once and written once, to the same place, and
  nothing else of the leaf is touched. A row of no tokens (a bucket's
  padding) names the block of the step before it, which the pipeline
  neither fetches nor writes back: it moves no state. A fresh row starts
  from zero inside the kernel. ``exp(dt A)`` reaches the kernel as
  scalars, ``dt x`` transposed so that a head's channels lie down the
  sublanes as its state's rows do; ``y`` comes back the same way. Off the
  chip: gather, ``ssd_step``, scatter (the kernel interpreted under the
  tests' ``_FORCE_INTERPRET``, as in ops/paged_attention.py).

A position with ``dt = 0`` leaves the state exactly as it was (decay 1,
nothing added): that is how callers mask padding.

``ssd_step`` and ``ssd_chunked`` are plain XLA; ``precision`` is that of
the float32 matmuls in here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_utils
from .pallas_utils import pl, pltpu

CHUNK = 128

# Test hook: force the Pallas step in interpreter mode off-TPU (same
# pattern as ops/paged_attention.py).
_FORCE_INTERPRET = False


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


# ------------------------------------------------- the step over the slots

#: what a bucket row is to the kernel (``_row_plan``)
_SKIP, _COPY, _LIVE, _FRESH = 0, 1, 2, 3


def _row_plan(slots, n_tokens, fresh):
    """What the kernel does at each bucket row, and whose slot the row's
    grid steps name: ``(slot [N], code [N])``. A live row (``n_tokens >
    0``, ``_LIVE`` or ``_FRESH``) names its own slot's tiles in turn. A
    padded row names the block the step before it named -- the last tile
    of the live row before it (``_SKIP``), or the first tile of the
    first live row where none came before (``_COPY``) -- so that the
    pipeline neither fetches nor writes back for it (``_state_block``)."""
    N = slots.shape[0]
    live = n_tokens > 0
    at = jnp.arange(N, dtype=jnp.int32)
    before = lax.cummax(jnp.where(live, at, -1))        # last live <= n
    first = jnp.argmax(live).astype(jnp.int32)          # 0 where none is
    led = before < 0
    code = jnp.where(live, jnp.where(fresh, _FRESH, _LIVE),
                     jnp.where(led, _COPY, _SKIP))
    return (slots[jnp.where(led, first, before)].astype(jnp.int32),
            code.astype(jnp.int32))


def _state_block(last, n, t, layer_ref, slot_ref, code_ref, *_):
    """The state block of grid step ``(row n, tile t)`` of ``last + 1``
    tiles a row."""
    code = code_ref[n]
    tile = jnp.where(code >= _LIVE, t, jnp.where(code == _COPY, 0, last))
    return (layer_ref[0], slot_ref[n], tile, 0, 0, 0)


def _step_kernel(layer_ref, slot_ref, code_ref, decay_ref, xt_ref, b_ref,
                 c_ref, s_ref, yt_ref, o_ref):
    """One row's tile of ``groups`` groups' heads: the state block
    [groups, R, P, S] as it lies in its slot, read once, stepped and
    written once to the same place. ``decay_ref`` [N * H] (scalar
    memory): ``exp(dt A)``; ``xt_ref`` [groups, P, R]: ``dt x`` with the
    head's channels down the sublanes, as the state's rows lie;
    ``b_ref`` / ``c_ref`` [G, S]: the row's, every group's. ``yt_ref``
    [groups, P, R]: ``S C``, transposed like ``xt_ref``."""
    del layer_ref, slot_ref
    n, t = pl.program_id(0), pl.program_id(1)
    groups, R, P, S = s_ref.shape[2:]
    G = b_ref.shape[1]
    code = code_ref[n]

    @pl.when(code >= _LIVE)
    def _():
        fresh = code == _FRESH
        lane = lax.broadcasted_iota(jnp.int32, (P, R), 1)

        def group(k, _):
            g = t * groups + k
            b = b_ref[0, pl.ds(g, 1), :]                        # [1, S]
            c = c_ref[0, pl.ds(g, 1), :]
            xt = xt_ref[0, k]                                   # [P, R]
            yt = jnp.zeros((P, R), jnp.float32)
            for r in range(R):
                decay = decay_ref[(n * G + g) * R + r]
                s = s_ref[0, 0, k, r]                           # [P, S]
                s = jnp.where(fresh, 0.0, s) * decay + xt[:, r:r + 1] * b
                o_ref[0, 0, k, r] = s
                yt = jnp.where(lane == r,
                               jnp.sum(s * c, axis=-1, keepdims=True), yt)
            yt_ref[0, k] = yt

        lax.fori_loop(0, groups, group, None)

    @pl.when(code < _LIVE)
    def _():
        yt_ref[...] = jnp.zeros_like(yt_ref)

    # a bucket whose first rows are padding names the first live row's
    # block before that row has written it: what goes back is what came
    @pl.when(code == _COPY)
    def _():
        o_ref[...] = s_ref[...]


#: groups of heads a grid step (B and C are a group's, so its heads are
#: the natural tile: 0.52 MB of state at the published sizes). On the
#: chip, five layers of [32] rows at the published [128, 64, 128], 18 /
#: 14 / 8 rows live: 1.64 / 1.34 / 0.89 ms at one group a step, 1.54 /
#: 1.26 / 0.82 at two, 1.52 / 1.23 / 0.80 at four, the same at eight
#: (whose four buffers pass the 16 MB of VMEM a kernel has by default):
#: a step costs 0.35 us whether or not its row is live
STEP_GROUPS = 4


@functools.partial(jax.jit, static_argnames="interpret")
def _step_in_kernel(pool, layer, slots, n_tokens, fresh, x, dt, A, B, C, D,
                    interpret: bool):
    """``ssd_step_slots`` through ``mamba2_step``. A jitted function of
    its own, so that a program traces and lowers it once and calls it a
    layer: traced a layer, the kernel's sixteen unrolled heads were a
    second a ``[S, 1]`` program of a server's start (PERF.md section 6,
    PR 53)."""
    f32 = jnp.float32
    L, NS, H, P, S = pool.shape
    N, G = B.shape[:2]
    R = H // G
    groups = math.gcd(G, STEP_GROUPS)
    tiles = G // groups
    slot, code = _row_plan(slots, n_tokens, fresh)
    x, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))
    # the head's channels down the sublanes, as the state's rows lie
    xt = (x * dt[..., None]).reshape(N, G, R, P).swapaxes(2, 3)

    def row(n, t, *_):
        return (n, 0, 0)

    def part(n, t, *_):
        return (n, t, 0, 0)

    state = pl.BlockSpec((1, 1, groups, R, P, S),
                         functools.partial(_state_block, tiles - 1))
    yt, pool = pl.pallas_call(
        _step_kernel,
        name="mamba2_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(N, tiles),
            in_specs=[pl.BlockSpec((1, groups, P, R), part),
                      pl.BlockSpec((1, G, S), row),
                      pl.BlockSpec((1, G, S), row), state],
            out_specs=[pl.BlockSpec((1, groups, P, R), part), state]),
        out_shape=[jax.ShapeDtypeStruct((N, G, P, R), f32),
                   jax.ShapeDtypeStruct((L, NS, G, R, P, S), pool.dtype)],
        # the pool in and out: nothing of it is copied, and what the
        # forward does not touch stays what it was
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(layer.reshape(1), slot, code, decay.reshape(N * H), xt,
      B.astype(f32), C.astype(f32), pool.reshape(L, NS, G, R, P, S))
    y = yt.swapaxes(2, 3).reshape(N, H, P) + D.astype(f32)[:, None] * x
    return (jnp.where((n_tokens > 0)[:, None, None], y, 0.0),
            pool.reshape(L, NS, H, P, S))


def ssd_step_slots(pool, layer, slots, n_tokens, fresh, x, dt, A, B, C, D):
    """``ssd_step`` on the rows' state where it lies: ``pool`` [L, slots,
    H, P, S] float32, row n's state at ``[layer, slots[n]]``. A row with
    ``n_tokens`` 0 is padding: its slot is neither read nor written and
    its ``y`` is 0. A ``fresh`` row starts from zero whatever its slot
    holds. x [N, H, P]; dt [N, H]; A, D [H]; B, C [N, G, S]. Returns (y
    [N, H, P] float32, the pool). On the chip (and under the test hook,
    interpreted) one Pallas kernel, ``mamba2_step``, that reads and
    writes each live row's state once; elsewhere the plain form:
    gather, ``ssd_step``, scatter."""
    if _FORCE_INTERPRET or pallas_utils.on_tpu():
        return _step_in_kernel(pool, jnp.asarray(layer, jnp.int32), slots,
                               n_tokens, fresh, x, dt, A, B, C, D,
                               interpret=not pallas_utils.on_tpu())
    live = n_tokens > 0
    state = jnp.where((fresh & live)[:, None, None, None], 0,
                      pool[layer, slots])
    y, state = ssd_step(x, jnp.where(live[:, None], dt, 0.0), A, B, C, D,
                        state)
    return (jnp.where(live[:, None, None], y, 0.0),
            pool.at[layer, slots].set(state))
