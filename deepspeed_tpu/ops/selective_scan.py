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

- ``s6_step_slots``: ``s6_step``'s arithmetic on the rows of a decode
  step where a server keeps their state, each row's at ``[layer, slot]``
  of the slots' leaf ``[L, slots, S, channels]`` (``mamba2_ssd
  .ssd_step_slots``' contract). On the chip one Pallas kernel,
  ``s6_step``: the leaf is aliased in and out and stays in HBM; the
  kernel walks the bucket's rows on the scalar core -- layer, slots and
  which rows hold a token in scalar memory -- and for each live row
  copies its ``[S, channels]`` block into fast memory, steps it and
  copies it back to the same place, the next live row's block on its way
  in and the last one's on its way out meanwhile. So a live row's state
  is read once and written once, a padded row costs a scalar compare,
  and nothing else of the leaf is touched. The decay ``exp(dt ⊗ A)`` is
  made inside the kernel from the row's ``dt`` and the resident ``A``: it
  never passes through HBM. A fresh row starts from zero inside the
  kernel. Nothing is planned outside it: the program holds the kernel
  and three small fusions a layer (what each row is, ``dt x``, ``B``
  beside ``C``). Off the chip: gather, ``s6_step``, scatter (the kernel
  interpreted under the tests' ``_FORCE_INTERPRET``, as in
  ops/mamba2_ssd.py).

A position with ``dt = 0`` leaves the state exactly as it was (decay 1,
nothing added): that is how callers mask padding. ``s6_step`` and
``s6_chunked`` are plain XLA.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_utils
from .pallas_utils import pl, pltpu

#: the tokens of one sub-chunk of ``s6_chunked``
SUB = 8

# Test hook: force the Pallas step in interpreter mode off-TPU (same
# pattern as ops/mamba2_ssd.py).
_FORCE_INTERPRET = False


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


# ------------------------------------------------- the step over the slots

#: channels the kernel's body takes at a time: [16, 512] float32 is eight
#: vector registers an operand. On the chip, 26 layers of [128] rows at
#: the published [16, 5120], 81 / 128 / 8 rows live: 2.72 / 3.96 / 0.81 ms
#: at 512, 2.76 / 3.96 / 0.82 at 1,024 -- a live row 1.19 us (its bytes
#: take 0.78 at the peak), the copies set the pace (PERF.md section 6,
#: PR 63)
STEP_LANES = 512
#: the kernel's fast memory: a bucket's ``dt``, ``dt x`` and ``y`` whole
#: ([128, 5120] float32 is 2.6 MB, each in two buffers), ``A`` and a
#: state in and out in two buffers each
STEP_VMEM = 40 * 2 ** 20


def _step_kernel(layer_ref, slot_ref, code_ref, dt_ref, dtx_ref, bc_ref,
                 a_ref, pool_hbm, y_ref, out_hbm, s_buf, o_buf, sem):
    """The bucket's rows with a token, one after the other: a row's state
    [S, CH] copied out of its slot, stepped and copied back to the same
    place, the next live row's on its way in and the last one's on its
    way out meanwhile; a padded row is stepped over on the scalar core.
    Scalar memory: the layer, the rows' slots, and what a row is (0:
    padding, 1: live, 2: live and starting from zero). ``dt_ref`` /
    ``dtx_ref`` [N, CH]: the rows' ``dt`` and ``dt x``; ``bc_ref`` [N,
    2 S]: ``B`` beside ``C``; ``a_ref`` [S, CH]: ``A``; ``pool_hbm`` /
    ``out_hbm``: the slots' leaf where it lies, one buffer. ``y_ref`` [N,
    CH]: ``sum_S h C``, a live row's (a padded row's is not written)."""
    N = y_ref.shape[0]
    S, CH = a_ref.shape
    lanes = math.gcd(CH, STEP_LANES)
    layer = layer_ref[0]

    def live_from(n):
        """The first row at or behind ``n`` with a token; N: none."""
        return lax.while_loop(
            lambda m: (m < N) & (code_ref[jnp.minimum(m, N - 1)] == 0),
            lambda m: m + 1, n)

    def copy_in(n, b):
        return pltpu.make_async_copy(pool_hbm.at[layer, slot_ref[n]],
                                     s_buf.at[b], sem.at[0, b])

    def copy_out(n, b):
        return pltpu.make_async_copy(o_buf.at[b],
                                     out_hbm.at[layer, slot_ref[n]],
                                     sem.at[1, b])

    sub = lax.broadcasted_iota(jnp.int32, (S, 2 * S), 0)
    lane = lax.broadcasted_iota(jnp.int32, (S, 2 * S), 1)

    def row(carry):
        n, done = carry         # this row; the live rows in front of it
        b = lax.rem(done, 2)
        behind = live_from(n + 1)
        pl.when(behind < N)(lambda: copy_in(behind, 1 - b).start())
        copy_in(n, b).wait()
        # the buffer out is free once the row two back has landed
        pl.when(done >= 2)(lambda: copy_out(n, b).wait())
        fresh = code_ref[n] == 2
        # B and C down the sublanes, as the state's index lies
        bc = bc_ref[pl.ds(n, 1), :]                             # [1, 2 S]
        bcol = jnp.sum(jnp.where(lane == sub, bc, 0.0), axis=1,
                       keepdims=True)
        ccol = jnp.sum(jnp.where(lane == sub + S, bc, 0.0), axis=1,
                       keepdims=True)
        for k in range(CH // lanes):
            at = pl.ds(k * lanes, lanes)
            h = jnp.where(fresh, 0.0, s_buf[b, :, at])
            h = jnp.exp(dt_ref[pl.ds(n, 1), at] * a_ref[:, at]) * h \
                + dtx_ref[pl.ds(n, 1), at] * bcol
            o_buf[b, :, at] = h
            y_ref[pl.ds(n, 1), at] = jnp.sum(h * ccol, axis=0,
                                             keepdims=True)
        copy_out(n, b).start()
        return behind, done + 1

    first = live_from(jnp.int32(0))
    pl.when(first < N)(lambda: copy_in(first, 0).start())
    _, done = lax.while_loop(lambda carry: carry[0] < N, row,
                             (first, jnp.int32(0)))
    # the last two rows' copies out (whose they were does not matter to
    # the wait: every row's is one state)
    pl.when(done >= 2)(lambda: copy_out(0, lax.rem(done, 2)).wait())
    pl.when(done >= 1)(lambda: copy_out(0, lax.rem(done + 1, 2)).wait())


@functools.partial(jax.jit, static_argnames="interpret")
def _step_in_kernel(pool, layer, slots, n_tokens, fresh, x, dt, A, B, C, D,
                    interpret: bool):
    """``s6_step_slots`` through the kernel ``s6_step``. A jitted function
    of its own, so that a program traces and lowers it once and calls it
    a layer (PERF.md section 6, PR 53)."""
    f32 = jnp.float32
    S, CH = pool.shape[2:]
    N = x.shape[0]
    live = n_tokens > 0
    x, dt = x.astype(f32), dt.astype(f32)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    whole = lambda *shape: pl.BlockSpec(                    # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    y, pool = pl.pallas_call(
        _step_kernel,
        name="s6_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[whole(N, CH), whole(N, CH), whole(N, 2 * S),
                      whole(S, CH), hbm],
            out_specs=[whole(N, CH), hbm],
            scratch_shapes=[pltpu.VMEM((2, S, CH), f32),
                            pltpu.VMEM((2, S, CH), f32),
                            pltpu.SemaphoreType.DMA((2, 2))]),  # [in|out, b]
        out_shape=[jax.ShapeDtypeStruct((N, CH), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool in and out: nothing of it is copied, and what the
        # forward does not touch stays what it was
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=STEP_VMEM),
        interpret=interpret,
    )(layer.reshape(1), slots.astype(jnp.int32),
      jnp.where(live, 1 + fresh.astype(jnp.int32), 0), dt, dt * x,
      jnp.concatenate([B, C], axis=-1).astype(f32), A.astype(f32), pool)
    # a padded row's y was never written: whatever lies there is dropped
    return jnp.where(live[:, None], y + D.astype(f32) * x, 0.0), pool


def s6_step_slots(pool, layer, slots, n_tokens, fresh, x, dt, A, B, C, D):
    """``s6_step`` on the rows' state where it lies: ``pool`` [L, slots,
    S, CH] float32, row n's state at ``[layer, slots[n]]``. A row with
    ``n_tokens`` 0 is padding: its slot is neither read nor written and
    its ``y`` is 0. A ``fresh`` row starts from zero whatever its slot
    holds. x, dt [N, CH]; A [S, CH]; B, C [N, S]; D [CH]. Returns (y [N,
    CH] float32, the pool). On the chip (and under the test hook,
    interpreted) one Pallas kernel, ``s6_step``, that reads and writes
    each live row's state once; elsewhere the plain form: gather,
    ``s6_step``, scatter."""
    if _FORCE_INTERPRET or pallas_utils.on_tpu():
        return _step_in_kernel(pool, jnp.asarray(layer, jnp.int32), slots,
                               n_tokens, fresh, x, dt, A, B, C, D,
                               interpret=not pallas_utils.on_tpu())
    live = n_tokens > 0
    state = jnp.where((fresh & live)[:, None, None], 0, pool[layer, slots])
    y, state = s6_step(x, jnp.where(live[:, None], dt, 0.0), A, B, C, D,
                       state)
    return jnp.where(live[:, None], y, 0.0), pool.at[layer, slots].set(state)
