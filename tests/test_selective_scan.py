"""The S6 recurrence (``ops/selective_scan.py``): the chunked form, the
one-token step and a plain ``lax.scan`` over time are one function; the
state is carried across chunk and sub-chunk boundaries; positions with
``dt = 0`` change nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepspeed_tpu.ops import selective_scan as s6

N, T, CH, S = 2, 37, 24, 4


@pytest.fixture(scope="module")
def inputs():
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    return dict(
        x=jax.random.normal(k[0], (N, T, CH)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (N, T, CH)) - 1.0),
        A=-jnp.exp(0.5 * jax.random.normal(k[2], (S, CH))),
        B=jax.random.normal(k[3], (N, T, S)),
        C=jax.random.normal(k[4], (N, T, S)),
        D=jax.random.normal(k[5], (CH,)),
        state=jax.random.normal(k[6], (N, S, CH)))


def over_time(x, dt, A, B, C, D, state):
    """The definition: h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t, y_t =
    h_t C_t + D x_t, a token at a time."""
    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None, :] * A) * h \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.einsum("nsc,ns->nc", h, c_t) + D * x_t

    h, y = lax.scan(token, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), h


def close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("sub", [1, 4, 8, 37, 64])
def test_chunked_is_the_scan_over_time(inputs, sub):
    want_y, want_h = over_time(**inputs)
    y, h = s6.s6_chunked(**inputs, sub=sub)
    assert y.dtype == h.dtype == jnp.float32
    close(y, want_y)
    close(h, want_h)


def test_stepped_is_the_scan_over_time(inputs):
    want_y, want_h = over_time(**inputs)
    h, ys = inputs["state"], []
    for t in range(T):
        y, h = s6.s6_step(inputs["x"][:, t], inputs["dt"][:, t], inputs["A"],
                          inputs["B"][:, t], inputs["C"][:, t], inputs["D"],
                          h)
        ys.append(y)
    close(jnp.stack(ys, 1), want_y)
    close(h, want_h)


@pytest.mark.parametrize("cut", [1, 8, 13, 36])
def test_the_state_is_carried_across_chunks_and_sub_chunks(inputs, cut):
    """Two chunks, cut inside a sub-chunk or on its edge, are the whole."""
    want_y, want_h = s6.s6_chunked(**inputs, sub=8)
    part = lambda lo, hi: {k: (v[:, lo:hi] if k in ("x", "dt", "B", "C")
                               else v) for k, v in inputs.items()}  # noqa
    y0, h = s6.s6_chunked(**part(0, cut), sub=8)
    y1, h = s6.s6_chunked(**dict(part(cut, T), state=h), sub=8)
    close(jnp.concatenate([y0, y1], 1), want_y)
    close(h, want_h)


def test_positions_without_a_step_change_no_state(inputs):
    """``dt = 0`` behind a row's valid end: the state stays what the
    valid positions left, bit for bit; so does a step of ``dt = 0``."""
    valid = jnp.asarray([20, 0])
    keep = (jnp.arange(T)[None, :] < valid[:, None])[..., None]
    masked = dict(inputs, dt=jnp.where(keep, inputs["dt"], 0.0))
    _, h = s6.s6_chunked(**masked, sub=8)
    short = {k: (v[:1, :20] if k in ("x", "dt", "B", "C") else v)
             for k, v in inputs.items()}
    _, want = s6.s6_chunked(**dict(short, state=inputs["state"][:1]), sub=8)
    np.testing.assert_array_equal(np.asarray(h[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(h[1]),
                                  np.asarray(inputs["state"][1]))
    _, stepped = s6.s6_step(inputs["x"][:, 0], jnp.zeros((N, CH)),
                            inputs["A"], inputs["B"][:, 0],
                            inputs["C"][:, 0], inputs["D"], inputs["state"])
    np.testing.assert_array_equal(np.asarray(stepped),
                                  np.asarray(inputs["state"]))


def test_served_types_in_float32_out(inputs):
    bf = {k: v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v
          for k, v in inputs.items()}
    y, h = s6.s6_chunked(**bf, sub=8)
    assert y.dtype == h.dtype == jnp.float32
    want_y, _ = over_time(**{k: v.astype(jnp.float32) for k, v in bf.items()})
    close(y, want_y)
