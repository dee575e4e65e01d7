"""The S6 recurrence (``ops/selective_scan.py``): the chunked form, the
one-token step and a plain ``lax.scan`` over time are one function; the
state is carried across chunk and sub-chunk boundaries; positions with
``dt = 0`` change nothing. And the layer round it (``models/mixers/
mamba1.py``) with Jamba's three norms inside, against the layer written a
token at a time: in the chunked form and in the step, and with the norms
off the layer that was there."""

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


# ------------------------------------------- the layer, with norms inside

def _layer_cfg(inner_norm):
    from deepspeed_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=64, hidden_size=16, intermediate_size=32, num_layers=1,
        num_heads=2, max_seq_len=64, norm="rmsnorm", norm_eps=1e-6,
        position="rope", rope_kinds=(), dtype=jnp.float32,
        layer_pattern=("mamba1",), mamba1_inner_size=32,
        mamba1_state_size=4, mamba1_dt_rank=3,
        mamba1_inner_norm=inner_norm)


def _layer_weights(cfg):
    """One layer's leaves, every gain and bias off its initial value."""
    from deepspeed_tpu.models import hybrid

    lp = hybrid.init_slot(cfg, "mamba1", jax.random.PRNGKey(5), 1)
    keys = jax.random.split(jax.random.PRNGKey(6), len(lp))
    return {name: (a + 0.3 * jax.random.normal(k, a.shape)
                   if "norm" in name or name.endswith("_b") else a)[0]
            for (name, a), k in zip(sorted(lp.items()), keys)}


def layer_over_time(cfg, lp, u, inner_norm):
    """The layer from its equations, a token at a time: u [T, H]."""
    ch, ns, rank, K = 32, 4, 3, 4
    rms = lambda a, w: a * lax.rsqrt(                       # noqa: E731
        jnp.mean(a * a, -1, keepdims=True) + cfg.norm_eps) * w
    A = -jnp.exp(lp["mamba1_A_log"])

    def token(carry, u_t):
        tail, h = carry                     # [K-1, CH], [S, CH]
        xz = u_t @ lp["mamba1_w_in"]
        taps = jnp.concatenate([tail, xz[None, :ch]])
        x = jax.nn.silu(jnp.sum(taps * lp["mamba1_conv_w"], 0)
                        + lp["mamba1_conv_b"])
        dbc = x @ lp["mamba1_w_x"]
        d, b, c = dbc[:rank], dbc[rank:rank + ns], dbc[rank + ns:]
        if inner_norm:
            d, b, c = (rms(d, lp["mamba1_dt_norm"]),
                       rms(b, lp["mamba1_b_norm"]),
                       rms(c, lp["mamba1_c_norm"]))
        dt = jax.nn.softplus(d @ lp["mamba1_w_dt"] + lp["mamba1_dt_b"])
        h = jnp.exp(dt * A) * h + (dt * x) * b[:, None]
        y = h.T @ c + lp["mamba1_D"] * x
        out = (y * jax.nn.silu(xz[ch:])) @ lp["mamba1_w_out"]
        return (taps[1:], h), out

    (_, h), out = lax.scan(token, (jnp.zeros((K - 1, ch)),
                                   jnp.zeros((ns, ch))), u)
    return out, h


@pytest.mark.parametrize("inner_norm", [True, False], ids=["norms", "bare"])
def test_the_layer_is_its_equations_chunked_and_stepped(inner_norm):
    from deepspeed_tpu.models.mixers import mamba1

    cfg = _layer_cfg(inner_norm)
    lp = _layer_weights(cfg)
    assert ("mamba1_dt_norm" in lp) == inner_norm
    T = 13                  # over a sub-chunk of eight and into the next
    u = jax.random.normal(jax.random.PRNGKey(7), (2, T, 16))
    zero = (jnp.zeros((2, 3, 32)), jnp.zeros((2, 4, 32)))
    n = jnp.asarray([T, T])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(
            lambda u: layer_over_time(cfg, lp, u, inner_norm)))(u)
        want = [(want[0][i], want[1][i]) for i in (0, 1)]
        out, _, state, _ = jax.jit(
            lambda u: mamba1.mamba1_mixer(cfg, u, lp, *zero, n))(u)
        step = jax.jit(lambda u_t, tail, h: mamba1.mamba1_mixer(
            cfg, u_t, lp, tail, h, jnp.asarray([1, 1])))
        tail, h, steps = *zero, []
        for t in range(T):
            o, tail, h, _ = step(u[:, t:t + 1], tail, h)
            steps.append(o[:, 0])
    for i in (0, 1):
        close(out[i], want[i][0])
        close(state[i], want[i][1])
        close(jnp.stack(steps, 1)[i], want[i][0])
        close(h[i], want[i][1])
    # the norms are in the program when the field is on, and only then
    text = str(jax.make_jaxpr(
        lambda u: mamba1.mamba1_mixer(cfg, u, lp, *zero, n)[0])(u))
    assert text.count("rsqrt") == (3 if inner_norm else 0)


def test_the_norms_change_the_layer_and_their_gains_are_read():
    from deepspeed_tpu.models.mixers import mamba1

    cfg = _layer_cfg(True)
    lp = _layer_weights(cfg)
    u = jax.random.normal(jax.random.PRNGKey(7), (1, 9, 16))
    zero = (jnp.zeros((1, 3, 32)), jnp.zeros((1, 4, 32)))
    n = jnp.asarray([9])
    with_norms = jax.jit(
        lambda w: mamba1.mamba1_mixer(cfg, u, w, *zero, n)[0])
    on = with_norms(lp)
    far = lambda other: float(jnp.abs(on - other).max()      # noqa: E731
                              / jnp.abs(on).max())
    assert far(mamba1.mamba1_mixer(_layer_cfg(False), u, lp, *zero, n)[0]) \
        > 0.05
    for name in ("mamba1_dt_norm", "mamba1_b_norm", "mamba1_c_norm"):
        assert far(with_norms(dict(lp, **{name: 2.0 * lp[name]}))) > 0.01, \
            name
