"""ops/gated_delta.py: the chunked form and the one-token recurrence
against a token-by-token loop written from the equations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import gated_delta as gd

HK, HV, DK, DV = 2, 4, 16, 8


def _inputs(seed, N, C):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (N, C, HK, DK))
    k = jax.random.normal(ks[1], (N, C, HK, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (N, C, HV, DV))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (N, C, HV)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (N, C, HV)))
    s0 = 0.3 * jax.random.normal(ks[5], (N, HV, DK, DV))
    return q, k, v, g, beta, s0


def _loop(q, k, v, g, beta, s):
    """The equations, one token at a time, in numpy float64."""
    q, k, v, g, beta, s = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, s))
    r = HV // HK
    q, k = np.repeat(q, r, axis=2), np.repeat(k, r, axis=2)
    N, C = v.shape[:2]
    out = np.zeros((N, C, HV, DV))
    s = s.copy()
    for n in range(N):
        for h in range(HV):
            S = s[n, h]
            for t in range(C):
                S = np.exp(g[n, t, h]) * S
                d = beta[n, t, h] * (v[n, t, h] - S.T @ k[n, t, h])
                S = S + np.outer(k[n, t, h], d)
                out[n, t, h] = S.T @ q[n, t, h]
            s[n, h] = S
    return out, s


@pytest.mark.parametrize("C,tile", [(1, 64), (5, 64), (8, 8), (32, 8),
                                    (64, 64), (128, 64), (96, 32), (73, 16)])
def test_chunked_form_is_the_loop(C, tile):
    args = _inputs(C, 2, C)
    want_o, want_s = _loop(*args)
    o, s = gd.gated_delta_chunked(*args, tile=tile)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


@pytest.mark.parametrize("tile", [8, 64])
def test_keys_that_point_the_same_way_stay_exact(tile):
    """Every key nearly the same unit vector, beta near 1 and hardly any
    decay: the strictly lower matrix to invert is nearly all ones, where a
    power series of it loses float32 (terms of 1e16 for an answer of 1)."""
    q, k, v, g, beta, s0 = _inputs(21, 2, 64)
    k = k[:, :1] + 0.05 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g, beta = 1e-3 * g, 0.98 + 0.0 * beta
    want_o, want_s = _loop(q, k, v, g, beta, s0)
    o, s = gd.gated_delta_chunked(q, k, v, g, beta, s0, tile=tile)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, atol=1e-4)
    np.testing.assert_allclose(s, want_s, atol=1e-4)


def test_one_token_step_is_the_loop():
    q, k, v, g, beta, s0 = _inputs(3, 3, 6)
    want_o, want_s = _loop(q, k, v, g, beta, s0)
    s = s0
    for t in range(6):
        o, s = gd.gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], s)
        np.testing.assert_allclose(o, want_o[:, t], atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


@pytest.mark.parametrize("widths", [(16, 16, 16), (8, 32, 8), (32, 1, 16),
                                    (1, 1, 48), (40, 8)])
def test_state_is_handed_across_chunk_boundaries(widths):
    T = sum(widths)
    q, k, v, g, beta, s0 = _inputs(7, 2, T)
    want_o, want_s = _loop(q, k, v, g, beta, s0)
    s, at, outs = s0, 0, []
    for w in widths:
        sl = slice(at, at + w)
        if w == 1:
            o, s = gd.gated_delta_step(q[:, at], k[:, at], v[:, at],
                                       g[:, at], beta[:, at], s)
            o = o[:, None]
        else:
            o, s = gd.gated_delta_chunked(q[:, sl], k[:, sl], v[:, sl],
                                          g[:, sl], beta[:, sl], s, tile=8)
        outs.append(o)
        at += w
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want_o, atol=3e-5)
    np.testing.assert_allclose(s, want_s, atol=3e-5)


@pytest.mark.parametrize("valid", [0, 3, 8, 13])
def test_masked_tail_leaves_the_state_bit_identical(valid):
    """Positions with g = 0 and beta = 0 change nothing: the state after
    a padded chunk is, bit for bit, the state after its valid part."""
    q, k, v, g, beta, s0 = _inputs(11, 2, 16)
    keep = (jnp.arange(16) < valid)[None, :, None]
    gm, bm = jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)
    _, s_pad = gd.gated_delta_chunked(q, k, v, gm, bm, s0, tile=16)
    if valid == 0:
        want = s0
    else:
        _, want = gd.gated_delta_chunked(
            q[:, :valid], k[:, :valid], v[:, :valid], g[:, :valid],
            beta[:, :valid], s0, tile=16)
        np.testing.assert_allclose(s_pad, want, atol=1e-6)
        return
    assert np.array_equal(np.asarray(s_pad), np.asarray(want))


def test_step_with_zero_gate_and_beta_is_the_identity():
    q, k, v, g, beta, s0 = _inputs(5, 2, 1)
    zero = jnp.zeros_like(g[:, 0])
    _, s = gd.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], zero, zero, s0)
    assert np.array_equal(np.asarray(s), np.asarray(s0))


@pytest.mark.parametrize("widths", [(8,), (3, 5), (1, 1, 6), (2, 1, 1, 4)])
def test_conv_resumes_from_its_tail(widths):
    K, CH, T = 4, 6, sum(widths)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, T, CH))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, CH))
    xp = np.concatenate([np.zeros((2, K - 1, CH)), np.asarray(x)], 1)
    want = sum(xp[:, j:j + T] * np.asarray(w)[j] for j in range(K))
    tail, at, outs = jnp.zeros((2, K - 1, CH)), 0, []
    for wd in widths:
        # pad the chunk to 8 columns: only the valid ones may count
        chunk = jnp.zeros((2, 8, CH)).at[:, :wd].set(x[:, at:at + wd])
        y, tail = gd.causal_conv(chunk, tail, w,
                                 jnp.full((2,), wd, jnp.int32))
        outs.append(y[:, :wd])
        at += wd
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=1e-5)
    np.testing.assert_allclose(tail, xp[:, T:T + K - 1], atol=0)


def test_conv_row_of_no_tokens_keeps_its_tail():
    tail = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 5))
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 4, 5))
    w = jnp.ones((4, 5))
    _, new = gd.causal_conv(x, tail, w, jnp.zeros((3,), jnp.int32))
    assert np.array_equal(np.asarray(new), np.asarray(tail))
