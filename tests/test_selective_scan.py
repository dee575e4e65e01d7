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


# --------------------------------------------- the step where the state lies

SLOT_CASES = {
    # name: (bucket rows, rows with a token, fresh rows)
    "all_live": (6, [1] * 6, []),
    "padding_first": (6, [0, 0, 1, 1, 1, 1], []),
    "padding_last": (6, [1, 1, 1, 0, 0, 0], []),
    "padding_between": (6, [1, 0, 0, 1, 0, 1], []),
    "padding_first_and_between": (6, [0, 1, 0, 0, 1, 1], [1]),
    "fresh_over_a_dirty_slot": (6, [1] * 6, [1, 3]),
    "one_live_row": (6, [0, 0, 0, 0, 1, 0], range(6)),
    "no_live_row": (6, [0] * 6, range(6)),
    "bucket_of_1": (1, [1], []),
    "bucket_of_1_fresh": (1, [1], [0]),
    "bucket_of_1_padding": (1, [0], [0]),
    "bucket_of_128": (128, [1] * 81 + [0] * 47, range(0, 128, 9)),
}


def _slot_case(case, L=2, S=4, CH=256, served=jnp.float32):
    """A pool of a slot a bucket row and the scratch slot behind them,
    and a bucket: (pool, slots, n_tokens, fresh, step inputs). A padded
    row names the scratch slot; a dirty slot holds what no step
    survives."""
    N, n, fresh = SLOT_CASES[case]
    NS = N + 2                      # one slot no row names, and the scratch
    ks = jax.random.split(jax.random.PRNGKey(11), 7)
    pool = jax.random.normal(ks[0], (L, NS, S, CH))
    step = dict(x=jax.random.normal(ks[1], (N, CH)).astype(served),
                dt=jax.nn.softplus(jax.random.normal(ks[2], (N, CH)) - 1.0),
                A=-jnp.exp(0.5 * jax.random.normal(ks[3], (S, CH))),
                B=jax.random.normal(ks[4], (N, S)).astype(served),
                C=jax.random.normal(ks[5], (N, S)).astype(served),
                D=jax.random.normal(ks[6], (CH,)))
    own = np.random.default_rng(3).permutation(N)       # out of order
    is_fresh = np.isin(np.arange(N), list(fresh))
    for row in np.flatnonzero(is_fresh & (np.asarray(n) > 0)):
        pool = pool.at[:, own[row]].set(jnp.nan if row % 2 else 1e30)
    slots = np.where(np.asarray(n) > 0, own, NS - 1)
    return (pool, jnp.asarray(slots, jnp.int32), jnp.asarray(n, jnp.int32),
            jnp.asarray(is_fresh), step)


def _gather_step_scatter(pool, layer, slots, n, fresh, step):
    """The oracle: ``s6_step`` on a gathered copy, scattered back."""
    live = n > 0
    state = jnp.where((fresh & live)[:, None, None], 0, pool[layer, slots])
    y, state = s6.s6_step(step["x"], jnp.where(live[:, None], step["dt"], 0),
                          step["A"], step["B"], step["C"], step["D"], state)
    return y, pool.at[layer, slots].set(state)


def _same(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)


@pytest.mark.parametrize("form", ["kernel", "plain"])
@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("case", list(SLOT_CASES))
def test_the_step_over_the_slots_is_the_step_round_a_gather_and_a_scatter(
        case, layer, form, monkeypatch):
    """``s6_step_slots`` -- the kernel ``s6_step`` interpreted, and the
    plain form -- against gather, ``s6_step``, scatter: ``y`` of the live
    rows and their slots to float32 round-off, a padded row's ``y`` 0;
    the scratch slot, every slot no live row names and the other layer of
    the pool bit for bit what they were; the layer index traced."""
    monkeypatch.setattr(s6, "_FORCE_INTERPRET", form == "kernel")
    pool, slots, n, fresh, step = _slot_case(case)
    before = np.asarray(pool)
    want_y, want_pool = _gather_step_scatter(pool, layer, slots, n, fresh,
                                             step)
    got_y, got_pool = jax.jit(
        lambda pool, layer: s6.s6_step_slots(
            pool, layer, slots, n, fresh, **step))(pool, jnp.int32(layer))
    assert got_y.dtype == got_pool.dtype == jnp.float32
    got_pool, live = np.asarray(got_pool), np.asarray(n) > 0
    named = np.asarray(slots)[live]
    if live.any():
        close(got_y[live], want_y[live])
        close(got_pool[layer, named], np.asarray(want_pool)[layer, named])
        assert np.isfinite(got_pool[layer, named]).all()
    assert (np.asarray(got_y)[~live] == 0).all()
    others = np.setdiff1d(np.arange(pool.shape[1]), named)
    assert pool.shape[1] - 1 in others                  # the scratch slot
    assert _same(got_pool[layer, others], before[layer, others])
    assert _same(got_pool[1 - layer], before[1 - layer])


@pytest.mark.parametrize("form", ["kernel", "plain"])
def test_two_layers_of_one_pool_are_stepped_one_after_the_other(form,
                                                                monkeypatch):
    """A forward's use: the leaf handed from layer to layer, each stepping
    its own rows of it -- what the second layer's call returns holds both
    layers' new states and nothing else changed."""
    monkeypatch.setattr(s6, "_FORCE_INTERPRET", form == "kernel")
    pool, slots, n, fresh, step = _slot_case("padding_first_and_between")
    want = pool
    for layer in (0, 1):
        _, want = _gather_step_scatter(want, layer, slots, n, fresh, step)

    @jax.jit
    def forward(pool):
        for layer in (0, 1):
            _, pool = s6.s6_step_slots(pool, layer, slots, n, fresh, **step)
        return pool

    got = np.asarray(forward(pool))
    named = np.asarray(slots)[np.asarray(n) > 0]
    close(got[:, named], np.asarray(want)[:, named])
    others = np.setdiff1d(np.arange(pool.shape[1]), named)
    assert _same(got[:, others], np.asarray(pool)[:, others])


def test_served_types_go_into_the_step_over_the_slots_float32_comes_out(
        monkeypatch):
    pool, slots, n, fresh, step = _slot_case("padding_last",
                                             served=jnp.bfloat16)
    assert step["x"].dtype == step["B"].dtype == jnp.bfloat16
    plain = s6.s6_step_slots(pool, 1, slots, n, fresh, **step)
    monkeypatch.setattr(s6, "_FORCE_INTERPRET", True)
    kernel = s6.s6_step_slots(pool, 1, slots, n, fresh, **step)
    for got in (plain, kernel):
        assert got[0].dtype == got[1].dtype == jnp.float32
    close(kernel[0], plain[0])
    close(kernel[1], plain[1])
    wide = {k: v.astype(jnp.float32) for k, v in step.items()}
    close(kernel[0], s6.s6_step_slots(pool, 1, slots, n, fresh, **wide)[0])


def test_a_one_token_forward_moves_no_state_sized_copy(monkeypatch):
    """The ``[S, 1]`` program as it is lowered for the chip: the state
    leaf goes to ``s6_step`` as it lies and comes back from it -- no
    gather, scatter or dynamic-update-slice has a state-sized operand,
    and no ``[N, S, CH]`` decay is made outside the kernel (the chunked
    form still gathers and scatters its one row's)."""
    import re

    from deepspeed_tpu.models.mixers import mamba1
    from deepspeed_tpu.models.mixers.base import Fwd
    from deepspeed_tpu.ops import pallas_utils

    import dataclasses

    # a state size that no other width of the layer shares
    cfg = dataclasses.replace(_layer_cfg(True), mamba1_state_size=8)
    lp = _layer_weights(cfg)
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    shapes = mamba1.state(cfg, 5)
    state = "x".join(map(str, shapes["mamba1_ssm"][0][2:])) + "xf32"
    h1 = jax.random.normal(jax.random.PRNGKey(8), (3, 40, 16))

    def forward(T):
        def run(pools, h1, n, slots, first):
            fwd = Fwd(shape=(3, T), n_tokens=n, ropes={}, pools=pools,
                      first_layer={"mamba1": first}, state_slots=slots,
                      fresh=n > 1, hand=("memory",), carry={})
            return (mamba1.paged(cfg, fwd)(h1, lp, 0), fwd.carry["memory"],
                    pools)
        pools = {name: jnp.zeros(shape, dt)
                 for name, (shape, dt) in shapes.items()}
        text = jax.jit(run).trace(
            pools, h1[:, :T], jnp.ones((3,), jnp.int32),
            jnp.arange(3, dtype=jnp.int32), jnp.int32(0)
        ).lower(lowering_platforms=("tpu",)).as_text()
        moved = [m.group(0) for m in re.finditer(
            r"stablehlo\.(gather|scatter|dynamic_update_slice)\b.*?"
            r"-> tensor<[^>]*>", text, re.S) if state in m.group(0)]
        decays = re.findall(r"stablehlo\.exponential [^\n]*tensor<3x(?:\d+x)?"
                            + state + ">", text)
        return moved, decays, text

    moved, decays, text = forward(1)
    assert not moved, moved
    assert not decays, decays
    assert text.count("tpu_custom_call") == 1 and "s6_step" in text
    moved, decays, _ = forward(40)      # what the assertions would catch
    assert moved and decays
