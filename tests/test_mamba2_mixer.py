"""The ``"mamba2"`` mixer kind (models/mixers/mamba2.py, ops/mamba2_ssd.py)
and a period whose positions are a mixer alone or an FFN alone
(models/hybrid.run_period): the chunked form against the one-token step
folded and against a sequential reference written here, across tile and
chunk boundaries, with rows that are padding and a row that starts fresh
on a slot that holds another sequence's state; the one-token step's
kernel (``mamba2_step``, interpreted) against gather, step and scatter
over slots in any order with padding anywhere, and the lowered ``[S, 1]``
program free of state-sized copies; the engine over such a model, its
counters, and every block and state slot given back."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.mixers import KINDS, PUT_TOTALS, Fwd, kinds_of
from deepspeed_tpu.models.mixers import mamba2
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.ops import mamba2_ssd as ssd

ARCH = dict(
    vocab_size=96, hidden_size=32, intermediate_size=24, num_layers=5,
    num_heads=4, num_kv_heads=2, head_size=8, max_seq_len=256,
    norm="rmsnorm", norm_eps=1e-5, position="rope", rope_kinds=(),
    tie_embeddings=False, dtype=jnp.float32,
    # a mixer alone, an FFN alone, a mixer with its FFN, ...
    layer_pattern=("mamba2", None, "full", "mamba2", None),
    layer_ffn=(False, True, False, True, True),
    mamba_num_heads=4, mamba_head_dim=8, mamba_state_size=16,
    mamba_n_groups=2, mamba_conv_kernel=4, mamba_chunk_size=16,
    moe_num_experts=8, moe_top_k=3, moe_dropless=True, moe_norm_topk=True,
    moe_score_func="sigmoid", moe_select_bias=True, moe_route_scale=2.5,
    moe_shared_gate=False, moe_held_experts=(2, 4),
    moe_intermediate_size=24, moe_shared_intermediate_size=40,
    moe_activation="relu2", moe_latent_size=16)


def close(a, b, tol=5e-6):
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30)


# ----------------------------------------------------------- the recurrence

def _inputs(N=2, T=150, H=4, P=8, G=2, S=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    return dict(
        x=jax.random.normal(ks[0], (N, T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (N, T, H))),
        # the fastest head forgets at exp(-H dt): the source's A = 1 .. H
        A=-jnp.arange(1, H + 1, dtype=jnp.float32),
        B=jax.random.normal(ks[2], (N, T, G, S)),
        C=jax.random.normal(ks[3], (N, T, G, S)),
        D=1.0 + 0.1 * jax.random.normal(ks[4], (H,)),
        state=jax.random.normal(ks[5], (N, H, P, S)))


def _sequential(x, dt, A, B, C, D, state):
    """The recurrence as the layer's equations say it, a token at a time,
    B and C repeated to the heads."""
    H, G = x.shape[2], B.shape[2]
    B, C = jnp.repeat(B, H // G, 2), jnp.repeat(C, H // G, 2)

    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t * A)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return h, jnp.einsum("nhps,nhs->nhp", h, c_t) + D[:, None] * x_t

    state, y = jax.lax.scan(token, state, tuple(
        a.swapaxes(0, 1) for a in (x, dt, B, C)))
    return y.swapaxes(0, 1), state


@pytest.mark.parametrize("chunk", [16, 64, 128])
def test_the_chunked_form_is_the_sequential_recurrence(chunk):
    # 150 tokens: a last tile that is padded, whatever the chunk
    a = _inputs()
    want, want_state = _sequential(**a)
    got, got_state = jax.jit(ssd.ssd_chunked, static_argnames="chunk")(
        a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"], a["state"],
        chunk=chunk)
    assert close(got, want) and close(got_state, want_state)


def test_the_step_folded_is_the_sequential_recurrence():
    a = _inputs(T=40)
    want, want_state = _sequential(**a)
    state, ys = a["state"], []
    for t in range(40):
        y, state = ssd.ssd_step(a["x"][:, t], a["dt"][:, t], a["A"],
                                a["B"][:, t], a["C"][:, t], a["D"], state)
        ys.append(y)
    assert close(jnp.stack(ys, 1), want) and close(state, want_state)


def test_a_step_of_zero_changes_no_state_bit_for_bit():
    a = _inputs()
    n = jnp.asarray([150, 70])      # the second row ends inside a tile
    keep = jnp.arange(150)[None, :, None] < n[:, None, None]
    dt = jnp.where(keep, a["dt"], 0.0)
    got, state = ssd.ssd_chunked(a["x"], dt, a["A"], a["B"], a["C"], a["D"],
                                 a["state"], chunk=64)
    short = {k: (v[1:, :70] if v.ndim > 3 or k == "dt" else v)
             for k, v in a.items() if k not in ("A", "D", "state")}
    want, want_state = _sequential(A=a["A"], D=a["D"],
                                   state=a["state"][1:], **short)
    assert close(got[1:, :70], want) and close(state[1:], want_state)
    # no valid token at all: the state comes back as it was given
    _, same = ssd.ssd_chunked(a["x"], 0 * a["dt"], a["A"], a["B"], a["C"],
                              a["D"], a["state"], chunk=64)
    assert (np.asarray(same) == np.asarray(a["state"])).all()
    _, same = ssd.ssd_step(a["x"][:, 0], 0 * a["dt"][:, 0], a["A"],
                           a["B"][:, 0], a["C"][:, 0], a["D"], a["state"])
    assert (np.asarray(same) == np.asarray(a["state"])).all()


def test_nothing_overflows_however_fast_a_head_forgets():
    # the published 128 heads: A = -128, and a step of 3 is exp(-384)
    a = _inputs(N=1, T=130, H=4)
    got, state = ssd.ssd_chunked(a["x"], 3.0 + a["dt"], 32.0 * a["A"],
                                 a["B"], a["C"], a["D"], a["state"])
    assert np.isfinite(np.asarray(got)).all()
    assert np.isfinite(np.asarray(state)).all()


# ------------------------------------------------------------------ the layer

@pytest.fixture(scope="module")
def layer():
    cfg = TransformerConfig(**ARCH)
    params = CausalLM(cfg).init(jax.random.PRNGKey(1))
    lp = jax.tree.map(lambda a: a[0], params["layers"]["slot0"])
    # the skip, the bias and the gains away from what they start at
    lp = dict(lp, mamba_D=lp["mamba_D"] + 0.3,
              mamba_norm_w=lp["mamba_norm_w"] * 1.2)
    h1 = jax.random.normal(jax.random.PRNGKey(2), (3, 60, cfg.hidden_size))
    return cfg, lp, h1


def _zero_state(cfg, rows):
    return {name: jnp.zeros((rows,) + shape[2:], dt)
            for name, (shape, dt) in mamba2.state(cfg, rows).items()}


def test_chunks_then_steps_are_the_whole_sequence(layer):
    cfg, lp, h1 = layer
    B, T, _ = h1.shape
    zero = _zero_state(cfg, B)
    n_all = jnp.full((B,), T, jnp.int32)
    want, want_tail, want_state = mamba2.mamba2_mixer(
        cfg, h1, lp, zero["mamba_conv"], zero["mamba_ssm"], n_all)
    # 40 tokens (two and a half tiles of 16), then 13 (inside a tile),
    # then the last 7 a token at a time
    tail, state, got = zero["mamba_conv"], zero["mamba_ssm"], []
    for a, b in ((0, 40), (40, 53)) + tuple((t, t + 1) for t in range(53, T)):
        y, tail, state = mamba2.mamba2_mixer(
            cfg, h1[:, a:b], lp, tail, state,
            jnp.full((B,), b - a, jnp.int32))
        got.append(y)
    assert close(jnp.concatenate(got, 1), want, 2e-5)
    assert close(tail, want_tail) and close(state, want_state, 2e-5)


def test_positions_beyond_n_tokens_change_neither_tail_nor_state(layer):
    cfg, lp, h1 = layer
    B, T, _ = h1.shape
    zero = _zero_state(cfg, B)
    n = jnp.asarray([T, 21, 0], jnp.int32)
    got, tail, state = mamba2.mamba2_mixer(
        cfg, h1, lp, zero["mamba_conv"] + 0.5, zero["mamba_ssm"] + 0.25, n)
    want, want_tail, want_state = mamba2.mamba2_mixer(
        cfg, h1[1:2, :21], lp, zero["mamba_conv"][1:2] + 0.5,
        zero["mamba_ssm"][1:2] + 0.25, jnp.asarray([21], jnp.int32))
    assert close(got[1:2, :21], want, 2e-5)
    assert close(tail[1:2], want_tail) and close(state[1:2], want_state, 2e-5)
    # a row of no tokens hands both back bit for bit, and so does a
    # one-token row that is padding
    assert (np.asarray(tail[2]) == 0.5).all()
    assert (np.asarray(state[2]) == 0.25).all()
    _, tail1, state1 = mamba2.mamba2_mixer(
        cfg, h1[:, :1], lp, zero["mamba_conv"] + 0.5,
        zero["mamba_ssm"] + 0.25, jnp.asarray([1, 1, 0], jnp.int32))
    assert (np.asarray(tail1[2]) == 0.5).all()
    assert (np.asarray(state1[2]) == 0.25).all()
    assert not (np.asarray(state1[0]) == 0.25).all()


def test_the_paged_layer_reads_and_writes_its_rows_slots(layer):
    """Two layers' leaves, four slots and a scratch: a fresh row starts
    from zero whatever its slot holds, another resumes from its slot, a
    padded row points at the scratch slot, the other layer's and the
    other slots' state stay as they were."""
    cfg, lp, h1 = layer
    shapes = mamba2.state(cfg, 5)
    assert set(shapes) == {"mamba_ssm", "mamba_conv"}
    assert not set(shapes) & set(KINDS["linear"].state(
        TransformerConfig(**dict(
            ARCH, layer_pattern=("linear",), layer_ffn=None, num_layers=1,
            moe_activation="silu", moe_latent_size=0,
            linear_num_key_heads=2, linear_num_value_heads=2,
            linear_key_head_dim=8, linear_value_head_dim=8)), 5))
    assert shapes["mamba_ssm"] == ((2, 5, 4, 8, 16), jnp.float32)
    assert shapes["mamba_conv"] == ((2, 5, 3, 96), jnp.float32)
    pools = {name: 0.1 + jnp.arange(np.prod(shape), dtype=jnp.float32
                                    ).reshape(shape).astype(dt) % 3.0
             for name, (shape, dt) in shapes.items()}
    before = {k: np.asarray(v) for k, v in pools.items()}
    n = jnp.asarray([40, 40, 0], jnp.int32)
    slots = jnp.asarray([3, 1, 4], jnp.int32)       # 4: the scratch slot
    fwd = Fwd(shape=(3, 40), n_tokens=n, ropes={}, pools=pools,
              first_layer={"mamba2": 1}, state_slots=slots,
              fresh=jnp.asarray([True, False, False]))
    got = mamba2.paged(cfg, fwd)(h1[:, :40], lp, 0)
    zero = _zero_state(cfg, 1)
    want0, tail0, state0 = mamba2.mamba2_mixer(
        cfg, h1[:1, :40], lp, zero["mamba_conv"], zero["mamba_ssm"], n[:1])
    want1, tail1, state1 = mamba2.mamba2_mixer(
        cfg, h1[1:2, :40], lp, before["mamba_conv"][1, 1][None],
        before["mamba_ssm"][1, 1][None], n[1:2])
    assert close(got[:1], want0, 2e-5) and close(got[1:2], want1, 2e-5)
    after = {k: np.asarray(v) for k, v in pools.items()}
    assert close(after["mamba_ssm"][1, 3], state0[0], 2e-5)
    assert close(after["mamba_ssm"][1, 1], state1[0], 2e-5)
    assert close(after["mamba_conv"][1, 3], tail0[0])
    for name in after:
        assert (after[name][0] == before[name][0]).all()        # layer 0
        assert (after[name][1, [0, 2, 4]]
                == before[name][1, [0, 2, 4]]).all()


# --------------------------------------------- the step where the state lies

def _slot_case(case, L=3, NS=7, N=6, H=4, P=8, G=2, S=16):
    """A pool of ``NS`` slots (the last the scratch slot) a layer and a
    bucket of ``N`` rows: (pool, slots, n_tokens, fresh, step inputs)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    pool = jax.random.normal(ks[0], (L, NS, H, P, S))
    step = dict(x=jax.random.normal(ks[1], (N, H, P)),
                dt=jax.nn.softplus(jax.random.normal(ks[2], (N, H))),
                A=-jnp.arange(1, H + 1, dtype=jnp.float32),
                B=jax.random.normal(ks[3], (N, G, S)),
                C=jax.random.normal(ks[4], (N, G, S)),
                D=1.0 + 0.1 * jax.random.normal(ks[5], (H,)))
    scratch = NS - 1
    n = [1] * N
    slots = [4, 0, 5, 2, 1, 3]          # out of order
    fresh = [False] * N
    if case == "padding_among_live":    # no prefix: padding first and between
        n = [0, 1, 0, 0, 1, 1]
    elif case == "padding_last":
        n = [1, 1, 1, 0, 0, 0]
    elif case == "fresh_over_garbage":
        fresh = [False, True, False, True, False, False]
        pool = pool.at[:, 0].set(jnp.nan).at[:, 2].set(1e30)
    elif case == "one_live_row":
        n = [0, 0, 0, 0, 1, 0]
        fresh = [True] * N              # what start_pos 0 makes of padding
    elif case == "no_live_row":
        n = [0] * N
        fresh = [True] * N
    elif case == "step_of_zero":
        step["dt"] = step["dt"].at[jnp.asarray([1, 4])].set(0.0)
    slots = [s if live else scratch for s, live in zip(slots, n)]
    return (pool, jnp.asarray(slots, jnp.int32), jnp.asarray(n, jnp.int32),
            jnp.asarray(fresh), step)


def _gather_step_scatter(pool, layer, slots, n, fresh, step):
    """The oracle: the plain step on a gathered copy, scattered back."""
    live = n > 0
    state = jnp.where((fresh & live)[:, None, None, None], 0,
                      pool[layer, slots])
    y, state = ssd.ssd_step(step["x"], jnp.where(live[:, None], step["dt"], 0),
                            step["A"], step["B"], step["C"], step["D"], state)
    return y, pool.at[layer, slots].set(state)


SLOT_CASES = ("all_live", "padding_among_live", "padding_last",
              "fresh_over_garbage", "one_live_row", "no_live_row",
              "step_of_zero")


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("case", SLOT_CASES)
def test_the_kernel_steps_the_state_where_it_lies(case, layer, monkeypatch):
    """``mamba2_step`` interpreted against gather, ``ssd_step``, scatter:
    ``y`` of the live rows and their slots to float32 round-off; the
    scratch slot, every slot no live row names and every other layer bit
    for bit what they were; the layer index traced."""
    monkeypatch.setattr(ssd, "_FORCE_INTERPRET", True)
    pool, slots, n, fresh, step = _slot_case(case)
    before = np.asarray(pool)
    want_y, want_pool = _gather_step_scatter(pool, layer, slots, n, fresh,
                                             step)
    got_y, got_pool = jax.jit(
        lambda pool, layer: ssd.ssd_step_slots(
            pool, layer, slots, n, fresh, **step))(pool, jnp.int32(layer))
    got_pool, live = np.asarray(got_pool), np.asarray(n) > 0
    named = np.asarray(slots)[live]
    if live.any():
        assert close(got_y[live], want_y[live])
        assert close(got_pool[layer, named], np.asarray(want_pool)[layer,
                                                                   named])
    assert (np.asarray(got_y)[~live] == 0).all()
    others = np.setdiff1d(np.arange(pool.shape[1]), named)
    same = lambda a, b: np.array_equal(a, b, equal_nan=True)    # noqa: E731
    assert same(got_pool[layer, others], before[layer, others])
    for other in set(range(pool.shape[0])) - {layer}:
        assert same(got_pool[other], before[other])
    if case == "step_of_zero":
        kept = np.asarray(slots)[[1, 4]]
        assert same(got_pool[layer, kept], before[layer, kept])


def test_off_the_chip_the_plain_form_gives_the_same(monkeypatch):
    pool, slots, n, fresh, step = _slot_case("padding_among_live")
    plain = ssd.ssd_step_slots(pool, 1, slots, n, fresh, **step)
    monkeypatch.setattr(ssd, "_FORCE_INTERPRET", True)
    kernel = ssd.ssd_step_slots(pool, 1, slots, n, fresh, **step)
    assert close(kernel[0], plain[0]) and close(kernel[1], plain[1])


def test_a_one_token_forward_moves_no_state_sized_copy(layer, monkeypatch):
    """The ``[S, 1]`` program as it is lowered for the chip: the state
    leaf goes to ``mamba2_step`` as it lies and comes back from it -- no
    gather, scatter or dynamic-update-slice has a state-sized operand
    (the chunked form still gathers and scatters its one row's)."""
    import re

    from deepspeed_tpu.ops import pallas_utils

    cfg, lp, h1 = layer
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    shapes = mamba2.state(cfg, 5)
    state = "x".join(map(str, shapes["mamba_ssm"][0][2:])) + "xf32"

    def forward(T):
        def run(pools, h1, n, slots, first):
            fwd = Fwd(shape=(3, T), n_tokens=n, ropes={}, pools=pools,
                      first_layer={"mamba2": first}, state_slots=slots,
                      fresh=n > 1)
            return mamba2.paged(cfg, fwd)(h1, lp, 0), pools
        pools = {name: jnp.zeros(shape, dt)
                 for name, (shape, dt) in shapes.items()}
        text = jax.jit(run).trace(
            pools, h1[:, :T], jnp.ones((3,), jnp.int32),
            jnp.arange(3, dtype=jnp.int32), jnp.int32(1)
        ).lower(lowering_platforms=("tpu",)).as_text()
        return [m.group(0) for m in re.finditer(
            r"stablehlo\.(gather|scatter|dynamic_update_slice)\b.*?"
            r"-> tensor<[^>]*>", text, re.S) if state in m.group(0)], text

    moved, text = forward(1)
    assert not moved, moved
    assert text.count("tpu_custom_call") == 1 and "mamba2_step" in text
    assert forward(40)[0]       # what the assertion would catch


# ---------------------------------------- positions of the period, the engine

def test_a_position_is_a_mixer_an_ffn_or_both():
    cfg = TransformerConfig(**ARCH)
    assert kinds_of(cfg) == ("full", "mamba2")
    assert (cfg.layers_of("mamba2"), cfg.layers_of("full")) == (2, 1)
    assert (cfg.num_sparse_layers, cfg.num_linear_layers,
            cfg.num_attn_layers) == (3, 2, 1)
    assert [cfg.ffn_at(i) for i in range(5)] == [False, True, False, True,
                                                 True]
    shapes = jax.eval_shape(CausalLM(cfg).init, jax.random.PRNGKey(0))
    slots = shapes["layers"]
    # one norm a part: a mixer alone has no FFN's, an FFN alone no mixer's
    assert "mlp_norm_w" not in slots["slot0"] and "w_in" not in slots["slot0"]
    assert "attn_norm_w" not in slots["slot1"] \
        and "mamba_w_in" not in slots["slot1"]
    assert {"attn_norm_w", "mlp_norm_w", "mamba_w_in", "w_in"} \
        <= set(slots["slot3"])
    # ungated experts in the latent, an ungated shared expert on the
    # full width, the latent's two projections
    assert "w_gate" not in slots["slot1"] \
        and "shared_w_gate" not in slots["slot1"]
    assert slots["slot1"]["w_in"].shape == (1, 4, 16, 24)
    assert slots["slot1"]["w_out"].shape == (1, 4, 24, 16)
    assert slots["slot1"]["shared_w_in"].shape == (1, 32, 40)
    assert slots["slot1"]["latent_w_in"].shape == (1, 32, 16)
    assert slots["slot1"]["latent_w_out"].shape == (1, 16, 32)
    specs = CausalLM(cfg).param_specs()["layers"]
    assert jax.tree.structure(jax.tree.map(lambda a: 0, slots)) \
        == jax.tree.structure(jax.tree.map(
            lambda a: 0, specs, is_leaf=lambda s: not isinstance(s, dict)))


@pytest.mark.parametrize("change", [
    dict(layer_ffn=(False, True, False, True)),             # one short
    dict(layer_ffn=(False, False, False, True, True)),      # neither part
    dict(layer_pattern=("mamba2", "ffn", "full", "mamba2", None)),
    dict(moe_activation="gelu"),
    dict(moe_num_experts=0),    # relu2 and a latent are the sparse FFN's
    dict(mamba_n_groups=3),
    dict(mamba_state_size=0),
])
def test_sizes_the_block_cannot_run_are_refused(change):
    with pytest.raises(ValueError):
        TransformerConfig(**dict(ARCH, **change))


def test_configurations_without_the_new_fields_keep_their_trees():
    old = dict(ARCH, layer_pattern=("full", "full"), layer_ffn=None,
               num_layers=4, moe_activation="silu", moe_latent_size=0)
    shapes = jax.eval_shape(CausalLM(TransformerConfig(**old)).init,
                            jax.random.PRNGKey(0))
    assert set(shapes["layers"]["slot0"]) == {
        "attn_norm_w", "mlp_norm_w", "wq", "wk", "wv", "wo", "router_wg",
        "router_b", "w_in", "w_gate", "w_out", "shared_w_in",
        "shared_w_gate", "shared_w_out"}
    assert shapes["layers"]["slot0"]["w_in"].shape == (2, 4, 32, 24)


OLDER_TWINS = ("dots3-note-prev", "minicpm-sala", "openpangu-ultra-moe-718b",
               "trinity-large-preview", "qwen3-next-80b-a3b")


@pytest.mark.parametrize("name", OLDER_TWINS)
def test_an_older_hybrid_twin_builds_and_runs_with_layer_ffn_unset(name):
    """``run_period`` and ``init_slot`` read the per-position field on
    every hybrid configuration: unset, every position carries its mixer
    and its FFN as before (a first draft raised ``len(None)`` here and no
    test of the new model saw it)."""
    import json
    import os
    import sys

    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark")
    path = os.path.join(here, "twins", "configs", name + ".json")
    if os.path.exists(path):
        with open(path) as f:
            arch = json.load(f)["transformer_config"]
    else:
        sys.path.insert(0, here)
        try:
            from qwen3_next_tiny import TINY_QWEN3_NEXT
        finally:
            sys.path.remove(here)
        arch = TINY_QWEN3_NEXT["transformer_config"]
    assert "layer_ffn" not in arch and None not in arch["layer_pattern"]
    cfg = TransformerConfig(**dict(arch, dtype=jnp.float32))
    assert cfg.layer_ffn is None
    assert all(cfg.ffn_at(i) for i in range(len(cfg.layer_pattern)))
    model = CausalLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    for i in range(len(cfg.layer_pattern)):
        assert {"attn_norm_w", "mlp_norm_w"} <= set(
            shapes["layers"][f"slot{i}"])
    logits = jax.jit(model.apply)(model.init(jax.random.PRNGKey(1)),
                                  jnp.arange(24, dtype=jnp.int32)[None] % 7)
    assert logits.shape == (1, 24, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.fixture(scope="module")
def served():
    """Two sequences through the engine, prefilled in chunks (one beside
    the other's decode steps) and decoded, against the model's own
    forward over each whole sequence."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)

    cfg = TransformerConfig(**ARCH)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(4)
    seqs = {7: rng.integers(0, 96, size=77).tolist(),
            9: rng.integers(0, 96, size=50).tolist()}
    with jax.default_matmul_precision("highest"):
        want = {u: np.asarray(jax.jit(model.apply)(
            params, jnp.asarray(t)[None]))[0] for u, t in seqs.items()}
    engine = InferenceEngineV2(model, params=params,
                               config=RaggedInferenceEngineConfig(
                                   kv_block_size=8, kv_blocks=64,
                                   max_ragged_sequence_count=4,
                                   max_chunk_tokens=32,
                                   max_ragged_batch_size=96,
                                   compile_ahead=0))
    sm = engine.state_manager
    got = {7: [], 9: []}
    # 7: 32 + 32 + 6 prompt tokens then 5 steps; 9 arrives while 7 decodes
    for a, b in ((0, 32), (32, 64), (64, 70)):
        out = engine.put([7], [seqs[7][a:b]])
    got[7].append((69, np.asarray(out[0])))
    out = engine.put([7, 9], [[seqs[7][70]], seqs[9][:32]])
    got[7].append((70, np.asarray(out[0])))
    split_put = dict(engine.last_put)
    out = engine.put([9, 7], [seqs[9][32:45], [seqs[7][71]]])
    got[9].append((44, np.asarray(out[0])))
    got[7].append((71, np.asarray(out[1])))
    for t in range(45, 50):
        out = engine.put([7, 9], [[seqs[7][t + 27]], [seqs[9][t]]])
        got[7].append((t + 27, np.asarray(out[0])))
        got[9].append((t, np.asarray(out[1])))
    last = dict(engine.last_put)
    used = sm.state_slots - sm.free_state_slots
    totals = dict(engine.put_totals)
    engine.flush(7)
    engine.flush(9)
    free = (sm.allocator.free_blocks, sm.free_state_slots)
    total = (sm.allocator.total_blocks, sm.state_slots)
    return dict(cfg=cfg, got=got, want=want, totals=totals, last=last,
                split_put=split_put, used=used, free=free, total=total,
                shapes={k: v.shape for k, v in sm.forward_cache.items()})


def test_the_engine_serves_the_model_through_the_cache(served):
    for uid, rows in served["got"].items():
        want = served["want"][uid]
        span = want.max() - want.min()
        for at, logits in rows:
            assert np.abs(logits - want[at]).max() < 2e-6 * span, (uid, at)
    # one attention layer's pool; two Mamba-2 layers' state, four slots
    # and a scratch
    assert served["shapes"] == {
        "k": (1, 64, 2, 8, 8), "v": (1, 64, 2, 8, 8),
        "mamba_ssm": (2, 5, 4, 8, 16), "mamba_conv": (2, 5, 3, 96)}


def test_the_counters_say_what_went_through_which_form(served):
    totals, cfg = served["totals"], served["cfg"]
    assert set(KINDS["mamba2"].totals) <= set(PUT_TOTALS)
    state = 4 * 8 * 16 * 4                      # heads x P x S x float32
    assert mamba2.state_bytes(cfg) == state
    # 7's 70 prompt tokens and 9's 45 in chunks; 7 steps of 7's, 5 of 9's
    assert totals["ssm_chunk_tokens"] == 70 + 45
    assert totals["ssm_rows_stepped"] == 7 + 5
    assert totals["tokens_valid"] == 115 + 12
    rows = 3 + 1 + 1 + 7 + 5        # a row a forward it was in
    assert totals["ssm_state_bytes"] == rows * 2 * 2 * state
    # the last put: both rows through the step
    assert served["last"]["ssm_rows_stepped"] == 2
    assert served["last"]["ssm_chunk_tokens"] == 0
    assert served["last"]["ssm_state_bytes"] == 2 * 2 * 2 * state
    assert served["last"]["state_slots_used"] == served["used"] == 2
    # a put of a chunk row beside a one-token row ran as two forwards,
    # and its record sums what they counted
    assert served["split_put"]["forwards"] == 2
    assert served["split_put"]["ssm_chunk_tokens"] == 32
    assert served["split_put"]["ssm_rows_stepped"] == 1
    assert served["split_put"]["ssm_state_bytes"] == 2 * 2 * 2 * state
    # three of the five positions carry an FFN: top-3 of 8, 4 held
    assert totals["moe_rows_routed"] == 127 * 3 * 3
    assert totals["moe_rows_held"] == sum(
        n * 9 * 4 // 8 for n in (32, 32, 6, 1, 32, 13, 1) + (2,) * 5)


def test_every_block_and_state_slot_comes_back(served):
    assert served["free"] == served["total"]


def test_no_caller_names_the_kind():
    """The ninth kind is a module, a line in ``KINDS`` and its config
    fields: what reads a model's kinds folds over the registry."""
    import inspect

    from deepspeed_tpu.inference.v2 import engine_v2, paged_model
    from deepspeed_tpu.inference.v2.ragged import manager
    from deepspeed_tpu.serving import metrics, replica

    for module in (hybrid, engine_v2, paged_model, manager, metrics,
                   replica):
        source = inspect.getsource(module)
        assert "mamba" not in source.lower(), module.__name__
    assert "mamba2" in KINDS
