"""MoE tests (reference tests/unit/moe/test_moe.py: gating correctness,
capacity, dispatch round-trip, expert-parallel training)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.moe import MoE, TopKGate, top1gating, top2gating
from deepspeed_tpu.moe.sharded_moe import moe_dispatch_combine, _capacity
from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.transformer import TINY_TEST, CausalLM
import dataclasses


def test_capacity():
    assert _capacity(64, 8, 1.0, 4) == 8
    assert _capacity(64, 8, 2.0, 4) == 16
    assert _capacity(8, 8, 0.5, 4) == 4  # min_capacity floor


def test_top1_dispatch_shapes_and_exclusivity():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
    l_aux, combine, dispatch, counts = top1gating(logits, capacity_factor=2.0)
    S, E, C = combine.shape
    assert (S, E) == (32, 4)
    # each token goes to at most one (expert, slot)
    assert np.all(np.asarray(dispatch).sum(axis=(1, 2)) <= 1)
    # aux loss near 1 for uniform routing
    assert 0.5 < float(l_aux) < 4.0
    assert int(np.asarray(counts).sum()) == 32


def test_top1_capacity_drops_tokens():
    # all tokens prefer expert 0 → capacity truncates
    logits = jnp.tile(jnp.asarray([[10.0, 0.0]]), (16, 1))
    l_aux, combine, dispatch, counts = top1gating(logits, capacity_factor=0.5,
                                                  min_capacity=4)
    kept = np.asarray(dispatch).sum()
    assert kept == 4 + 0  # capacity 4 on expert 0, none elsewhere


def test_top2_routes_two_experts():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
    l_aux, combine, dispatch, counts = top2gating(logits, capacity_factor=2.0)
    per_token = np.asarray(dispatch).sum(axis=(1, 2))
    assert per_token.max() <= 2
    assert per_token.mean() > 1.0
    # combine weights per token sum to ~1 when both kept
    w = np.asarray(combine).sum(axis=(1, 2))
    np.testing.assert_allclose(w[per_token == 2], 1.0, atol=1e-5)


def test_dispatch_combine_identity_expert():
    """With identity experts and top-1 full capacity, y == gate_prob * x."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    l_aux, combine, dispatch, _ = top1gating(logits, capacity_factor=4.0)
    y = moe_dispatch_combine(x, combine, dispatch, lambda e: e)
    gates = np.asarray(jax.nn.softmax(logits, axis=-1).max(axis=-1))
    np.testing.assert_allclose(np.asarray(y), gates[:, None] * np.asarray(x),
                               rtol=1e-5, atol=1e-6)


def test_moe_layer_forward_backward():
    moe = MoE(hidden_size=32, intermediate_size=64, num_experts=4, k=2,
              capacity_factor=2.0, activation="silu")
    params = moe.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 32)).astype(np.float32))

    def loss(p):
        y, l_aux, _ = moe.apply(p, x)
        return jnp.mean(jnp.square(y)) + 0.01 * l_aux

    g = jax.grad(loss)(params)
    for leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
    # router receives gradient
    assert float(jnp.abs(g["gate"]["wg"]).sum()) > 0


def test_moe_transformer_trains_with_expert_parallel():
    """End-to-end: MoE model on a mesh with expert axis = 2."""
    cfg = dataclasses.replace(TINY_TEST, moe_num_experts=4, moe_top_k=1,
                              moe_capacity_factor=2.0)
    model = CausalLM(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "mesh": {"data": -1, "expert": 2},
        "steps_per_print": 100,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    # expert dim sharded over expert axis
    w_in = engine.state.params["layers"]["w_in"]
    assert "expert" in str(w_in.sharding.spec)

    rng = np.random.default_rng(0)
    data = {"input_ids": rng.integers(
        0, cfg.vocab_size, size=(engine.train_batch_size(), 33), dtype=np.int64)}
    losses = []
    for _ in range(6):
        loss = engine(data)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------- dropless
def test_dropless_matches_per_expert_loop():
    """ragged_dot grouped GEMM == explicit per-expert computation."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.grouped import dropless_moe_mlp

    rng = np.random.default_rng(0)
    N, H, M, E = 24, 8, 16, 4
    tokens = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(N, E)).astype(np.float32))
    w_in = jnp.asarray(rng.normal(size=(E, H, M)).astype(np.float32)) * 0.2
    w_out = jnp.asarray(rng.normal(size=(E, M, H)).astype(np.float32)) * 0.2
    w_gate = jnp.asarray(rng.normal(size=(E, H, M)).astype(np.float32)) * 0.2

    out, l_aux = dropless_moe_mlp(tokens, logits, w_in, w_out, w_gate,
                                  activation="silu")

    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    expert = np.asarray(jnp.argmax(logits, axis=-1))
    ref = np.zeros((N, H), np.float32)
    for i in range(N):
        e = expert[i]
        t = np.asarray(tokens[i])
        h = (1 / (1 + np.exp(-t @ np.asarray(w_gate[e])))) \
            * (t @ np.asarray(w_gate[e])) * (t @ np.asarray(w_in[e]))
        ref[i] = (h @ np.asarray(w_out[e])) * probs[i, e]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)
    assert np.isfinite(float(l_aux))


def test_dropless_no_tokens_dropped_under_imbalance():
    """Every token contributes even when one expert gets most of them
    (the capacity path would drop overflow)."""
    import jax.numpy as jnp

    from deepspeed_tpu.moe.grouped import dropless_moe_mlp

    rng = np.random.default_rng(1)
    N, H, M, E = 32, 8, 16, 4
    tokens = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32))
    logits = jnp.zeros((N, E)).at[:, 0].set(10.0)   # all to expert 0
    w_in = jnp.asarray(rng.normal(size=(E, H, M)).astype(np.float32))
    w_out = jnp.asarray(rng.normal(size=(E, M, H)).astype(np.float32))
    out, _ = dropless_moe_mlp(tokens, logits, w_in, w_out, None,
                              activation="gelu")
    assert (np.abs(np.asarray(out)).sum(axis=-1) > 0).all()


def test_dropless_causal_lm_trains(devices8):
    import dataclasses

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM, TINY_TEST

    model = CausalLM(dataclasses.replace(
        TINY_TEST, num_kv_heads=4, moe_num_experts=4, moe_dropless=True))
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "mesh": {"data": -1, "fsdp": 1},
        "steps_per_print": 10**9,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 256, size=(32, 33),
                                       dtype=np.int64)}
    import itertools
    losses = [float(engine.train_batch(itertools.repeat(batch)))
              for _ in range(6)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_dropless_ep_matches_single_shard(devices8):
    """Expert-parallel dropless (gather → per-shard ragged_dot →
    psum_scatter under the partial-manual expert shard_map) reproduces the
    single-shard dropless output and aux loss exactly."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.moe.grouped import dropless_moe_mlp, dropless_moe_mlp_ep

    mesh = Mesh(np.array(devices8).reshape(2, 4), ("expert", "data"))
    rng = np.random.default_rng(3)
    N, H, M, E = 32, 8, 16, 4
    tokens = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(N, E)).astype(np.float32))
    w_in = jnp.asarray(rng.normal(size=(E, H, M)).astype(np.float32)) * 0.2
    w_out = jnp.asarray(rng.normal(size=(E, M, H)).astype(np.float32)) * 0.2
    w_gate = jnp.asarray(rng.normal(size=(E, H, M)).astype(np.float32)) * 0.2

    ref, aux_ref = dropless_moe_mlp(tokens, logits, w_in, w_out, w_gate,
                                    activation="silu")
    tok_s = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
    espec = NamedSharding(mesh, P("expert", None, None))
    out, aux = jax.jit(
        lambda t, lg, wi, wo, wg: dropless_moe_mlp_ep(
            t, lg, wi, wo, wg, mesh=mesh, activation="silu"))(
        tok_s, logits, jax.device_put(w_in, espec),
        jax.device_put(w_out, espec), jax.device_put(w_gate, espec))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


def test_dropless_ep_no_gate_and_imbalance(devices8):
    """EP dropless without SwiGLU, all tokens on one expert shard: no
    token dropped, other shard contributes exact zeros."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.moe.grouped import dropless_moe_mlp, dropless_moe_mlp_ep

    mesh = Mesh(np.array(devices8).reshape(2, 4), ("expert", "data"))
    rng = np.random.default_rng(4)
    N, H, M, E = 16, 8, 16, 4
    tokens = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32))
    logits = jnp.zeros((N, E)).at[:, 1].set(9.0)    # all → expert 1 (shard 0)
    w_in = jnp.asarray(rng.normal(size=(E, H, M)).astype(np.float32))
    w_out = jnp.asarray(rng.normal(size=(E, M, H)).astype(np.float32))
    ref, _ = dropless_moe_mlp(tokens, logits, w_in, w_out, None,
                              activation="gelu")
    espec = NamedSharding(mesh, P("expert", None, None))
    out, _ = jax.jit(
        lambda t, lg, wi, wo: dropless_moe_mlp_ep(
            t, lg, wi, wo, None, mesh=mesh, activation="gelu"))(
        jax.device_put(tokens, NamedSharding(mesh, P("data", None))),
        logits, jax.device_put(w_in, espec), jax.device_put(w_out, espec))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    assert (np.abs(np.asarray(out)).sum(axis=-1) > 0).all()


def test_dropless_ep_causal_lm_matches_capacity_loss(devices8):
    """A dropless-EP CausalLM on an expert=2 mesh trains, and its loss
    matches the capacity path at a capacity factor high enough that no
    token drops (top-1: both paths then compute the same function)."""
    import itertools

    losses = {}
    for dropless in (True, False):
        model = CausalLM(dataclasses.replace(
            TINY_TEST, num_kv_heads=4, moe_num_experts=4,
            moe_dropless=dropless, moe_capacity_factor=4.0))
        cfg = {
            "train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "mesh": {"data": -1, "expert": 2},
            "steps_per_print": 10**9,
        }
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, 256, size=(32, 33),
                                           dtype=np.int64)}
        losses[dropless] = [
            float(engine.train_batch(itertools.repeat(batch)))
            for _ in range(4)]
    assert np.isfinite(losses[True]).all()
    assert losses[True][-1] < losses[True][0]
    # same function at non-dropping capacity → same training trajectory
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=2e-4, atol=2e-4)


def test_registry_picks_dropless_under_ep():
    """The v2 module registry routes moe_dropless + expert_parallel>1 to
    the EP grouped-GEMM implementation (the r4 exclusion is gone)."""
    from deepspeed_tpu.inference.v2.modules import DSModuleRegistry
    from deepspeed_tpu.parallel import topology as topo

    from functools import partial

    from deepspeed_tpu.moe.grouped import dropless_moe_mlp_ep

    t = topo.MeshTopology.build(expert=2, data=-1)
    topo.set_topology(t)
    try:
        fn = DSModuleRegistry.instantiate(
            "moe", moe_dropless=True, expert_parallel=2)
        assert isinstance(fn, partial) and fn.func is dropless_moe_mlp_ep
    finally:
        topo.reset_topology()


# ----------------------------------- a period's experts in their stack

def _stack(rng, periods, n, k, m):
    return jnp.asarray(rng.normal(size=(periods, n, k, m)).astype(np.float32))


def _sizes_at(sizes, p, periods):
    """``sizes`` [n] as period p's share of the group sizes of a stack of
    ``periods`` x n groups: every other period's groups are empty."""
    n = sizes.shape[0]
    return jnp.zeros((periods * n,), jnp.int32).at[p * n:(p + 1) * n].set(
        sizes)


@pytest.mark.parametrize("p", range(3))
@pytest.mark.parametrize("sizes", [
    pytest.param([5, 3, 8, 4], id="every_row"),
    pytest.param([2, 0, 7, 0], id="empty_groups_and_rows_behind"),
    pytest.param([0, 0, 0, 20], id="one_group"),
    pytest.param([0, 0, 0, 0], id="no_rows")])
def test_grouped_matmul_indexes_a_period_in_its_stack(p, sizes):
    """``grouped_matmul`` over the whole stack [P · n, k, m] with the
    period's group sizes at ``p · n`` is, bit for bit, ``grouped_matmul``
    over ``stack[p]``: the rows of the period's groups (what lies behind
    the last group is undefined either way)."""
    from deepspeed_tpu.moe.grouped import grouped_matmul

    rng = np.random.default_rng(7)
    P, n, k, m = 3, 4, 16, 24
    stack = _stack(rng, P, n, k, m)
    lhs = jnp.asarray(rng.normal(size=(20, k)).astype(np.float32))
    sizes = jnp.asarray(sizes, jnp.int32)
    whole = grouped_matmul(lhs, stack.reshape(P * n, k, m),
                           _sizes_at(sizes, p, P))
    own = grouped_matmul(lhs, stack[p], sizes)
    rows = int(sizes.sum())
    np.testing.assert_array_equal(np.asarray(whole)[:rows],
                                  np.asarray(own)[:rows])
    assert whole.shape == own.shape == (20, m)


@pytest.mark.parametrize("p", range(2))
@pytest.mark.parametrize("held,activation,padded", [
    (None, "silu", False), ((2, 4), "silu", True), ((0, 8), "reglu", True),
    ((4, 4), "relu2", False), (None, "relu2", True)])
def test_dropless_indexes_a_period_in_its_stacks(p, held, activation, padded):
    """``dropless_moe_mlp(period=p)`` on the stacks of two periods'
    experts, traced as a scan's body would call it, is bit for bit the
    call on the period's own leaves — all experts or a held share, gated
    or not, with padding rows under a bound on the valid ones."""
    from deepspeed_tpu.moe.grouped import GATED, dropless_moe_mlp

    rng = np.random.default_rng(11)
    N, H, M, E, P = 24, 8, 16, 8, 2
    n = E if held is None else held[1]
    tokens = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(N, E)).astype(np.float32))
    w_in, w_out = _stack(rng, P, n, H, M), _stack(rng, P, n, M, H)
    w_gate = _stack(rng, P, n, H, M) if activation in GATED else None
    kw = dict(activation=activation, top_k=3, renormalize=True, held=held)
    if padded:
        kw.update(valid=jnp.arange(N) % 3 != 0, max_rows=16)

    def flat(w):
        return None if w is None else w.reshape((P * n,) + w.shape[2:])

    whole, aux = jax.jit(lambda period: dropless_moe_mlp(
        tokens, logits, flat(w_in), flat(w_out), flat(w_gate),
        period=period, **kw))(jnp.int32(p))
    own, aux_own = jax.jit(lambda: dropless_moe_mlp(
        tokens, logits, w_in[p], w_out[p],
        None if w_gate is None else w_gate[p], **kw))()
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(own))
    assert float(aux) == float(aux_own)
    assert np.abs(np.asarray(own)).max() > 0


def test_dropless_refuses_stacks_that_are_no_whole_periods():
    from deepspeed_tpu.moe.grouped import dropless_moe_mlp

    w = jnp.zeros((6, 8, 16), jnp.float32)
    with pytest.raises(ValueError, match="no whole periods of 4"):
        dropless_moe_mlp(jnp.zeros((4, 8)), jnp.zeros((4, 8)), w,
                         jnp.zeros((6, 16, 8)), held=(0, 4), period=0)


@pytest.mark.parametrize("p", range(2))
def test_the_pallas_gmm_gives_other_periods_groups_no_tile(p):
    """What ``grouped_matmul`` counts on of the kernel JAX ships, held
    here in interpret mode: with the period's group sizes in a vector
    over the whole stack's groups, the grid has as many active tiles as
    over the period's own experts (an empty group gets none, in front of
    the period's or behind), each reads the stack at the period's index,
    and the rows are the same."""
    import importlib

    # (the package's ``gmm`` is its differentiable wrapper, not the module)
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    rng = np.random.default_rng(5)
    P, n, k, m, rows = 2, 4, 128, 128, 256
    stack = _stack(rng, P, n, k, m)
    lhs = jnp.asarray(rng.normal(size=(rows, k)).astype(np.float32))
    sizes = jnp.asarray([5, 0, 130, 20], jnp.int32)

    def tiles(group_sizes):
        (_, group_ids, _), active = megablox.make_group_metadata(
            group_sizes=group_sizes, m=rows, tm=128, start_group=jnp.int32(0),
            num_nonzero_groups=group_sizes.shape[0],
            visit_empty_groups=False)
        return np.asarray(group_ids)[:int(active)]

    np.testing.assert_array_equal(tiles(_sizes_at(sizes, p, P)),
                                  tiles(sizes) + p * n)
    whole, own = (megablox.gmm(lhs, rhs, gs,
                               preferred_element_type=jnp.float32,
                               tiling=(128, 128, 128), interpret=True)
                  for rhs, gs in ((stack.reshape(P * n, k, m),
                                   _sizes_at(sizes, p, P)),
                                  (stack[p], sizes)))
    np.testing.assert_array_equal(np.asarray(whole)[:155],
                                  np.asarray(own)[:155])
