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
