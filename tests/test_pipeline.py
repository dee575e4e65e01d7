"""Pipeline parallelism tests (reference tests/unit/runtime/pipe/
test_topology.py and test_pipe_schedule.py, plus SPMD pipeline execution)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TINY_TEST
from deepspeed_tpu.parallel import topology as topo
from deepspeed_tpu.parallel.pipeline import pipelined_layer_apply
from deepspeed_tpu.runtime.pipe import (
    ProcessTopology, PipeModelDataParallelTopology, TrainSchedule,
    InferenceSchedule, ForwardPass, BackwardPass, LoadMicroBatch,
    OptimizerStep, LayerSpec, PipelineModule)
from deepspeed_tpu.runtime.pipe.module import partition_balanced


# ---------------------------------------------------------------- topology
def test_process_topology_rank_mapping():
    t = ProcessTopology(axes=["pipe", "data"], dims=[2, 4])
    assert t.world_size() == 8
    assert t.get_rank(pipe=0, data=0) == 0
    assert t.get_rank(pipe=0, data=3) == 3
    assert t.get_rank(pipe=1, data=0) == 4
    assert t.get_coord(5) == t.ProcessCoord(pipe=1, data=1)


def test_axis_comm_lists():
    t = PipeModelDataParallelTopology(num_pp=2, num_mp=2, num_dp=2)
    pipe_groups = t.get_axis_comm_lists("pipe")
    assert len(pipe_groups) == 4
    for g in pipe_groups:
        assert len(g) == 2
    assert t.filter_match(pipe=0) == [0, 1, 2, 3]


def test_rank_repr():
    t = PipeModelDataParallelTopology(num_pp=2, num_mp=2, num_dp=1)
    r = t.get_rank_repr(t.get_rank(pipe=1, data=0, model=1))
    assert "pipe_01" in r and "model_01" in r


# ---------------------------------------------------------------- schedules
def test_inference_schedule_covers_all_microbatches():
    sched = InferenceSchedule(micro_batches=4, stages=2, stage_id=0)
    steps = sched.steps()
    fwd = [c for cmds in steps for c in cmds if isinstance(c, ForwardPass)]
    loads = [c for cmds in steps for c in cmds if isinstance(c, LoadMicroBatch)]
    assert len(fwd) == 4
    assert len(loads) == 4  # first stage loads every microbatch


@pytest.mark.parametrize("stages,stage_id", [(2, 0), (2, 1), (4, 2)])
def test_train_schedule_1f1b_counts(stages, stage_id):
    M = 6
    sched = TrainSchedule(micro_batches=M, stages=stages, stage_id=stage_id)
    steps = sched.steps()
    fwd = [c for cmds in steps for c in cmds if isinstance(c, ForwardPass)]
    bwd = [c for cmds in steps for c in cmds if isinstance(c, BackwardPass)]
    opt = [c for cmds in steps for c in cmds if isinstance(c, OptimizerStep)]
    assert len(fwd) == M
    assert len(bwd) == M
    assert len(opt) == 1
    # every microbatch forwarded before its backward
    fwd_order = [c.buffer_id for cmds in steps for c in cmds
                 if isinstance(c, ForwardPass)]
    assert len(fwd_order) == M


# ------------------------------------------------------------- partitioning
def test_partition_balanced_uniform():
    parts = partition_balanced([1.0] * 8, 4)
    assert parts[0] == 0 and parts[-1] == 8
    sizes = [parts[i + 1] - parts[i] for i in range(4)]
    assert sizes == [2, 2, 2, 2]


def test_partition_balanced_weighted():
    # one huge layer should sit alone
    parts = partition_balanced([10.0, 1.0, 1.0, 1.0], 2)
    assert parts == [0, 1, 4]


def test_pipeline_module_stage_assignment():
    class Dummy:
        def __init__(self, n):
            self.n = n

        def num_params(self):
            return self.n

    layers = [LayerSpec(Dummy, 100), LayerSpec(Dummy, 1), LayerSpec(Dummy, 1),
              LayerSpec(Dummy, 100)]
    pm = PipelineModule(layers, num_stages=2, partition_method="parameters")
    assert pm.stage_owner(0) == 0
    assert pm.stage_owner(3) == 1
    assert len(pm.stage_layers(0)) + len(pm.stage_layers(1)) == 4


# ---------------------------------------------------------- SPMD execution
def test_spmd_pipeline_matches_sequential():
    """Pipelined layer apply must equal the plain scan."""
    t = topo.MeshTopology.build(pipe=4, data=-1)
    topo.set_topology(t)
    L, B, T, H = 8, 4, 8, 16
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(L, H, H)).astype(np.float32)) * 0.1
    x = jnp.asarray(rng.normal(size=(B, T, H)).astype(np.float32))

    def layer_fn(carry, wi, micro_idx):
        return jnp.tanh(carry @ wi), jnp.zeros((), jnp.float32)

    out_pipe, _aux = pipelined_layer_apply(layer_fn, w, x, num_micro=4, mesh=t.mesh)

    def seq(x):
        for i in range(L):
            x = jnp.tanh(x @ w[i])
        return x

    np.testing.assert_allclose(np.asarray(out_pipe), np.asarray(seq(x)),
                               rtol=2e-5, atol=2e-5)


def test_spmd_pipeline_grads_match():
    t = topo.MeshTopology.build(pipe=2, data=-1)
    topo.set_topology(t)
    L, B, T, H = 4, 4, 4, 8
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(L, H, H)).astype(np.float32)) * 0.1
    x = jnp.asarray(rng.normal(size=(B, T, H)).astype(np.float32))

    def layer_fn(carry, wi, micro_idx):
        return jnp.tanh(carry @ wi), jnp.zeros((), jnp.float32)

    def loss_pipe(w):
        out, _aux = pipelined_layer_apply(layer_fn, w, x, 2, mesh=t.mesh)
        return jnp.sum(out ** 2)

    def loss_seq(w):
        y = x
        for i in range(L):
            y = jnp.tanh(y @ w[i])
        return jnp.sum(y ** 2)

    g1 = jax.grad(loss_pipe)(w)
    g2 = jax.grad(loss_seq)(w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-4, atol=2e-5)


def test_engine_trains_with_pipeline_parallel():
    cfg = dataclasses.replace(TINY_TEST, num_kv_heads=4)
    model = CausalLM(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "mesh": {"data": -1, "pipe": 2},
        "pipeline": {"stages": 2, "micro_batches": 4},
        "steps_per_print": 100,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    # layer stack sharded over pipe axis
    wq = engine.state.params["layers"]["wq"]
    assert "pipe" in str(wq.sharding.spec)

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


def test_pipeline_matches_unpipelined_loss():
    cfg = dataclasses.replace(TINY_TEST, num_kv_heads=4, pipeline_microbatches=2)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(4, 33), dtype=np.int64))}

    topo.reset_topology()
    topo.set_topology(topo.MeshTopology.build(data=-1))
    loss_dense = float(model.loss(params, batch))

    topo.reset_topology()
    topo.set_topology(topo.MeshTopology.build(pipe=2, data=-1))
    loss_pp = float(model.loss(params, batch))
    np.testing.assert_allclose(loss_pp, loss_dense, rtol=1e-4)


def test_pipeline_moe_aux_loss_nonzero():
    """MoE aux loss must flow out of the pipelined path (not silently zero)."""
    cfg = dataclasses.replace(TINY_TEST, num_kv_heads=4, moe_num_experts=4,
                              moe_capacity_factor=2.0, pipeline_microbatches=2)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(4, 32), dtype=np.int64))

    topo.reset_topology()
    topo.set_topology(topo.MeshTopology.build(pipe=2, data=-1))
    _, aux = model.apply(params, batch, return_aux=True)
    assert float(aux) > 0, "pipelined MoE aux loss is zero"

    topo.reset_topology()
    topo.set_topology(topo.MeshTopology.build(data=-1))
    _, aux_dense = model.apply(params, batch, return_aux=True)
    # microbatched gating differs slightly from full-batch gating, but the
    # magnitudes must agree
    np.testing.assert_allclose(float(aux), float(aux_dense), rtol=0.3)
