"""DeepSpeedTpuEngine — the core training runtime.

Counterpart of reference ``runtime/engine.py:175`` (``DeepSpeedEngine``):
same lifecycle (``forward`` :1757 / ``backward`` :1898 / ``step`` :2096,
gradient accumulation, clipping, dynamic fp16 loss scaling
``runtime/fp16/loss_scaler.py:91``, checkpoint save/load :3006/:2657,
throughput + wall-clock timers) — re-designed around XLA:

- The train state (master fp32 params, optimizer moments, gradient
  accumulator, loss-scale state, counters) is one pytree whose shardings are
  produced by the ZeRO plan (``parallel/sharding.py``). ZeRO stages 1/2/3 are
  *out_shardings*, not subsystems.
- ``forward`` runs a single jitted fwd+bwd+accumulate program (a functional
  runtime cannot split autograd across host calls without recomputing;
  ``backward(loss)`` is the API-parity no-op that advances the micro-step,
  matching the contract ``loss = engine(batch); engine.backward(loss);
  engine.step()``).
- ``step`` runs the jitted update program at accumulation boundaries:
  unscale, global-norm clip, overflow-gated optimizer step (``lax.cond`` —
  the reference's ``_take_model_step`` overflow skip), loss-scale update,
  schedule-computed LR (traced — no host round trip).
- Buffer donation keeps params/moments in-place in HBM.
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import comm as dist
from ..models.transformer import CausalLM
from ..ops.optimizers import OptimizerState, build_optimizer, FusedAdam
from ..parallel import topology as topo
from ..parallel.sharding import ZeroShardingPlan
from ..utils.logging import logger, log_dist
from ..utils.timer import (FORWARD_MICRO_TIMER, STEP_GLOBAL_TIMER,
                           SynchronizedWallClockTimer, ThroughputTimer)
from .config import DeepSpeedTpuConfig, DtypeEnum, load_config
from .lr_schedules import LRSchedulerShim, build_schedule
from .dataloader import DeepSpeedTpuDataLoader


class ScaleState(NamedTuple):
    """Dynamic loss scale state (reference fp16/loss_scaler.py:91)."""
    scale: jnp.ndarray        # f32 scalar
    good_steps: jnp.ndarray   # i32 scalar
    hysteresis: jnp.ndarray   # i32 scalar


class TrainState(NamedTuple):
    params: Any               # fp32 master weights
    opt_state: OptimizerState
    grad_acc: Any             # fp32 accumulator (scaled grads summed)
    scale_state: ScaleState
    global_step: jnp.ndarray  # i32
    skipped_steps: jnp.ndarray  # i32


class DeepSpeedTpuEngine:
    """See module docstring. Construct via ``deepspeed_tpu.initialize``."""

    def __init__(self, args=None, model=None, optimizer=None,
                 model_parameters=None, training_data=None, lr_scheduler=None,
                 mesh=None, collate_fn=None, config=None, rng=None):
        self.config: DeepSpeedTpuConfig = load_config(
            getattr(args, "deepspeed_config", None) if config is None else config)
        dist.init_distributed(config=self.config)

        # -- topology ------------------------------------------------------
        if mesh is not None:
            self.topology = mesh if isinstance(mesh, topo.MeshTopology) else topo.MeshTopology(mesh)
        elif topo.has_topology():
            self.topology = topo.get_topology()
        else:
            self.topology = topo.MeshTopology.build(self.config.mesh)
        # -- MiCS (reference runtime/zero/mics.py:55 MiCS_Init) -------------
        # mics_shard_size=k shards params over a k-sized sub-group and
        # replicates across the rest of the DP world. TPU-natively the
        # sub-group IS the fsdp mesh axis (ICI-contiguous), replication is
        # the data axis — so honoring the flag means shaping the mesh, after
        # which the ZeRO-3 plan + XLA collectives do the rest (the
        # hierarchical gather of mics.py:227 is XLA's collective scheduling
        # over ICI/DCN; mics_hierarchical_params_gather needs no manual
        # two-phase gather here).
        zc0 = self.config.zero_optimization
        if zc0.mics_shard_size and zc0.mics_shard_size > 0:
            k = int(zc0.mics_shard_size)
            if zc0.stage != 3:
                raise ValueError(
                    f"mics_shard_size={k} requires zero_optimization.stage=3 "
                    "(MiCS is a ZeRO-3 variant, reference mics.py:55)")
            fsdp_size = self.topology.mesh.shape.get("fsdp", 1)
            if fsdp_size != k:
                if mesh is None and fsdp_size == 1 \
                        and self.topology.world_size % k == 0:
                    # engine-built default mesh: reshape fsdp to the shard
                    # group, data soaks up the replication factor
                    self.topology = topo.MeshTopology.build(
                        self.config.mesh, fsdp=k, data=-1)
                else:
                    raise ValueError(
                        f"mics_shard_size={k} conflicts with the mesh fsdp "
                        f"axis ({fsdp_size}); size the fsdp axis to the MiCS "
                        "shard group (params shard over fsdp, replicate over "
                        "data)")
            log_dist(
                f"MiCS: shard group={k} (fsdp axis), replication="
                f"{self.topology.axis_size('data')} (data axis)", ranks=[0])
        topo.set_topology(self.topology)
        self.mesh = self.topology.mesh

        self._apply_elasticity()
        self.config.resolve_batch_sizes(self.topology.get_data_parallel_world_size())

        # -- model ---------------------------------------------------------
        self.module = self._resolve_model(model)
        self.zero_stage = self.config.zero_optimization.stage
        spec_tree = (self.module.param_specs()
                     if hasattr(self.module, "param_specs") else None)
        hpz_size = self.config.zero_optimization.zero_hpz_partition_size
        if hpz_size > 1:
            # hpZ maps the secondary (weight-shard) group onto the fsdp mesh
            # axis and the primary partition onto fsdp×data; the configured
            # group size must therefore equal the fsdp axis size — honoring
            # an arbitrary size would need a different mesh, so reject
            # rather than silently reinterpret (reference zero/config.py:256).
            fsdp_size = self.topology.mesh.shape.get("fsdp", 1)
            if hpz_size != fsdp_size:
                raise ValueError(
                    f"zero_hpz_partition_size={hpz_size} must equal the mesh "
                    f"fsdp axis size ({fsdp_size}); size the mesh's fsdp axis "
                    "to the intended secondary-partition group")
        self.plan = ZeroShardingPlan(
            self.topology, self.zero_stage, spec_tree, hpz=hpz_size > 1)

        # -- precision -----------------------------------------------------
        self.precision = self.config.precision
        self.compute_dtype = self.precision.to_jnp()
        self.fp16_enabled = self.precision == DtypeEnum.fp16
        self.bf16_enabled = self.precision == DtypeEnum.bf16
        self.dynamic_loss_scale = self.fp16_enabled and self.config.fp16.loss_scale == 0
        self._static_scale = (self.config.fp16.loss_scale
                              if self.fp16_enabled and not self.dynamic_loss_scale else 1.0)

        # -- optimizer + schedule -----------------------------------------
        oc = self.config.optimizer
        self.client_optimizer = optimizer
        if optimizer is not None and not isinstance(optimizer, str):
            self.opt = optimizer  # duck-typed: init/step
        else:
            self.opt = build_optimizer(oc.type if oc else "Adam",
                                       oc.params if oc else {"lr": 1e-3})
        # 1-bit optimizers take over gradient communication (ops/onebit.py):
        # the engine computes unreduced per-worker grads under shard_map and
        # the optimizer owns the (compressed) cross-worker reduction —
        # reference runtime/engine.py:1194 likewise skips the engine
        # allreduce for these optimizer types.
        from ..ops.onebit import OneBitOptimizer

        self._onebit = isinstance(self.opt, OneBitOptimizer)
        if self._onebit:
            bad_axes = {a: s for a, s in dict(self.mesh.shape).items()
                        if a != "data" and s > 1}
            if bad_axes:
                raise ValueError(
                    "1-bit optimizers require pure data parallelism (they "
                    f"own the gradient reduction); mesh has {bad_axes}")
            if self.zero_stage > 1:
                raise ValueError(
                    "1-bit optimizers require zero_optimization.stage <= 1 "
                    "(reference onebit/adam.py compatibility constraint)")
            if self._offload_config() is not None:
                raise ValueError("1-bit optimizers are incompatible with "
                                 "optimizer offload")
            self.opt.dp_size = self.topology.get_data_parallel_world_size()

        base_lr = getattr(self.opt, "lr", 1e-3)
        sc = self.config.scheduler
        if lr_scheduler is not None:
            self.schedule = lr_scheduler  # callable step -> lr
        else:
            self.schedule = build_schedule(sc.type if sc else None,
                                           sc.params if sc else None,
                                           fallback_lr=base_lr)
        self.lr_scheduler = LRSchedulerShim(
            self.schedule,
            step_source=lambda: int(self.state.global_step)
            if getattr(self, "state", None) is not None else 0)

        # -- state init (sharded from birth — zero.Init role) --------------
        self._rng = rng if rng is not None else jax.random.PRNGKey(self.config.seed)
        self.state = self._init_state()

        # -- ZeRO++ (qwZ/qgZ explicit quantized collectives) ---------------
        self._setup_zeropp()

        # -- data ----------------------------------------------------------
        self.training_dataloader = None
        self._data_iter = None  # persistent train_batch iterator (ADVICE r1)
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)

        # -- curriculum learning (seqlen curriculum; reference engine.py
        # curriculum legacy path + data_pipeline/curriculum_scheduler.py) --
        self.curriculum_scheduler = None
        cl = self.config.curriculum_learning or {}
        if cl.get("enabled"):
            from .data_pipeline import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(cl)

        # -- compression (QAT/pruning baked into the step programs) --------
        self._compression = None
        if self.config.compression_training:
            from ..compression import CompressionTransform

            ct = CompressionTransform(
                {"compression_training": self.config.compression_training})
            if ct:
                self._compression = ct

        # -- step programs -------------------------------------------------
        self._build_step_fns()

        # -- counters / telemetry -----------------------------------------
        self.micro_steps = 0          # micro steps since engine start
        self.global_steps = 0         # host mirror of state.global_step
        # NOTE: skipped_steps is a property over state.skipped_steps — the
        # device counter is authoritative and reading it lazily avoids a
        # host-device sync on every optimizer boundary (ADVICE r1 / review r2).
        self._pending_loss = None
        self._last_lr = float(self.schedule(0))
        self.timers = SynchronizedWallClockTimer(sync_fn=self._sync)
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=self.config.steps_per_print,
            monitor_memory=self.config.memory_breakdown)
        self.monitor = self._build_monitor()
        # step profiling (docs/OBSERVABILITY.md "Step profiling"):
        # wall_clock_breakdown (reference engine.py flag) brackets fwd+bwd
        # and the optimizer step with synchronized timers — a
        # block_until_ready per bracket, so real device time is measured,
        # at a throughput cost. An enabled telemetry block alone records
        # the fwd_bwd / optimizer_step spans ("train" trace) round the
        # dispatches and synchronizes nothing: tracing must not change
        # what it traces.
        self.tracer = self.config.telemetry.build_tracer()
        self._profile_steps = bool(self.config.wall_clock_breakdown)

        log_dist(
            f"DeepSpeedTpuEngine ready: mesh={dict(self.mesh.shape)} "
            f"zero_stage={self.zero_stage} precision={self.precision.value} "
            f"micro_batch={self.train_micro_batch_size_per_gpu()} "
            f"gas={self.gradient_accumulation_steps()}", ranks=[0])

    # ------------------------------------------------------------------ setup
    def _apply_elasticity(self):
        """Elastic batch config (reference elasticity/elasticity.py:233 via
        runtime/config.py elasticity hookup): validate the current chip
        count against the elastic config's valid set and, with
        ``ignore_non_elastic_batch_info``, adopt the elastic
        (batch, micro, gas) for this world size. Scale-up/down is
        restart-based: universal checkpoints resume on any valid mesh."""
        ec = self.config.elasticity
        if not ec.enabled:
            return
        from ..elasticity import (ElasticityConfigError,
                                  ElasticityIncompatibleWorldSize,
                                  compute_elastic_config)

        batch_keys_set = any(
            isinstance(v, int) for v in (self.config.train_batch_size,
                                         self.config.train_micro_batch_size_per_gpu,
                                         self.config.gradient_accumulation_steps))
        if batch_keys_set and not ec.ignore_non_elastic_batch_info:
            raise ElasticityConfigError(
                "elasticity is enabled but batch sizes are also set; remove "
                "them or set elasticity.ignore_non_elastic_batch_info "
                "(reference elasticity adopts the same all-or-nothing rule)")
        world = self.topology.world_size
        batch, valid, micro = compute_elastic_config(
            {"elasticity": {
                "enabled": True,
                "max_train_batch_size": ec.max_train_batch_size,
                "micro_batch_sizes": list(ec.micro_batch_sizes),
                "min_gpus": ec.min_gpus, "max_gpus": ec.max_gpus,
                "version": ec.version,
                "prefer_larger_batch": ec.prefer_larger_batch,
                "model_parallel_size": ec.model_parallel_size,
                "num_gpus_per_node": ec.num_gpus_per_node}},
            world_size=world, return_microbatch=True)
        dp = self.topology.get_data_parallel_world_size()
        if micro is None or batch % (micro * dp):
            raise ElasticityIncompatibleWorldSize(
                f"elastic batch {batch} unreachable with dp={dp} and micro "
                f"candidates {list(ec.micro_batch_sizes)}")
        self.config.train_batch_size = batch
        self.config.train_micro_batch_size_per_gpu = micro
        self.config.gradient_accumulation_steps = batch // (micro * dp)
        log_dist(
            f"elasticity: batch={batch} micro={micro} "
            f"gas={self.config.gradient_accumulation_steps} "
            f"valid_chips={valid}", ranks=[0])

    def _resolve_model(self, model):
        if model is None:
            raise ValueError("model is required")
        if isinstance(model, str):
            from ..models import build_model

            return build_model(model)
        return model

    def _sync(self):
        jax.block_until_ready(self.state.params) if self.state else None

    def _build_monitor(self):
        try:
            from ..monitor.monitor import MonitorMaster

            return MonitorMaster(self.config)
        except Exception:
            return None

    def _model_dtype_override(self):
        """Push engine precision + pipeline/remat settings into the model
        config when the model is a framework CausalLM."""
        if not isinstance(self.module, CausalLM):
            return
        over = {}
        if self.module.cfg.dtype != self.compute_dtype:
            over["dtype"] = self.compute_dtype
        pmb = self.config.pipeline.micro_batches
        if pmb and self.module.cfg.pipeline_microbatches != pmb:
            over["pipeline_microbatches"] = pmb
        if over:
            self.module = CausalLM(dataclasses.replace(self.module.cfg, **over))

    def _offload_config(self):
        oc = self.config.zero_optimization.offload_optimizer
        if oc is None or str(oc.device.value) == "none":
            return None
        return oc

    def _setup_zeropp(self):
        """ZeRO++ qwZ/qgZ: install explicit quantized-collective transforms
        on the model (reference partition_parameters.py:679 CUDAQuantizer +
        coalesced_collectives.py:31 all_to_all_quant_reduce; see
        parallel/zeropp.py for the TPU formulation)."""
        zc = self.config.zero_optimization
        if not (zc.zero_quantized_weights or zc.zero_quantized_gradients):
            return
        if self.zero_stage < 3:
            raise ValueError(
                "zero_quantized_weights/gradients (ZeRO++) require "
                f"zero_optimization.stage=3, got stage={self.zero_stage}")
        if not isinstance(self.module, CausalLM):
            raise ValueError("ZeRO++ transforms require a framework CausalLM "
                             "(custom modules: wire parallel/zeropp.py "
                             "make_quantized_gather_transform directly)")
        from jax.sharding import PartitionSpec

        from ..parallel.zeropp import make_quantized_gather_transform

        qw = 8 if zc.zero_quantized_weights else None
        qg = 8 if zc.zero_quantized_gradients else None
        # per-layer view: strip the stacked-layers leading dim from each spec
        layer_specs = {k: PartitionSpec(*ns.spec[1:])
                       for k, ns in self._param_shardings["layers"].items()}
        self.module.layer_transform = make_quantized_gather_transform(
            self.mesh, layer_specs, qw_bits=qw, qg_bits=qg)
        g_specs = {}
        for grp in ("embed", "final_norm", "lm_head"):
            for k, ns in self._param_shardings.get(grp, {}).items():
                g_specs[f"{grp}.{k}"] = ns.spec
        self.module.global_transform = make_quantized_gather_transform(
            self.mesh, g_specs, qw_bits=qw, qg_bits=qg)
        if self.module.layer_transform or self.module.global_transform:
            log_dist(f"ZeRO++ enabled: qwZ={bool(qw)} qgZ={bool(qg)}",
                     ranks=[0])

    def _init_state(self) -> TrainState:
        self._model_dtype_override()
        init_rng, self._rng = jax.random.split(self._rng)

        # Init params already sharded (the reference's zero.Init
        # partition_parameters.py:734 — params never exist unsharded).
        shapes = jax.eval_shape(self.module.init, init_rng)
        p_shard = self.plan.params(shapes)
        params = jax.jit(self.module.init, out_shardings=p_shard)(init_rng)

        # ZeRO-Offload: split leaves between host optimizer and device
        oc = self._offload_config()
        self._offload_plan = None
        if oc is not None:
            from .zero_offload import OffloadOptimizerPlan

            opt_cfg = self.config.optimizer
            self._offload_plan = OffloadOptimizerPlan(
                params, opt_cfg.type if opt_cfg else "Adam",
                opt_cfg.params if opt_cfg else {},
                device=str(oc.device.value), ratio=oc.ratio,
                nvme_path=oc.nvme_path,
                aio_threads=self.config.aio.thread_count)

        if self._offload_plan is not None:
            # device optimizer covers only the non-offloaded subtree
            p_leaves = jax.tree_util.tree_flatten(params)[0]
            s_leaves = jax.tree_util.tree_flatten(p_shard)[0]
            kept = {str(i): p_leaves[i] for i in self._offload_plan.kept}
            kept_shard = {str(i): s_leaves[i] for i in self._offload_plan.kept}
            opt_shapes = jax.eval_shape(self.opt.init, kept)
            o_shard = OptimizerState(
                step=self.plan.replicated(),
                moments={mk: kept_shard for mk in opt_shapes.moments})
            opt_state = jax.jit(self.opt.init, out_shardings=o_shard)(kept)
        elif self._onebit:
            # Error-feedback moments are per-worker state: leading dp axis,
            # sharded over the data mesh axis (ops/onebit.py contract).
            from jax.sharding import NamedSharding, PartitionSpec

            dspec = NamedSharding(self.mesh, PartitionSpec("data"))
            rep = self.plan.replicated()
            opt_shapes = jax.eval_shape(self.opt.init, params)
            o_moments = {
                k: jax.tree.map(
                    lambda _: dspec if k in self.opt.dp_moment_keys else rep,
                    sub)
                for k, sub in opt_shapes.moments.items()}
            o_shard = OptimizerState(step=rep, moments=o_moments)
            opt_state = jax.jit(self.opt.init, out_shardings=o_shard)(params)
        else:
            opt_shapes = jax.eval_shape(self.opt.init, params)
            o_shard = OptimizerState(
                step=self.plan.replicated(),
                moments=self.plan.opt_state(opt_shapes.moments))
            opt_state = jax.jit(self.opt.init, out_shardings=o_shard)(params)

        if self._onebit:
            # Per-worker (unreduced) gradient accumulators: leading dp axis
            # sharded over 'data' — each worker accumulates its own grads;
            # the optimizer's compressed collective does the averaging.
            from jax.sharding import NamedSharding, PartitionSpec

            dp = self.topology.get_data_parallel_world_size()
            acc_shapes = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((dp,) + s.shape, s.dtype),
                shapes)
            g_shard = jax.tree.map(
                lambda _: NamedSharding(self.mesh, PartitionSpec("data")),
                acc_shapes)
            grad_acc = jax.jit(
                lambda: jax.tree.map(jnp.zeros_like, acc_shapes),
                out_shardings=g_shard)()
        else:
            g_shard = self.plan.grads(shapes)
            grad_acc = jax.jit(lambda: jax.tree.map(jnp.zeros_like, shapes),
                               out_shardings=g_shard)()

        scale0 = (2.0 ** self.config.fp16.initial_scale_power
                  if self.dynamic_loss_scale else self._static_scale)
        scale_state = ScaleState(
            scale=jnp.asarray(scale0, jnp.float32),
            good_steps=jnp.zeros((), jnp.int32),
            hysteresis=jnp.asarray(self.config.fp16.hysteresis, jnp.int32))
        self._param_shardings = p_shard
        self._opt_shardings = o_shard
        self._grad_shardings = g_shard
        return TrainState(params=params, opt_state=opt_state, grad_acc=grad_acc,
                          scale_state=scale_state,
                          global_step=jnp.zeros((), jnp.int32),
                          skipped_steps=jnp.zeros((), jnp.int32))

    # ----------------------------------------------------------- jitted steps
    def _build_step_fns(self):
        plan = self.plan
        module = self.module
        opt = self.opt
        schedule = self.schedule
        gas = self.gradient_accumulation_steps()
        clip = self.config.gradient_clipping
        fp16 = self.fp16_enabled
        dynamic = self.dynamic_loss_scale
        fpc = self.config.fp16
        predivide = self.config.prescale_gradients
        dp_size = self.topology.get_data_parallel_world_size()

        state_shardings = TrainState(
            params=self._param_shardings,
            opt_state=self._opt_shardings,
            grad_acc=self._grad_shardings,
            scale_state=ScaleState(*(plan.replicated(),) * 3),
            global_step=plan.replicated(),
            skipped_steps=plan.replicated())
        self._state_shardings = state_shardings
        batch_sharding = plan.batch()

        compression = self._compression

        def micro(state: TrainState, batch, rng):
            """fwd + bwd + accumulate (one micro batch)."""
            scale = state.scale_state.scale

            def loss_fn(params):
                if compression is not None:   # QAT/pruning: STE to masters
                    params = compression(params, state.global_step)
                loss = module.loss(params, batch, rng)
                return (loss * scale / (dp_size if predivide else 1.0)).astype(jnp.float32), loss

            # program scopes (docs/OBSERVABILITY.md "XLA alignment"):
            # inside loss_and_grad JAX's own op_name prefixes tell the
            # passes apart — jvp(…) forward, transpose(jvp(…)) backward,
            # and the checkpoint marker on the recomputed forward
            with jax.named_scope("loss_and_grad"):
                grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
            with jax.named_scope("grad_accumulate"):
                grad_acc = jax.tree.map(jnp.add, state.grad_acc, grads)
            return state._replace(grad_acc=grad_acc), loss

        def unscale_and_clip(state: TrainState):
            with jax.named_scope("grad_norm_clip"):
                scale = state.scale_state.scale
                denom = scale * gas / (dp_size if predivide else 1.0)
                grads = jax.tree.map(lambda g: g / denom, state.grad_acc)
                flat = jax.tree.leaves(grads)
                sumsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in flat)
                gnorm = jnp.sqrt(sumsq)
                overflow = ~jnp.isfinite(gnorm)
                if clip > 0:
                    coeff = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    grads = jax.tree.map(lambda g: g * coeff, grads)
                return grads, gnorm, overflow

        def next_scale_state(ss: ScaleState, overflow):
            """Dynamic loss scale automaton (reference loss_scaler.py:136)."""
            if not (fp16 and dynamic):
                return ss
            window = fpc.loss_scale_window
            min_scale = fpc.min_loss_scale

            def on_overflow(s):
                new_h = jnp.maximum(s.hysteresis - 1, 0)
                shrink = new_h <= 0
                new_scale = jnp.where(
                    shrink, jnp.maximum(s.scale / 2.0, min_scale), s.scale)
                return ScaleState(
                    scale=new_scale, good_steps=jnp.zeros((), jnp.int32),
                    hysteresis=jnp.where(
                        shrink, jnp.asarray(fpc.hysteresis, jnp.int32), new_h))

            def on_good(s):
                grown = s.good_steps + 1 >= window
                return ScaleState(
                    scale=jnp.where(grown, s.scale * 2.0, s.scale),
                    good_steps=jnp.where(grown, 0, s.good_steps + 1).astype(jnp.int32),
                    hysteresis=s.hysteresis)

            return lax.cond(overflow, on_overflow, on_good, ss)

        def book_keeping(state, new_params, new_opt, overflow):
            zero_acc = jax.tree.map(jnp.zeros_like, state.grad_acc)
            return TrainState(
                params=new_params, opt_state=new_opt, grad_acc=zero_acc,
                scale_state=next_scale_state(state.scale_state, overflow),
                global_step=state.global_step + jnp.where(overflow, 0, 1),
                skipped_steps=state.skipped_steps + jnp.where(overflow, 1, 0))

        def update(state: TrainState):
            """unscale → clip → (overflow-gated) optimizer step → new scale."""
            grads, gnorm, overflow = unscale_and_clip(state)
            lr = schedule(state.global_step)

            def do_step(_):
                return opt.step(state.params, grads, state.opt_state, lr)

            def skip(_):
                return state.params, state.opt_state

            with jax.named_scope("optimizer"):
                new_params, new_opt = lax.cond(overflow, skip, do_step, None)
            new_state = book_keeping(state, new_params, new_opt, overflow)
            metrics = {"grad_norm": gnorm, "lr": lr, "overflow": overflow,
                       "loss_scale": state.scale_state.scale}
            return new_state, metrics

        offload_plan = getattr(self, "_offload_plan", None)

        def finalize_offload(state: TrainState):
            """Offload variant: device update for the kept subtree, grads of
            offloaded leaves returned for the host optimizer."""
            grads, gnorm, overflow = unscale_and_clip(state)
            lr = schedule(state.global_step)
            p_leaves = jax.tree_util.tree_flatten(state.params)[0]
            g_leaves = jax.tree_util.tree_flatten(grads)[0]
            kept = {str(i): p_leaves[i] for i in offload_plan.kept}
            kept_grads = {str(i): g_leaves[i] for i in offload_plan.kept}

            def do_step(_):
                return opt.step(kept, kept_grads, state.opt_state, lr)

            def skip(_):
                return kept, state.opt_state

            new_kept, new_opt = lax.cond(overflow, skip, do_step, None)
            new_leaves = list(p_leaves)
            for i in offload_plan.kept:
                new_leaves[i] = new_kept[str(i)]
            new_params = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(state.params), new_leaves)
            off_grads = {str(i): g_leaves[i] for i in offload_plan.offloaded}
            new_state = book_keeping(state, new_params, new_opt, overflow)
            metrics = {"grad_norm": gnorm, "lr": lr, "overflow": overflow,
                       "loss_scale": state.scale_state.scale}
            return new_state, off_grads, metrics

        if getattr(self, "_onebit", False):
            # 1-bit optimizer path: the whole micro/update runs inside
            # shard_map over the data axis so gradients stay per-worker
            # (unreduced) and the optimizer owns the compressed collective
            # (ops/onebit.py; reference onebit optimizers likewise take over
            # the engine's allreduce). Two compiled update programs — full-
            # precision warmup vs int8-compressed — dispatched host-side on
            # freeze_step, so no traced branch wraps the collectives.
            from ..compat import shard_map
            from jax.sharding import PartitionSpec as P

            mesh = self.mesh
            is_shard = lambda x: isinstance(x, jax.sharding.Sharding)  # noqa: E731
            state_specs = jax.tree.map(lambda s: s.spec, state_shardings,
                                       is_leaf=is_shard)

            def micro_onebit(state: TrainState, batch, rng):
                def shard_fn(state, batch, rng):
                    scale = state.scale_state.scale

                    def loss_fn(params):
                        if compression is not None:
                            params = compression(params, state.global_step)
                        loss = module.loss(params, batch, rng)
                        return (loss * scale).astype(jnp.float32), loss

                    grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
                    grad_acc = jax.tree.map(
                        lambda a, g: a + g[None].astype(a.dtype),
                        state.grad_acc, grads)
                    loss = lax.pmean(loss, "data")
                    return state._replace(grad_acc=grad_acc), loss

                return shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=(state_specs, P("data"), P()),
                    out_specs=(state_specs, P()),
                    check_vma=False)(state, batch, rng)

            opt_dp = self.topology.get_data_parallel_world_size()

            def make_update_onebit(compressed: bool):
                step_fn = (opt.compressed_step_local if compressed
                           else opt.warmup_step_local)

                def update_onebit(state: TrainState):
                    def shard_fn(state):
                        scale = state.scale_state.scale
                        denom = scale * gas
                        local = jax.tree.map(lambda a: a[0] / denom,
                                             state.grad_acc)
                        # Root-mean of per-worker squared norms: an upper
                        # bound on the averaged-grad norm costing one scalar
                        # psum (the exact norm would need the full-precision
                        # gradient psum this path exists to avoid) — see
                        # ops/onebit.py "Documented divergences".
                        sumsq = sum(
                            jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in jax.tree.leaves(local))
                        gnorm = jnp.sqrt(lax.psum(sumsq, "data") / opt_dp)
                        overflow = ~jnp.isfinite(gnorm)
                        if clip > 0:
                            coeff = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                            local = jax.tree.map(lambda g: g * coeff, local)
                        lr = schedule(state.global_step)
                        # No lax.cond around the optimizer here: its branches
                        # would trap collectives inside a conditional. Run
                        # the step unconditionally, select on overflow.
                        new_p, new_opt = step_fn(state.params, local,
                                                 state.opt_state, lr)
                        pick = lambda n, o: jnp.where(overflow, o, n)  # noqa: E731
                        new_p = jax.tree.map(pick, new_p, state.params)
                        new_opt = jax.tree.map(pick, new_opt, state.opt_state)
                        new_state = book_keeping(state, new_p,
                                                 new_opt, overflow)
                        metrics = {"grad_norm": gnorm, "lr": lr,
                                   "overflow": overflow,
                                   "loss_scale": state.scale_state.scale}
                        return new_state, metrics

                    return shard_map(
                        shard_fn, mesh=mesh,
                        in_specs=(state_specs,),
                        out_specs=(state_specs,
                                   {"grad_norm": P(), "lr": P(),
                                    "overflow": P(), "loss_scale": P()}),
                        check_vma=False)(state)

                return update_onebit

            micro = micro_onebit
            update = make_update_onebit(compressed=True)
            self._update_warm_raw = make_update_onebit(compressed=False)
            self._update_warm_fn = jax.jit(
                self._update_warm_raw,
                out_shardings=(state_shardings, None),
                donate_argnums=(0,))

        # NOTE: no in_shardings on any of these jits. The state/batch arrays
        # are committed with the plan's shardings already (init runs under
        # out_shardings; batches via device_put), so jit infers identical
        # input shardings from the arrays — and pinning in_shardings to
        # default layouts was measured to cost ~3x step time on TPU (it
        # defeats XLA's input-layout selection, forcing full-state relayouts
        # per call). The TPU path instead pins *XLA-preferred* layouts, found
        # by a one-time AUTO-format compile at the first forward
        # (_autotune_layouts below).
        self._micro_raw = micro
        self._update_raw = update
        self._finalize_raw = finalize_offload if offload_plan is not None else None
        self._layouts_tuned = False
        self._state_formats = None      # the formats the autotune pinned
        self._micro_fn = jax.jit(
            micro,
            out_shardings=(state_shardings, plan.replicated()),
            donate_argnums=(0,))
        if offload_plan is not None:
            self._update_fn = None
            self._finalize_fn = jax.jit(
                finalize_offload,
                out_shardings=(state_shardings, None, None),
                donate_argnums=(0,))
        else:
            self._finalize_fn = None
            self._update_fn = jax.jit(
                update,
                out_shardings=(state_shardings, None),
                donate_argnums=(0,))

        def eval_step(state: TrainState, batch, rng):
            params = state.params
            if compression is not None:
                params = compression(params, state.global_step)
            return module.loss(params, batch, None)

        self._eval_fn = jax.jit(eval_step)

    def _autotune_layouts(self, batch, rng):
        """One-time XLA layout autotuning for the hot step (TPU only).

        XLA picks faster-than-default in-memory layouts for the train state
        when allowed to (measured ~3x step time on a 536M LM on v5e when the
        state is pinned to default layouts). Compile the micro program once
        with AUTO input/output formats, read back the layouts XLA chose, move
        the live state into them, and rebuild the step jits pinned to those
        concrete formats so state cycles micro→update→micro with zero
        relayouts. Counterpart of the reference's kernel/layout autotuning
        role (it has no direct equivalent — CUDA torch controls layouts
        explicitly)."""
        self._layouts_tuned = True
        if jax.default_backend() != "tpu":
            return
        from jax.experimental.layout import Format, Layout

        # a failure here raises: training on at default layouts would cost
        # ~3x step time (above) without anything saying so
        ss = self._state_shardings
        is_shard = lambda x: isinstance(x, jax.sharding.Sharding)
        auto_state = jax.tree.map(lambda s: Format(Layout.AUTO, s), ss,
                                  is_leaf=is_shard)
        rep = self.plan.replicated()
        micro_auto = jax.jit(
            self._micro_raw,
            in_shardings=(auto_state, None, None),
            out_shardings=(auto_state, rep),
            donate_argnums=(0,))
        # AUTO layouts require abstract (ShapeDtypeStruct) args to lower.
        avals = jax.eval_shape(lambda s, b, r: (s, b, r),
                               self.state, batch, rng)
        compiled = micro_auto.lower(*avals).compile()
        out_state_fmt = compiled.output_formats[0]
        # Move the live state into the preferred layouts (one-time cost)
        # and pin every step program to them.
        self.state = jax.device_put(self.state, out_state_fmt)
        self._micro_fn = jax.jit(
            self._micro_raw,
            in_shardings=(out_state_fmt, None, None),
            out_shardings=(out_state_fmt, rep),
            donate_argnums=(0,))
        if self._finalize_raw is not None:
            self._finalize_fn = jax.jit(
                self._finalize_raw,
                in_shardings=(out_state_fmt,),
                out_shardings=(out_state_fmt, None, None),
                donate_argnums=(0,))
        else:
            self._update_fn = jax.jit(
                self._update_raw,
                in_shardings=(out_state_fmt,),
                out_shardings=(out_state_fmt, None),
                donate_argnums=(0,))
            if getattr(self, "_onebit", False):
                self._update_warm_fn = jax.jit(
                    self._update_warm_raw,
                    in_shardings=(out_state_fmt,),
                    out_shardings=(out_state_fmt, None),
                    donate_argnums=(0,))
        self._state_formats = out_state_fmt
        log_dist("layout autotune: state pinned to XLA-preferred formats",
                 ranks=[0])

    # ------------------------------------------------------------- data plumbing
    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None,
                     num_local_io_workers=None, data_sampler=None,
                     route=None):
        """Reference engine.py:1665 ``deepspeed_io``. In the single-controller
        view one batch is the *global* micro batch (per-device micro ×
        DP world), sharded over the data axes at device_put."""
        global_micro = (self.train_micro_batch_size_per_gpu()
                        * self.topology.get_data_parallel_world_size())
        return DeepSpeedTpuDataLoader(
            dataset,
            batch_size=batch_size or global_micro,
            topology=self.topology,
            collate_fn=collate_fn,
            seed=self.config.seed,
            data_sampler=data_sampler)

    def _device_batch(self, batch):
        """Shard a host batch over the data axes."""
        sharding = self.plan.batch()

        def put(x):
            x = np.asarray(x)
            return jax.device_put(x, sharding)

        if isinstance(batch, dict):
            return {k: put(v) for k, v in batch.items()}
        if isinstance(batch, (tuple, list)):
            return {"input_ids": put(batch[0]), "labels": put(batch[1])} \
                if len(batch) == 2 else {"input_ids": put(batch[0])}
        return {"input_ids": put(batch)}

    # ----------------------------------------------------------------- API
    def __call__(self, batch, *args, **kwargs):
        return self.forward(batch, *args, **kwargs)

    def forward(self, batch, *args, **kwargs):
        """Run fwd+bwd+accumulate for one micro batch; returns the loss.

        Gradient work happens here (functional autograd); ``backward`` is
        the parity call that advances the micro counter.
        """
        self.tput_timer.start()
        batch = self._device_batch(batch) if not self._is_device_batch(batch) else batch
        if self.tput_timer.flops_per_sample is None:
            self._autofill_flops_per_sample(batch)
        step_rng = jax.random.fold_in(self._rng, self.micro_steps)
        if not self._layouts_tuned:
            self._autotune_layouts(batch, step_rng)
        if self._profile_steps:
            # synchronized bracket: start() waits out pending device work,
            # stop() blocks until this micro step's fwd+bwd really ran (the
            # two are one fused program — they cannot be timed separately
            # from the host; docs/OBSERVABILITY.md)
            fwd_timer = self.timers(FORWARD_MICRO_TIMER)
            fwd_timer.start()
        # one call site, profiled, traced or neither (the flash kernels'
        # compile-cache keys carry it); the span is the dispatch unless
        # the timer above makes it the whole micro step
        with self.tracer.span(
                "fwd_bwd", trace_id="train",
                attrs={"micro_step": self.micro_steps}
                if self.tracer.enabled else None):
            self.state, loss = self._micro_fn(self.state, batch, step_rng)
            if self._profile_steps:
                fwd_timer.stop(record=True)
        self._pending_loss = loss
        if self.config.check_numerics and not self.fp16_enabled \
                and not np.isfinite(float(loss)):
            # numeric sanitizer (reference runtime/utils.py CheckOverflow /
            # loss_scaler._has_inf_or_nan): name the poisoned leaves rather
            # than letting NaNs propagate silently. Debug mode — the float()
            # forces a device sync per micro step.
            raise FloatingPointError(
                f"check_numerics: non-finite loss {float(loss)} at micro "
                f"step {self.micro_steps}; offending state leaves: "
                f"{self._numerics_scan()}")
        return loss

    def _autofill_flops_per_sample(self, batch):
        """Feed :class:`ThroughputTimer` its per-sample FLOPs from the
        flops profiler's analytic counting (profiling/flops_profiler.py)
        so samples/sec reports come with a TFLOPS estimate without the
        user wiring anything. Non-CausalLM modules (no analytic model)
        set 0.0 — tflops() then stays silent — and never retry."""
        if not isinstance(self.module, CausalLM) \
                or not isinstance(batch, dict) or "input_ids" not in batch:
            self.tput_timer.flops_per_sample = 0.0
            return
        from ..profiling.flops_profiler import train_step_flops

        seq = max(1, int(batch["input_ids"].shape[-1]) - 1)
        self.tput_timer.flops_per_sample = float(
            train_step_flops(self.module.cfg, 1, seq))

    def _numerics_scan(self):
        """Per-leaf finiteness scan of params + accumulated grads; returns
        the pytree paths of non-finite leaves (reference fp16
        loss_scaler.py _has_inf_or_nan per-tensor scan, as one jitted
        tree-map instead of a host loop). The jitted scanner is cached —
        a fresh jit per call would retrace every step."""
        if not hasattr(self, "_numerics_scan_fn"):
            self._numerics_scan_fn = jax.jit(lambda t: jax.tree.map(
                lambda x: jnp.all(jnp.isfinite(x.astype(jnp.float32))), t))
        tree = {"params": self.state.params, "grad_acc": self.state.grad_acc}
        flags = jax.device_get(self._numerics_scan_fn(tree))
        return sorted(
            jax.tree_util.keystr(kp)
            for kp, ok in jax.tree_util.tree_flatten_with_path(flags)[0]
            if not bool(ok))

    @staticmethod
    def _is_device_batch(batch):
        return isinstance(batch, dict) and all(
            isinstance(v, jax.Array) for v in batch.values())

    def backward(self, loss=None, retain_graph=False):
        """API-parity (reference engine.py:1898): gradients were produced in
        ``forward``; this advances the micro-step counter."""
        self.micro_steps += 1
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return self.micro_steps % self.gradient_accumulation_steps() == 0

    def step(self):
        """Reference engine.py:2096: optimizer step at accumulation boundary."""
        if not self.is_gradient_accumulation_boundary():
            return
        # sanitizer scan must run BEFORE the update: the jitted update
        # zeroes grad_acc and overflow-gates the param write, so post-hoc
        # state would name nothing
        pre_scan = (self._numerics_scan()
                    if self.config.check_numerics and not self.fp16_enabled
                    else None)
        if self._profile_steps:
            step_timer = self.timers(STEP_GLOBAL_TIMER)
            step_timer.start()
        with self.tracer.span(
                "optimizer_step", trace_id="train",
                attrs={"global_step": self.global_steps}
                if self.tracer.enabled else None) as opt_span:
            if self._offload_plan is not None:
                metrics = self._offload_step()
            elif self._onebit and self.global_steps < self.opt.freeze_step:
                # Warmup phase: full-precision momentum/variance build-up
                # (host-dispatched — see _build_step_fns onebit path).
                self.state, metrics = self._update_warm_fn(self.state)
            else:
                self.state, metrics = self._update_fn(self.state)
            if self._profile_steps:
                step_timer.stop(record=True)   # synced: real update duration
                # reading the flag waits for the update: only under the
                # synchronizing profile
                opt_span.set("skipped", bool(np.asarray(
                    metrics.get("overflow", False))))
        if pre_scan is not None \
                and not np.isfinite(float(metrics.get("grad_norm", 0.0))):
            # under fp16 the dynamic-loss-scale automaton owns overflow
            # (skip + rescale); everywhere else a non-finite grad norm is a
            # real numeric fault — fail loudly with the leaf names
            raise FloatingPointError(
                f"check_numerics: non-finite grad norm at step "
                f"{self.global_steps}; offending state leaves: {pre_scan}")
        self.global_steps += 1
        self.lr_scheduler.step()
        self._last_metrics = metrics
        self.tput_timer.stop(report_speed=(
            self.global_steps % self.config.steps_per_print == 0))
        if self.global_steps % self.config.steps_per_print == 0:
            m = {k: float(v) for k, v in metrics.items()}
            log_dist(
                f"step={self.global_steps} loss={float(self._pending_loss):.4f} "
                f"lr={m['lr']:.3e} grad_norm={m['grad_norm']:.3f} "
                f"loss_scale={m['loss_scale']:.0f}", ranks=[0])
            events = [
                ("Train/loss", float(self._pending_loss), self.global_steps),
                ("Train/lr", m["lr"], self.global_steps)]
            if self._profile_steps:
                # per-global-step wall-clock breakdown over the window
                # since the last report (fwd_microstep accumulates gas
                # micro steps per global step): the "what fraction of a
                # step is fwd+bwd vs optimizer" numbers, through the same
                # monitor fan-out as the loss curves
                names = [n for n in (FORWARD_MICRO_TIMER, STEP_GLOBAL_TIMER)
                         if self.timers.has(n)]
                means = self.timers.log(
                    names, normalizer=self.config.steps_per_print)
                events += [(f"Train/timer/{k}_ms", v, self.global_steps)
                           for k, v in means.items()]
            events.append(("Train/samples_per_sec",
                           self.tput_timer.avg_samples_per_sec(),
                           self.global_steps))
            if self.tput_timer.flops_per_sample:
                events.append(("Train/tflops", self.tput_timer.tflops(),
                               self.global_steps))
            if self.tput_timer.memory_bytes is not None:
                events.append(("Train/device_mem_gib",
                               self.tput_timer.memory_bytes / 2**30,
                               self.global_steps))
            if self.monitor is not None:
                self.monitor.write_events(events)
        return metrics

    def _offload_step(self):
        """Host-side optimizer step for offloaded leaves (ZeRO-Offload):
        device finalize → grads to host → C++ SIMD update of fp32 masters →
        masters stream back into the sharded device params."""
        # Drive the host LR from the authoritative device counter: the jitted
        # path uses state.global_step, which does NOT advance on fp16-overflow
        # skipped steps, while self.global_steps advances on every boundary.
        # Using the host mirror would permanently desync offloaded-leaf LR
        # from device-resident leaves after any overflow (ADVICE r1).
        lr_host = float(self.schedule(int(self.state.global_step)))
        self.state, off_grads, metrics = self._finalize_fn(self.state)
        if not bool(metrics["overflow"]):
            plan = self._offload_plan
            # Pipelined host step (round-2 weak #4): leaf i's C++ optimizer
            # update runs on a worker thread while leaf i+1's gradient is
            # still transferring device→host — the reference's stream
            # overlap (stage_1_and_2.py:1096) as a transfer/compute
            # pipeline. One worker keeps leaf updates ordered; the C++ op
            # is OpenMP-parallel internally.
            if not hasattr(self, "_offload_pool"):
                from concurrent.futures import ThreadPoolExecutor

                self._offload_pool = ThreadPoolExecutor(max_workers=1)
            futures = []
            for i in plan.offloaded:
                g = np.asarray(jax.device_get(off_grads[str(i)]))
                futures.append(self._offload_pool.submit(
                    plan.host_update_leaf, i, g, lr_host))
            for f in futures:
                f.result()
            p_leaves = jax.tree_util.tree_flatten(self.state.params)[0]
            kept = {str(i): p_leaves[i] for i in plan.kept}
            new_params = plan.merge(kept, plan.masters, self._param_shardings)
            self.state = self.state._replace(params=new_params)
        return metrics

    def train_batch(self, data_iter=None):
        """Full effective batch: GAS micro steps + update (pipeline-engine
        parity, reference pipe/engine.py:312).

        The no-arg form keeps ONE persistent iterator across calls (reference
        PipelineEngine keeps self.data_iterator, pipe/engine.py:114) so that
        successive train_batch() calls walk the dataset instead of restarting
        it; the loader repeats across epochs via RepeatingLoader.
        """
        if data_iter is not None:
            it = data_iter
        else:
            if self._data_iter is None:
                from .dataloader import RepeatingLoader
                loader = self.training_dataloader
                if not isinstance(loader, RepeatingLoader):
                    loader = RepeatingLoader(loader)
                self._data_iter = iter(loader)
            it = self._data_iter
        fp = self.config.flops_profiler
        profiling = (fp.enabled and isinstance(self.module, CausalLM)
                     and self.global_steps + 1 == fp.profile_step)
        if profiling:
            if self.global_steps == 0:
                logger.warning("flops_profiler.profile_step=1 times the "
                               "first step, which includes XLA compilation")
            self._sync()
            t0 = time.perf_counter()
        losses = []
        seq_len = None
        for _ in range(self.gradient_accumulation_steps()):
            batch = next(it)
            if self.curriculum_scheduler is not None:
                batch = self._apply_curriculum(batch)
            if profiling and seq_len is None and isinstance(batch, dict):
                seq_len = int(np.asarray(batch["input_ids"]).shape[-1]) - 1
            losses.append(self.forward(batch))
            self.backward()
        self.step()
        if profiling:
            self._sync()
            dt = time.perf_counter() - t0
            from ..profiling import FlopsProfiler

            prof = FlopsProfiler(engine=self)
            report = prof.profile_report(
                batch_size=self.train_batch_size(),
                seq_len=seq_len or self.module.cfg.max_seq_len,
                step_time=dt)
            if fp.output_file:
                with open(fp.output_file, "w") as fh:
                    fh.write(report)
            else:
                print(report)
        return jnp.mean(jnp.stack(losses))

    def reset_data_iterator(self):
        """Drop the persistent no-arg ``train_batch`` iterator so the next
        call rebuilds it from ``training_dataloader``'s current position —
        the hook the resilience supervisor uses after restoring dataloader
        state from a checkpoint (runtime/resilience.py)."""
        self._data_iter = None

    def _apply_curriculum(self, batch):
        """Seqlen curriculum: truncate the token batch to the scheduled
        difficulty (reference engine curriculum path; difficulty_step
        quantization bounds the number of distinct compiled shapes)."""
        difficulty = self.curriculum_scheduler.update_difficulty(
            self.global_steps + 1)
        if not isinstance(batch, dict) or "input_ids" not in batch:
            return batch
        ids = batch["input_ids"]
        seq = int(np.asarray(ids).shape[-1]) - 1
        if difficulty >= seq:
            return batch
        out = dict(batch)
        for key in ("input_ids", "labels", "attention_mask"):
            if key in out:
                out[key] = np.asarray(out[key])[..., :difficulty + 1]
        return out

    def comms_report(self, batch=None, print_log: bool = True):
        """Static collective analysis of the compiled step programs
        (utils/comms_logging.analyze_compiled): per-op counts + per-shard
        bytes on the wire each step. Covers what the eager comms logger
        cannot see — collectives fused inside jit (ZeRO gathers, qwZ/qgZ
        quantized collectives, 1-bit int8 allreduce, TP/EP/SP traffic)."""
        from ..utils.comms_logging import (analyze_compiled,
                                           format_compiled_comms)

        if batch is None:
            micro = self.train_micro_batch_size_per_gpu()
            dp = self.topology.get_data_parallel_world_size()
            seq = getattr(getattr(self.module, "cfg", None), "max_seq_len",
                          128)
            batch = {"input_ids": np.zeros((micro * dp, min(seq, 128) + 1),
                                           np.int64)}
        batch = self._device_batch(batch)
        rng = jax.random.fold_in(self._rng, 0)

        rep = self.plan.replicated()

        def aval(x):
            # eval_shape drops shardings; keep them or GSPMD partitioning
            # (and thus every collective) vanishes from the lowered
            # program. Eagerly-created scalars carry SingleDeviceSharding —
            # normalize those to mesh-replicated so all args share devices.
            if isinstance(x, jax.Array):
                sh = x.sharding
                if isinstance(sh, jax.sharding.SingleDeviceSharding):
                    sh = rep
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
            return x

        avals = jax.tree.map(aval, (self.state, batch, rng))
        report = analyze_compiled(
            self._micro_fn.lower(*avals).compile())
        # the micro program runs gas times per optimizer step
        gas = self.gradient_accumulation_steps()
        for rec in report.values():
            rec["count"] *= gas
            rec["bytes"] *= gas
        update_fn = self._finalize_fn if self._finalize_fn is not None \
            else self._update_fn
        upd = analyze_compiled(update_fn.lower(avals[0]).compile())
        for op, rec in upd.items():
            dst = report.setdefault(op, {"count": 0, "bytes": 0,
                                         "group_sizes": set(),
                                         "dtypes": set()})
            dst["count"] += rec["count"]
            dst["bytes"] += rec["bytes"]
            dst["group_sizes"] |= rec["group_sizes"]
            dst["dtypes"] |= rec["dtypes"]
        if print_log:
            log_dist(format_compiled_comms(report), ranks=[0])
        return report

    def set_compression(self, transform):
        """Attach a CompressionTransform after construction (the
        ``init_compression(engine, config)`` path — reference
        compression/compress.py:100) and rebuild the step programs."""
        self._compression = transform if transform else None
        self._build_step_fns()
        self._layouts_tuned = False

    def set_custom_curriculum_learning_schedule(self, schedule_fn):
        """Reference engine.py set_custom_curriculum_learning_schedule."""
        if self.curriculum_scheduler is None:
            raise RuntimeError("curriculum_learning is not enabled")
        self.curriculum_scheduler.set_custom_get_difficulty(schedule_fn)

    def eval_batch(self, batch):
        batch = self._device_batch(batch) if not self._is_device_batch(batch) else batch
        return self._eval_fn(self.state, batch, None)

    # ------------------------------------------------------------- accessors
    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    @property
    def optimizer(self):
        return self.opt

    def get_lr(self):
        # state.global_step is authoritative (does not count overflow-skipped
        # steps); the host mirror would report a drifted LR after overflows.
        return [float(self.schedule(int(self.state.global_step)))]

    def get_global_grad_norm(self) -> Optional[float]:
        m = getattr(self, "_last_metrics", None)
        return float(m["grad_norm"]) if m else None

    @property
    def loss_scale(self) -> float:
        return float(self.state.scale_state.scale)

    @property
    def skipped_steps(self) -> int:
        """Overflow-skipped steps; reads the authoritative device counter
        lazily (no per-step host sync)."""
        return int(self.state.skipped_steps)

    def zero_optimization(self) -> bool:
        return self.zero_stage > 0

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    # ---------------------------------------------------------- checkpointing
    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True, exclude_frozen_parameters=False,
                        async_save=False, urgent=False):
        from .checkpointing import save_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state or {},
                     save_latest=save_latest, async_save=async_save,
                     urgent=urgent)

    def wait_pending_checkpoint(self):
        """Join an async_save's background writes (+ cross-host barrier)."""
        from .checkpointing import wait_pending_save

        wait_pending_save(self)

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False):
        from .checkpointing import load_checkpoint as _load

        return _load(self, load_dir, tag=tag,
                     load_optimizer_states=load_optimizer_states,
                     load_module_only=load_module_only)

    def save_16bit_model(self, save_dir, save_filename="pytorch_model.bin",
                         exclude_frozen_parameters=False):
        """Reference engine.py:3488: export params in compute dtype,
        consolidated (fully replicated)."""
        from .checkpointing import save_16bit_model as _save16

        return _save16(self, save_dir, save_filename)
