"""Runner for cells that train: ``deepspeed_tpu.initialize`` and then
``engine(batch)`` / ``engine.backward`` / ``engine.step`` over seeded
batches, cycled. A step ends when its loss and the update's metrics have
reached the host, so the host clock around it measures finished work."""

from __future__ import annotations

import time

import numpy as np

from . import traffic
from .device import TraceWindow, compile_cache_off, memory_peak_bytes
from .model import transformer_config
from .probe import Probe


def build(info: dict, seed: int, chips: int):
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM
    from deepspeed_tpu.parallel import topology as topo

    wl, mix = info["workload"], info["traffic"]
    cfg = transformer_config(info, **wl.get("arch_overrides", {}))
    if mix["sequence_tokens"] > cfg.max_seq_len:
        raise ValueError("sequences longer than the positions run")
    config = dict(wl["train_config"])
    config["train_micro_batch_size_per_gpu"] = int(mix["sequences_per_chip"])
    config["seed"] = seed
    import jax

    # the mesh over the chips the cell asks for, whatever else the
    # machine holds (a rehearsal's eight CPU devices)
    topo.reset_topology()
    mesh = topo.MeshTopology.build(devices=jax.devices()[:chips],
                                   **wl["mesh"])
    engine, *_ = deepspeed_tpu.initialize(model=CausalLM(cfg), mesh=mesh,
                                          config=config)
    if engine.topology.world_size != chips:
        raise RuntimeError(f"the mesh spans {engine.topology.world_size} "
                           f"devices, the cell asks for {chips}")
    return cfg, engine


def one_step(engine, batch) -> float:
    loss = engine(batch)
    engine.backward(loss)
    metrics = engine.step()
    value = float(loss)
    for v in (metrics or {}).values():      # the update has finished too
        np.asarray(v)
    return value


def check_loss(engine, batch, info, tolerance: float) -> dict:
    """The engine's loss on (its current parameters, this batch) against
    that of the configuration's block reference (``info["block"]``) on
    the same two. The reference reads the fp32
    masters where they lie; under the mesh XLA gathers each layer's
    weights as the scan reaches it, so no second copy of the model is
    held. Then one more engine step on that batch gives the engine's
    loss for those same parameters."""
    import jax

    block, arch = info["block"], info["config"]["transformer_config"]
    want = float(jax.jit(lambda p, t: block.loss(p, t, arch, q_block=512))(
        engine.state.params, np.asarray(batch, np.int32)))
    got = one_step(engine, {"input_ids": batch})
    err = abs(got - want) / abs(want)
    return {"ok": bool(np.isfinite(got) and err <= tolerance),
            "engine_loss": got, "reference_loss": want, "rel_err": err,
            "tolerance": tolerance,
            "why": None if err <= tolerance else
            f"engine loss {got:.5f} vs reference {want:.5f}"}


def run(info: dict, args, watch, process_t0: float) -> dict:
    cell, mix, wl = info["cell"], info["traffic"], info["workload"]
    chips, traced = int(cell["chips"]), bool(args.trace)
    batches = None
    # The engine pins its step programs to the layouts its one-time
    # autotune read back from XLA. An executable that comes out of the
    # persistent compile cache does not keep those output layouts: the
    # next program then refuses its argument ("Layout passed to jit does
    # not match the layout on the respective arg" — every warm run on the
    # chip, PR 25; first in the micro step, and with only the autotune
    # compile kept out of the cache, in the update). So the engine's
    # programs are compiled afresh in every run, all of them in the
    # warm-up; the cache is on again for the reference. PERF.md section 7.
    with compile_cache_off():
        cfg, engine = build(info, args.seed, chips)
        batches = traffic.generator(info).batches(mix, cfg.vocab_size,
                                                  args.seed, chips)
        losses = [one_step(engine, {"input_ids": batches[i % len(batches)]})
                  for i in range(int(wl.get("warm_up_steps", 2)))]
    tokens_per_step = batches[0].shape[0] * (batches[0].shape[1] - 1)
    probe = Probe()
    if traced:      # after the warm-up, so both kinds of run compile alike
        probe.wrap(engine, "forward", "micro")
        probe.wrap(engine, "step", "update")
    compiles_before = watch.count
    w0 = time.monotonic()
    setup_s = w0 - process_t0
    trace = None
    if traced:
        span = min(float(wl.get("trace_s", 5.0)), float(args.seconds))
        trace = TraceWindow(args.trace_dir, w0 + args.seconds - span,
                            w0 + args.seconds)
    ends, i = [], len(losses)
    while time.monotonic() < w0 + args.seconds:
        losses.append(one_step(engine,
                               {"input_ids": batches[i % len(batches)]}))
        ends.append(time.monotonic())
        i += 1
    compiles_in_window = watch.count - compiles_before
    peak = memory_peak_bytes(chips)
    xplane = trace.finish() if trace is not None else None

    check = check_loss(engine, batches[i % len(batches)], info,
                       info["config"]["check"]["loss_tolerance"])
    n = len(batches)
    fell = float(np.mean(losses[-n:])) < float(np.mean(losses[:n])) \
        if len(losses) >= 2 * n else losses[-1] < losses[0]
    why = [w for w in (
        check.get("why"),
        None if all(np.isfinite(losses)) else "a loss is not finite",
        None if fell else f"loss did not fall: {losses[:2]} … {losses[-2:]}",
        None if compiles_in_window == 0 else
        f"{compiles_in_window} compilations inside the window",
        None if ends else "no step finished in the window") if w]
    starts = [w0] + ends[:-1]
    return {
        "correct": not why, "why_not": why,
        "attempted": len(ends), "failed": 0,
        "setup_s": setup_s, "memory_peak_bytes": peak,
        "window": (w0, ends[-1] if ends else w0 + args.seconds),
        "step_seconds": [b - a for a, b in zip(starts, ends)],
        "tokens_per_step": tokens_per_step, "losses": losses,
        "xplane": xplane, "probe": probe, "program_spans": [],
        "trace_marks": trace.marks if trace is not None else None,
        # each number ``correct`` compared, beside its limit
        "checks": {"loss_rel_err": (check["rel_err"], check["tolerance"]),
                   "compiles_in_window": (compiles_in_window, 0)},
        "counters": {"compiles_in_window": compiles_in_window,
                     "loss_check": check, "steps": len(ends),
                     "first_losses": losses[:3], "last_losses": losses[-3:]},
        "arch": dict(info["config"]["transformer_config"],
                     **wl.get("arch_overrides", {})),
        "sequence_tokens": int(mix["sequence_tokens"]), "chips": chips,
    }
