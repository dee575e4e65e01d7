"""Autotuning experiment worker — one rank of a multi-process trial.

Counterpart of the reference's experiment scheduler's launched scripts
(``autotuning/scheduler.py`` resource manager + the ``deepspeed``-launched
experiment runs it scrapes): the tuner shells out to the launcher
(``--launcher local --num_local_procs N``) with this module as the user
script; each rank rendezvouses through ``comm.init_distributed`` (the env
contract the launcher sets), builds the candidate engine over the REAL
multi-process mesh, times steps, and rank 0 writes the result JSON the
tuner reads back. This prices mesh-split candidates under true
multi-process collectives instead of single-process GSPMD.

Spec file (JSON): ``{"env": {...}, "model": {"kind": "causal_lm",
"config": {...TransformerConfig fields...}}, "config": {...engine
config with the candidate mesh/stage/micro...}, "seq_len": int,
"start_profile_step": int, "end_profile_step": int}``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import time


def _build_model(spec):
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import (CausalLM,
                                                  TransformerConfig)

    if spec.get("kind") != "causal_lm":
        raise ValueError(f"unknown model kind {spec.get('kind')!r}")
    d = dict(spec["config"])
    d["dtype"] = getattr(jnp, d.get("dtype", "float32"))
    if isinstance(d.get("sliding_window"), list):
        d["sliding_window"] = tuple(d["sliding_window"])
    return CausalLM(TransformerConfig(**d))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.spec) as fh:
        spec = json.load(fh)
    # env (e.g. JAX_PLATFORMS / XLA_FLAGS for CPU test meshes) must land
    # before jax import; the launcher already exported the rendezvous vars
    for k, v in spec.get("env", {}).items():
        os.environ[k] = str(v)

    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu import comm

    comm.init_distributed()
    model = _build_model(spec["model"])
    engine, _, _, _ = deepspeed_tpu.initialize(model=model,
                                               config=spec["config"])
    dp = engine.topology.get_data_parallel_world_size()
    micro = int(spec["config"]["train_micro_batch_size_per_gpu"])
    seq_len = int(spec.get("seq_len", 128))
    vocab = getattr(model.cfg, "vocab_size", 1024)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, vocab,
                                       size=(micro * dp, seq_len + 1),
                                       dtype=np.int64)}
    it = itertools.repeat(batch)
    start = int(spec.get("start_profile_step", 3))
    end = int(spec.get("end_profile_step", 5))
    for _ in range(start):                    # warmup / compile
        engine.train_batch(it)
    engine._sync()
    comm.barrier()
    t0 = time.perf_counter()
    for _ in range(max(1, end - start)):
        engine.train_batch(it)
    engine._sync()
    comm.barrier()
    dt = (time.perf_counter() - t0) / max(1, end - start)
    if jax.process_index() == 0:
        tokens = micro * dp * seq_len
        with open(args.out, "w") as fh:
            json.dump({"status": "ok", "step_time_s": dt,
                       "tokens_per_sec": tokens / dt,
                       "processes": jax.process_count()}, fh)


if __name__ == "__main__":
    main()
