"""Does the system still start on the chip?

Drives the two normal entry points once on a TPU, at the published widths
of Pythia-1.4B (hidden 2048, ffn 8192, 16 heads × 128, vocab 50304; random
weights from ``--seed``, no checkpoint, no network), and checks what comes
out by the repo's own means:

    python chip_smoke.py             # one chip: kernels, serve, train
    python chip_smoke.py --chips 4   # four chips: only the sharded paths

- ``kernels``: every Pallas entry point, Mosaic-compiled, against its XLA
  formulation on the same chip.
- ``serve``: ``ServingFrontend`` over one ``InferenceEngineV2`` (all 24
  layers), 8 requests submitted together, checked against a plain-XLA
  ``CausalLM.apply`` of the same params.
- ``train``: ``deepspeed_tpu.initialize`` (bf16, ZeRO-2), 5 steps. Every
  width is kept; depth is cut to what 16 GB holds with fp32 masters, Adam
  moments and a gradient accumulator (``TRAIN_LAYERS``).
- ``--chips 4``: ZeRO-3 over ``fsdp: 4`` and serving over ``tensor: 4``,
  each against the same program on one device of the same host.

One process, no retries, no watchdog, no artifact file. It fails — non-zero
exit, ``"ok": false`` and the reason — when the platform is not ``tpu``,
when fewer devices are present than asked for, or when any phase raises.
It assumes no peak rate and no memory size: what it needs to know of the
device it reads from ``device.memory_stats()``. Each phase prints one JSON
line; its seconds are a smoke's, not metrics. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The phase functions take the model and the sizes as arguments so that
``tests/test_chip_smoke.py`` can rehearse the control flow with the tiny
config on the CPU mesh; the script itself has no such option.
"""

import argparse
import dataclasses
import gc
import json
import sys
import time

import numpy as np

#: train depth at Pythia-1.4B widths, batch 8 × 2048, remat on: the
#: described-v5e compile of the engine's micro step (memory_analysis) needs
#: 15.44 GB of 15.75 GB at 8 layers and is refused at 9 (PR 24, CHANGES.md)
TRAIN_LAYERS = 8

KERNEL_CALL = "tpu_custom_call"


# ----------------------------------------------------------------- plumbing

def _programs_built():
    """Backend compiles so far (cache reads included), as the program's
    recorder of JAX's build events counts them."""
    from deepspeed_tpu.telemetry.builds import RECORDER

    return RECORDER.counters()["program_builds"]


def check(ok, why):
    """A check of the smoke: raises — ``assert`` would vanish under -O."""
    if not ok:
        raise AssertionError(why)


def device_record():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_record():
    """``bytes_limit`` / ``bytes_in_use`` / ``peak_bytes_in_use`` of device
    0 as the backend reports them — or that it reports none (no zeros)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return {"memory_stats": "not reported by this backend"}
    return {k: int(stats[k]) for k in
            ("bytes_limit", "bytes_in_use", "peak_bytes_in_use") if k in stats}


def run_phase(name, fn, **kwargs):
    """Run one phase and print its JSON line. A raise propagates."""
    # the program's own record of what JAX built (backend compiles, cache
    # reads included, and the persistent cache's hits among them)
    from deepspeed_tpu.telemetry.builds import RECORDER

    t0 = time.monotonic()
    before = RECORDER.snapshot()["announced"]["compile"]
    checked = fn(**kwargs)
    built = RECORDER.snapshot(since=t0)
    after = built["announced"]["compile"]
    dev = device_record()
    line = {"phase": name, "platform": dev["platform"],
            "device_kind": dev["kind"], "devices": dev["count"],
            "seconds": round(time.monotonic() - t0, 2),
            "compile_seconds": round(after["seconds"] - before["seconds"], 2),
            "compilations": after["count"] - before["count"],
            "compile_cache_hits": built["cache_hits"],
            "checked": checked, "memory": memory_record()}
    print(json.dumps(line), flush=True)
    return checked


def _release():
    """Drop what the last phase left on the device."""
    import jax

    gc.collect()
    jax.clear_caches()


def _on_tpu():
    from deepspeed_tpu.ops.pallas_utils import on_tpu

    return on_tpu()


def _compiled(fn, *args, **kwargs):
    """Compile ``fn`` (a function, or a jit as the program built it) for
    these arguments. On TPU the program must hold a Mosaic kernel — a
    kernel that quietly became its XLA formulation fails here."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    exe = jitted.lower(*args, **kwargs).compile()
    has_kernel = KERNEL_CALL in exe.as_text()
    if _on_tpu() and not has_kernel:
        raise AssertionError(
            f"{getattr(fn, '__name__', fn)}: no {KERNEL_CALL} in the "
            "compiled program — the Pallas kernel did not run")
    return exe, has_kernel


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))


def _tolerance(dtype):
    """Largest relative-to-range disagreement two correct programs may
    show in this compute dtype (bf16: eight bits of mantissa, a few
    roundings deep)."""
    import jax.numpy as jnp

    return 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 1e-3


# ------------------------------------------------------------------ kernels

def kernels_phase(cfg, seed=0, batch=2, seq=2048, n_seqs=8, block_size=64):
    """Each Pallas entry point at ``cfg``'s widths against its XLA
    formulation on this device (the numerics checks of the old
    tpu_smoke.py, at real shapes)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import flash_attention as fa
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.ops import quantizer as qz

    rng = np.random.default_rng(seed)
    dt = cfg.dtype
    tol = _tolerance(dt)
    H, KH, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    out = {}

    def rand(*shape, dtype=dt, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape), dtype)

    # flash attention, forward and backward, the model's own block sizes
    q, k, v = (rand(batch, seq, H, D), rand(batch, seq, KH, D),
               rand(batch, seq, KH, D))

    def flash_loss(q, k, v):
        o = fa.flash_attention(q, k, v, True, cfg.flash_block_q,
                               cfg.flash_block_kv)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def xla_loss(q, k, v):
        o = fa._attention_xla(q, k, v, True)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def grads(loss):
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    exe, has = _compiled(grads(flash_loss), q, k, v)
    (_, o), g = exe(q, k, v)
    (_, o_ref), g_ref = jax.jit(grads(xla_loss))(q, k, v)
    errs = [_rel_err(o, o_ref)] + [_rel_err(a, b) for a, b in zip(g, g_ref)]
    check(max(errs) <= 2 * tol, f"flash fwd/dq/dk/dv rel err {errs}")
    out["flash_fwd_bwd"] = {"shape": [batch, seq, H, D], "kernel": has,
                            "max_rel_err": round(max(errs), 5)}

    # paged attention: decode and prefill-chunk rows over a shuffled pool,
    # plain pools and int8 pools with their scale planes
    MB = -(-cfg.max_seq_len // block_size)
    NB = n_seqs * MB
    for quant in (False, True):
        for chunk in (1, 256):
            ctx = rng.integers(chunk, cfg.max_seq_len, size=n_seqs)
            tables = np.full((n_seqs, MB), -1, np.int32)
            perm = rng.permutation(NB)
            pos = 0
            for i, c in enumerate(ctx):
                nblk = -(-int(c) // block_size)
                tables[i, :nblk] = perm[pos:pos + nblk]
                pos += nblk
            pool = (NB, KH, block_size, D)
            if quant:
                pools = [jnp.asarray(rng.integers(-127, 128, pool), jnp.int8)
                         for _ in "kv"]
                scales = {name: jnp.asarray(rng.uniform(0.004, 0.012,
                                                        (NB, KH)), jnp.float32)
                          for name in ("k_scale", "v_scale")}
            else:
                pools, scales = [rand(*pool) for _ in "kv"], {}
            args = [rand(n_seqs, chunk, H, D), *pools, jnp.asarray(tables),
                    jnp.asarray(ctx - chunk, jnp.int32),
                    jnp.full((n_seqs,), chunk, jnp.int32)]
            exe, has = _compiled(pa.paged_attention, *args, **scales)
            err = _rel_err(exe(*args, **scales),
                           jax.jit(pa.paged_attention_xla)(*args, **scales))
            check(err <= 2 * tol, f"paged quant={quant} C={chunk}: {err}")
            out[f"paged_{'int8' if quant else 'plain'}_c{chunk}"] = {
                "kernel": has, "max_rel_err": round(err, 5)}

    # weight-quant serving matmul and the block (de)quantize pair, at the
    # MLP's two projection shapes
    h, m = cfg.hidden_size, cfg.intermediate_size
    for kdim, n in ((h, m), (m, h)):
        w = rand(kdim, n, dtype=jnp.float32, scale=0.02)
        exe, has = _compiled(lambda w: qz.quantize_blockwise(w, block=128), w)
        qw, qs = exe(w)
        q_ref, s_ref = jax.jit(lambda w: qz._quantize_xla(w, 8, 128))(w)
        steps = np.abs(np.asarray(qw, np.int32) - np.asarray(q_ref, np.int32))
        # the two may round a tie apart; never by more than one step
        check(steps.max() <= 1 and steps.mean() < 1e-3,
              f"quantize [{kdim},{n}]: max {steps.max()} mean {steps.mean()}")
        np.testing.assert_allclose(np.asarray(qs), np.asarray(s_ref),
                                   rtol=1e-6)
        exe_d, has_d = _compiled(
            lambda q, s: qz.dequantize_blockwise(q, s, block=128), qw, qs)
        back = np.abs(np.asarray(exe_d(qw, qs)) - np.asarray(w))
        half_step = np.repeat(np.asarray(qs), 128, -1) * 0.501 + 1e-7
        check((back <= half_step).all(),
              f"dequantize [{kdim},{n}]: round trip beyond half a step")
        out[f"quantize_{kdim}x{n}"] = {"kernel": has and has_d,
                                      "steps_apart": int(steps.max())}
        for rows in (n_seqs, 256):
            x = rand(rows, kdim)
            exe, has = _compiled(
                lambda x, q, s: qz.quantized_matmul(x, q, s, block=128),
                x, qw, qs)
            ref = jax.jit(lambda x, q, s: (
                x.astype(jnp.float32)
                @ qz._dequantize_xla(q, s, 128, jnp.float32)))(x, qw, qs)
            err = _rel_err(exe(x, qw, qs), ref)
            check(err <= 2 * tol, f"qmm m={rows} [{kdim},{n}]: {err}")
            out[f"qmm_m{rows}_{kdim}x{n}"] = {"kernel": has,
                                              "max_rel_err": round(err, 5)}
    return out


# -------------------------------------------------------------------- serve

def _seeded_params(model, seed, dtype):
    """Random weights from the seed, held in the model's compute dtype (as
    a checkpoint of that dtype would be)."""
    import jax

    init = jax.jit(lambda key: jax.tree.map(lambda x: x.astype(dtype),
                                            model.init(key)))
    return init(jax.random.PRNGKey(seed))


def _reference_logits(cfg, params, prompts):
    """Logits at each prompt's last position from ``CausalLM.apply`` with
    plain XLA attention — no paged pool, no chunking, no Pallas kernel.
    Prompts are right-padded to one length (causal: the pad is unseen)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import CausalLM

    ref = CausalLM(dataclasses.replace(cfg, attention_impl="reference"))
    width = max(len(p) for p in prompts)

    @jax.jit
    def last_logits(params, tokens, last):
        return ref.apply(params, tokens)[0, last].astype(jnp.float32)

    out = []
    for p in prompts:
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :len(p)] = p
        out.append(np.asarray(last_logits(params, jnp.asarray(tokens),
                                          len(p) - 1)))
    return out


def _engine_logits(engine, uid, prompt):
    """The engine's own logits at the prompt's last position: the prompt
    fed through ``put`` in chunks, as the scheduler feeds it."""
    chunk = engine.config.max_chunk_tokens
    for i in range(0, len(prompt), chunk):
        logits = engine.put([uid], [prompt[i:i + chunk]])
    engine.flush(uid)
    return np.asarray(logits[0], np.float32)


def serve_phase(cfg, seed=0, prompt_lens=(), max_new=32, engine_cfg=None):
    """8 requests through ``ServingFrontend`` → ``ContinuousBatchingScheduler``
    → ``InferenceEngineV2``, submitted together so SplitFuse mixes prefill
    chunks with decodes; then the engine against ``CausalLM.apply``."""
    import jax

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in prompt_lens]
    model = CausalLM(cfg)
    params = _seeded_params(model, seed, cfg.dtype)
    ecfg = RaggedInferenceEngineConfig(**(engine_cfg or {}))
    engine = InferenceEngineV2(model, params=params, config=ecfg)
    kv_blocks = ecfg.kv_blocks
    leaves = jax.tree.leaves(engine.params)
    weights = {"dtype": sorted({str(x.dtype) for x in leaves}),
               "bytes": int(sum(x.nbytes for x in leaves))}

    # the bucket shapes the engine's forward is asked for ([seqs, chunk])
    shapes = []
    forward = engine.paged.forward

    def recording_forward(params, kv, tokens, *rest):
        if tokens.shape not in shapes:
            shapes.append(tuple(tokens.shape))
        return forward(params, kv, tokens, *rest)

    engine.paged.forward = recording_forward

    def serve_all(fe):
        handles = [fe.submit(p, max_new_tokens=max_new) for p in prompts]
        check(fe.wait_all(handles, timeout=900), "requests did not finish")
        streams = [[ev.token for ev in h.drain()] for h in handles]
        for h, toks in zip(handles, streams):
            check(h.finish_reason == "length" and len(toks) == max_new,
                  f"request {h.uid}: {h.finish_reason}, {len(toks)} tokens")
        return streams

    fe = ServingFrontend([engine], ServingConfig())
    try:
        streams = serve_all(fe)
        warm = _programs_built()
        serve_all(fe)                # again: every bucket is compiled now
        after_warmup = _programs_built() - warm
        free = engine.state_manager.available_blocks
        check(free == kv_blocks, f"{kv_blocks - free} KV blocks not returned")
    finally:
        fe.shutdown(drain=False, timeout=30)

    # the forward that ran holds the Pallas paged kernel, not the XLA gather
    n_seqs, chunk = shapes[0]
    tbl = np.zeros((n_seqs, engine.batch.max_blocks_per_seq), np.int32)
    vec = np.zeros((n_seqs,), np.int32)
    _, has_kernel = _compiled(forward, engine.params,
                              engine.state_manager.kv_cache,
                              np.zeros((n_seqs, chunk), np.int32), vec, vec,
                              tbl)

    # the engine against the plain forward, same params, same device
    tol = _tolerance(cfg.dtype)
    ref = _reference_logits(cfg, params, prompts)
    worst, checked_first = 0.0, 0
    for i, (p, want) in enumerate(zip(prompts, ref)):
        got = _engine_logits(engine, 10_000 + i, p)
        scale = float(np.max(np.abs(want)))
        err = float(np.max(np.abs(got - want))) / scale
        worst = max(worst, err)
        check(np.isfinite(got).all() and err <= tol,
              f"request {i}: engine vs CausalLM.apply logits differ by {err}")
        top2 = np.sort(want)[-2:]
        if top2[1] - top2[0] > 2 * tol * scale:      # a clear winner
            checked_first += 1
            check(streams[i][0] == int(np.argmax(want)),
                  f"request {i}: first token is not the reference argmax")
    check(engine.state_manager.available_blocks == kv_blocks,
          "the logits replay left KV blocks allocated")
    return {"model_layers": cfg.num_layers, "requests": len(prompts),
            "prompt_tokens": list(map(len, prompts)), "new_tokens": max_new,
            "logits_max_rel_err": round(worst, 5), "tolerance": tol,
            "first_token_checked": checked_first,
            "kv_blocks_returned": kv_blocks,
            "pallas_call_in_forward": has_kernel,
            "weights": weights, "bucket_shapes": shapes,
            "compilations_after_warmup": after_warmup}


# -------------------------------------------------------------------- train

def _train_config(micro_batch, stage, seed, mesh=None):
    config = {"train_micro_batch_size_per_gpu": micro_batch,
              "gradient_accumulation_steps": 1,
              "optimizer": {"type": "AdamW",
                            "params": {"lr": 3e-4, "weight_decay": 0.1}},
              "bf16": {"enabled": True},
              "zero_optimization": {"stage": stage},
              "steps_per_print": 10 ** 9, "seed": seed}
    if mesh is not None:
        config["mesh"] = mesh
    return config


def _reduced(cfg, full_layers):
    """The cut of scale, as the records name it: [published, run]."""
    return {"num_layers": [full_layers or cfg.num_layers, cfg.num_layers]}


def _train_steps(engine, batch, steps):
    losses = []
    for _ in range(steps):
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"loss not finite: {losses}")
    return losses


def _flash_in_micro_step(engine, batch):
    """Is the flash kernel in the compiled micro step? (lowered again from
    the engine's own jit — a cache read once the step has run)"""
    import jax

    _, has = _compiled(engine._micro_fn, engine.state,
                       engine._device_batch(batch),
                       jax.random.PRNGKey(0))
    return has


def train_phase(cfg, seed=0, batch=8, seq=2048, steps=5, full_layers=None):
    """``deepspeed_tpu.initialize`` (bf16, ZeRO-2, ``mesh: {data: -1}``) and
    a few steps on one repeated seeded batch."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM
    from deepspeed_tpu.parallel import topology as topo

    topo.reset_topology()
    dp = len(jax.devices())         # mesh {data: -1}: every device
    check(batch % dp == 0, f"batch {batch} over {dp} data-parallel devices")
    engine, *_ = deepspeed_tpu.initialize(
        model=CausalLM(cfg),
        config=_train_config(batch // dp, 2, seed, mesh={"data": -1}))
    data = {"input_ids": np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int64)}
    losses = _train_steps(engine, data, steps)
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    tuned = engine._state_formats is not None
    if _on_tpu():
        check(tuned, "layout autotune did not take effect")
    return {"reduced": _reduced(cfg, full_layers), "remat": cfg.remat, "params": CausalLM(cfg).num_params(),
            "batch": [batch, seq], "steps": steps,
            "losses": [round(x, 4) for x in losses],
            "flash_call_in_micro_step": _flash_in_micro_step(engine, data),
            "layouts_tuned": tuned}


# --------------------------------------------------------------- four chips

def _shard_shares(params, min_elems=1 << 20):
    """For every large parameter, the smallest and largest share of its
    bytes that any one device holds."""
    import jax

    lo, hi = 1.0, 0.0
    for leaf in jax.tree.leaves(params):
        if leaf.size < min_elems:
            continue
        held = {}
        for s in leaf.addressable_shards:
            held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
        shares = [held.get(d.id, 0) / leaf.nbytes for d in jax.devices()]
        lo, hi = min(lo, min(shares)), max(hi, max(shares))
    return lo, hi


def zero3_phase(cfg, seed=0, batch=8, seq=2048, steps=3, full_layers=None):
    """ZeRO-3 over ``fsdp: <all devices>`` against the same steps on a
    one-device mesh of the same host: same losses, and the parameters
    really spread over the devices."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM
    from deepspeed_tpu.parallel import topology as topo

    n = len(jax.devices())
    data = {"input_ids": np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int64)}

    def run(mesh_topology, micro):
        topo.reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=CausalLM(cfg), mesh=mesh_topology,
            config=_train_config(micro, 3, seed))
        shares = _shard_shares(engine.state.params)
        losses = _train_steps(engine, data, steps)
        has_flash = _flash_in_micro_step(engine, data)
        return losses, shares, has_flash

    one = topo.MeshTopology.build(devices=jax.devices()[:1], data=1)
    ref_losses, _, _ = run(one, batch)
    _release()
    losses, (lo, hi), has_flash = run(
        topo.MeshTopology.build(data=1, fsdp=n), batch // n)
    np.testing.assert_allclose(losses, ref_losses, rtol=_tolerance(cfg.dtype))
    check(abs(lo - 1 / n) < 0.01 and abs(hi - 1 / n) < 0.01,
          f"a device holds {lo:.3f}..{hi:.3f} of a large parameter, not 1/{n}")
    return {"reduced": _reduced(cfg, full_layers), "mesh": {"fsdp": n}, "steps": steps,
            "losses": [round(x, 4) for x in losses],
            "losses_one_device": [round(x, 4) for x in ref_losses],
            "param_share_per_device": [round(lo, 4), round(hi, 4)],
            "flash_call_in_micro_step": has_flash}


def tensor_serve_phase(cfg, seed=0, engine_cfg=None, lens=(256, 100, 200)):
    """``InferenceEngineV2`` over ``tensor: <all devices>`` against the
    one-device engine: a prefill ``put`` and then one mixed ``put`` (two
    decode rows beside a prompt chunk) give the same logits, with pools
    and kernel split by kv-head."""
    import jax

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM
    from deepspeed_tpu.parallel import topology as topo

    n = len(jax.devices())
    rng = np.random.default_rng(seed)
    a, b, c = (rng.integers(0, cfg.vocab_size, size=k).tolist() for k in lens)
    model = CausalLM(cfg)
    params = _seeded_params(model, seed, cfg.dtype)

    def run(mesh):
        engine = InferenceEngineV2(
            model, params=params, mesh=mesh,
            config=RaggedInferenceEngineConfig(**(engine_cfg or {})))
        first = np.asarray(engine.put([1, 2], [a, b]), np.float32)
        nxt = [int(np.argmax(row)) for row in first]
        mixed = np.asarray(engine.put([1, 2, 3], [[nxt[0]], [nxt[1]], c]),
                           np.float32)
        return engine, first, mixed

    _, first_ref, mixed_ref = run(None)
    _release()
    topo.reset_topology()
    engine, first, mixed = run(topo.MeshTopology.build(data=1, tensor=n))
    tol = _tolerance(cfg.dtype)
    errs = [_rel_err(first, first_ref), _rel_err(mixed, mixed_ref)]
    check(max(errs) <= tol, f"tensor:{n} vs one device logits: {errs}")

    # split, not replicated: each device's pool shard and the kernel's
    # pool operand hold kv_heads / n heads
    pool = engine.state_manager.kv_cache["k"]
    local = pool.addressable_shards[0].data.shape
    check(local[2] * n == pool.shape[2], f"pool shard {local} of {pool.shape}")
    exe, has_kernel = _compiled(       # the mixed put's bucket, [4, 256]
        engine.paged.forward, engine.params, engine.state_manager.kv_cache,
        np.zeros((4, 256), np.int32), np.zeros((4,), np.int32),
        np.zeros((4,), np.int32),
        np.zeros((4, engine.batch.max_blocks_per_seq), np.int32))
    calls = [ln for ln in exe.as_text().splitlines() if KERNEL_CALL in ln]
    # the kernel reads the stacked pool where it lies: its operand is the
    # device's whole [L, NB, KH/n, bs, D] shard, not a layer's slab of it
    operand = "[{},{},{},{},{}]".format(*local)
    check(all(operand in ln for ln in calls),
          f"paged kernel does not run on a {operand} pool shard")
    return {"mesh": {"tensor": n}, "logits_max_rel_err": round(max(errs), 5),
            "tolerance": tol, "pool_shard": list(local),
            "pallas_call_on_shard": has_kernel}


# --------------------------------------------------------------------- main

#: engine sizing for the Pythia-1.4B serve phases: 64-token blocks, a pool
#: that holds the 8 requests whole (8 × (1024 + 32) tokens = 132 blocks)
#: with room to spare
SERVE_ENGINE = {"kv_block_size": 64, "kv_blocks": 192}


def run(chips, seed):
    import jax

    from deepspeed_tpu.models.transformer import PYTHIA_1B4
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(json.dumps({"compile_cache_dir": cache_dir,
                      "jax": jax.__version__, "seed": seed}), flush=True)
    shallow = dataclasses.replace(PYTHIA_1B4, num_layers=TRAIN_LAYERS,
                                  remat=True)
    full = PYTHIA_1B4.num_layers
    if chips == 1:
        run_phase("kernels", kernels_phase, cfg=PYTHIA_1B4, seed=seed)
        _release()
        lens = 128 * np.random.default_rng(seed).integers(1, 9, size=8)
        lens[:2] = (128, 1024)                      # both ends, always
        run_phase("serve", serve_phase, cfg=PYTHIA_1B4, seed=seed,
                  prompt_lens=[int(x) for x in lens],
                  engine_cfg=SERVE_ENGINE)
        _release()
        run_phase("train", train_phase, cfg=shallow, seed=seed,
                  full_layers=full)
    else:
        run_phase("zero3_fsdp", zero3_phase, cfg=shallow, seed=seed,
                  full_layers=full)
        _release()
        run_phase("serve_tensor", tensor_serve_phase, cfg=PYTHIA_1B4,
                  seed=seed, engine_cfg=SERVE_ENGINE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded paths and what they are "
                         "compared with (the driver runs 1)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = None
    try:
        device = device_record()
        if device["platform"] != "tpu":
            raise RuntimeError(
                f"platform is {device['platform']!r}, not 'tpu': this "
                "script only means anything on the chip")
        if device["count"] < args.chips:
            raise RuntimeError(f"{args.chips} chips asked for, "
                               f"{device['count']} present")
        run(args.chips, args.seed)
    except Exception as e:      # not to carry on: to say why, then fail
        import traceback

        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "reason": f"{type(e).__name__}: {e}"[:2000],
                          "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
