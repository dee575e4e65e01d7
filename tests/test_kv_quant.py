"""int8 KV-cache quantization (ISSUE 6): int8 pools + per-(layer, block,
kv-head) scale planes in DSStateManager, fused quantized write /
in-kernel dequantized read in PagedCausalLM, engine/serving config
plumbing, occupancy observability, and composition with every subsystem
that touches KV blocks (prefix cache, speculative trim, failover,
cancel). The quant-off engine must behave byte-for-byte like the
pre-quant engine; quant-on carries bounded-divergence + perplexity
gates (docs/SERVING.md "KV quantization")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kv_quant import (blocks_for_budget,
                                                 kv_bytes_per_block,
                                                 validate_kv_quant)
from deepspeed_tpu.inference.v2.ragged import BlockedAllocator, DSStateManager
from deepspeed_tpu.inference.v2.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.inference.v2.spec import NGramProposer
from deepspeed_tpu.inference.v2.testing import (assert_greedy_parity,
                                                greedy_generate)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

VOCAB = 128
BS = 16          # kv block size used throughout


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(vocab_size=VOCAB, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=4,
                            max_seq_len=256, norm="rmsnorm",
                            activation="silu", position="rope")
    model = CausalLM(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def make_engine(model, params, quant=True, kv_blocks=64, max_seqs=8,
                qdtype="int8", **cfg_kw):
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=256, max_ragged_sequence_count=max_seqs,
        max_chunk_tokens=32, kv_blocks=kv_blocks, kv_block_size=BS,
        max_tracked_sequences=64, kv_quant_enabled=quant,
        kv_quant_dtype=qdtype, **cfg_kw)
    return InferenceEngineV2(model, params=params, config=vcfg)


# the representation axis (ISSUE 13): the PR 6 suite runs for both the
# int8 pools and fp8_e4m3 on the reserved ``kv_quant.dtype`` surface —
# same scale machinery, different payload dtype
KV_DTYPES = ("int8", "fp8_e4m3")


def rand_prompt(rng, n):
    return rng.integers(0, VOCAB, size=n).tolist()


# ------------------------------------------------------------ state + bytes
@pytest.mark.parametrize("qdtype", KV_DTYPES)
def test_quantized_pools_and_scale_planes(model_and_params, qdtype):
    model, params = model_and_params
    eng = make_engine(model, params, quant=True, qdtype=qdtype)
    kv = eng.state_manager.kv_cache
    L, KH, D = model.cfg.num_layers, model.cfg.kv_heads, model.cfg.head_dim
    want = jnp.int8 if qdtype == "int8" else jnp.float8_e4m3fn
    assert kv["k"].dtype == want and kv["v"].dtype == want
    assert kv["k_scale"].shape == (L, 64, KH)
    assert kv["k_scale"].dtype == jnp.float32
    # quant-off: no scale planes at all (the forward branches on the
    # cache pytree, so absence IS the byte-identical historical program)
    off = make_engine(model, params, quant=False)
    assert set(off.state_manager.kv_cache) == {"k", "v"}


def test_bytes_per_block_and_budget(model_and_params):
    model, params = model_and_params
    cfg = model.cfg
    base = kv_bytes_per_block(cfg, BS, quant=False)
    i8 = kv_bytes_per_block(cfg, BS, quant=True)
    slab = cfg.num_layers * cfg.kv_heads * BS * cfg.head_dim
    assert base == 2 * slab * jnp.dtype(cfg.dtype).itemsize
    assert i8 == 2 * slab + 2 * cfg.num_layers * cfg.kv_heads * 4
    assert i8 < base
    # the headline claim: a fixed byte budget buys >= 1.5x the blocks
    budget = 32 * base
    assert blocks_for_budget(budget, cfg, BS, quant=True) >= 48
    eng = make_engine(model, params, quant=True)
    occ = eng.occupancy()
    assert occ["bytes_per_block"] == i8
    assert occ["bytes_total"] == 64 * i8


def test_validate_kv_quant_rejects_unknown():
    validate_kv_quant("int8", "block")
    validate_kv_quant("fp8_e4m3", "block")    # ISSUE 13: now real
    with pytest.raises(ValueError, match="dtype"):
        validate_kv_quant("fp8", "block")     # the short spelling is not
    with pytest.raises(ValueError, match="scale_granularity"):
        validate_kv_quant("int8", "tensor")


def test_allocator_occupancy_math():
    a = BlockedAllocator(8, bytes_per_block=100)
    a.allocate(3)
    occ = a.occupancy()
    assert occ == {"total_blocks": 8, "free_blocks": 5, "in_use_blocks": 3,
                   "bytes_per_block": 100, "bytes_in_use": 300,
                   "bytes_total": 800}


# ----------------------------------------------------- disabled byte-parity
def test_disabled_path_byte_identical(model_and_params):
    """kv_quant config present-but-disabled must produce the exact same
    logits as an engine that never heard of it."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    prompt = rand_prompt(rng, 30)
    eng_default = InferenceEngineV2(model, params=params,
                                    config=RaggedInferenceEngineConfig(
                                        max_ragged_batch_size=256,
                                        max_ragged_sequence_count=8,
                                        max_chunk_tokens=32, kv_blocks=64,
                                        kv_block_size=BS))
    eng_off = make_engine(model, params, quant=False)
    la = np.asarray(eng_default.put([1], [prompt]))
    lb = np.asarray(eng_off.put([1], [prompt]))
    np.testing.assert_array_equal(la, lb)


def test_disabled_greedy_stream_identical(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(1)
    prompts = [rand_prompt(rng, 25), rand_prompt(rng, 18)]
    g_default = greedy_generate(
        InferenceEngineV2(model, params=params,
                          config=RaggedInferenceEngineConfig(
                              max_ragged_batch_size=256,
                              max_ragged_sequence_count=8,
                              max_chunk_tokens=32, kv_blocks=64,
                              kv_block_size=BS)),
        prompts, uid_base=1, max_new_tokens=10)
    g_off = greedy_generate(make_engine(model, params, quant=False),
                            prompts, uid_base=1, max_new_tokens=10)
    assert_greedy_parity(g_default, g_off, label="kv_quant disabled")


# ------------------------------------------------- quality gates (quant on)
@pytest.mark.parametrize("qdtype", KV_DTYPES)
def test_bounded_divergence_and_logit_error(model_and_params, qdtype):
    model, params = model_and_params
    rng = np.random.default_rng(2)
    prompts = [rand_prompt(rng, 30) for _ in range(3)]
    g_off = greedy_generate(make_engine(model, params, quant=False),
                            prompts, uid_base=1, max_new_tokens=16)
    g_on = greedy_generate(make_engine(model, params, quant=True,
                                       qdtype=qdtype),
                           prompts, uid_base=1, max_new_tokens=16)
    fracs = []
    for a, b in zip(g_off, g_on):
        matched = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                       min(len(a), len(b)))
        fracs.append(matched / max(1, len(a)))
    # int8 with per-block scales: ~0.1% relative logit error on this
    # model — streams should mostly agree; gate loosely, report exactly
    assert np.mean(fracs) >= 0.5, f"divergence too large: {fracs}"
    # teacher-forced logits stay close
    p = prompts[0]
    la = np.asarray(make_engine(model, params, quant=False).put([9], [p]))
    lb = np.asarray(make_engine(model, params, quant=True,
                                qdtype=qdtype).put([9], [p]))
    rel = np.max(np.abs(la - lb)) / (np.max(np.abs(la)) + 1e-9)
    assert rel < 0.05, f"relative logit error {rel}"


@pytest.mark.parametrize("qdtype", KV_DTYPES)
def test_perplexity_delta_gate(model_and_params, qdtype):
    """Teacher-forced perplexity of the quantized engine within 5% of
    the unquantized engine (the bench kv_quant phase's gate, in
    miniature) — both the int8 and fp8_e4m3 representations."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    toks = rand_prompt(rng, 64)
    chunk = 16

    def nll(eng, uid):
        total, count = 0.0, 0
        for lo in range(0, len(toks), chunk):
            ch = toks[lo:lo + chunk]
            logits = np.asarray(eng.put([uid], [ch],
                                        verify_width=len(ch)))[0]
            for j in range(len(ch)):
                t = lo + j + 1
                if t >= len(toks):
                    break
                row = logits[j].astype(np.float64)
                lse = row.max() + np.log(np.exp(row - row.max()).sum())
                total += lse - row[toks[t]]
                count += 1
        return total / count

    ppl_off = np.exp(nll(make_engine(model, params, quant=False), 1))
    ppl_on = np.exp(nll(make_engine(model, params, quant=True,
                                    qdtype=qdtype), 1))
    assert abs(ppl_on / ppl_off - 1.0) <= 0.05, (ppl_off, ppl_on)


# ------------------------------------------------------------- composition
@pytest.mark.parametrize("qdtype", KV_DTYPES)
def test_trim_across_block_boundary_requantizes(model_and_params, qdtype):
    """Speculative rollback across a block boundary: the freed block
    returns to the pool, the partial block re-quantizes on the next
    write, and decoding continues — both representations."""
    model, params = model_and_params
    rng = np.random.default_rng(4)
    eng = make_engine(model, params, quant=True, qdtype=qdtype)
    uid = 7
    eng.put([uid], [rand_prompt(rng, 30)])       # seen=30 (2 blocks)
    eng.put([uid], [rand_prompt(rng, 5)])        # seen=35 (3 blocks)
    seq = eng.state_manager.get_sequence(uid)
    assert (seq.seen_tokens, len(seq.kv_blocks)) == (35, 3)
    free0 = eng.occupancy()["free_blocks"]
    assert eng.trim_sequence(uid, 7) == 1        # 35 -> 28: drops block 2
    assert (seq.seen_tokens, len(seq.kv_blocks)) == (28, 2)
    assert eng.occupancy()["free_blocks"] == free0 + 1
    # rewrite across the trimmed region and keep decoding
    logits = np.asarray(eng.put([uid], [rand_prompt(rng, 10)]))
    assert logits.shape == (1, VOCAB)
    assert seq.seen_tokens == 38
    eng.flush(uid)
    assert eng.occupancy()["in_use_blocks"] == 0


@pytest.mark.parametrize("qdtype", KV_DTYPES)
def test_spec_decode_composes_bounded(model_and_params, qdtype):
    """Speculation over a quantized cache: mechanically sound (propose/
    verify/trim) and bounded-divergent vs plain greedy on the SAME
    quantized engine config (byte-losslessness is a bf16-cache guarantee
    — trim cannot roll back a monotone scale, documented). The dtype
    axis exercises spec-decode trim over fp8 pools too."""
    model, params = model_and_params
    rng = np.random.default_rng(5)
    motif = rand_prompt(rng, 5)
    prompts = [motif * 5 + rand_prompt(rng, 3) for _ in range(2)]
    plain = greedy_generate(make_engine(model, params, quant=True,
                                        qdtype=qdtype),
                            prompts, uid_base=1, max_new_tokens=20)
    sched = ContinuousBatchingScheduler(
        make_engine(model, params, quant=True, qdtype=qdtype),
        proposer=NGramProposer(ngram_max=3), max_draft_tokens=4)
    spec = greedy_generate(prompts=prompts, uid_base=1, max_new_tokens=20,
                           scheduler=sched)
    stats = sched.spec_stats()
    assert stats["proposed"] > 0 and stats["accepted"] > 0
    fracs = []
    for a, b in zip(plain, spec):
        matched = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                       min(len(a), len(b)))
        fracs.append(matched / max(1, len(a)))
    assert np.mean(fracs) >= 0.5, f"spec divergence too large: {fracs}"


@pytest.mark.parametrize("qdtype", KV_DTYPES)
def test_prefix_shared_blocks_share_scales(model_and_params, qdtype):
    """A prefix-cache hit under kv_quant shares the quantized blocks AND
    their scale-plane entries (scales are indexed by pool block id): the
    second request re-prefills only the tail and still matches the
    uncached quantized engine's stream exactly."""
    model, params = model_and_params
    rng = np.random.default_rng(6)
    sysp = rand_prompt(rng, 40)
    tail_a, tail_b = rand_prompt(rng, 7), rand_prompt(rng, 7)
    cached = make_engine(model, params, quant=True, qdtype=qdtype,
                         enable_prefix_cache=True)
    g_warm = greedy_generate(cached, [sysp + tail_a], uid_base=100,
                             max_new_tokens=8)
    stats0 = cached.prefix_stats()
    g_hit = greedy_generate(cached, [sysp + tail_b], uid_base=200,
                            max_new_tokens=8)
    stats = cached.prefix_stats()
    assert stats["hits"] - stats0["hits"] >= 2          # blocks shared
    assert stats["tokens_saved"] - stats0["tokens_saved"] >= 2 * BS
    # same prompts through a cache-less quantized engine: identical
    # streams — dequantizing a shared block with its shared scale is
    # exactly what the writer stored
    plain = make_engine(model, params, quant=True, qdtype=qdtype)
    p_warm = greedy_generate(plain, [sysp + tail_a], uid_base=100,
                             max_new_tokens=8)
    p_hit = greedy_generate(plain, [sysp + tail_b], uid_base=200,
                            max_new_tokens=8)
    assert_greedy_parity(p_warm + p_hit, g_warm + g_hit,
                         label="prefix cache under kv_quant")


def test_cancel_frees_quantized_blocks(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(7)
    eng = make_engine(model, params, quant=True)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(1, rand_prompt(rng, 40), max_new_tokens=50)
    for _ in range(3):
        sched.step()
    assert eng.occupancy()["in_use_blocks"] > 0
    assert sched.cancel(1)
    occ = eng.occupancy()
    assert occ["in_use_blocks"] == 0
    assert occ["free_blocks"] == occ["total_blocks"]


def test_failover_resume_with_quantized_kv(model_and_params):
    """A replica crash mid-stream under kv_quant: requests fail over,
    resume from prompt + delivered tokens on the survivor's quantized
    cache, and the streams match an unfaulted quantized run."""
    from deepspeed_tpu.serving import (RequestState, ServingConfig,
                                       ServingFrontend)

    model, params = model_and_params
    rng = np.random.default_rng(8)
    prompts = [rand_prompt(rng, 20) for _ in range(4)]

    def factory(i):
        return make_engine(model, params, quant=True)

    def run(faulted):
        scfg = ServingConfig(
            max_queue_depth=64,
            kv_quant={"enabled": True},
            fault_tolerance={"enabled": True, "max_retries": 3,
                             "restart_backoff_s": 0.05,
                             "supervisor_poll_s": 0.02},
            faults=({"enabled": True, "schedule": [
                {"kind": "crash", "replica": 0, "at_step": 3}]}
                if faulted else {"enabled": False}))
        fe = ServingFrontend([factory(0), factory(1)], scfg,
                             engine_factory=factory)
        handles = [fe.submit(p, max_new_tokens=6) for p in prompts]
        assert fe.wait_all(handles, timeout=120)
        gens = [[ev.token for ev in h.drain()] for h in handles]
        states = [h.state for h in handles]
        fe.shutdown(drain=False, timeout=5)
        return gens, states

    gens_ok, _ = run(faulted=False)
    gens_chaos, states = run(faulted=True)
    assert all(s == RequestState.FINISHED for s in states)
    assert_greedy_parity(gens_ok, gens_chaos,
                         label="failover under kv_quant")


def test_configure_kv_quant_toggle_and_guard(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(9)
    eng = make_engine(model, params, quant=False)
    eng.configure_kv_quant(True)
    assert eng.state_manager.kv_quant
    assert eng.state_manager.kv_cache["k"].dtype == jnp.int8
    eng.put([1], [rand_prompt(rng, 10)])
    with pytest.raises(RuntimeError, match="tracked"):
        eng.configure_kv_quant(False)
    eng.configure_kv_quant(True)        # no-op while unchanged: fine
    eng.flush(1)
    eng.configure_kv_quant(False)
    assert set(eng.state_manager.kv_cache) == {"k", "v"}
    with pytest.raises(ValueError, match="dtype"):
        eng.configure_kv_quant(True, dtype="fp8")
    # the reserved dtype surface is now real: int8 -> fp8_e4m3 rebuilds
    # the pools at the new representation (legal while drained)
    eng.configure_kv_quant(True, dtype="fp8_e4m3")
    assert eng.state_manager.kv_cache["k"].dtype == jnp.float8_e4m3fn
    assert eng.state_manager.kv_quant_dtype == "fp8_e4m3"


# -------------------------------------------------- serving config + gauges
def test_serving_config_applies_kv_quant(model_and_params):
    from deepspeed_tpu.serving import KVQuantConfig, ServingConfig
    from deepspeed_tpu.serving import ServingFrontend

    model, params = model_and_params
    kq = KVQuantConfig(enabled=True)
    vcfg = RaggedInferenceEngineConfig()
    kq.apply(vcfg)
    assert vcfg.kv_quant_enabled and vcfg.kv_quant_dtype == "int8"
    eng = make_engine(model, params, quant=False)
    fe = ServingFrontend([eng], ServingConfig(kv_quant={"enabled": True}))
    try:
        assert eng.state_manager.kv_quant
        rng = np.random.default_rng(10)
        h = fe.submit(rand_prompt(rng, 20), max_new_tokens=4)
        assert fe.wait_all([h], timeout=60)
        snap = fe.metrics_snapshot()
        assert "kv_blocks_in_use" in snap and "kv_bytes_in_use" in snap
        # finished request freed its blocks; gauges reflect the pool
        occ = eng.occupancy()
        assert snap["kv_blocks_in_use"] == occ["in_use_blocks"]
        assert snap["kv_bytes_in_use"] == occ["bytes_in_use"]
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_ds_config_mounts_kv_quant():
    from deepspeed_tpu.runtime.config import DeepSpeedTpuConfig

    c = DeepSpeedTpuConfig(**{"train_micro_batch_size_per_gpu": 1,
                              "kv_quant": {"enabled": True},
                              "serving": {"kv_quant": {"enabled": True}}})
    assert c.kv_quant.enabled and c.serving.kv_quant.enabled
    assert c.kv_quant.dtype == "int8"


def test_tp_sharded_quant_matches_single_device(model_and_params):
    """TP serving with quantized pools: the shard_map in/out specs carry
    the scale operands (sharded over kv-heads like the pools), so a
    TP-sharded quant engine must match the single-device quant engine
    exactly — same int8 pools, same scales, same logits."""
    from deepspeed_tpu.parallel import topology as topo

    model, params = model_and_params
    single = make_engine(model, params, quant=True)
    topo.reset_topology()
    t = topo.MeshTopology.build(data=4, tensor=2)
    sharded = InferenceEngineV2(
        model, params=params, mesh=t,
        config=RaggedInferenceEngineConfig(
            max_ragged_batch_size=256, max_ragged_sequence_count=8,
            max_chunk_tokens=32, kv_blocks=64, kv_block_size=BS,
            max_tracked_sequences=64, kv_quant_enabled=True))
    assert sharded.state_manager.kv_cache["k"].dtype == jnp.int8
    rng = np.random.default_rng(12)
    prompts = {1: rand_prompt(rng, 7), 2: rand_prompt(rng, 12)}
    for uid, p in prompts.items():
        a = np.asarray(single.put([uid], [p]))
        b = np.asarray(sharded.put([uid], [p]))
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    for step in range(3):
        nxt = [[int(rng.integers(0, VOCAB))] for _ in prompts]
        a = np.asarray(single.put(list(prompts), nxt))
        b = np.asarray(sharded.put(list(prompts), nxt))
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5,
                                   err_msg=f"decode step {step}")
    topo.reset_topology()


# ------------------------------------------------------- kernel-level check
def test_pallas_kernel_dequant_matches_xla(monkeypatch):
    from deepspeed_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    rng = np.random.default_rng(11)
    N, C, H, KH, D, NB, bs, MB = 2, 4, 4, 2, 8, 16, 8, 4
    q = jnp.asarray(rng.normal(size=(N, C, H, D)), jnp.float32)
    kq = jnp.asarray(rng.integers(-127, 128, size=(NB, KH, bs, D)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, size=(NB, KH, bs, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.1, size=(NB, KH)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.1, size=(NB, KH)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(NB)[:N * MB].reshape(N, MB), jnp.int32)
    sp = jnp.asarray([5, 12], jnp.int32)
    nt = jnp.asarray([4, 4], jnp.int32)
    ref = pa.paged_attention_xla(q, kq, vq, tbl, sp, nt,
                                 k_scale=ks, v_scale=vs)
    out = pa.paged_attention(q, kq, vq, tbl, sp, nt,
                             k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # the quantized XLA gather equals dense attention over the
    # dequantized pools — dequantization is exact, not approximate
    kf = kq.astype(jnp.float32) * ks[:, :, None, None]
    vf = vq.astype(jnp.float32) * vs[:, :, None, None]
    dense = pa.paged_attention_xla(q, kf, vf, tbl, sp, nt)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(dense))


# ------------------------------------------------------ bench schema check
def test_bench_schema_validator():
    import importlib
    import os
    import sys

    os.environ.setdefault("BENCH_TIMEOUT_S", "0")   # no watchdog in tests
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    bench = importlib.import_module("bench")
    occ = {k: 1 for k in bench._OCCUPANCY_KEYS}
    good = {"kv_quant": {"max_concurrent_base": 8, "max_concurrent_int8": 16,
                         "max_concurrent_fp8": 16,
                         "concurrency_ratio": 2.0, "budget_bytes": 1024,
                         "ppl_base": 1.0, "ppl_int8": 1.0, "ppl_fp8": 1.0,
                         "ppl_ratio": 1.0, "ppl_ratio_fp8": 1.0,
                         "ppl_gate_ok": True, "ppl_gate_ok_fp8": True,
                         "greedy_parity": True,
                         "mean_matched_prefix_frac": 1.0,
                         "mean_matched_prefix_frac_fp8": 1.0,
                         "disabled_parity": True, "kv_occupancy": occ}}
    good["weight_quant"] = {
        "param_bytes_fp32": 4096, "param_bytes_int8": 1024,
        "weight_compression_x": 4.0, "bytes_gate_ok": True,
        "host_byte_budget": 1 << 20,
        "replicas_at_budget_base": 2, "replicas_at_budget_int8": 8,
        "prefill_ttft_base_ms": 9.0, "prefill_ttft_int8_ms": 8.0,
        "decode_tpot_base_ms": 2.0, "decode_tpot_int8_ms": 1.8,
        "ppl_base": 1.0, "ppl_int8": 1.0, "ppl_ratio": 1.0,
        "ppl_gate_ok": True, "mean_matched_prefix_frac": 1.0,
        "greedy_parity": True, "disabled_parity": True,
        "kv_occupancy": dict(occ)}
    for name in bench._STAMPED_PHASES:
        if name in ("kv_quant", "weight_quant", "train_chaos", "disagg",
                    "slo", "kv_tier", "overload", "autoscale", "fabric"):
            continue            # typed phases built explicitly
        good[name] = {"kv_occupancy": dict(occ)}
    good["kv_tier"] = {"tier_on_p50_ttft_ms": 10.7,
                       "tier_off_p50_ttft_ms": 14.1,
                       "ttft_improved": True, "blocks_spilled": 64,
                       "blocks_restored": 64, "blocks_dropped": 0,
                       "prefix_hit_rate_on": 0.89,
                       "prefix_hit_rate_off": 0.0,
                       "greedy_parity": True, "disabled_parity": True,
                       "kv_occupancy": dict(occ)}
    good["slo"] = {"alert_fired": True, "alert_resolved": True,
                   "fire_to_resolve_s": 4.9, "alerts_firing_peak": 1,
                   "alerts_firing_final": 0, "window_p95_ttft_ms": 12.5,
                   "cum_p95_ttft_ms": 12.5, "window_agrees": True,
                   "noise_floor_pct": 1.0, "overhead_slo_pct": 0.3,
                   "overhead_ok": True, "journal_events": 2,
                   "journal_schema_ok": True, "disabled_parity": True,
                   "kv_occupancy": dict(occ)}
    good["train_chaos"] = {"recovery_time_s": 0.12, "steps_lost": 1,
                           "resume_parity": True,
                           "sigterm_resume_parity": True,
                           "injectors_off_parity": True, "restarts": 1,
                           "n_steps": 8, "crash_at_step": 5,
                           "urgent_save_s": 0.01,
                           "kv_occupancy": dict(occ)}
    good["disagg"] = {"handoffs_completed": 13, "handoff_fallbacks": 0,
                      "tpot_improved": True, "handoff_parity": True,
                      "disabled_parity": True, "replicas": 4,
                      "decode_reserve_tokens": 8,
                      "kv_occupancy": dict(occ)}
    good["overload"] = {"n_requests": 24, "kv_blocks": 8,
                        "overload_ratio": 10.25,
                        "oversubscription_factor": 2.5,
                        "zero_wedges": True, "completed_on": 24,
                        "completed_off": 0,
                        "completed_per_sec_on": 9.6,
                        "completed_per_sec_off": 0.0,
                        "sequences_preempted": 12,
                        "sequences_resumed": 12,
                        "p95_interactive_ttft_ms": 2500.0,
                        "p99_interactive_ttft_ms": 2500.0,
                        "p95_interactive_tpot_ms": 2.4,
                        "p99_interactive_tpot_ms": 2.5,
                        "preempt_parity": True, "disabled_parity": True,
                        "kv_occupancy": dict(occ)}
    good["autoscale"] = {"n_requests": 30, "min_replicas": 1,
                         "max_replicas": 3, "static_replicas": 3,
                         "slo_attainment_elastic": 1.0,
                         "slo_attainment_static": 1.0,
                         "attainment_ok": True,
                         "replica_seconds_elastic": 16.2,
                         "replica_seconds_static": 21.9,
                         "elastic_beats_static_cost": True,
                         "scale_ups": 2, "scale_downs": 2, "reroles": 0,
                         "peak_replicas": 3, "final_replicas": 1,
                         "requests_evacuated": 0,
                         "greedy_parity": True, "disabled_parity": True,
                         "kv_occupancy": dict(occ)}
    good["fabric"] = {"replicas": 2, "n_requests": 8, "prompt_len": 24,
                      "max_new": 8, "chunk_blocks": 1,
                      "local_p50_ttft_ms": 1287.3,
                      "local_p95_ttft_ms": 1287.4,
                      "local_p50_tpot_ms": 2.3, "local_p95_tpot_ms": 3.5,
                      "fabric_p50_ttft_ms": 1967.6,
                      "fabric_p95_ttft_ms": 1989.7,
                      "fabric_p50_tpot_ms": 3.4,
                      "fabric_p95_tpot_ms": 169.7,
                      "rpc_calls": 22, "rpc_p50_ms": 0.8,
                      "rpc_p95_ms": 175.0,
                      "rpc_overhead_p50_ttft_ms": 680.3,
                      "handoffs_completed_local": 10,
                      "handoffs_completed_fabric": 10,
                      "handoff_fallbacks_fabric": 0,
                      "handle_disconnects": 0,
                      "parity": True, "disabled_parity": True,
                      "zero_wedges": True, "kv_occupancy": dict(occ)}
    good["multitenant"] = {"n_flood": 12, "n_interactive": 5,
                           "flood_max_new": 10, "interactive_max_new": 6,
                           "solo_p95_ttft_ms": 1635.7,
                           "fair_on_p95_ttft_ms": 1921.0,
                           "fair_off_p95_ttft_ms": 2158.6,
                           "isolation_ratio_on": 1.174,
                           "starvation_ratio_off": 1.32,
                           "isolation_ok": True,
                           "flood_tokens_on": 120,
                           "flood_progress_ok": True,
                           "fair_beats_off": True,
                           "tenant_b_submitted": 5, "tenant_b_shed": 0,
                           "zero_wedges": True,
                           "greedy_parity": True, "disabled_parity": True,
                           "kv_occupancy": dict(occ)}
    good["affinity"] = {"n_requests": 72, "n_replicas": 3,
                        "n_families": 9, "shared_prefix_tokens": 112,
                        "max_new": 3,
                        "affinity_on_p50_ttft_ms": 44.3,
                        "affinity_on_p95_ttft_ms": 1591.1,
                        "affinity_off_p50_ttft_ms": 91.5,
                        "affinity_off_p95_ttft_ms": 1869.8,
                        "ttft_improved": True,
                        "prefix_tokens_saved_on": 5600,
                        "prefix_tokens_saved_off": 2352,
                        "tokens_saved_improved": True,
                        "affinity_hits": 50, "affinity_misses": 22,
                        "share_cap_ok": True,
                        "warmup_blocks": 32, "warmup_s": 0.49,
                        "warmup_first_hit_ok": True,
                        "predictive_first_grow_tick": 5,
                        "watermark_first_grow_tick": 8,
                        "predictive_earlier": True,
                        "predictive_peak_queue": 28.0,
                        "watermark_peak_queue": 35.5,
                        "predictive_no_flap": True,
                        "greedy_parity": True, "disabled_parity": True,
                        "kv_occupancy": dict(occ)}
    good["federation"] = {"frontends": 2, "n_requests": 8,
                          "prompt_len": 24, "max_new": 8,
                          "exported_replicas": 1,
                          "requests_federated": 4,
                          "standalone_p50_ttft_ms": 3379.3,
                          "standalone_p95_ttft_ms": 3647.8,
                          "federated_p50_ttft_ms": 3271.0,
                          "federated_p95_ttft_ms": 3568.3,
                          "peer_rpc_calls": 5, "peer_rpc_p50_ms": 0.6,
                          "peer_rpc_p95_ms": 1.0,
                          "kill_n_requests": 4, "kill_max_new": 96,
                          "requests_failed_over": 2,
                          "failover_recovery_s": 0.268,
                          "parity": True, "kill_parity": True,
                          "disabled_parity": True, "zero_wedges": True,
                          "kv_occupancy": dict(occ)}
    good["fleet_obs"] = {"replicas": 2, "n_requests": 8,
                         "prompt_len": 24, "max_new": 6,
                         "wall_off_s": 0.272, "wall_off_rerun_s": 0.302,
                         "wall_on_s": 0.282, "noise_floor_pct": 11.4,
                         "overhead_enabled_pct": 3.9,
                         "spans_total": 192, "server_spans": 16,
                         "spans_forwarded": 68,
                         "min_ttft_coverage": 0.999,
                         "ttft_coverage_ok": True,
                         "chains_complete": True,
                         "trace_path": "/tmp/trace_fleet_1.json",
                         "trace_valid": True, "journal_sources": 2,
                         "journal_events_forwarded": 6,
                         "journal_events_dropped": 0,
                         "journal_exactly_once": True,
                         "clock_offset_ms": 0.08,
                         "http_metrics_ok": True, "http_health_ok": True,
                         "fleetctl_ok": True, "parity": True,
                         "disabled_parity": True, "zero_wedges": True,
                         "kv_occupancy": dict(occ)}
    good["net_chaos"] = {"replicas": 3, "n_requests": 9,
                         "prompt_len": 24, "max_new": 6,
                         "completed_under_chaos": 1.0,
                         "recovery_time_s": 1.666,
                         "quarantines_journaled": 1,
                         "readmits_journaled": 1,
                         "frames_corrupt": 3,
                         "frames_corrupt_fatal": 0,
                         "faults_injected": 40,
                         "parity": True, "disabled_parity": True,
                         "kv_occupancy": dict(occ)}
    assert bench.validate_serving_schema(good) == []
    # multitenant typed checks: bool-for-int rejected, missing named
    bad_mt = dict(good)
    bad_mt["multitenant"] = {"n_flood": True, "isolation_ok": 1}
    problems_mt = bench.validate_serving_schema(bad_mt)
    assert any("multitenant.n_flood" in p for p in problems_mt)
    assert any("multitenant.isolation_ok" in p for p in problems_mt)
    assert any("multitenant.fair_beats_off: missing" in p
               for p in problems_mt)
    # affinity typed checks: bool-for-int rejected, missing named
    bad_af = dict(good)
    bad_af["affinity"] = {"affinity_hits": True, "share_cap_ok": 1}
    problems_af = bench.validate_serving_schema(bad_af)
    assert any("affinity.affinity_hits" in p for p in problems_af)
    assert any("affinity.share_cap_ok" in p for p in problems_af)
    assert any("affinity.warmup_first_hit_ok: missing" in p
               for p in problems_af)
    # federation typed checks: bool-for-int rejected, missing named
    bad_fd = dict(good)
    bad_fd["federation"] = {"requests_federated": True, "kill_parity": 1}
    problems_fd = bench.validate_serving_schema(bad_fd)
    assert any("federation.requests_federated" in p for p in problems_fd)
    assert any("federation.kill_parity" in p for p in problems_fd)
    assert any("federation.failover_recovery_s: missing" in p
               for p in problems_fd)
    # fleet_obs typed checks: bool-for-int rejected, missing named
    bad_fo = dict(good)
    bad_fo["fleet_obs"] = {"journal_sources": True, "fleetctl_ok": 1}
    problems_fo = bench.validate_serving_schema(bad_fo)
    assert any("fleet_obs.journal_sources" in p for p in problems_fo)
    assert any("fleet_obs.fleetctl_ok" in p for p in problems_fo)
    assert any("fleet_obs.min_ttft_coverage: missing" in p
               for p in problems_fo)
    # fabric typed checks: bool-for-int rejected, missing fields named
    bad_fb = dict(good)
    bad_fb["fabric"] = {"rpc_calls": True, "parity": 1}
    problems_fb = bench.validate_serving_schema(bad_fb)
    assert any("fabric.rpc_calls" in p for p in problems_fb)
    assert any("fabric.parity" in p for p in problems_fb)
    assert any("fabric.zero_wedges: missing" in p for p in problems_fb)
    # autoscale typed checks: bool-for-int rejected, missing named
    bad_as = dict(good)
    bad_as["autoscale"] = {"scale_ups": True, "attainment_ok": 1}
    problems_as = bench.validate_serving_schema(bad_as)
    assert any("autoscale.scale_ups" in p for p in problems_as)
    assert any("autoscale.attainment_ok" in p for p in problems_as)
    assert any("autoscale.greedy_parity: missing" in p
               for p in problems_as)
    # overload typed checks: bool-for-int rejected, missing fields named
    bad_ov = dict(good)
    bad_ov["overload"] = {"completed_on": True, "zero_wedges": 1}
    problems_ov = bench.validate_serving_schema(bad_ov)
    assert any("overload.completed_on" in p for p in problems_ov)
    assert any("overload.zero_wedges" in p for p in problems_ov)
    assert any("overload.preempt_parity: missing" in p
               for p in problems_ov)
    # disagg typed checks: missing and mistyped fields are named
    bad_dg = dict(good)
    bad_dg["disagg"] = {"handoffs_completed": True, "handoff_parity": 1}
    problems_dg = bench.validate_serving_schema(bad_dg)
    assert any("disagg.handoffs_completed" in p for p in problems_dg)
    assert any("disagg.handoff_parity" in p for p in problems_dg)
    assert any("disagg.disabled_parity: missing" in p for p in problems_dg)
    # kv_tier typed checks: missing and mistyped (bool-for-int) named
    bad_kt = dict(good)
    bad_kt["kv_tier"] = {"blocks_restored": True, "greedy_parity": 1}
    problems_kt = bench.validate_serving_schema(bad_kt)
    assert any("kv_tier.blocks_restored" in p for p in problems_kt)
    assert any("kv_tier.greedy_parity" in p for p in problems_kt)
    assert any("kv_tier.disabled_parity: missing" in p
               for p in problems_kt)
    # skipped phases are exempt from field checks
    skipped = dict(good)
    skipped["chaos"] = {"phase_skipped": "phase budget 240s exceeded"}
    assert bench.validate_serving_schema(skipped) == []
    # missing/garbled fields are named
    bad = dict(good)
    bad["kv_quant"] = {"max_concurrent_base": "eight"}
    problems = bench.validate_serving_schema(bad)
    assert any("max_concurrent_base" in p for p in problems)
    assert any("concurrency_ratio: missing" in p for p in problems)
    bad2 = dict(good)
    bad2["prefix"] = {"n_requests": 1}
    assert any("prefix.kv_occupancy" in p
               for p in bench.validate_serving_schema(bad2))
    # train_chaos typed checks: wrong types and missing fields are named,
    # a bool where an int is expected is rejected, a skip stamp is exempt
    bad3 = dict(good)
    bad3["train_chaos"] = {"recovery_time_s": "fast", "steps_lost": True,
                           "kv_occupancy": dict(occ)}
    problems3 = bench.validate_serving_schema(bad3)
    assert any("train_chaos.recovery_time_s" in p for p in problems3)
    assert any("train_chaos.steps_lost" in p for p in problems3)
    assert any("train_chaos.resume_parity: missing" in p for p in problems3)
    skipped2 = dict(good)
    skipped2["train_chaos"] = {"phase_skipped": "not selected"}
    assert bench.validate_serving_schema(skipped2) == []
    # the shared typed-phase checker applies the bool guard to kv_quant
    # too: a bool where an int is expected is named, not silently passed
    bad4 = dict(good)
    bad4["kv_quant"] = dict(good["kv_quant"], max_concurrent_base=True)
    assert any("kv_quant.max_concurrent_base" in p
               for p in bench.validate_serving_schema(bad4))
    # weight_quant typed checks: bool-for-int rejected, missing named
    bad_wq = dict(good)
    bad_wq["weight_quant"] = {"param_bytes_fp32": True, "bytes_gate_ok": 1}
    problems_wq = bench.validate_serving_schema(bad_wq)
    assert any("weight_quant.param_bytes_fp32" in p for p in problems_wq)
    assert any("weight_quant.bytes_gate_ok" in p for p in problems_wq)
    assert any("weight_quant.disabled_parity: missing" in p
               for p in problems_wq)
    # slo typed checks: missing/mistyped fields named; a journal that
    # failed validate_events is a schema problem in its own right
    bad5 = dict(good)
    bad5["slo"] = {"alert_fired": 1, "kv_occupancy": dict(occ)}
    problems5 = bench.validate_serving_schema(bad5)
    assert any("slo.alert_fired" in p for p in problems5)
    assert any("slo.journal_schema_ok: missing" in p for p in problems5)
    bad6 = dict(good)
    bad6["slo"] = dict(good["slo"], journal_schema_ok=False)
    assert any("journal events failed schema" in p
               for p in bench.validate_serving_schema(bad6))
    skipped3 = dict(good)
    skipped3["slo"] = {"phase_skipped": "not selected"}
    assert bench.validate_serving_schema(skipped3) == []


def test_phase_runner_skip_and_budget(tmp_path, monkeypatch):
    import importlib
    import sys

    monkeypatch.setenv("BENCH_TIMEOUT_S", "0")
    sys.path.insert(0, str(tmp_path.parent))  # no-op, keeps sys.path sane
    bench = importlib.import_module("bench")
    monkeypatch.setenv("BENCH_PHASE_DIR", str(tmp_path))
    monkeypatch.setenv("BENCH_PHASE_TIMEOUT_S", "1")
    monkeypatch.delenv("BENCH_PHASES", raising=False)
    monkeypatch.delenv("BENCH_RESUME", raising=False)
    runner = bench.PhaseRunner(stamp=lambda: {"total_blocks": 1})
    # a phase that exceeds its budget degrades to a stamp, and later
    # phases in the SAME process skip too (the abandoned worker may
    # still be mutating shared engine state — racing it would corrupt
    # their numbers); skip stamps are never cached as artifacts
    import time as _t
    out = runner.run("wedge", lambda: _t.sleep(10))
    assert "budget" in out["phase_skipped"]
    assert out["kv_occupancy"] == {"total_blocks": 1}
    after_wedge = runner.run("after-wedge", lambda: {"x": 9})
    assert "prior phase wedged" in after_wedge["phase_skipped"]
    assert not (tmp_path / "phase_wedge.json").exists()
    # a completing phase writes its artifact; resume loads it
    out = bench.PhaseRunner().run("quick", lambda: {"x": 1})
    assert out["x"] == 1 and (tmp_path / "phase_quick.json").exists()
    monkeypatch.setenv("BENCH_RESUME", "1")
    runner2 = bench.PhaseRunner()
    cached = runner2.run("quick", lambda: {"x": 2})
    assert cached["x"] == 1 and cached["phase_cached"]
    # a phase that raises is stamped AND listed as failed (main exits
    # non-zero on it); it does not stop the phases after it
    monkeypatch.delenv("BENCH_RESUME", raising=False)
    runner3 = bench.PhaseRunner()

    def die():
        raise RuntimeError("kernel refused")

    out = runner3.run("dead", die)
    assert "RuntimeError: kernel refused" in out["phase_skipped"]
    assert runner3.run("after", lambda: {"x": 3})["x"] == 3
    assert runner3.failed == ["dead"] and runner.failed == ["wedge"]
    assert bench._verdict({"failed_phases": runner3.failed}) == 1
    assert bench._verdict({"failed_phases": []}) == 0
    # a phase that spawns replica processes is skipped by name where this
    # process holds the chip — stated in the output, not a failure
    own = runner3.run("fabric", die, needs_own_chip=True)
    assert "a chip belongs to one process" in own["phase_skipped"]
    assert runner3.failed == ["dead"]
    # an unknown device kind is an error, not an assumed peak
    class _Dev:
        device_kind = "Quantum v9"
    monkeypatch.setattr(bench.jax, "devices", lambda: [_Dev()])
    with pytest.raises(ValueError, match="quantum v9"):
        bench.detect_peak()
