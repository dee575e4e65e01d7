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
from deepspeed_tpu.inference.v2.testing import share_forward
from deepspeed_tpu.inference.v2.kv_quant import (kv_bytes_per_block,
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


_FORWARDS = {}


def make_engine(model, params, quant=True, kv_blocks=64, max_seqs=8,
                qdtype="int8", **cfg_kw):
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=256, max_ragged_sequence_count=max_seqs,
        max_chunk_tokens=32, kv_blocks=kv_blocks, kv_block_size=BS,
        max_tracked_sequences=64, kv_quant_enabled=quant,
        kv_quant_dtype=qdtype, **cfg_kw)
    # one block size and one step budget: what a trace reads beside its
    # arguments (pool dtype, blocks and scales are arguments) is the
    # model's, so a model's engines share one jitted forward
    return share_forward(InferenceEngineV2(model, params=params, config=vcfg),
                         _FORWARDS, id(model))


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
    assert budget // i8 >= 48
    eng = make_engine(model, params, quant=True)
    occ = eng.occupancy()
    assert occ["bytes_per_block"] == i8
    assert occ["bytes_total"] == 64 * i8


@pytest.mark.parametrize("qdtype", KV_DTYPES)
def test_fixed_byte_budget_serves_more_sequences(model_and_params, qdtype):
    """The headline claim in counts, not bytes: at one KV byte budget,
    with the pool as the only bound, the scheduler keeps more sequences
    decoding at once on quantized blocks than on full-precision ones, and
    every request of the same burst completes both ways."""
    model, params = model_and_params
    plen, gen, base_blocks = 24, 8, 8
    budget = base_blocks * kv_bytes_per_block(model.cfg, BS, quant=False)
    q_blocks = budget // kv_bytes_per_block(model.cfg, BS, quant=True)
    per_seq = -(-(plen + gen) // BS)
    n_req = q_blocks // per_seq + 2         # past the quantized capacity
    rng = np.random.default_rng(21)
    reqs = [rand_prompt(rng, plen) for _ in range(n_req)]

    def peak_running(quant, kv_blocks):
        eng = make_engine(model, params, quant=quant, qdtype=qdtype,
                          kv_blocks=int(kv_blocks), max_seqs=n_req + 1,
                          admission_reservation=True)
        sched = ContinuousBatchingScheduler(eng)
        for i, p in enumerate(reqs):
            sched.submit(100 + i, p, max_new_tokens=gen)
        peak = steps = 0
        while sched.has_work and steps < 5000:
            sched.step()
            steps += 1
            peak = max(peak, len(sched.running))
        done = sum(r.finish_reason == "length"
                   for r in sched.finished.values())
        assert eng.free_blocks == kv_blocks
        return peak, done

    peak_base, done_base = peak_running(False, base_blocks)
    peak_q, done_q = peak_running(True, q_blocks)
    assert done_base == done_q == n_req
    assert peak_base <= base_blocks // per_seq + 1
    assert peak_q >= 1.5 * peak_base, (peak_q, peak_base)


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
    the unquantized engine, for both the int8 and the fp8_e4m3
    representation (the quality gate of docs/SERVING.md "KV quantization")."""
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
