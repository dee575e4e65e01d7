"""int8/fp8 weight serving (ISSUE 13 tentpole): the CausalLM param tree
quantized once at engine build (inference/v2/weight_quant.py), every
matmul running from the quantized tree via ops/quantizer.quantized_matmul,
config plumbing across engine/serving/runtime, per-replica apply on every
frontend build path (boot, restart, autoscaler grow), param-byte
observability, and TP scale-plane sharding. Disabled must be
byte-for-byte the historical pytree and program."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2 import weight_quant as WQ
from deepspeed_tpu.inference.v2.testing import (assert_greedy_parity,
                                                greedy_generate)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

VOCAB = 128
BS = 16


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(vocab_size=VOCAB, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=4,
                            max_seq_len=256, norm="rmsnorm",
                            activation="silu", position="rope")
    model = CausalLM(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def untied_model_and_params():
    cfg = TransformerConfig(vocab_size=VOCAB, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=4,
                            max_seq_len=256, norm="rmsnorm",
                            activation="silu", position="rope",
                            tie_embeddings=False)
    model = CausalLM(cfg)
    return model, model.init(jax.random.PRNGKey(1))


def make_engine(model, params, wq=True, **cfg_kw):
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=256, max_ragged_sequence_count=8,
        max_chunk_tokens=32, kv_blocks=64, kv_block_size=BS,
        max_tracked_sequences=64, weight_quant_enabled=wq, **cfg_kw)
    return InferenceEngineV2(model, params=params, config=vcfg)


def rand_prompt(rng, n):
    return rng.integers(0, VOCAB, size=n).tolist()


# -------------------------------------------------------- tree + byte math
def test_quantized_tree_structure_and_bytes(model_and_params):
    model, params = model_and_params
    qparams, stats = WQ.quantize_weights(model.cfg, params)
    layers = qparams["layers"]
    for name in ("wq", "wk", "wv", "wo", "w_in", "w_out", "w_gate"):
        assert WQ.is_quantized(layers[name]), name
        node = layers[name]
        assert node["qw"].dtype == jnp.int8
        assert node["qs"].dtype == jnp.float32
        assert node["qw"].shape == params["layers"][name].shape
        # scales: same leading dims, last dim = groups
        assert node["qs"].shape[:-1] == node["qw"].shape[:-1]
    # non-matmul leaves untouched (same objects, not copies)
    assert qparams["embed"]["wte"] is params["embed"]["wte"]
    assert qparams["final_norm"]["w"] is params["final_norm"]["w"]
    assert layers["attn_norm_w"] is params["layers"]["attn_norm_w"]
    # byte accounting: the quantized share cut >= 3.5x vs its fp32 form
    fp32_matmul_bytes = sum(
        WQ._leaf_bytes(params["layers"][n])
        for n in ("wq", "wk", "wv", "wo", "w_in", "w_out", "w_gate"))
    assert fp32_matmul_bytes / stats["param_bytes_quantized"] >= 3.5
    assert stats["param_bytes_total"] < WQ.param_stats(params)[
        "param_bytes_total"]
    assert stats["params_quantized"] == 7


def test_skip_list_and_untied_lm_head(untied_model_and_params):
    model, params = untied_model_and_params
    qparams, stats = WQ.quantize_weights(model.cfg, params)
    assert WQ.is_quantized(qparams["lm_head"]["w"])
    skipped, stats2 = WQ.quantize_weights(model.cfg, params,
                                          skip=["lm_head", "wq"])
    assert skipped["lm_head"]["w"] is params["lm_head"]["w"]
    assert skipped["layers"]["wq"] is params["layers"]["wq"]
    assert stats2["params_quantized"] == stats["params_quantized"] - 2


def test_validate_rejects_unknown():
    WQ.validate_weight_quant("int8", 128)
    WQ.validate_weight_quant("fp8_e4m3", 64)
    with pytest.raises(ValueError, match="dtype"):
        WQ.validate_weight_quant("int4", 128)
    with pytest.raises(ValueError, match="block"):
        WQ.validate_weight_quant("int8", 0)


# ----------------------------------------------------- disabled byte-parity
def test_disabled_path_byte_identical(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(0)
    prompt = rand_prompt(rng, 30)
    eng_default = InferenceEngineV2(
        model, params=params,
        config=RaggedInferenceEngineConfig(
            max_ragged_batch_size=256, max_ragged_sequence_count=8,
            max_chunk_tokens=32, kv_blocks=64, kv_block_size=BS))
    eng_off = make_engine(model, params, wq=False)
    la = np.asarray(eng_default.put([1], [prompt]))
    lb = np.asarray(eng_off.put([1], [prompt]))
    np.testing.assert_array_equal(la, lb)
    # pytree untouched: identical leaves, no {"qw","qs"} nodes anywhere
    # (q, k and v excepted, which every unquantized engine on one device
    # serves as one leaf: paged_model.fuse_qkv)
    assert eng_off.params["embed"] is params["embed"]
    assert all(leaf is params["layers"][name]
               for name, leaf in eng_off.params["layers"].items()
               if name != "wqkv")
    assert not any(WQ.is_quantized(l) for l in
                   jax.tree.leaves(eng_off.params, is_leaf=WQ.is_quantized)
                   if isinstance(l, dict))


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
    g_off = greedy_generate(make_engine(model, params, wq=False),
                            prompts, uid_base=1, max_new_tokens=10)
    assert_greedy_parity(g_default, g_off, label="weight_quant disabled")


# ------------------------------------------------- quality gates (quant on)
@pytest.mark.parametrize("wdtype", ["int8", "fp8_e4m3"])
def test_bounded_divergence_and_logit_error(model_and_params, wdtype):
    model, params = model_and_params
    rng = np.random.default_rng(2)
    prompts = [rand_prompt(rng, 30) for _ in range(3)]
    g_off = greedy_generate(make_engine(model, params, wq=False),
                            prompts, uid_base=1, max_new_tokens=16)
    g_on = greedy_generate(
        make_engine(model, params, wq=True, weight_quant_dtype=wdtype),
        prompts, uid_base=1, max_new_tokens=16)
    fracs = []
    for a, b in zip(g_off, g_on):
        matched = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                       min(len(a), len(b)))
        fracs.append(matched / max(1, len(a)))
    assert np.mean(fracs) >= 0.5, f"divergence too large: {fracs}"
    p = prompts[0]
    la = np.asarray(make_engine(model, params, wq=False).put([9], [p]))
    lb = np.asarray(make_engine(model, params, wq=True,
                                weight_quant_dtype=wdtype).put([9], [p]))
    rel = np.max(np.abs(la - lb)) / (np.max(np.abs(la)) + 1e-9)
    assert rel < 0.05, f"relative logit error {rel}"


def test_perplexity_delta_gate(model_and_params):
    """Teacher-forced perplexity of the int8-weight engine within 1% of
    the full-precision engine (the quality gate of docs/SERVING.md
    "Weight quantization"), and the verify_width path rides the
    quantized tree."""
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

    ppl_off = np.exp(nll(make_engine(model, params, wq=False), 1))
    ppl_on = np.exp(nll(make_engine(model, params, wq=True), 1))
    assert abs(ppl_on / ppl_off - 1.0) <= 0.01, (ppl_off, ppl_on)


def test_composes_with_quantized_kv(model_and_params):
    """Weight quant + KV quant (int8 and fp8) on one engine: both
    representations active, decode proceeds, logits stay close to the
    full-precision engine."""
    model, params = model_and_params
    rng = np.random.default_rng(4)
    prompt = rand_prompt(rng, 30)
    la = np.asarray(make_engine(model, params, wq=False).put([1], [prompt]))
    for kdtype in ("int8", "fp8_e4m3"):
        eng = make_engine(model, params, wq=True, kv_quant_enabled=True,
                          kv_quant_dtype=kdtype)
        lb = np.asarray(eng.put([1], [prompt]))
        rel = np.max(np.abs(la - lb)) / (np.max(np.abs(la)) + 1e-9)
        assert rel < 0.05, (kdtype, rel)
        for _ in range(3):
            lb = np.asarray(eng.put([1], [[int(np.argmax(lb))]]))


# ------------------------------------------------------- configure + guards
def test_configure_weight_quant_guards(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(5)
    eng = make_engine(model, params, wq=False)
    eng.put([1], [rand_prompt(rng, 10)])
    with pytest.raises(RuntimeError, match="tracked"):
        eng.configure_weight_quant(True)
    eng.flush(1)
    eng.configure_weight_quant(True)
    assert eng.config.weight_quant_enabled
    assert WQ.is_quantized(eng.params["layers"]["wq"])
    # idempotent re-apply with the same representation
    eng.configure_weight_quant(True)
    # lossy: disable or re-code raises
    with pytest.raises(RuntimeError, match="already quantized"):
        eng.configure_weight_quant(False)
    with pytest.raises(RuntimeError, match="already quantized"):
        eng.configure_weight_quant(True, dtype="fp8_e4m3")
    # bad dtype rejected before touching anything
    eng2 = make_engine(model, params, wq=False)
    with pytest.raises(ValueError, match="dtype"):
        eng2.configure_weight_quant(True, dtype="int3")
    assert not eng2.config.weight_quant_enabled


def test_param_stats_shape(model_and_params):
    model, params = model_and_params
    off = make_engine(model, params, wq=False)
    on = make_engine(model, params, wq=True)
    s_off, s_on = off.param_stats(), on.param_stats()
    assert s_off["param_bytes_quantized"] == 0
    assert s_on["param_bytes_quantized"] > 0
    assert s_on["param_bytes_total"] < s_off["param_bytes_total"]
    assert s_on["weight_quant_dtype"] == "int8"


# -------------------------------------------------- serving config + gauges
def test_serving_config_applies_weight_quant(model_and_params):
    from deepspeed_tpu.serving import (ServingConfig, ServingFrontend,
                                       WeightQuantConfig)

    model, params = model_and_params
    wq = WeightQuantConfig(enabled=True)
    vcfg = RaggedInferenceEngineConfig()
    wq.apply(vcfg)
    assert vcfg.weight_quant_enabled and vcfg.weight_quant_dtype == "int8"
    assert vcfg.weight_quant_skip == ["embed", "final_norm"]
    eng = make_engine(model, params, wq=False)
    fe = ServingFrontend([eng],
                         ServingConfig(weight_quant={"enabled": True}))
    try:
        assert eng.config.weight_quant_enabled
        assert WQ.is_quantized(eng.params["layers"]["wq"])
        rng = np.random.default_rng(6)
        h = fe.submit(rand_prompt(rng, 20), max_new_tokens=4)
        assert fe.wait_all([h], timeout=60)
        snap = fe.metrics_snapshot()
        stats = eng.param_stats()
        assert snap["param_bytes_total"] == stats["param_bytes_total"]
        assert snap["param_bytes_quantized"] == \
            stats["param_bytes_quantized"]
        assert snap["param_bytes_quantized"] > 0
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_ds_config_mounts_weight_quant():
    from deepspeed_tpu.runtime.config import DeepSpeedTpuConfig

    c = DeepSpeedTpuConfig(**{
        "train_micro_batch_size_per_gpu": 1,
        "weight_quant": {"enabled": True, "dtype": "fp8_e4m3"},
        "serving": {"weight_quant": {"enabled": True, "block": 64}}})
    assert c.weight_quant.enabled and c.weight_quant.dtype == "fp8_e4m3"
    assert c.serving.weight_quant.block == 64


# ---------------------------------------------------------------- TP serving
def test_tp_sharded_weight_quant_matches_single_device(model_and_params):
    """TP serving from a quantized tree: the scale planes shard with
    their weight shards (expand_spec_tree mirrors the logical spec onto
    qw and qs), so — at a block that divides the per-shard width, making
    the representation TP-invariant — the sharded engine matches the
    single-device quantized engine exactly."""
    from deepspeed_tpu.parallel import topology as topo

    model, params = model_and_params

    def vcfg():
        return RaggedInferenceEngineConfig(
            max_ragged_batch_size=256, max_ragged_sequence_count=8,
            max_chunk_tokens=32, kv_blocks=64, kv_block_size=BS,
            max_tracked_sequences=64, weight_quant_enabled=True,
            weight_quant_block=16)

    single = InferenceEngineV2(model, params=params, config=vcfg())
    topo.reset_topology()
    t = topo.MeshTopology.build(data=4, tensor=2)
    sharded = InferenceEngineV2(model, params=params, mesh=t, config=vcfg())
    node = sharded.params["layers"]["wq"]
    assert WQ.is_quantized(node)
    assert "tensor" in str(node["qw"].sharding.spec)
    assert "tensor" in str(node["qs"].sharding.spec)
    rng = np.random.default_rng(7)
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


# ----------------------------------------------- autoscaler grow composition
def test_fleet_scale_up_applies_weight_quant_before_traffic(
        model_and_params):
    """Regression for the PR 12 grow path silently skipping per-replica
    config apply: a FleetController scale-up must build the new replica
    through the frontend's full wiring, so weight_quant is applied to
    the factory-fresh engine BEFORE it takes traffic (structurally
    guaranteed: configure_weight_quant raises once sequences are
    tracked, so a grown replica that serves at all was quantized
    first)."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    model, params = model_and_params
    built = []

    def factory(i):
        eng = make_engine(model, params, wq=False)
        built.append(eng)
        return eng

    scfg = ServingConfig(
        max_queue_depth=256,
        weight_quant={"enabled": True},
        autoscaler={"enabled": True, "min_replicas": 1, "max_replicas": 3,
                    "scale_up_queue_per_replica": 2.0,
                    "up_stable_ticks": 1, "scale_up_cooldown_s": 0.1,
                    "down_stable_ticks": 1000,
                    "tick_interval_s": 0.05})
    rng = np.random.default_rng(8)
    fe = ServingFrontend([factory(0)], scfg, engine_factory=factory)
    try:
        hs = [fe.submit(rand_prompt(rng, int(rng.integers(8, 20))),
                        max_new_tokens=24) for _ in range(24)]
        assert fe.wait_all(hs, timeout=600)
        deadline = time.monotonic() + 60
        while (time.monotonic() < deadline
               and fe.autoscaler.stats()["scale_ups"] < 1):
            time.sleep(0.05)
        assert fe.autoscaler.stats()["scale_ups"] >= 1, \
            "burst never grew the fleet"
        assert len(built) >= 2, "factory never built a grown replica"
        for eng in built:
            assert eng.config.weight_quant_enabled
            assert WQ.is_quantized(eng.params["layers"]["wq"])
        snap = fe.metrics_snapshot()
        assert snap["requests_completed"] == 24
        # fleet-summed param gauges cover every accepting replica
        assert snap["param_bytes_quantized"] > 0
    finally:
        fe.shutdown(drain=False, timeout=5)
