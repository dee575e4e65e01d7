"""The serving layout of a dense model's parameters (``paged_model.fuse_qkv``:
q, k and v one stacked leaf ``wqkv``, one matmul in the ``qkv`` scope)
against the three-leaf body it replaces, on the same seeded weights: a
Pythia block (MHA, biases, partial rotary, parallel residual) and a Mistral
block (GQA 32 / 8, no bias) through ``forward``, ``forward_verify`` and a
mixed ``[S, C]`` put; which trees are fused and which stay apart (quantized
nodes, a ``tensor`` axis); and the counter that says which path an engine
took. Tiny models on the CPU; what the layout buys is the chip's
(tests/test_tpu_compile.py, ``PERF.md`` PR 37)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import engine_v2, weight_quant
from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.paged_model import (QKV_LEAVES, fuse_qkv,
                                                    split_qkv)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

BLOCKS = {
    "pythia": TransformerConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=3,
        num_heads=4, max_seq_len=256, norm="layernorm",
        activation="gelu_exact", position="rope", rope_pct=0.25,
        parallel_residual=True, tie_embeddings=False, use_bias=True),
    "mistral": TransformerConfig(
        vocab_size=256, hidden_size=256, intermediate_size=128, num_layers=2,
        num_heads=32, num_kv_heads=8, max_seq_len=256, norm="rmsnorm",
        activation="silu", position="rope", tie_embeddings=False),
}
SIZING = dict(max_ragged_sequence_count=8, max_chunk_tokens=32,
              max_ragged_batch_size=128, kv_blocks=64, kv_block_size=8,
              max_tracked_sequences=16)


def seeded(block, dtype):
    """The block's model in ``dtype`` and weights whose biases and gains
    are not the zeros and ones ``init`` leaves them at: a body that
    dropped ``wqkv_b`` or cut it at the wrong column would disagree."""
    model = CausalLM(dataclasses.replace(BLOCKS[block], dtype=dtype))
    params = model.init(jax.random.PRNGKey(3))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    return model, jax.tree.unflatten(tree, [
        (leaf + 0.05 * jax.random.normal(k, leaf.shape)).astype(dtype)
        for leaf, k in zip(leaves, keys)])


def engines(block, dtype, monkeypatch):
    """The same weights behind the serving layout and behind three leaves
    (an engine built while ``fuse_qkv`` does nothing)."""
    model, params = seeded(block, dtype)
    config = RaggedInferenceEngineConfig(**SIZING)
    fused = InferenceEngineV2(model, params=params, config=config)
    with monkeypatch.context() as patch:
        patch.setattr(engine_v2, "fuse_qkv", lambda tree: tree)
        apart = InferenceEngineV2(model, params=params, config=config)
    assert fused.qkv_fused and not apart.qkv_fused
    return fused, apart


def prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def put_plain(eng):
    """A prompt chunk, then decode steps of its sequence alone."""
    out = [eng.put([1], [prompt(1, 20)])]
    out += [eng.put([1], [[7 + i]]) for i in range(2)]
    return out


def put_mixed(eng):
    """Two prompts, then a step that carries a chunk of a third beside
    their one-token rows: an [S, C] bucket with padded rows and columns."""
    eng.put([1, 2], [prompt(1, 9), prompt(2, 17)])
    return [eng.put([1, 2, 3], [[5], [6], prompt(3, 11)]),
            eng.put([3, 1], [[8], [9]])]


def put_verify(eng):
    """Drafts verified at a width: the logits of each row's last four
    positions, through ``forward_verify``."""
    eng.put([1, 2], [prompt(1, 12), prompt(2, 5)])
    return [eng.put([1, 2], [prompt(4, 4), prompt(5, 4)], verify_width=4,
                    defer_commit=True)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("puts", [put_plain, put_mixed, put_verify],
                         ids=lambda f: f.__name__[4:])
@pytest.mark.parametrize("block", list(BLOCKS))
def test_one_matmul_gives_the_three_leaf_bodys_logits(block, puts, dtype,
                                                      monkeypatch):
    """Column j of ``x @ [wq | wk | wv]`` is the same sum of the same
    products as column j of its own leaf's matmul: in float32 the two
    bodies agree exactly, in bfloat16 inside a hundredth of the logits'
    range (the benchmark's tolerances are 0.02 and 0.07 of it; on this
    CPU they agree exactly there too)."""
    fused, apart = engines(block, dtype, monkeypatch)
    for got, want in zip(puts(fused), puts(apart)):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        assert got.shape == want.shape
        spread = want.max() - want.min()
        bound = 1e-2 if dtype == jnp.bfloat16 else 0.0
        assert np.abs(got - want).max() <= bound * spread
    assert fused.put_totals["forwards_qkv_fused"] == \
        fused.put_totals["forwards"] == apart.put_totals["forwards"] > 0
    assert apart.put_totals["forwards_qkv_fused"] == 0


@pytest.mark.parametrize("block", list(BLOCKS))
def test_the_engine_holds_one_copy(block):
    """A fused engine's tree has ``wqkv`` (and ``wqkv_b`` where the family
    has biases), q's columns then k's then v's, and none of the three
    leaves; ``split_qkv`` gives the model's tree back bit for bit; a tree
    that is already fused goes through untouched (tests hand one engine's
    ``params`` to the next)."""
    model, params = seeded(block, jnp.float32)
    cfg = model.cfg
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(**SIZING))
    layers = eng.params["layers"]
    assert not set(layers) & {n + s for n in QKV_LEAVES for s in ("", "_b")}
    width = (cfg.num_heads + 2 * cfg.kv_heads) * cfg.head_dim
    assert layers["wqkv"].shape == (cfg.num_layers, cfg.hidden_size, width)
    assert ("wqkv_b" in layers) == cfg.use_bias == ("wq_b" in
                                                    params["layers"])
    if cfg.use_bias:
        assert layers["wqkv_b"].shape == (cfg.num_layers, width)
    back = split_qkv(cfg, eng.params)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # every other leaf is the caller's own array, not a copy
    assert layers["wo"] is params["layers"]["wo"]
    assert fuse_qkv(eng.params) is eng.params
    assert split_qkv(cfg, params) is params
    again = InferenceEngineV2(model, params=eng.params,
                              config=RaggedInferenceEngineConfig(**SIZING))
    assert again.params is eng.params
    eng.put([1], [prompt(1, 5)])
    assert eng.put_totals["forwards_qkv_fused"] == 1


@pytest.mark.parametrize("given", ["three_leaves", "fused"])
def test_a_quantized_tree_keeps_three_nodes(given):
    """Quantized ``{"qw", "qs"}`` nodes stay apart (a scale block would
    straddle the seams of one leaf), whether the engine is given the
    model's tree or one another engine fused, at build or by
    ``configure_weight_quant`` afterwards: the same nodes, the same
    logits, and no forward counted as fused."""
    model, params = seeded("pythia", jnp.float32)
    if given == "fused":
        params = fuse_qkv(params)

    def config(**kw):
        return RaggedInferenceEngineConfig(**SIZING, **kw)

    want_model, want_params = seeded("pythia", jnp.float32)
    want = InferenceEngineV2(want_model, params=want_params, config=config(
        weight_quant_enabled=True, weight_quant_block=16))
    built = InferenceEngineV2(model, params=params, config=config(
        weight_quant_enabled=True, weight_quant_block=16))
    later = InferenceEngineV2(model, params=params, config=config())
    assert later.qkv_fused
    later.configure_weight_quant(True, block=16)
    for eng in (want, built, later):
        assert not eng.qkv_fused
        assert all(weight_quant.is_quantized(eng.params["layers"][n])
                   for n in QKV_LEAVES)
        assert "wq_b" in eng.params["layers"]
    logits = [np.asarray(eng.put([1], [prompt(1, 14)]))
              for eng in (want, built, later)]
    np.testing.assert_array_equal(logits[0], logits[1])
    np.testing.assert_array_equal(logits[0], logits[2])
    assert built.put_totals["forwards_qkv_fused"] == 0
    assert built.put_totals["forwards"] == 1


@pytest.mark.parametrize("given", ["three_leaves", "fused"])
def test_a_tensor_axis_keeps_three_leaves(given):
    """Under a mesh with ``tensor: 2`` the three leaves shard by their own
    heads (one leaf's columns would have to be interleaved a shard): the
    engine builds from either tree, serves on three leaves, and agrees
    with the fused engine of one device."""
    from deepspeed_tpu.parallel import topology as topo

    model, params = seeded("mistral", jnp.float32)
    single = InferenceEngineV2(model, params=params,
                               config=RaggedInferenceEngineConfig(**SIZING))
    topo.reset_topology()
    try:
        mesh = topo.MeshTopology.build(data=4, tensor=2)
        sharded = InferenceEngineV2(
            model, params=single.params if given == "fused" else params,
            mesh=mesh, config=RaggedInferenceEngineConfig(**SIZING))
        assert single.qkv_fused and not sharded.qkv_fused
        assert "tensor" in str(sharded.params["layers"]["wk"].sharding.spec)
        for uids, toks in (([1, 2], [prompt(1, 9), prompt(2, 17)]),
                           ([1, 2], [[5], [6]])):
            np.testing.assert_allclose(
                np.asarray(sharded.put(uids, toks)),
                np.asarray(single.put(uids, toks)), atol=2e-5, rtol=2e-5)
        assert sharded.put_totals["forwards_qkv_fused"] == 0
        assert single.put_totals["forwards_qkv_fused"] == 2
    finally:
        topo.reset_topology()


def test_the_replica_publishes_the_path_taken():
    """Served: ``forwards_qkv_fused`` reaches the registry beside
    ``forwards`` (their ratio is the share of the fleet's forwards on the
    serving layout), and no ``stage`` / ``forward`` span carries it: an
    engine takes one path for all its forwards."""
    import time

    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    model, params = seeded("pythia", jnp.float32)
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(**SIZING))
    fe = ServingFrontend([eng], ServingConfig(
        max_queue_depth=8, telemetry={"enabled": True}))
    try:
        handles = [fe.submit(prompt(u, 6), max_new_tokens=5)
                   for u in range(3)]
        assert fe.wait_all(handles, timeout=300)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not any(
                s["name"] == "idle_wait" and s["attrs"].get("open")
                for s in fe.tracer.export()):
            time.sleep(0.002)
        spans = fe.tracer.export()
        snap = fe.metrics_snapshot()
    finally:
        fe.shutdown(drain=False, timeout=5)
    assert snap["forwards_qkv_fused"] == snap["forwards"] == \
        eng.put_totals["forwards"] > 0
    assert not any("forwards_qkv_fused" in s["attrs"] for s in spans
                   if s["name"] in ("stage", "forward"))
    assert "forwards_qkv_fused" not in eng.last_put
