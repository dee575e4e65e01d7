"""Latent attention's cache in the serving engine (docs/SERVING.md "The
pool contract"): one leaf ``kv`` [L, NB, bs, W] with no head axis, sized
and counted by what a row occupies; what moves whole blocks of the group
— the prefix cache, export / import, the preemption stash, a trim —
works on it; what assumes a kv-head axis is refused with the typed error;
the put's record says which path its queries took."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (FORWARD_ONLY,
                                                  InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.models.hybrid import (LatentKVUnsupported,
                                         RecurrentStateUnsupported,
                                         ReleasedKVUnsupported)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.ops import latent_attention as la

TWIN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark",
                    "twins", "configs", "openpangu-ultra-moe-718b.json")
BS, BLOCKS = 8, 128


@pytest.fixture(scope="module")
def pangu():
    with open(TWIN) as f:
        body = json.load(f)
    cfg = TransformerConfig(**dict(body["transformer_config"],
                                   dtype=jnp.float32))
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(2))
    # gains off their initial value, so that a dropped norm would show
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 1.05 if "norm" in str(path[-1]) else a, params)
    return model, params, dict(body["engine"], compile_ahead=0)


@pytest.fixture()
def expanded(monkeypatch):
    """Chunks of 32 take the expanded path, in tiles of 16 keys."""
    monkeypatch.setattr(la, "ABSORB_MAX_QUERIES", 8)
    monkeypatch.setattr(la, "EXPAND_TILE", 16)


#: engines of one sizing and one setting of the paths' switch (both are
#: read when a forward is traced) share one jitted forward
#: (``testing.share_forward``)
_FORWARDS = {}


def engine(pangu, **sizing):
    from deepspeed_tpu.inference.v2.testing import share_forward

    model, params, base = pangu
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(
                                **dict(base, **sizing)))
    return share_forward(eng, _FORWARDS, (
        tuple(sorted(sizing.items())), la.ABSORB_MAX_QUERIES, la.EXPAND_TILE))


def prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 128, size=n).tolist()


def feed(eng, uid, tokens, chunk=32):
    for at in range(0, len(tokens), chunk):
        out = eng.put([uid], [tokens[at:at + chunk]])
    return np.asarray(out[0])


def close(got, want):
    return np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_the_pool_is_one_leaf_with_no_head_axis_and_counts_what_it_occupies(
        pangu):
    model, _, _ = pangu
    cfg = model.cfg
    assert cfg.is_latent and cfg.head_dim == 16 + 8 and cfg.rot_dim == 8
    assert cfg.latent_dim == 40 and cfg.latent_width == 128
    assert cfg.kv_groups() == ((0, 3),)
    assert cfg.kv_layout(BS) == (("kv",), (BS, 128))
    eng = engine(pangu)
    sm = eng.state_manager
    (group,) = sm.groups
    assert group.leaves == ("kv",) and group.names == ["kv"]
    assert group.block_shape == (BS, 128)
    assert sm.kv_cache["kv"].shape == group.pool_shape == (3, BLOCKS, BS, 128)
    # a block's bytes are the padded rows', in every layer: not 40 numbers
    per_block = 3 * BS * 128 * 4
    assert sm.allocator.bytes_per_block == per_block
    feed(eng, 1, prompt(1, 50))
    occ = eng.occupancy()
    assert occ["bytes_in_use"] == 7 * per_block
    assert occ["bytes_total"] == BLOCKS * per_block
    assert sm.resident_bytes()["kv_bytes_resident"] == 7 * per_block
    # the rows hold (c, k_r) and zeros behind them
    rows = np.asarray(sm.kv_cache["kv"][:, sm.get_sequence(1).kv_blocks[0]])
    assert np.abs(rows[..., :40]).min() > 0 and not rows[..., 40:].any()
    # the pool is donated and written in place, like the others
    before = sm.kv_cache["kv"]
    eng.put([1], [[3]])
    assert before.is_deleted()


def test_the_config_says_what_a_latent_model_may_be(pangu):
    model, _, _ = pangu
    base = {f.name: getattr(model.cfg, f.name)
            for f in model.cfg.__dataclass_fields__.values()}
    for wrong in (dict(kv_lora_rank=0), dict(qk_rope_head_dim=7),
                  dict(layer_pattern=("latent", "full"))):
        with pytest.raises(ValueError, match="latent"):
            TransformerConfig(**dict(base, **wrong))


@pytest.mark.parametrize("path", ["absorbed", "expanded"])
def test_a_shared_prefix_is_matched_and_attended_to(pangu, path, request):
    if path == "expanded":
        request.getfixturevalue("expanded")
    eng = engine(pangu, enable_prefix_cache=True)
    sm = eng.state_manager
    shared = prompt(10, 48)
    feed(eng, 1, shared + prompt(11, 8))
    rest = prompt(12, 40)
    assert sm.match_prefix(2, shared + rest) == 48
    # the later chunks' queries read the six shared blocks, never written
    # by this sequence
    got = feed(eng, 2, rest)
    assert close(got, feed(engine(pangu), 3, shared + rest))
    assert eng.prefix_stats()["tokens_saved"] == 48
    eng.flush(1)
    eng.flush(2)
    assert sm.available_blocks == BLOCKS


@pytest.mark.parametrize("chunk_blocks", [0, 3])
def test_export_import_and_the_preemption_stash_move_whole_blocks(
        pangu, chunk_blocks):
    src, dst = engine(pangu), engine(pangu)
    tokens = prompt(5, 70)
    feed(src, 5, tokens)
    payload = src.export_sequence(5, chunk_blocks=chunk_blocks)
    slabs = payload["chunks"][0] if chunk_blocks else payload["slabs"]
    assert set(slabs) == {"kv"} and slabs["kv"].shape[2:] == (BS, 128)
    dst.import_sequence(9, payload, tokens)
    want = np.asarray(src.put([5], [[7]])[0])
    assert close(np.asarray(dst.put([9], [[7]])[0]), want)
    # parked and brought back into the engine it left
    src.preempt_stash(5, src.export_sequence(5))
    src.flush(5)
    assert src.state_manager.available_blocks == BLOCKS
    src.import_sequence(5, src.preempt_restore_payload(5), tokens + [7])
    assert close(np.asarray(src.put([5], [[9]])[0]),
                 np.asarray(dst.put([9], [[9]])[0]))


def test_a_trim_rolls_tokens_back(pangu):
    eng = engine(pangu)
    tokens = prompt(6, 60)
    feed(eng, 1, tokens[:50])
    eng.put([1], [tokens[50:58]], defer_commit=True)
    assert eng.trim_sequence(1, 5) == 1       # 58 -> 53 tokens: 8 -> 7 blocks
    eng.commit_tokens(1, tokens[50:53])
    got = np.asarray(eng.put([1], [[tokens[53]]])[0])
    assert close(got, feed(engine(pangu), 2, tokens[:54]))


def test_features_that_assume_a_kv_head_axis_raise_the_typed_error(pangu):
    for kw in (dict(kv_quant_enabled=True),
               dict(enable_prefix_cache=True, kv_tier_enabled=True)):
        with pytest.raises(LatentKVUnsupported, match="kv-head"):
            engine(pangu, **kw)
    eng = engine(pangu, enable_prefix_cache=True)
    with pytest.raises(LatentKVUnsupported, match="quantized"):
        eng.configure_kv_quant(True)
    with pytest.raises(LatentKVUnsupported, match="KV tier"):
        eng.configure_kv_tier(True)
    # TP serving: no head to split
    from deepspeed_tpu.parallel.topology import MeshTopology

    model, params, base = pangu
    with pytest.raises(LatentKVUnsupported, match="TP serving"):
        InferenceEngineV2(model, params=params,
                          config=RaggedInferenceEngineConfig(**base),
                          mesh=MeshTopology.build(tensor=2, data=4))
    assert issubclass(LatentKVUnsupported, NotImplementedError)
    assert not issubclass(LatentKVUnsupported, (ReleasedKVUnsupported,
                                                RecurrentStateUnsupported))
    # a hybrid block's forward verifies no drafts, this one's neither
    with pytest.raises(RecurrentStateUnsupported, match="verification"):
        eng.put([1], [[3, 4]], verify_width=2)
    # nothing was lost to the refusals
    tokens = prompt(1, 50)
    assert close(feed(eng, 1, tokens), feed(engine(pangu), 2, tokens))


def test_the_puts_record_says_which_path_its_queries_took(pangu, expanded):
    eng = engine(pangu)
    feed(eng, 1, prompt(1, 40))       # chunks of 32 and 8: the second at 32
    assert eng.last_put["latent_q_expanded"] == 8
    assert eng.last_put["latent_rows_expanded"] == 48    # 40 in tiles of 16
    assert eng.last_put["prefill_tokens"] == 8
    feed(eng, 2, prompt(2, 20))
    # a put of both kinds runs as two forwards: its record sums them
    eng.put([1, 2, 3], [[5], [6], prompt(3, 30)])
    rec = eng.last_put
    assert rec["forwards"] == 2
    assert rec["latent_q_absorbed"] == 2 and rec["latent_q_expanded"] == 30
    assert rec["latent_keys_absorbed"] == 41 + 21
    assert rec["latent_pairs_absorbed"] == 41 + 21
    assert rec["kv_read_tokens"] - rec["latent_keys_absorbed"] == 30
    assert rec["qk_pairs"] - rec["latent_pairs_absorbed"] == 30 * 31 // 2
    assert rec["latent_rows_expanded"] == 32 and rec["prefill_tokens"] == 30
    totals = eng.put_totals
    assert totals["prefill_tokens"] == 40 + 20 + 30
    assert totals["latent_q_absorbed"] == 2
    assert totals["latent_q_expanded"] == 90
    assert totals["latent_rows_expanded"] \
        == (32 + 48) + (32) + 32     # by sequence: 1, 2, 3


def test_the_counters_ride_on_the_forward_span_and_through_the_frontend(
        pangu, expanded):
    import time

    from deepspeed_tpu.inference.v2.scheduler import \
        ContinuousBatchingScheduler
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend
    from deepspeed_tpu.telemetry import Tracer

    eng = engine(pangu)
    tr = Tracer()
    sched = ContinuousBatchingScheduler(eng, tracer=tr)
    sched.submit(1, prompt(1, 50), max_new_tokens=3)
    while sched.step() != [1]:
        pass
    forwards = [s for s in tr.export() if s["name"] == "forward"]
    assert sum(s["attrs"]["latent_rows_expanded"] for s in forwards) == 32 + 64
    assert sum(s["attrs"]["prefill_tokens"] for s in forwards) == 50
    assert sum(s["attrs"]["latent_q_absorbed"] for s in forwards) == 2
    for s in (s for s in tr.export() if s["name"] == "stage"):
        assert not any(k.startswith(FORWARD_ONLY) for k in s["attrs"])
    fe = ServingFrontend([engine(pangu)], ServingConfig())
    try:
        handle = fe.submit(prompt(1, 90), max_new_tokens=4)
        fe.wait_all([handle], timeout=120)
        assert handle.finish_reason == "length"
        deadline = time.monotonic() + 10
        while fe.metrics_snapshot()["latent_q_absorbed"] < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        snap = fe.metrics_snapshot()
        assert snap["prefill_tokens"] == 90 and snap["latent_q_expanded"] == 90
        assert snap["latent_rows_expanded"] == 32 + 64 + 96
        assert snap["latent_q_absorbed"] == 3
    finally:
        fe.shutdown(drain=False, timeout=30)
