"""K/V by layer group through ``InferenceEngineV2`` (docs/SERVING.md "The
pool contract"): layers of a window and layers of the whole context keep
their K/V in pools of their own, a sequence has a block table a group,
and a window group's blocks behind the window go back to the free list
while the sequence lives. A tiny twin of the published shape of
Trinity-Large — a leading dense layer, periods of window x 3 + full, 6
query heads a KV head, a window of 32 = four blocks of 8, shorter than
the prompts — and a Mistral-shaped dense twin (one window for all layers:
one group, the same mechanism), in float32 on the CPU."""

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    FORWARD_ONLY, InferenceEngineV2, RaggedInferenceEngineConfig,
    _keys_and_pairs)
from deepspeed_tpu.inference.v2.ragged.manager import DSStateManager
from deepspeed_tpu.inference.v2.scheduling_utils import (SchedulingError,
                                                         SchedulingResult)
from deepspeed_tpu.models.hybrid import (RecurrentStateUnsupported,
                                         ReleasedKVUnsupported)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "tests", "benchmark", "twins", "configs",
                       "trinity-large-preview.json")) as _f:
    ARCH = json.load(_f)["transformer_config"]
WINDOW, BS = ARCH["sliding_window"], 8
SIZING = dict(max_ragged_batch_size=64, max_ragged_sequence_count=4,
              max_chunk_tokens=24, kv_blocks=64, kv_block_size=BS)
DENSE = TransformerConfig(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=256, sliding_window=WINDOW,
    norm="rmsnorm", activation="silu", position="rope",
    tie_embeddings=False, dtype=jnp.float32)


def _perturbed(model):
    params = model.init(jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(flat))
    flat = [leaf + 0.05 * jax.random.normal(k, leaf.shape)
            if any(w in jax.tree_util.keystr(path)
                   for w in ("norm", "router_b")) else leaf
            for (path, leaf), k in zip(flat, keys)]
    return jax.tree_util.tree_unflatten(tree, flat)


@pytest.fixture(scope="module")
def trinity():
    model = CausalLM(TransformerConfig(**dict(ARCH, dtype=jnp.float32)))
    return model, _perturbed(model)


@pytest.fixture(scope="module")
def dense():
    model = CausalLM(DENSE)
    return model, _perturbed(model)


#: engines of one model at one sizing share one jitted forward
#: (``testing.share_forward``; release on or off is the manager's, not the
#: program's)
_FORWARDS = {}


def engine(model_and_params, **sizing):
    from deepspeed_tpu.inference.v2.testing import share_forward

    model, params = model_and_params
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(
                                **dict(SIZING, **sizing)))
    return share_forward(eng, _FORWARDS,
                         (id(model), tuple(sorted(sizing.items()))))


def prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 128, size=n).tolist()


def feed(eng, uid, tokens, chunk=24):
    for at in range(0, len(tokens), chunk):
        out = eng.put([uid], [tokens[at:at + chunk]])
    return np.asarray(out[0])


def decode(eng, uid, tokens, steps, chunk=24):
    got, tokens = [feed(eng, uid, tokens, chunk)], list(tokens)
    for _ in range(steps):
        tokens.append(int(np.argmax(got[-1])))
        got.append(np.asarray(eng.put([uid], [[tokens[-1]]])[0]))
    return got, tokens


def reference_block():
    sys.path.insert(0, REPO)
    from benchmark import manifest as mf

    return mf.find_module(mf.HERE, "blocks", "trinity")


def no_release(monkeypatch):
    """The same engine with nothing handed back: what the pools held
    before K/V had lifetimes."""
    monkeypatch.setattr(DSStateManager, "release_behind",
                        lambda self, seq: 0)


def live(seq):
    return [[b for b in table if b >= 0] for table in seq.tables]


# ------------------------------------------------------- against reference

@pytest.mark.parametrize("chunk", [24, 20, 7],
                         ids=lambda c: f"chunks-of-{c}")
def test_chunks_across_the_windows_edge_then_decode_agree_with_the_reference(
        trinity, chunk):
    """Prefill in chunks that straddle the window's edge (32: neither 24,
    20 nor 7 divides it), then decode through both caches: the engine's
    logits at every step against the plain reference, which attends by
    mask over every earlier key and drops nothing."""
    model, params = trinity
    eng = engine(trinity)
    tokens = prompt(3, 100)
    got, tokens = decode(eng, 7, tokens, 6, chunk=chunk)
    want = np.asarray(reference_block().tie_margins(
        params, jnp.asarray(tokens), ARCH, q_block=16)[0])
    scale = np.abs(want).max()
    for step, g in enumerate(got):
        assert np.abs(g - want[99 + step]).max() < 1e-4 * scale, step
    assert eng.put_totals["kv_blocks_released"] > 0
    seq = eng.state_manager.get_sequence(7)
    # the whole-context group holds every block, the window group the
    # window's: positions 106 - 31 .. 105 lie in blocks 9 .. 13
    assert len(live(seq)[0]) == 14 and seq.released == [0, 9]
    assert len(live(seq)[1]) == 5
    eng.flush(7)
    assert [g.allocator.free_blocks for g in eng.state_manager.groups] \
        == [g.allocator.total_blocks for g in eng.state_manager.groups]


def test_apply_and_the_engine_agree(trinity):
    """The training path and the serving path run the same parts."""
    model, params = trinity
    tokens = prompt(5, 70)
    want = np.asarray(model.apply(params, jnp.asarray([tokens]))[0, -1])
    got = feed(engine(trinity), 1, tokens)
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


# ------------------------------------------------- release on, release off

def test_release_on_and_off_give_equal_logits_and_fewer_resident_blocks(
        trinity, monkeypatch):
    tokens = prompt(11, 120)
    on = engine(trinity)
    got_on, _ = decode(on, 1, tokens, 5)
    held_on = [g.allocator.occupancy()["in_use_blocks"]
               for g in on.state_manager.groups]
    ratio = on.last_put["kv_bytes_resident"] / on.last_put["kv_bytes_unreleased"]
    no_release(monkeypatch)
    off = engine(trinity)
    got_off, _ = decode(off, 1, tokens, 5)
    held_off = [g.allocator.occupancy()["in_use_blocks"]
                for g in off.state_manager.groups]
    for a, b in zip(got_on, got_off):
        assert np.array_equal(a, b)
    assert held_on[0] == held_off[0] == 16          # 125 tokens, whole
    assert held_off[1] == 16 and held_on[1] == 5    # the window's blocks
    # 2 full layers and 7 window layers: (2 * 16 + 7 * 5) of 9 * 16
    assert ratio == pytest.approx((2 * 16 + 7 * 5) / (9 * 16))
    assert off.put_totals["kv_blocks_released"] == 0


def test_every_block_of_every_group_is_back_after_flush_and_none_is_shared(
        trinity):
    """Four sequences of different lengths interleaved, chunk by chunk:
    while they live no block id is in two tables of a group, a handed-back
    block is free at once, and after the flushes both pools are whole."""
    eng = engine(trinity, kv_blocks=96)
    sm = eng.state_manager
    prompts = {u: prompt(20 + u, n) for u, n in
               ((1, 130), (2, 45), (3, 90), (4, 17))}
    at = {u: 0 for u in prompts}
    released = 0
    while any(at[u] < len(p) for u, p in prompts.items()):
        uids = [u for u, p in prompts.items() if at[u] < len(p)]
        eng.put(uids, [prompts[u][at[u]:at[u] + 16] for u in uids])
        for u in uids:
            at[u] += 16
        released += eng.last_put.get("kv_blocks_released", 0)
        for g, group in enumerate(sm.groups):
            ids = [b for u in prompts for b in live(sm.get_sequence(u))[g]]
            assert len(ids) == len(set(ids)), "a live block handed out twice"
            assert group.allocator.free_blocks \
                == group.allocator.total_blocks - len(ids)
    assert released == eng.put_totals["kv_blocks_released"] > 0
    assert sm.available_blocks < eng.config.kv_blocks
    for u in prompts:
        eng.flush(u)
    assert sm.available_blocks == sm.free_blocks == eng.config.kv_blocks
    assert sm.groups[1].allocator.free_blocks \
        == sm.groups[1].allocator.total_blocks


def test_a_put_that_fails_before_dispatch_releases_nothing(trinity,
                                                           monkeypatch):
    eng = engine(trinity)
    tokens = prompt(31, 60)
    feed(eng, 1, tokens[:40])
    seq = eng.state_manager.get_sequence(1)
    before = (seq.seen_tokens, list(seq.released), live(seq)[1][:],
              eng.put_totals["kv_blocks_released"])

    def boom(*a, **k):
        raise RuntimeError("lowering failed")

    forward = eng.paged.forward
    monkeypatch.setattr(eng.paged, "forward", boom)
    with pytest.raises(RuntimeError, match="lowering failed"):
        eng.put([1], [tokens[40:60]])
    # the blocks allocated for the chunk stay the sequence's; nothing
    # behind the window went back, nothing was counted
    assert (seq.seen_tokens, seq.released) == before[:2]
    assert live(seq)[1][:len(before[2])] == before[2]
    assert eng.put_totals["kv_blocks_released"] == before[3]
    monkeypatch.setattr(eng.paged, "forward", forward)
    retried = np.asarray(eng.put([1], [tokens[40:60]])[0])
    fresh = feed(engine(trinity), 2, tokens, chunk=20)
    assert np.abs(retried - fresh).max() < 1e-5 * np.abs(fresh).max()


# ------------------------------------------------------------ the manager

def test_pools_are_by_group_and_kv_blocks_stays_the_one_number(trinity):
    eng = engine(trinity)
    sm = eng.state_manager
    assert [(g.window, g.layers) for g in sm.groups] == [(0, 2), (WINDOW, 7)]
    cache = sm.kv_cache
    assert set(cache) == {"k", "v", "k1", "v1"}
    assert cache["k"].shape == (2, 64, 2, BS, 16)
    # the window group's pool: 4 sequences x (32 / 8 + 2) + 64 / 8 blocks,
    # the most its sequences can hold at once
    assert cache["k1"].shape == (7, 4 * 6 + 8, 2, BS, 16)
    assert eng.window_pool_blocks(WINDOW) == 32
    assert sm.available_blocks == sm.free_blocks == eng.config.kv_blocks == 64
    occ = eng.occupancy()
    assert occ["total_blocks"] == 64
    assert [g["window"] for g in occ["groups"]] == [0, WINDOW]
    assert [g["total_blocks"] for g in occ["groups"]] == [64, 32]
    per_layer = 2 * 2 * BS * 16 * 4                     # K and V, float32
    assert [g["bytes_per_block"] for g in occ["groups"]] \
        == [2 * per_layer, 7 * per_layer]
    assert occ["bytes_total"] == 64 * 2 * per_layer + 32 * 7 * per_layer
    feed(eng, 1, prompt(1, 70))
    occ = eng.occupancy()
    assert [g["in_use_blocks"] for g in occ["groups"]] == [9, 5]
    assert occ["blocks_released"] == 4


@pytest.mark.parametrize("kv_blocks, want", [
    (64, 32), (40, 32), (24, 24), (16, 16)])
def test_the_window_pools_size_is_a_rule_of_kv_blocks(trinity, kv_blocks,
                                                      want):
    """``benchmark/tolerance.py`` builds the engine with a smaller
    ``kv_blocks`` and nothing else changed: no group outgrows the first,
    and the pool has no number of its own to state."""
    eng = engine(trinity, kv_blocks=kv_blocks)
    assert [g.allocator.total_blocks for g in eng.state_manager.groups] \
        == [kv_blocks, want]
    got = feed(eng, 1, prompt(2, 60))
    assert np.isfinite(got).all()
    eng.flush(1)
    assert eng.state_manager.available_blocks == kv_blocks


def test_a_window_pool_that_is_short_refuses_admission(trinity, monkeypatch):
    # the rule never sizes it short; a pool of 5 stands for one that is
    monkeypatch.setattr(InferenceEngineV2, "window_pool_blocks",
                        lambda self, window: 5 if window else
                        self.config.kv_blocks)
    eng = engine(trinity)
    feed(eng, 1, prompt(1, 40))                     # holds 4 of the 5
    assert eng.can_schedule([2], [16]) == SchedulingResult.KVCacheLimitExceeded
    with pytest.raises(SchedulingError):
        eng.put([2], [prompt(2, 16)])
    eng.flush(1)
    assert eng.can_schedule([2], [16]) == SchedulingResult.Success


def test_the_puts_record_by_group(trinity):
    eng = engine(trinity)
    tokens = prompt(4, 80)
    feed(eng, 1, tokens[:72])
    eng.put([1], [tokens[72:80]])
    put = eng.last_put
    # positions 72..79 under a window of 32: the walk covers blocks 5..9
    # of the window group (first live key 72 - 31 = 41) and 0..9 of the
    # whole-context group
    assert put["kv_blocks_live"] == 10 + 5
    assert put["kv_table_slots"] == 2 * eng.batch.max_blocks_per_seq
    assert put["kv_read_tokens"] == 80 and put["qk_pairs"] == 8 * 72 + 36
    assert (put["kv_g0_window"], put["kv_g1_window"]) == (0, WINDOW)
    assert put["kv_g0_read_tokens"] == 80
    assert put["kv_g0_qk_pairs"] == 8 * 72 + 36
    assert put["kv_g1_read_tokens"] == 80 - 41
    assert put["kv_g1_qk_pairs"] == 8 * WINDOW
    assert (put["kv_g0_in_use"], put["kv_g0_total"]) == (10, 64)
    # allocated up to block 9, blocks 0..5 handed back (the next query,
    # position 80, sees keys from 49 on: block 6)
    assert (put["kv_g1_in_use"], put["kv_g1_total"]) == (4, 32)
    assert put["kv_blocks_released"] == 1
    assert put["moe_rows_routed"] == 8 * 4 * 8          # 8 sparse layers
    # the one definition of these counts, which the block's cost function
    # is handed (benchmark/kv_group_readers.py)
    assert _keys_and_pairs(WINDOW, 72, 8) == (put["kv_g1_read_tokens"],
                                              put["kv_g1_qk_pairs"])
    assert _keys_and_pairs(0, 72, 8) == (80, put["qk_pairs"])
    # a put of several forwards sums what its forwards counted
    feed(eng, 2, prompt(5, 40), chunk=20)
    eng.put([1, 2, 3], [[1], [2], prompt(6, 24)])
    put = eng.last_put
    assert put["forwards"] == 2
    assert put["kv_g1_read_tokens"] == 32 + 32 + 24
    assert put["kv_g1_qk_pairs"] == 32 + 32 + 24 * 25 // 2
    assert put["kv_g0_read_tokens"] == 81 + 41 + 24 == put["kv_read_tokens"]


def test_the_span_attributes_ride_on_forward_and_stage_keeps_its_keys(
        trinity):
    from deepspeed_tpu.inference.v2.scheduler import \
        ContinuousBatchingScheduler
    from deepspeed_tpu.telemetry import Tracer

    eng = engine(trinity)
    tr = Tracer()
    sched = ContinuousBatchingScheduler(eng, tracer=tr)
    sched.submit(1, prompt(1, 50), max_new_tokens=3)
    while sched.step() != [1]:
        pass
    forwards = [s for s in tr.export() if s["name"] == "forward"]
    stages = [s for s in tr.export() if s["name"] == "stage"]
    assert any(s["attrs"].get("kv_blocks_released") for s in forwards)
    for s in forwards:
        assert {"kv_g0_in_use", "kv_g1_in_use", "kv_bytes_resident",
                "kv_blocks_live"} <= set(s["attrs"])
    for s in stages:
        assert not any(k.startswith(FORWARD_ONLY) for k in s["attrs"])
        assert {"bucket_seqs", "kv_read_tokens", "free_blocks"} \
            <= set(s["attrs"])


def test_the_released_count_is_published_through_the_frontend(dense):
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    fe = ServingFrontend([engine(dense)], ServingConfig())
    try:
        assert fe.metrics_snapshot()["kv_blocks_released"] == 0
        handle = fe.submit(prompt(1, 90), max_new_tokens=4)
        fe.wait_all([handle], timeout=120)
        assert handle.finish_reason == "length"
        deadline = time.monotonic() + 10
        while fe.metrics_snapshot()["kv_blocks_released"] == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert fe.metrics_snapshot()["kv_blocks_released"] \
            == (93 - WINDOW + 1) // BS
    finally:
        fe.shutdown(drain=False, timeout=30)


# ------------------------------------------------------------ refusals

def test_features_that_assume_the_whole_context_raise_the_typed_error(
        trinity):
    model, params = trinity
    for kw in (dict(enable_prefix_cache=True), dict(kv_quant_enabled=True),
               dict(enable_prefix_cache=True, kv_tier_enabled=True)):
        with pytest.raises(ReleasedKVUnsupported):
            engine(trinity, **kw)
    eng = engine(trinity)
    with pytest.raises(ReleasedKVUnsupported):
        eng.configure_prefix_cache(True)
    with pytest.raises(ReleasedKVUnsupported):
        eng.configure_kv_quant(True)
    tokens = prompt(1, 50)
    feed(eng, 1, tokens)
    with pytest.raises(ReleasedKVUnsupported, match="export_sequence"):
        eng.export_sequence(1)
    with pytest.raises(ReleasedKVUnsupported, match="trim_sequence"):
        eng.trim_sequence(1, 2)
    with pytest.raises(ReleasedKVUnsupported, match="import_sequence"):
        eng.import_sequence(9, {"block_size": BS}, tokens)
    # the refusals are NotImplementedErrors of their own, and name the groups
    assert issubclass(ReleasedKVUnsupported, NotImplementedError)
    assert not issubclass(ReleasedKVUnsupported, RecurrentStateUnsupported)
    with pytest.raises(RecurrentStateUnsupported, match="verification"):
        eng.put([1], [[3, 4]], verify_width=2)
    # nothing was lost to the refusals
    got = np.asarray(eng.put([1], [[5]])[0])
    want = feed(engine(trinity), 2, tokens + [5])
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


# ----------------------------------- one window for all layers: one group

def test_a_dense_model_past_its_window_gives_the_logits_it_gave(dense,
                                                                monkeypatch):
    """Mistral's shape: the same mechanism with one group. Past the
    window the logits are what they were with every block kept, and the
    sequence holds the window's blocks and the chunk's, no more."""
    tokens = prompt(8, 150)
    on = engine(dense)
    got_on, _ = decode(on, 1, tokens, 4)
    seq = on.state_manager.get_sequence(1)
    held = len(live(seq)[0])
    assert held <= WINDOW // BS + 1 and seq.released == [15]
    assert on.state_manager.available_blocks == 64 - held
    assert on.put_totals["kv_blocks_released"] == 15
    record = dict(on.last_put)
    no_release(monkeypatch)
    off = engine(dense)
    got_off, _ = decode(off, 1, tokens, 4)
    for a, b in zip(got_on, got_off):
        assert np.array_equal(a, b)
    assert off.state_manager.available_blocks == 64 - 20
    # the walk's count is the window's, released or not
    assert record["kv_blocks_live"] == off.last_put["kv_blocks_live"] == 5
    assert record["kv_g0_read_tokens"] == WINDOW
    on.flush(1)
    assert on.state_manager.available_blocks == 64


def test_a_dense_put_inside_its_window_keeps_its_record_key_for_key(dense):
    eng = engine(dense)
    eng.put([1], [prompt(1, 20)])
    assert set(eng.last_put) == {"bucket_seqs", "bucket_chunk", "rows",
                                 "valid_tokens", "kv_read_tokens", "qk_pairs",
                                 "kv_blocks_live", "kv_table_slots",
                                 "free_blocks"}
    assert eng.last_put["kv_blocks_live"] == 3
    # a model with no window has no released count to publish at all
    plain = InferenceEngineV2(
        CausalLM(dataclasses.replace(DENSE, sliding_window=None)),
        config=RaggedInferenceEngineConfig(**SIZING))
    assert "kv_blocks_released" not in plain.put_totals
    assert eng.put_totals["kv_blocks_released"] == 0


def test_a_rollback_inside_the_live_blocks_works_and_past_them_is_refused(
        dense):
    eng = engine(dense)
    tokens = prompt(9, 90)
    feed(eng, 1, tokens[:80])
    # drafts ride in with their commit deferred: nothing goes back yet
    before = eng.put_totals["kv_blocks_released"]
    eng.put([1], [tokens[80:88]], verify_width=8, defer_commit=True)
    assert eng.put_totals["kv_blocks_released"] == before
    assert eng.trim_sequence(1, 5) == 0
    eng.commit_tokens(1, tokens[80:83])
    seq = eng.state_manager.get_sequence(1)
    assert seq.seen_tokens == 83
    assert seq.released == [(83 - WINDOW + 1) // BS]
    assert eng.put_totals["kv_blocks_released"] == seq.released[0]
    got = np.asarray(eng.put([1], [[tokens[83]]])[0])
    want = feed(engine(dense), 2, tokens[:84])
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    with pytest.raises(ReleasedKVUnsupported, match="rollback past"):
        eng.trim_sequence(1, 60)
    with pytest.raises(ReleasedKVUnsupported, match="export_sequence"):
        eng.export_sequence(1)


def test_a_shared_prefix_block_loses_one_reference_behind_the_window(dense):
    eng = engine(dense, enable_prefix_cache=True)
    sm = eng.state_manager
    shared = prompt(10, 48)
    feed(eng, 1, shared + prompt(11, 8))
    # sequence 1 (at position 56) has handed blocks 0..2 back: the cache
    # keeps them; it still shares 3..5 with the cache
    first = list(range(6))
    assert sm.get_sequence(1).kv_blocks[:6] == [-1, -1, -1, 3, 4, 5]
    assert [sm.allocator.ref_count(b) for b in first] == [1] * 3 + [2] * 3
    assert sm.match_prefix(2, shared + prompt(12, 40)) == 48
    assert [sm.allocator.ref_count(b) for b in first] == [2] * 3 + [3] * 3
    feed(eng, 2, prompt(12, 40))
    # sequence 2 is at position 88: the blocks before 57 // 8 went back,
    # and those it shared lost its reference, no more
    assert sm.get_sequence(2).released == [7]
    assert [sm.allocator.ref_count(b) for b in first] == [1] * 3 + [2] * 3
    got = np.asarray(eng.put([2], [[7]])[0])
    want = feed(engine(dense), 3, shared + prompt(12, 40) + [7])
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    eng.flush(1)
    eng.flush(2)
    assert sm.available_blocks == 64


# ------------------------------------------------------------- the kernels

@pytest.mark.parametrize("chunk, group, want", [
    (1024, 6, 256), (1024, 8, 256), (1024, 4, 512), (256, 4, 256),
    (1024, 2, 1024), (1, 6, 1), (768, 6, 256), (64, 48, 32)])
def test_a_long_chunk_of_a_wide_group_is_cut_in_pieces_that_divide_it(
        chunk, group, want):
    from deepspeed_tpu.ops import paged_attention as pa

    tile = pa._chunk_tile(chunk, group)
    assert tile == want and chunk % tile == 0
    assert tile * group <= pa.MAX_QUERY_ROWS or tile == 1


@pytest.mark.parametrize("k, n, want", [
    (2048, 512, (2048, 512)), (512, 2048, (512, 2048)),
    (3072, 3072, (1536, 1536)), (32, 24, (32, 24)),
    (4096, 14336, (1024, 2048)), (3072, 12288, (1536, 1536))])
def test_the_grouped_matmuls_weight_tile_fits_the_kernels_memory(k, n, want):
    from deepspeed_tpu.moe import grouped

    tk, tn = grouped.gmm_tiles(k, n)
    assert (tk, tn) == want
    assert k % tk == 0 and n % tn == 0
    assert 2 * tk * tn * 2 <= grouped.GMM_WEIGHT_TILE_BYTES or (tk, tn) \
        == (min(k, 2048), min(n, 2048))


def test_six_query_heads_a_kv_head_through_the_kernel(monkeypatch):
    """The Pallas kernel (interpreted) at a group of 6 and a chunk cut in
    pieces, window and whole context, against the XLA formulation; the
    table entries behind the window are -1 and are never read."""
    from deepspeed_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "MAX_QUERY_ROWS", 96)
    rng = np.random.default_rng(0)
    N, C, H, KH, D, bs, NB, MB = 2, 32, 12, 2, 16, 8, 40, 16
    q = jnp.asarray(rng.normal(size=(N, C, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, NB, KH, bs, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, NB, KH, bs, D)), jnp.float32)
    start = jnp.asarray([70, 9], jnp.int32)
    n_tok = jnp.asarray([32, 20], jnp.int32)
    tables = rng.permutation(NB)[:N * MB].reshape(N, MB).astype(np.int32)
    window = 24
    behind = np.array(tables)
    behind[0, :(70 - window + 1) // bs] = -1
    assert pa._chunk_tile(C, H // KH) == 16
    for w, tbl in ((0, tables), (window, tables), (window, behind)):
        got = pa.paged_attention(q, k, v, jnp.asarray(tbl), start, n_tok,
                                 window=w, layer=0)
        want = pa.paged_attention_xla(q, k, v, jnp.asarray(tables), start,
                                      n_tok, window=w, layer=0)
        for row, n in enumerate((32, 20)):
            assert np.allclose(got[row, :n], want[row, :n], atol=2e-5), w


# -------------------------------------------------------------- the router

@pytest.mark.parametrize("held", [None, (4, 4)], ids=["all", "a-share"])
def test_sigmoid_scores_a_selection_bias_and_a_scale(held):
    """top-k over score + bias, the weights from the unbiased scores,
    normalised, times the scale — against the sum written out."""
    from deepspeed_tpu.moe.grouped import dropless_moe_mlp

    rng = np.random.default_rng(3)
    N, H, M, E, k = 40, 16, 12, 8, 3
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(N, E)), jnp.float32)
    bias = jnp.asarray(0.5 * rng.normal(size=(E,)), jnp.float32)
    lo, n = held or (0, E)
    w_in, w_gate = (jnp.asarray(rng.normal(size=(E, H, M)), jnp.float32)
                    for _ in range(2))
    w_out = jnp.asarray(rng.normal(size=(E, M, H)), jnp.float32)
    got, _ = dropless_moe_mlp(
        x, logits, w_in[lo:lo + n], w_out[lo:lo + n], w_gate[lo:lo + n],
        activation="silu", top_k=k, renormalize=True, held=held,
        score_func="sigmoid", select_bias=bias, route_scale=2.448)
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :k]
    want = np.zeros((N, H), np.float32)
    for t in range(N):
        total = s[t, chosen[t]].sum() + 1e-20
        for e in chosen[t]:
            if lo <= e < lo + n:
                y = (jax.nn.silu(x[t] @ w_gate[e]) * (x[t] @ w_in[e])) @ w_out[e]
                want[t] += 2.448 * s[t, e] / total * np.asarray(y)
    assert np.allclose(got, want, rtol=2e-4, atol=2e-4)
    # the bias chose, and did not weigh: without it other experts are met
    plain, _ = dropless_moe_mlp(
        x, logits, w_in[lo:lo + n], w_out[lo:lo + n], w_gate[lo:lo + n],
        activation="silu", top_k=k, renormalize=True, held=held,
        score_func="sigmoid", route_scale=2.448)
    assert not np.allclose(got, plain, atol=1e-3)


def test_the_config_says_what_a_hybrid_block_may_be():
    ok = dict(ARCH, dtype=jnp.float32)
    cfg = TransformerConfig(**ok)
    assert cfg.kv_groups() == ((0, 2), (WINDOW, 7))
    assert (cfg.num_periods, cfg.num_attn_layers, cfg.num_sparse_layers,
            cfg.num_linear_layers) == (2, 9, 8, 0)
    assert TransformerConfig(num_layers=4, sliding_window=8).kv_groups() \
        == ((8, 4),)
    # layers that differ in window inside one stacked pool: one group,
    # nothing released
    assert TransformerConfig(
        num_layers=4, sliding_window=(None, 8, None, 8)).kv_groups() \
        == ((0, 4),)
    for wrong in (dict(sliding_window=None), dict(num_layers=8),
                  dict(lead_layers=["dense"]),
                  dict(layer_pattern=["full"], lead_layers=[],
                       num_layers=2)):
        with pytest.raises(ValueError):
            TransformerConfig(**dict(ok, **wrong))
    with pytest.raises(ValueError, match="lead_layers"):
        TransformerConfig(lead_layers=("full",))


def test_the_tables_as_an_array_follow_the_lists(trinity, dense):
    """What a forward's staging copies from (``table_rows``) is the lists,
    after allocation, a release behind the window, a rollback and the
    blocks that grow back."""
    eng = engine(trinity)
    sm = eng.state_manager
    tokens = prompt(9, 90)
    for at in range(0, 90, 24):
        eng.put([5], [tokens[at:at + 24]])
        seq = sm.get_sequence(5)
        assert sm.table_rows(seq).tolist() == seq.tables
    assert seq.released[1] > 0 and -1 in sm.table_rows(seq)[1]
    assert sm.table_rows(seq).base is seq.rows      # a view, nothing copied
    eng.flush(5)
    # one group, inside its window: a rollback, then other blocks grow back
    eng = engine(dense, kv_blocks=64)
    sm = eng.state_manager
    feed(eng, 6, prompt(6, 28))
    seq = sm.get_sequence(6)
    before = sm.table_rows(seq).tolist()
    eng.put([9], [prompt(1, 8)])        # another sequence takes the next block
    assert eng.trim_sequence(6, 10) > 0
    eng.put([6], [prompt(2, 12)])
    assert sm.table_rows(seq).tolist() == [seq.kv_blocks] != before
    assert eng.batch.block_tables[0, :len(seq.kv_blocks)].tolist() \
        == seq.kv_blocks
