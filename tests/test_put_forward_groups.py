"""Which rows of a put run together (``InferenceEngineV2._forward_groups``):
the rule as a table with the programs it reaches, a merged put against the
same rows run apart (logits, draws, pool), the put's record, and that every
shape a put can reach is one ``forward_shapes`` names and one the
benchmark's warm-up runs. Tiny float32 models on the CPU; the times behind
the rule are the chip's (``PERF.md``, PR 33 and PR 41)."""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.testing import share_forward
from deepspeed_tpu.models.transformer import (
    TINY_TEST, CausalLM, TransformerConfig)

DENSE = dataclasses.replace(TINY_TEST, max_seq_len=1024, dtype=jnp.float32)
HYBRID = TransformerConfig(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=4,
    num_heads=4, num_kv_heads=2, head_size=16, max_seq_len=256,
    norm="rmsnorm", norm_zero_centered=True, activation="silu",
    position="rope", rope_pct=0.25, tie_embeddings=False, dtype=jnp.float32,
    layer_pattern=("linear", "linear", "linear", "full"),
    attn_output_gate=True, qk_norm=True,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel=4,
    moe_num_experts=8, moe_top_k=2, moe_dropless=True, moe_norm_topk=True,
    moe_held_experts=(2, 4), moe_intermediate_size=16,
    moe_shared_intermediate_size=16)
#: the serving cells' geometry (the engine's defaults): 32 sequences,
#: 256-token chunks, 768 tokens a step
CELLS = dict(max_ragged_sequence_count=32, max_chunk_tokens=256,
             max_ragged_batch_size=768, kv_blocks=256, kv_block_size=16)
SMALL = dict(max_ragged_sequence_count=8, max_chunk_tokens=128,
             max_ragged_batch_size=512, kv_blocks=128, kv_block_size=16)


@pytest.fixture(scope="module")
def dense():
    model = CausalLM(DENSE)
    return model, model.init(jax.random.PRNGKey(0))


def build(model_and_params, **sizing):
    model, params = model_and_params
    return InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(**sizing))


@pytest.fixture(scope="module")
def cells_engine(dense):
    return build(dense, **CELLS)


@pytest.fixture(scope="module")
def hybrid_engine():
    model = CausalLM(HYBRID)
    return build((model, model.init(jax.random.PRNGKey(0))),
                 max_ragged_sequence_count=4, max_chunk_tokens=16,
                 max_ragged_batch_size=64, kv_blocks=64, kv_block_size=8)


def stub_forward(engine, seen):
    """In place of the paged forward (and the verifying one): notes the
    tokens' shape, returns zeros a row of the batch."""
    vocab = engine.model.cfg.vocab_size

    def forward(params, cache, tokens, start_pos, *rest, verify_width=0):
        seen.append(tuple(tokens.shape))
        shape = (len(start_pos),) + (verify_width,) * bool(verify_width)
        return (jnp.zeros(shape + (vocab,), jnp.float32), cache,
                engine.next_ids)

    engine.paged.forward = engine.paged.forward_verify = forward


# ------------------------------------------------------------------ the rule

ALL = "everyone"


@pytest.mark.parametrize("widths,verify_width,groups,programs", [
    # one chunk beside 31 decodes: one merged forward, the wide row first
    ([256] + [1] * 31, 0, ALL, [(1, 288)]),
    ([1, 256, 1], 0, [[1, 0, 2]], [(1, 260)]),
    ([256, 1], 0, ALL, [(1, 258)]),
    # a chunk part is no narrower than the free positions
    ([16] + [1] * 31, 0, ALL, [(1, 160)]),
    ([5] + [1] * 31, 0, ALL, [(1, 160)]),
    # the first wide row merges, the others run on their own
    ([1, 256, 1, 100, 1], 0, [[1, 0, 2, 4], [3]], [(1, 260), (1, 128)]),
    ([256, 200], 0, [[0], [1]], [(1, 256), (1, 256)]),
    ([256, 256, 256], 0, [[0], [1], [2]], [(1, 256)] * 3),
    # 128 bucket positions at most: whole and padded, whatever rows fill it
    ([4] + [1] * 31, 0, ALL, [(32, 4)]),
    ([30, 9, 1, 1], 0, ALL, [(4, 32)]),
    # nothing to part: one wide row alone, one-token rows alone
    ([256], 0, ALL, [(1, 256)]),
    ([1] * 32, 0, ALL, [(32, 1)]),
    # a put that verifies drafts stays whole in its padded bucket
    ([256] + [1] * 31, 4, ALL, [(32, 256)]),
])
def test_a_dense_put_merges_its_first_wide_row_with_its_one_token_rows(
        dense, widths, verify_width, groups, programs):
    eng = build(dense, **CELLS)
    want = [list(range(len(widths)))] if groups == ALL else groups
    assert eng._forward_groups(widths, verify_width) == want
    seen = []
    stub_forward(eng, seen)
    logits = eng.put(list(range(len(widths))), [[0] * n for n in widths],
                     verify_width=verify_width)
    assert seen == programs
    assert np.asarray(logits).shape[0] == len(widths)
    assert eng.put_totals["forwards_merged"] == \
        sum(shape in eng._merged_rows for shape in seen)
    assert eng.put_totals["positions_computed"] == \
        sum(s * c for s, c in seen)


@pytest.mark.parametrize("widths,groups", [
    ([16, 1], [[1], [0]]), ([5, 1, 1, 7], [[1, 2], [0], [3]]),
    ([16, 16], [[0], [1]]), ([2, 1], [[1], [0]]),
    ([16], ALL), ([1, 1, 1], ALL),
])
def test_a_hybrid_put_parts_at_every_size_as_before(hybrid_engine, widths,
                                                    groups):
    want = [list(range(len(widths)))] if groups == ALL else groups
    assert hybrid_engine._forward_groups(widths) == want
    assert hybrid_engine.forward_shapes() == [(1, 1), (1, 16), (2, 1), (4, 1)]


def test_forward_shapes_are_the_rows_the_columns_the_free_and_the_merged(
        cells_engine):
    shapes = cells_engine.forward_shapes()
    assert len(shapes) == len(set(shapes)) == 44          # no more than before
    assert {(1, c) for c in (1, 2, 4, 8, 16, 32, 64, 128, 256)} <= set(shapes)
    assert {(s, 1) for s in (1, 2, 4, 8, 16, 32)} <= set(shapes)
    padded = {(s, c) for s, c in shapes if s > 1 and c > 1}
    assert len(padded) == 20 and all(s * c <= 128 for s, c in padded)
    assert {(2, 64), (4, 32), (32, 4)} <= padded
    merged = {(1, c + s): s for s in (2, 4, 8, 16, 32) for c in (128, 256)}
    assert cells_engine._merged_rows == merged
    assert set(shapes) == {(1, c) for c in (1, 2, 4, 8, 16, 32, 64, 128, 256)
                           } | {(s, 1) for s in (2, 4, 8, 16, 32)} \
        | padded | set(merged)


def test_a_sum_that_names_two_programs_is_left_out(dense):
    """The tokens' shape names the program: with 128 rows a batch,
    ``[1, 128 + 128]`` would be ``[1, 256]``'s twin -- that bucket's puts
    run apart."""
    eng = build(dense, max_ragged_sequence_count=128, max_chunk_tokens=256,
                max_ragged_batch_size=768, kv_blocks=256, kv_block_size=16)
    shapes = eng.forward_shapes()
    assert len(shapes) == len(set(shapes))
    assert (1, 256) not in eng._merged_rows
    assert eng._merged_rows[1, 256 + 128] == 128
    assert eng._forward_groups([100] + [1] * 99) == \
        [list(range(1, 100)), [0]]
    assert eng._forward_groups([200] + [1] * 99) == [list(range(100))]


# ------------------------------------------------- merged against apart

def tokens(seed, n, vocab=DENSE.vocab_size):
    return np.random.default_rng(seed).integers(0, vocab, size=n).tolist()


def run_puts(eng, puts):
    return [np.asarray(eng.put(uids, rows)) for uids, rows in puts]


def apart(eng):
    """The same engine with no padded and no merged forward: every wide
    row ``[1, C]``, the one-token rows ``[S, 1]``."""
    eng._free_positions, eng._merged_rows = 0, {}
    return eng


def same_sequences(a, b, uids, atol=1e-5):
    for uid in uids:
        x = a.state_manager.get_sequence(uid)
        y = b.state_manager.get_sequence(uid)
        assert x.seen_tokens == y.seen_tokens
        assert (x.chain_hash, x.hashed_blocks, x.pending_tokens) == \
            (y.chain_hash, y.hashed_blocks, y.pending_tokens)
        assert len(x.kv_blocks) == len(y.kv_blocks)
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(a.state_manager.forward_cache[name]
                           [:, np.asarray(x.kv_blocks)], np.float32),
                np.asarray(b.state_manager.forward_cache[name]
                           [:, np.asarray(y.kv_blocks)], np.float32),
                atol=atol, rtol=0)


def test_a_merged_put_is_the_parted_put_row_for_row(dense):
    """The same puts through an engine that merges and one that runs every
    wide row and the one-token rows apart: logits in the put's row order,
    the blocks the rows wrote, the tokens seen and the prefix cache's
    chain agree."""
    puts = [([u], [tokens(u, n)]) for u, n in ((1, 40), (2, 9), (3, 70),
                                               (4, 33))]
    # two chunk rows among four decodes: the first merges, [1, 128 + 8],
    # the second runs [1, 128]
    puts.append(([5, 1, 2, 6, 3, 4],
                 [tokens(5, 100), [7], [8], tokens(6, 70), [9], [10]]))
    puts.append(([6, 5, 1, 2, 3],                           # [1, 128 + 8]
                 [[11], tokens(15, 128), [12], [13], [14]]))
    puts.append(([4, 5], [tokens(16, 128), [15]]))          # [1, 128 + 2]
    puts.append(([1, 2, 3, 4, 5, 6], [[t] for t in range(20, 26)]))
    sizing = dict(SMALL, enable_prefix_cache=True)
    merged = build(dense, **sizing)
    parted = apart(build(dense, **sizing))
    got, want = run_puts(merged, puts), run_puts(parted, puts)
    assert merged.put_totals["puts_split"] == 1
    assert parted.put_totals["puts_split"] == 3
    assert merged.put_totals["forwards"] == 4 + 2 + 1 + 1 + 1
    assert merged.put_totals["forwards_merged"] == 3
    assert parted.put_totals["forwards"] == 4 + 3 + 2 + 2 + 1
    assert parted.put_totals["forwards_merged"] == 0
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=0)
    same_sequences(merged, parted, range(1, 7))
    assert merged.prefix_stats() == parted.prefix_stats()
    assert merged.state_manager.available_blocks == \
        parted.state_manager.available_blocks


VARIANTS = {
    "gqa-rope": dict(num_heads=4, num_kv_heads=2, position="rope"),
    "mha-partial-rotary": dict(num_heads=4, num_kv_heads=4, position="rope",
                               rope_pct=0.25, parallel_residual=True,
                               norm="layernorm", use_bias=True),
    "mha-learned": dict(num_heads=4, num_kv_heads=4, position="learned",
                        norm="layernorm", use_bias=True),
    "gqa-alibi-window": dict(num_heads=4, num_kv_heads=2, position="alibi",
                             sliding_window=48),
}


_VARIANT_MODELS, _FORWARDS = {}, {}


@pytest.mark.parametrize("ones,width", [(1, 2), (2, 40), (3, 256), (31, 40)])
@pytest.mark.parametrize("variant", VARIANTS.values(), ids=VARIANTS.keys())
def test_a_chunk_row_beside_one_token_rows_is_the_same_rows_run_apart(
        variant, ones, width):
    """One chunk row of ``width`` tokens beside ``ones`` one-token rows --
    one of them a ``DEVICE_TOKEN`` row, the chunk row in the middle of the
    put, every row at a context of its own -- merged ``[1, C + S]`` (or,
    at 128 bucket positions and under, padded) against the same rows run
    apart: logits, the greedy draws, the next step's logits from the
    tokens that stayed on the device, and every block the rows wrote."""
    from deepspeed_tpu.inference.v2.engine_v2 import DEVICE_TOKEN

    cfg = TransformerConfig(
        vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2,
        max_seq_len=512, attention_impl="reference", dtype=jnp.float32,
        **variant)
    # a variant's model is built once, and its engines share one jitted
    # forward (``testing.share_forward``): a case compiles the buckets no
    # earlier case of its variant ran
    key = tuple(sorted(variant.items()))
    if key not in _VARIANT_MODELS:
        model = CausalLM(cfg)
        _VARIANT_MODELS[key] = model, model.init(jax.random.PRNGKey(1))
    both = _VARIANT_MODELS[key]
    merged, parted = (share_forward(build(both, **CELLS), _FORWARDS, key)
                      for _ in range(2))
    engines = merged, apart(parted)
    uids = list(range(1, ones + 2))
    at = ones // 2                      # where the chunk row sits in the put
    out = []
    for eng in engines:
        # contexts of 3 + i tokens; the chunk row's sequence has 70
        first = eng.put(uids, [tokens(u, 70 if i == at else 3 + i, 97)
                               for i, u in enumerate(uids)])
        rows = [[int(t)] for t in first.next_tokens()]
        rows[at] = tokens(99, width, 97)
        rows[-1] = [DEVICE_TOKEN]
        put = eng.put(uids, rows)
        after = eng.put(uids, [[DEVICE_TOKEN]] * len(uids))
        out.append((np.asarray(put), put.next_tokens(), np.asarray(after)))
    merged, parted = engines
    program = merged._merged_shape(ones + 1, width)
    assert merged.put_totals["forwards_merged"] == (program is not None)
    assert parted.put_totals["forwards_merged"] == 0
    assert merged.put_totals["forwards"] == parted.put_totals["forwards"] - 1
    for got, want in zip(*out):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    same_sequences(merged, parted, uids, atol=2e-5)


@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
def test_the_merged_forward_calls_the_kernel_once_a_part(kv_heads,
                                                         monkeypatch):
    """The same with the paged kernel itself (Pallas, interpreted) where
    the chip runs it: once over the chunk as ``[1, C]``, once over the
    rows as ``[S, 1]``, against the XLA formulation run apart."""
    from deepspeed_tpu.ops import paged_attention as pa

    cfg = TransformerConfig(
        vocab_size=97, hidden_size=64, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=kv_heads, max_seq_len=256,
        position="rope", dtype=jnp.float32)
    model = CausalLM(cfg)
    both = model, model.init(jax.random.PRNGKey(2))
    sizing = dict(max_ragged_sequence_count=4, max_chunk_tokens=64,
                  max_ragged_batch_size=128, kv_blocks=32, kv_block_size=16)
    parted = apart(build(both, **sizing))
    calls = []
    real = pa._paged_pallas
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "_paged_pallas", lambda q, *a, **kw: (
        calls.append(tuple(q.shape[:2])), real(q, *a, **kw))[1])
    merged = build(both, **sizing)
    puts = [([1, 2, 3], [tokens(1, 20, 97), tokens(2, 3, 97),
                         tokens(3, 33, 97)]),
            ([2, 1, 3], [[5], tokens(4, 50, 97), [6]])]
    del calls[:]
    got = run_puts(merged, puts)[-1]
    assert merged.put_totals["forwards_merged"] == 1
    assert calls[-2:] == [(1, 64), (4, 1)]      # traced once: one layer scan
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", False)
    np.testing.assert_allclose(got, run_puts(parted, puts)[-1], atol=2e-5,
                               rtol=0)
    same_sequences(merged, parted, (1, 2, 3), atol=2e-5)


SERVED = {
    "kv-int8": dict(kv_quant_enabled=True, kv_quant_dtype="int8"),
    "kv-fp8": dict(kv_quant_enabled=True, kv_quant_dtype="fp8_e4m3"),
    "weights-int8": dict(weight_quant_enabled=True, weight_quant_block=16),
    "tensor-2": dict(tensor=2),
}


@pytest.mark.parametrize("served", SERVED.values(), ids=SERVED.keys())
def test_quantized_pools_quantized_weights_and_a_tensor_axis_merge_too(
        served, dense):
    """The engines whose forward differs underneath -- int8 / fp8 pools
    (``quantized_block_write``, the kernel's scale operands), a quantized
    weight tree and a 2-way ``tensor`` mesh (three q / k / v leaves, the
    attention under ``shard_map``) -- run the same merged program: a chunk
    row beside decodes, and a second chunk row behind it, against the same
    rows run apart."""
    from deepspeed_tpu.parallel import topology as topo

    served = dict(served)
    mesh = None
    if served.pop("tensor", 0):
        topo.reset_topology()
        mesh = topo.MeshTopology.build(data=4, tensor=2)
    model, params = dense
    # the two engines are one model at one sizing: one jitted forward, so
    # that the buckets both reach (the single rows' chunks) compile once
    shared = {}
    engines = [share_forward(InferenceEngineV2(
        model, params=params, mesh=mesh,
        config=RaggedInferenceEngineConfig(**SMALL, **served)),
        shared, "served") for _ in range(2)]
    merged, parted = engines[0], apart(engines[1])
    assert merged.qkv_fused == (not mesh and "weight_quant_enabled"
                                not in served)
    puts = [([u], [tokens(u, n)]) for u, n in ((1, 40), (2, 9), (3, 21))]
    puts.append(([1, 4, 2, 3, 5], [[7], tokens(4, 100), [8], [9],
                                   tokens(5, 60)]))
    puts.append(([5, 1, 2, 3, 4], [tokens(6, 30), [1], [2], [3], [4]]))
    got, want = run_puts(merged, puts), run_puts(parted, puts)
    assert merged.put_totals["forwards_merged"] == 2
    assert merged.put_totals["puts_split"] == 1
    quantized_pool = "kv_quant_enabled" in served
    for g, w in zip(got, want):
        # a quantized block is written whole at its scale: the same rows
        # in another order of forwards round the same way
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=0)
    same_sequences(merged, parted, range(1, 6),
                   atol=0 if quantized_pool else 2e-5)
    if mesh is not None:
        topo.reset_topology()


def test_a_split_puts_record_sums_its_forwards(dense):
    eng = build(dense, **SMALL)
    eng.put([1], [tokens(1, 20)])
    eng.put([2], [tokens(2, 5)])
    whole = dict(eng.last_put)
    assert "forwards" not in whole          # one forward: the record as it was
    assert set(whole) == {"bucket_seqs", "bucket_chunk", "rows",
                          "valid_tokens", "kv_read_tokens", "qk_pairs",
                          "kv_blocks_live", "kv_table_slots", "free_blocks"}
    eng.put([5], [tokens(5, 3)])
    before = dict(eng.put_totals)
    eng.put([3, 1, 2, 4, 5], [tokens(3, 100), [5], [6], tokens(4, 30), [7]])
    put = eng.last_put
    # [1, 128 + 4] + [1, 32]: the last forward's bucket, the sums
    assert (put["bucket_seqs"], put["bucket_chunk"]) == (1, 32)
    assert put["forwards"] == 2 and put["rows"] == 5
    assert put["valid_tokens"] == 100 + 1 + 1 + 30 + 1
    assert put["kv_read_tokens"] == 100 + 21 + 6 + 30 + 4
    assert put["qk_pairs"] == 100 * 101 // 2 + 21 + 6 + 30 * 31 // 2 + 4
    assert put["kv_blocks_live"] == 7 + 2 + 1 + 2 + 1
    assert put["kv_table_slots"] == (4 + 1) * eng.batch.max_blocks_per_seq
    assert put["free_blocks"] == eng.state_manager.available_blocks
    after = eng.put_totals
    assert after["puts_split"] == before["puts_split"] + 1 == 1
    assert after["forwards"] == before["forwards"] + 2
    assert after["forwards_merged"] == before["forwards_merged"] + 1 == 1
    assert after["positions_computed"] == \
        before["positions_computed"] + 132 + 32
    assert after["tokens_valid"] == before["tokens_valid"] + 133
    # a merged forward alone is the put: its record says its tokens' shape
    eng.put([1, 2, 6], [[8], [9], tokens(6, 90)])
    put = eng.last_put
    assert "forwards" not in put and set(put) == set(whole)
    assert (put["bucket_seqs"], put["bucket_chunk"], put["rows"],
            put["valid_tokens"]) == (1, 128 + 4, 3, 92)
    assert put["kv_table_slots"] == 4 * eng.batch.max_blocks_per_seq


# --------------------------------------------- what a put reaches is warmed

def random_puts(rng, n_puts, engine):
    """Puts as the scheduler packs them: up to 32 rows, a chunk of at most
    256 tokens each, 768 tokens in all; a few chunk rows, the rest one
    token. Counts and widths are drawn evenly over their powers of two,
    so that the small buckets come up as often as the large."""
    cfg = engine.config
    for at in range(n_puts):
        rows = min(int(2 ** rng.uniform(0, 5.2)),
                   cfg.max_ragged_sequence_count)
        wide = int(rng.integers(0, min(rows, 4) + 1))
        widths = [min(int(2 ** rng.uniform(1, 8.2)), cfg.max_chunk_tokens)
                  for _ in range(wide)] + [1] * (rows - wide)
        while sum(widths) > cfg.max_ragged_batch_size:
            widths[widths.index(max(widths))] //= 2
        rng.shuffle(widths)
        uids = [1000 * at + i for i in range(rows)]
        yield uids, [[0] * n for n in widths]


def test_every_shape_a_put_reaches_is_named_and_warmed(dense):
    """``forward_shapes`` is exactly what puts can ask of the forward, and
    the benchmark's warm-up — one wide row beside one-token rows at every
    bucket, then every row count — runs every one of them: the rule reads
    buckets alone, so no mix of rows finds a program the warm-up did not.
    Held bucket by bucket for every put of at most 32 rows and 768 tokens
    with one wide row, and over random puts with several. A stub stands
    for the forward."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark import serve_runner

    eng = build(dense, **CELLS)
    warmed, reached = [], []
    stub_forward(eng, warmed)
    calls = serve_runner.warm_up(eng)
    assert calls == 54 + 32
    assert set(warmed) == set(eng.forward_shapes())
    assert len(set(warmed)) <= 44           # what a dense engine held before
    assert eng.state_manager.available_blocks == CELLS["kv_blocks"]

    # every bucket a window's put can have: its programs, from the rule
    cfg = eng.config
    for rows in range(1, cfg.max_ragged_sequence_count + 1):
        for width in range(1, cfg.max_chunk_tokens + 1):
            if width + rows - 1 > cfg.max_ragged_batch_size:
                continue
            whole = eng.batch.bucket(rows, width)
            merged = eng._merged_shape(rows, width) if width > 1 < rows \
                else None
            assert (merged or whole) in warmed
            assert merged is None or whole[0] * whole[1] > 128
            groups = eng._forward_groups([width] + [1] * (rows - 1))
            assert groups == [list(range(rows))]    # one forward either way

    stub_forward(eng, reached)
    rng = np.random.default_rng(33)
    for uids, rows in random_puts(rng, 600, eng):
        logits = np.asarray(eng.put(uids, rows))
        assert logits.shape == (len(uids), DENSE.vocab_size)
        for u in uids:
            eng.flush(u)
    assert set(reached) <= set(warmed)
    # and the walk was wide enough to mean something
    assert len(set(reached)) >= 36 and eng.put_totals["puts_split"] > 100
    assert eng.put_totals["forwards_merged"] > 100


@pytest.fixture(scope="module")
def compile_watch():
    """JAX's own count of backend compiles, as ``benchmark.serve_runner``
    counts them in a window (listeners cannot be taken back: one a
    module)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark import device

    return device.CompileWatch()


@pytest.mark.parametrize("kind", ["dense", "hybrid"])
def test_a_scheduler_run_after_the_warm_up_compiles_nothing(
        kind, dense, compile_watch, monkeypatch):
    """The warm-up contract, with the real forward: after ``engine.put`` +
    ``np.asarray`` over every bucket and every row count (the benchmark's
    ``warm_up``, to the letter), a scheduler's run over mixed traffic —
    prompts of several chunks, rows joining and leaving, rows whose token
    is on the device, merged and parted puts, the ids read back — compiles
    nothing:
    one forward program a shape, whichever way its tokens arrive, and no
    eager operation on the step's path."""
    from benchmark import serve_runner
    from deepspeed_tpu.inference.v2.scheduler import (
        ContinuousBatchingScheduler)

    if kind == "dense":
        # as if 32 positions were free, not 128: [4, 16] merges, [1, 16 + 4]
        monkeypatch.setattr(engine_v2, "_FREE_POSITIONS", 32)
        eng = build(dense, max_ragged_sequence_count=4, max_chunk_tokens=16,
                    max_ragged_batch_size=48, kv_blocks=64, kv_block_size=8)
        assert eng._merged_rows == {(1, 20): 4}
    else:
        model = CausalLM(HYBRID)
        eng = build((model, model.init(jax.random.PRNGKey(0))),
                    max_ragged_sequence_count=4, max_chunk_tokens=16,
                    max_ragged_batch_size=48, kv_blocks=64, kv_block_size=8)
    before = compile_watch.count
    serve_runner.warm_up(eng)
    assert compile_watch.count - before >= len(eng.forward_shapes())
    before = compile_watch.count
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(5)
    arrivals = {0: [(40, 9), (3, 14)], 3: [(17, 5)], 4: [(1, 6), (33, 3)],
                11: [(16, 1), (9, 12)], 15: [(5, 30)]}
    uid = 0
    for step in range(200):
        for n, new in arrivals.get(step, ()):
            sched.submit(uid, rng.integers(0, 128, size=n).tolist(),
                         max_new_tokens=new)
            uid += 1
        if step > 15 and not sched.has_work:
            break
        sched.step()
    assert len(sched.finished) == uid == 8 and not sched.has_work
    assert sched.step_stats()["steps_overlapped"] > 20
    assert eng.put_totals["puts_split"] > 0
    assert (eng.put_totals.get("forwards_merged", 0) > 0) == (kind == "dense")
    assert compile_watch.count == before
    assert eng.state_manager.available_blocks == 64


# --------------------------------------- the record on its way to an operator

def test_a_split_put_reaches_the_spans_and_the_registry(dense):
    """Served: four short requests decoding and two long prompts beside
    them. The step that carries a chunk of each is an [8, 128] bucket: the
    first chunk row runs merged with the decodes, the second as a forward
    of its own. Its ``forward`` and ``stage`` spans say how many, every
    other ``stage`` keeps the keys it had, and the replica publishes
    ``puts_split`` and ``forwards_merged`` beside ``forwards``."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    eng = build(dense, **SMALL)
    fe = ServingFrontend([eng], ServingConfig(
        max_queue_depth=8, telemetry={"enabled": True}))
    try:
        handles = [fe.submit(tokens(u, 3), max_new_tokens=60)
                   for u in range(4)]
        deadline = time.monotonic() + 60    # the four are decoding
        while time.monotonic() < deadline and not eng.put_totals["forwards"]:
            time.sleep(0.002)
        handles += [fe.submit(tokens(u, 100), max_new_tokens=4)
                    for u in (9, 10)]
        assert fe.wait_all(handles, timeout=300)
        # the worker publishes after its last step and then idles
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not any(
                s["name"] == "idle_wait" and s["attrs"].get("open")
                for s in fe.tracer.export()):
            time.sleep(0.002)
        spans = fe.tracer.export()
        snap = fe.metrics_snapshot()
    finally:
        fe.shutdown(drain=False, timeout=5)
    forwards = [s["attrs"] for s in spans if s["name"] == "forward"]
    stages = [s["attrs"] for s in spans if s["name"] == "stage"]
    assert len(forwards) == len(stages) >= 60
    split = [a for a in stages if "forwards" in a]
    assert split and all(a["forwards"] >= 2 for a in split)
    assert [a["forwards"] for a in forwards if "forwards" in a] == \
        [a["forwards"] for a in split]
    whole = {"bucket_seqs", "bucket_chunk", "rows", "valid_tokens",
             "kv_read_tokens", "qk_pairs", "free_blocks"}
    assert all(set(a) == whole for a in stages if "forwards" not in a)
    assert all(set(a) == whole | {"forwards"} for a in split)
    assert snap["puts_split"] == eng.put_totals["puts_split"] == len(split)
    assert snap["forwards_merged"] == eng.put_totals["forwards_merged"] > 0
    assert snap["forwards"] == eng.put_totals["forwards"] == \
        len(stages) + sum(a["forwards"] - 1 for a in split)
