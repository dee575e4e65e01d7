"""Which rows of a put run together (``InferenceEngineV2._forward_groups``):
the rule as a table, a split put against the same rows in one forward, the
put's record, and that every shape a put can reach is one
``forward_shapes`` names and one the benchmark's warm-up runs. Tiny float32
models on the CPU; the times that fixed the limit are the chip's
(``PERF.md``, PR 33)."""

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
    """In place of the paged forward: notes the shape, returns zeros."""
    vocab = engine.model.cfg.vocab_size

    def forward(params, cache, tokens, *rest):
        seen.append(tuple(tokens.shape))
        return (jnp.zeros((tokens.shape[0], vocab), jnp.float32), cache,
                engine.next_ids)

    engine.paged.forward = forward


# ------------------------------------------------------------------ the rule

ALL = "everyone"


@pytest.mark.parametrize("widths,verify_width,groups", [
    # one chunk beside 31 decodes, [32, 256]: the one-token rows first
    ([256] + [1] * 31, 0, [list(range(1, 32)), [0]]),
    # [2, 256] and [32, 16] hold 512 positions: at the limit, one forward
    ([256, 1], 0, ALL),
    ([256, 200], 0, ALL),
    ([16] + [1] * 31, 0, ALL),
    # one position more of width, or one row more: the next bucket
    ([17] + [1] * 31, 0, [list(range(1, 32)), [0]]),
    ([256, 1, 1], 0, [[1, 2], [0]]),
    # the bucket decides, not which rows fill it: all chunks, no decode
    ([256, 256, 256], 0, [[0], [1], [2]]),
    ([1, 256, 1, 100, 1], 0, [[0, 2, 4], [1], [3]]),
    # nothing to part: one wide row alone, one-token rows alone
    ([256], 0, ALL),
    ([1] * 32, 0, ALL),
    # a small bucket of drafts; and a put that verifies them stays whole
    ([3] + [1] * 31, 0, ALL),
    ([256] + [1] * 31, 4, ALL),
])
def test_a_dense_put_parts_past_two_weight_passes_of_positions(
        cells_engine, widths, verify_width, groups):
    assert engine_v2._JOINT_POSITIONS == 512
    want = [list(range(len(widths)))] if groups == ALL else groups
    assert cells_engine._forward_groups(widths, verify_width) == want


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


def test_forward_shapes_are_the_rows_the_columns_and_the_small_buckets(
        cells_engine):
    shapes = cells_engine.forward_shapes()
    assert len(shapes) == len(set(shapes)) == 44          # 54 before
    assert {(1, c) for c in (1, 2, 4, 8, 16, 32, 64, 128, 256)} <= set(shapes)
    assert {(s, 1) for s in (1, 2, 4, 8, 16, 32)} <= set(shapes)
    assert {(2, 256), (4, 128), (32, 16)} <= set(shapes)
    assert not {(4, 256), (32, 32), (32, 256)} & set(shapes)
    assert all(s == 1 or c == 1 or s * c <= 512 for s, c in shapes)


# ------------------------------------------------- split against one forward

def tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, DENSE.vocab_size, size=n).tolist()


def run_puts(eng, puts):
    return [np.asarray(eng.put(uids, rows)) for uids, rows in puts]


def test_a_split_put_is_the_joint_put_row_for_row(dense):
    """The same puts through an engine that parts them and one that does
    not (the limit lifted): logits, the blocks the rows wrote, the tokens
    seen and the prefix cache's chain agree."""
    puts = [([u], [tokens(u, n)]) for u, n in ((1, 40), (2, 9), (3, 70),
                                               (4, 33))]
    # [8, 128] = 1,024 positions: two chunk rows among four decodes
    puts.append(([5, 1, 2, 6, 3, 4],
                 [tokens(5, 100), [7], [8], tokens(6, 70), [9], [10]]))
    puts.append(([6, 5, 1, 2, 3],                               # [8, 128]
                 [[11], tokens(15, 128), [12], [13], [14]]))
    puts.append(([4, 5], [tokens(16, 128), [15]]))  # [2, 128]: left whole
    puts.append(([1, 2, 3, 4, 5, 6], [[t] for t in range(20, 26)]))
    sizing = dict(SMALL, enable_prefix_cache=True)
    split = build(dense, **sizing)
    joint = build(dense, **sizing)
    joint._joint_positions = 1 << 30
    got, want = run_puts(split, puts), run_puts(joint, puts)
    assert split.put_totals["puts_split"] == 2
    assert joint.put_totals["puts_split"] == 0
    assert split.put_totals["forwards"] == 4 + 3 + 2 + 1 + 1
    assert joint.put_totals["forwards"] == len(puts)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=0)
    for uid in range(1, 7):
        a = split.state_manager.get_sequence(uid)
        b = joint.state_manager.get_sequence(uid)
        assert a.seen_tokens == b.seen_tokens
        assert (a.chain_hash, a.hashed_blocks, a.pending_tokens) == \
            (b.chain_hash, b.hashed_blocks, b.pending_tokens)
        assert len(a.kv_blocks) == len(b.kv_blocks)
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(split.state_manager.forward_cache[name]
                           [:, np.asarray(a.kv_blocks)]),
                np.asarray(joint.state_manager.forward_cache[name]
                           [:, np.asarray(b.kv_blocks)]), atol=1e-5, rtol=0)
    assert split.prefix_stats() == joint.prefix_stats()
    assert split.state_manager.available_blocks == \
        joint.state_manager.available_blocks


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
    # [4, 1] + [1, 128] + [1, 32]: the last forward's bucket, the sums
    assert (put["bucket_seqs"], put["bucket_chunk"]) == (1, 32)
    assert put["forwards"] == 3 and put["rows"] == 5
    assert put["valid_tokens"] == 100 + 1 + 1 + 30 + 1
    assert put["kv_read_tokens"] == 100 + 21 + 6 + 30 + 4
    assert put["qk_pairs"] == 100 * 101 // 2 + 21 + 6 + 30 * 31 // 2 + 4
    assert put["kv_blocks_live"] == 7 + 2 + 1 + 2 + 1
    assert put["kv_table_slots"] == (4 + 1 + 1) * eng.batch.max_blocks_per_seq
    assert put["free_blocks"] == eng.state_manager.available_blocks
    after = eng.put_totals
    assert after["puts_split"] == before["puts_split"] + 1 == 1
    assert after["forwards"] == before["forwards"] + 3
    assert after["positions_computed"] == \
        before["positions_computed"] + 4 + 128 + 32
    assert after["tokens_valid"] == before["tokens_valid"] + 133


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
    the bucket alone, so no mix of rows finds a program the warm-up did
    not. A stub stands for the forward."""
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
    assert eng.state_manager.available_blocks == CELLS["kv_blocks"]

    stub_forward(eng, reached)
    rng = np.random.default_rng(33)
    for uids, rows in random_puts(rng, 600, eng):
        logits = np.asarray(eng.put(uids, rows))
        assert logits.shape == (len(uids), DENSE.vocab_size)
        for u in uids:
            eng.flush(u)
    assert set(reached) <= set(warmed)
    # and the walk was wide enough to mean something
    assert len(set(reached)) >= 40 and eng.put_totals["puts_split"] > 100


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
        kind, dense, compile_watch):
    """The warm-up contract, with the real forward: after ``engine.put`` +
    ``np.asarray`` over every bucket and every row count (the benchmark's
    ``warm_up``, to the letter), a scheduler's run over mixed traffic —
    prompts of several chunks, rows joining and leaving, rows whose token
    is on the device, parted puts, the ids read back — compiles nothing:
    one forward program a shape, whichever way its tokens arrive, and no
    eager operation on the step's path."""
    from benchmark import serve_runner
    from deepspeed_tpu.inference.v2.scheduler import (
        ContinuousBatchingScheduler)

    if kind == "dense":
        eng = build(dense, max_ragged_sequence_count=4, max_chunk_tokens=16,
                    max_ragged_batch_size=48, kv_blocks=64, kv_block_size=8)
        eng._joint_positions = 32       # so that [4, 16] parts
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
    assert compile_watch.count == before
    assert eng.state_manager.available_blocks == 64


# --------------------------------------- the record on its way to an operator

def test_a_split_put_reaches_the_spans_and_the_registry(dense):
    """Served: four short requests decoding and a long prompt beside them.
    The step that carries a chunk of it is an [8, 128] bucket and runs as
    forwards of its own: its ``forward`` and ``stage`` spans say how many,
    every other ``stage`` keeps the keys it had, and the replica publishes
    ``puts_split`` beside ``forwards``."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    eng = build(dense, **SMALL)
    fe = ServingFrontend([eng], ServingConfig(
        max_queue_depth=8, telemetry={"enabled": True}))
    try:
        handles = [fe.submit(tokens(u, 3), max_new_tokens=60)
                   for u in range(4)]
        handles.append(fe.submit(tokens(9, 100), max_new_tokens=4))
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
    assert snap["forwards"] == eng.put_totals["forwards"] == \
        len(stages) + sum(a["forwards"] - 1 for a in split)
