"""Deterministic serving load test on the CPU mesh (ISSUE 1 acceptance):
mixed priorities/deadlines through the full stack, an over-capacity burst
that sheds with Rejected (bounded queue), cancellation that returns KV
blocks, replica fault degradation, and registry-sourced telemetry (the
numbers ``ServingFrontend.metrics_snapshot`` hands an operator)."""

import time

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.serving import (Priority, Rejected, RequestState,
                                   ServingConfig, ServingFrontend)

VOCAB = 128


def tiny_engine(kv_blocks=64, max_seqs=4):
    cfg = TransformerConfig(vocab_size=VOCAB, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=2,
                            max_seq_len=128, norm="rmsnorm",
                            activation="silu", position="rope")
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=max_seqs,
        max_chunk_tokens=32, kv_blocks=kv_blocks, kv_block_size=8,
        max_tracked_sequences=16)
    return InferenceEngineV2(CausalLM(cfg), config=vcfg)


@pytest.fixture
def frontend():
    fe = ServingFrontend([tiny_engine()], ServingConfig(max_queue_depth=8))
    yield fe
    fe.shutdown(drain=False, timeout=5)


def prompts(n, rng, lo=8, hi=32):
    return [rng.integers(0, VOCAB, size=int(l)).tolist()
            for l in rng.integers(lo, hi, size=n)]


def test_requests_complete_and_stream(frontend):
    rng = np.random.default_rng(0)
    handles = [frontend.submit(p, max_new_tokens=6)
               for p in prompts(3, rng)]
    assert frontend.wait_all(handles, timeout=120)
    for h in handles:
        assert h.state == RequestState.FINISHED
        assert h.finish_reason == "length"
        toks = [ev.token for ev in h.drain()]
        assert len(toks) == 6
        assert all(0 <= t < VOCAB for t in toks)


def test_streaming_iterator_terminates(frontend):
    rng = np.random.default_rng(1)
    h = frontend.submit(prompts(1, rng)[0], max_new_tokens=5)
    seen = [ev.index for ev in h.stream(timeout=120)]
    assert seen == list(range(5))
    assert h.state == RequestState.FINISHED


def test_overcapacity_burst_sheds_and_admitted_complete(frontend):
    """The acceptance scenario: a burst far beyond queue+engine capacity
    is shed with Rejected("overloaded") — no unbounded queue growth — and
    every admitted request still completes."""
    rng = np.random.default_rng(2)
    handles, rejected = [], 0
    for p in prompts(40, rng):
        try:
            handles.append(frontend.submit(p, max_new_tokens=4))
        except Rejected as e:
            assert e.reason == "overloaded"
            rejected += 1
    assert rejected > 0, "burst was not over capacity"
    assert len(frontend.admission) <= frontend.config.max_queue_depth
    assert frontend.wait_all(handles, timeout=300)
    snap = frontend.metrics_snapshot()
    assert snap["requests_shed"] == rejected
    assert snap["requests_completed"] == len(handles)
    assert snap["shed_rate"] == pytest.approx(rejected / 40)
    # histograms actually populated by the load
    assert snap["ttft_s"]["count"] == len(handles)
    assert snap["ttft_s"]["p95"] >= snap["ttft_s"]["p50"] > 0
    assert snap["queue_wait_s"]["count"] >= len(handles)


def test_mixed_priorities_order_under_backlog():
    """Backlog beyond the replica's concurrency slots stays in the
    admission queue, where HIGH jumps ahead of already-queued LOW."""
    fe = ServingFrontend([tiny_engine()], ServingConfig(max_queue_depth=32))
    try:
        rng = np.random.default_rng(3)
        # more LOWs than the replica has slots (max_ragged_sequence_count
        # = 4): the excess queues, and HIGHs submitted later overtake it
        lows = [fe.submit(p, max_new_tokens=8, priority=Priority.LOW)
                for p in prompts(8, rng)]
        highs = [fe.submit(p, max_new_tokens=8, priority=Priority.HIGH)
                 for p in prompts(3, rng)]
        assert fe.wait_all(lows + highs, timeout=300)
        first_high = min(h._req.first_token_t for h in highs)
        last_low = max(h._req.first_token_t for h in lows)
        assert first_high < last_low, (
            "HIGH priority should reach the engine before the last LOW")
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_cancel_frees_kv_blocks(frontend):
    rng = np.random.default_rng(4)
    engine = frontend.router.replicas[0].engine
    free0 = engine.free_blocks
    h = frontend.submit(prompts(1, rng, lo=30, hi=32)[0], max_new_tokens=90)
    # wait until it actually holds KV blocks
    deadline = time.monotonic() + 60
    while engine.free_blocks == free0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert engine.free_blocks < free0, "request never took KV blocks"
    h.cancel()
    assert h._req.wait(60)
    assert h.state == RequestState.CANCELLED
    assert h.finish_reason == "cancelled"
    # blocks back in the pool promptly (not at would-be completion time)
    deadline = time.monotonic() + 10
    while engine.free_blocks != free0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert engine.free_blocks == free0
    assert frontend.metrics_snapshot()["requests_cancelled"] == 1


def test_deadline_expiry_accounting(frontend):
    rng = np.random.default_rng(5)
    h = frontend.submit(prompts(1, rng, lo=30, hi=32)[0],
                        max_new_tokens=90, deadline_ms=120.0)
    assert h._req.wait(60)
    assert h.state == RequestState.EXPIRED
    assert h.finish_reason == "deadline"
    snap = frontend.metrics_snapshot()
    assert snap["requests_expired"] == 1
    # expiry released the sequence: all KV blocks return
    engine = frontend.router.replicas[0].engine
    deadline = time.monotonic() + 10
    while engine.free_blocks != engine.config.kv_blocks \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert engine.free_blocks == engine.config.kv_blocks


def test_two_replicas_share_load():
    engines = [tiny_engine(), tiny_engine()]
    fe = ServingFrontend(engines, ServingConfig(max_queue_depth=32))
    try:
        rng = np.random.default_rng(6)
        handles = [fe.submit(p, max_new_tokens=4)
                   for p in prompts(8, rng)]
        assert fe.wait_all(handles, timeout=300)
        used = {h._req.replica_id for h in handles}
        assert used == {0, 1}, f"router used only replicas {used}"
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_dead_replica_degrades_not_fails():
    """Kill one replica's engine mid-service: its requests FAIL, the
    other replica keeps serving, new work routes around the corpse."""
    engines = [tiny_engine(), tiny_engine()]
    fe = ServingFrontend(engines, ServingConfig(max_queue_depth=32))
    try:
        from deepspeed_tpu.serving import ReplicaState

        rng = np.random.default_rng(7)
        fe.router.replicas[0].state = ReplicaState.DEAD
        handles = [fe.submit(p, max_new_tokens=3)
                   for p in prompts(4, rng)]
        assert fe.wait_all(handles, timeout=300)
        assert all(h.state == RequestState.FINISHED for h in handles)
        assert all(h._req.replica_id == 1 for h in handles)
        assert fe.metrics_snapshot()["replicas_healthy"] == 1
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_shutdown_drain_completes_inflight():
    fe = ServingFrontend([tiny_engine()], ServingConfig(max_queue_depth=8))
    rng = np.random.default_rng(8)
    handles = [fe.submit(p, max_new_tokens=3) for p in prompts(2, rng)]
    fe.shutdown(drain=True, timeout=120)
    assert all(h.state == RequestState.FINISHED for h in handles)
    with pytest.raises(Rejected) as ei:
        fe.submit(prompts(1, rng)[0])
    assert ei.value.reason == "draining"


def test_frontend_metrics_snapshot_keys():
    """The registry keys an operator's dashboard reads are in the
    snapshot after a burst that sheds, with the TTFT histogram's
    percentiles beside them."""
    fe = ServingFrontend([tiny_engine()], ServingConfig(max_queue_depth=4))
    try:
        rng = np.random.default_rng(9)
        handles = []
        for p in prompts(10, rng):
            try:
                handles.append(fe.submit(p, max_new_tokens=2))
            except Rejected:
                pass
        fe.wait_all(handles, timeout=300)
        snap = fe.metrics_snapshot()
        for key in ("requests_submitted", "requests_completed",
                    "requests_shed", "tokens_generated", "shed_rate"):
            assert key in snap
        assert {"p50", "p95", "count"} <= set(snap["ttft_s"])
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_cancel_queued_request_frees_depth_slot():
    """Cancelling a request still waiting in the admission queue must
    terminate it immediately and free its depth slot — not leave a
    phantom entry until it would reach the heap top."""
    fe = ServingFrontend([tiny_engine()], ServingConfig(max_queue_depth=8))
    try:
        rng = np.random.default_rng(10)
        # saturate the replica's 4 slots; wait until all are dispatched
        busy = [fe.submit(p, max_new_tokens=40)
                for p in prompts(4, rng, lo=24, hi=32)]
        deadline = time.monotonic() + 30
        while len(fe.admission) and time.monotonic() < deadline:
            time.sleep(0.01)
        queued = [fe.submit(p, max_new_tokens=2) for p in prompts(2, rng)]
        depth = len(fe.admission)
        assert depth >= 1, "nothing queued; can't test cancel"
        victim = queued[0]
        victim.cancel()
        assert victim._req.wait(1.0), "queued cancel was not immediate"
        assert victim.state == RequestState.CANCELLED
        assert len(fe.admission) == depth - 1   # slot freed eagerly
        assert fe.wait_all(busy + queued[1:], timeout=300)
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_forced_shutdown_terminates_inflight():
    """shutdown(drain=False) with work in flight: every handle still
    reaches a terminal state (FAILED/REJECTED), no stream hangs."""
    fe = ServingFrontend([tiny_engine()], ServingConfig(max_queue_depth=8))
    rng = np.random.default_rng(11)
    handles = [fe.submit(p, max_new_tokens=60)
               for p in prompts(6, rng, lo=24, hi=32)]
    fe.shutdown(drain=False, timeout=5)
    assert fe.wait_all(handles, timeout=30), (
        "forced shutdown left requests without a terminal state")
    assert all(h.state != RequestState.QUEUED and
               h.state != RequestState.RUNNING for h in handles)


def test_from_engine_factory_and_default_priority():
    """ServingConfig.num_replicas and default_priority are consumed: the
    factory path builds the fleet, and submit() without a priority uses
    the configured default."""
    fe = ServingFrontend.from_engine_factory(
        lambda i: tiny_engine(),
        ServingConfig(num_replicas=2, default_priority=Priority.HIGH,
                      max_queue_depth=8))
    try:
        assert len(fe.router.replicas) == 2
        rng = np.random.default_rng(12)
        h = fe.submit(prompts(1, rng)[0], max_new_tokens=2)
        assert h._req.priority == Priority.HIGH
        assert fe.wait_all([h], timeout=120)
    finally:
        fe.shutdown(drain=False, timeout=5)
