"""Every forward the engine hands to the device is a ``dispatch`` span
(docs/OBSERVABILITY.md "The scheduler step"): one a forward with consecutive
ordinals, a parted put's forwards each with their own bucket and counts, a
child of ``stage``, joined to its requests' ``prefill`` spans by uid; with
the tracer off a put allocates nothing for it; and the step says whether the
device had run dry when it was handed over (``starved``), which reaches the
registry as ``steps_starved``. A tiny dense model and a tiny hybrid, float32
on the CPU."""

import dataclasses
import time
import tracemalloc

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.testing import share_forward
from deepspeed_tpu.inference.v2.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models.transformer import (
    TINY_TEST, CausalLM, TransformerConfig)
from deepspeed_tpu.telemetry import NOOP_TRACER, Tracer

DENSE = dataclasses.replace(TINY_TEST, max_seq_len=512, dtype=jnp.float32)
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
SIZING = dict(max_ragged_sequence_count=4, max_chunk_tokens=16,
              max_ragged_batch_size=48, kv_blocks=96, kv_block_size=8)
MODELS, FORWARDS = {}, {}


def engine(kind="dense"):
    if kind not in MODELS:
        model = CausalLM(DENSE if kind == "dense" else HYBRID)
        MODELS[kind] = model, model.init(jax.random.PRNGKey(0))
    model, params = MODELS[kind]
    # engines of a kind share one jitted forward (``testing.share_forward``)
    return share_forward(
        InferenceEngineV2(model, params=params,
                          config=RaggedInferenceEngineConfig(**SIZING)),
        FORWARDS, kind)


def named(tracer, name):
    return [s for s in tracer.export() if s["name"] == name]


# --------------------------------------------------------- the span itself

def test_one_dispatch_span_a_forward_with_consecutive_ordinals():
    eng, tr = engine(), Tracer()
    assert eng.tracer is NOOP_TRACER        # until a scheduler hands its own
    sched = ContinuousBatchingScheduler(eng, tracer=tr)
    assert eng.tracer is tr
    before = eng.put_totals["forwards"]
    sched.submit(7, list(range(1, 30)), max_new_tokens=5)
    sched.submit(8, list(range(40, 45)), max_new_tokens=5)
    sched.run_to_completion()
    spans = named(tr, "dispatch")
    assert len(spans) == eng.put_totals["forwards"] - before > 4
    assert [s["attrs"]["ordinal"] for s in spans] == \
        list(range(before + 1, eng.put_totals["forwards"] + 1))
    # a child of the stage that staged it, on the scheduler's trace
    stages = {s["span_id"]: s for s in named(tr, "stage")}
    for s in spans:
        stage = stages[s["parent_id"]]
        assert stage["t_start"] <= s["t_start"] <= s["t_end"] <= stage["t_end"]
        assert s["trace_id"] == stage["trace_id"] == "scheduler"
        assert set(s["attrs"]) == {"ordinal", "bucket_seqs", "bucket_chunk",
                                   "rows", "valid_tokens", "merged_ones",
                                   "uids"}
    # a put that ran as one forward: the span says what the record says
    first = spans[0]["attrs"]
    assert (first["bucket_seqs"], first["bucket_chunk"], first["rows"],
            first["valid_tokens"], first["uids"]) == (2, 16, 2, 21, "7 8")


def test_a_parted_put_is_one_span_a_forward_each_with_its_own_counts():
    eng, tr = engine("hybrid"), Tracer()
    ContinuousBatchingScheduler(eng, tracer=tr)
    eng.put([1, 2], [list(range(1, 10)), list(range(1, 4))])
    tr.clear()
    # two one-token rows and a chunk row: a hybrid put parts them
    eng.put([1, 2, 3], [[5], [6], list(range(1, 14))])
    record, spans = eng.last_put, named(tr, "dispatch")
    assert record["forwards"] == len(spans) == 2
    ones, chunk = (s["attrs"] for s in spans)
    assert (ones["bucket_seqs"], ones["bucket_chunk"], ones["rows"],
            ones["valid_tokens"], ones["uids"]) == (2, 1, 2, 2, "1 2")
    assert (chunk["bucket_seqs"], chunk["bucket_chunk"], chunk["rows"],
            chunk["valid_tokens"], chunk["uids"]) == (1, 16, 1, 13, "3")
    assert chunk["ordinal"] == ones["ordinal"] + 1 == \
        eng.put_totals["forwards"]
    # the put's record holds the sums
    assert record["rows"] == ones["rows"] + chunk["rows"]
    assert record["valid_tokens"] == \
        ones["valid_tokens"] + chunk["valid_tokens"]
    assert (record["bucket_seqs"], record["bucket_chunk"]) == (1, 16)


def test_a_verification_says_its_width():
    eng, tr = engine(), Tracer()
    ContinuousBatchingScheduler(eng, tracer=tr)
    eng.put([1], [list(range(1, 9))])
    eng.put([1], [[3, 4, 5]], verify_width=4, defer_commit=True)
    plain, verify = (s["attrs"] for s in named(tr, "dispatch"))
    assert "verify_width" not in plain and verify["verify_width"] == 4


def test_uids_join_a_request_to_the_forwards_that_fed_it():
    eng, tr = engine(), Tracer()
    sched = ContinuousBatchingScheduler(eng, tracer=tr)
    sched.submit(11, list(range(1, 40)), max_new_tokens=3, trace_id="req-a")
    sched.submit(12, list(range(1, 6)), max_new_tokens=3, trace_id="req-b")
    sched.run_to_completion()
    prefill = {s["trace_id"]: s for s in named(tr, "prefill")}
    assert prefill["req-a"]["attrs"]["uid"] == 11
    assert prefill["req-b"]["attrs"]["uid"] == 12
    fed = {uid: [s for s in named(tr, "dispatch")
                 if str(uid) in s["attrs"]["uids"].split()]
           for uid in (11, 12)}
    # 39 prompt tokens in chunks of 16 are three forwards, then decode
    assert len(fed[11]) == 3 + 2 and len(fed[12]) == 1 + 2
    a = prefill["req-a"]
    during = [s for s in fed[11] if s["t_start"] < a["t_end"]]
    assert sum(s["attrs"]["bucket_chunk"] > 1 for s in during) == 3


def test_with_the_tracer_off_a_put_allocates_nothing_for_the_span():
    """The pin ``tests/test_telemetry.py`` has for ``span``, on the engine's
    own call site: no attrs dict, no string of uids, no span — nothing is
    allocated on the lines that build them, nor in the tracer."""
    import inspect

    eng = engine()
    assert eng.tracer is NOOP_TRACER
    eng.put([1], [list(range(1, 9))])
    source, first = inspect.getsourcelines(InferenceEngineV2._forward_rows)
    at = [first + i for i, line in enumerate(source)]
    lo = next(n for n, line in zip(at, source) if "tracer.enabled" in line)
    hi = next(n for n, line in zip(at, source) if 'span("dispatch"' in line)
    assert 0 < hi - lo < 16
    engine_file = inspect.getsourcefile(InferenceEngineV2)
    tracer_file = Tracer.__init__.__code__.co_filename
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(400):
            eng.put([1], [[3]])
            eng.flush(1)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(st.count_diff for st in after.compare_to(before, "lineno")
                if st.traceback and st.count_diff > 0 and (
                    st.traceback[0].filename == tracer_file
                    or (st.traceback[0].filename == engine_file
                        and lo <= st.traceback[0].lineno <= hi)))
    # 400 puts would leave hundreds of objects had each built its attrs;
    # tracemalloc catches at most a few in-flight call objects
    assert grown <= 8, f"{grown} objects over 400 untraced puts"
    assert NOOP_TRACER.export() == []


# ------------------------------------------------------------------ starved

def test_starved_says_whether_the_step_ahead_had_finished(monkeypatch):
    eng, tr = engine(), Tracer()
    sched = ContinuousBatchingScheduler(eng, tracer=tr)
    sched.submit(1, list(range(1, 9)), max_new_tokens=8)
    dry = []            # what the device says, set by hand
    real_put = eng.put

    def put(*args, **kwargs):
        handle = real_put(*args, **kwargs)
        if dry:
            handle.ran_dry = dry[-1]
        return handle

    monkeypatch.setattr(eng, "put", put)
    dry.append(True)
    sched.step()        # the device is idle, but nothing is in flight
    assert named(tr, "step")[-1]["attrs"] == {"overlapped": False,
                                              "starved": False}
    # its forward is unread and, says the handle, unfinished
    dry.append(False)
    sched.step()
    assert named(tr, "step")[-1]["attrs"] == {"overlapped": True,
                                              "starved": False}
    assert sched.step_stats() == {"steps": 2, "steps_overlapped": 1,
                                  "steps_starved": 0}
    # unread still, but the device had finished it: the host is late
    dry.append(True)
    sched.step()
    assert named(tr, "step")[-1]["attrs"] == {"overlapped": True,
                                              "starved": True}
    assert sched.step_stats() == {"steps": 3, "steps_overlapped": 2,
                                  "steps_starved": 1}
    sched.run_to_completion()
    assert len(sched.finished[1].generated) == 8


def test_a_put_asks_the_device_without_waiting():
    eng = engine()
    first = eng.put([1], [list(range(1, 9))])
    assert first.ran_dry is True        # nothing was ever handed over
    jax.block_until_ready(eng.next_ids)
    second = eng.put([1], [[3]])
    assert second.ran_dry is True       # what was has finished
    # a parted put asks once, as its first forward goes: its own forwards
    # are not what it waited for
    hybrid = engine("hybrid")
    hybrid.put([1, 2], [list(range(1, 10)), list(range(1, 4))])
    jax.block_until_ready(hybrid.next_ids)
    parted = hybrid.put([1, 2, 3], [[5], [6], list(range(1, 14))])
    assert hybrid.last_put["forwards"] == 2 and parted.ran_dry is True
    # a device still at work says so (the forward itself left out)
    class Busy:
        def is_ready(self):
            return False

        def copy_to_host_async(self):
            pass

    busy = engine()
    busy.put([1], [list(range(1, 9))])
    busy.next_ids = Busy()

    def forward_rows(*args):
        busy.last_put = {}
        return jnp.zeros((1, 8))

    busy._forward_rows = forward_rows
    assert busy.put([1], [[3]]).ran_dry is False


def test_a_scheduler_that_cannot_run_ahead_is_never_starved():
    eng, tr = engine(), Tracer()
    sched = ContinuousBatchingScheduler(
        eng, tracer=tr, sample_fn=lambda logits: int(logits.argmax()))
    sched.submit(1, list(range(1, 9)), max_new_tokens=6)
    sched.run_to_completion()
    stats = sched.step_stats()
    assert stats["steps"] >= 6
    assert stats["steps_overlapped"] == stats["steps_starved"] == 0
    assert not any(s["attrs"]["starved"] for s in named(tr, "step"))


def test_steps_starved_reaches_the_registry():
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    fe = ServingFrontend([engine()], ServingConfig(
        max_queue_depth=8, telemetry={"enabled": True}))
    try:
        assert fe.metrics_snapshot()["steps_starved"] == 0    # pre-declared
        handles = [fe.submit(list(range(1, 10)), max_new_tokens=12)
                   for _ in range(3)]
        assert fe.wait_all(handles, timeout=300)
        sched = fe.router.replicas[0].scheduler
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and (
                fe.metrics_snapshot().get("scheduler_steps", 0)
                < sched.step_stats()["steps"] or sched.has_work):
            time.sleep(0.005)
        snap, stats = fe.metrics_snapshot(), sched.step_stats()
        steps = [s for s in fe.tracer.export() if s["name"] == "step"]
        assert fe.router.replicas[0].engine.tracer is fe.tracer
    finally:
        fe.shutdown(drain=False, timeout=5)
    assert snap["scheduler_steps"] == stats["steps"] > 10
    assert snap["steps_starved"] == stats["steps_starved"] \
        == sum(s["attrs"]["starved"] for s in steps)
    assert stats["steps_starved"] <= stats["steps_overlapped"]
    # and the spans of the serving path: dispatch under stage under step,
    # on the replica's trace
    spans = fe.tracer.export()
    by_id = {s["span_id"]: s for s in spans}
    mine = [s for s in spans if s["name"] == "dispatch"]
    assert len(mine) >= stats["steps"]
    for s in mine:
        assert by_id[s["parent_id"]]["name"] == "stage"
        assert s["trace_id"] == "replica-0"


@pytest.mark.parametrize("kind", ["dense", "hybrid"])
def test_tokens_are_the_same_traced_or_not(kind):
    def run(tracer):
        sched = ContinuousBatchingScheduler(engine(kind), tracer=tracer)
        sched.submit(1, list(range(1, 40)), max_new_tokens=6)
        sched.submit(2, list(range(3, 9)), max_new_tokens=6)
        sched.run_to_completion()
        return [sched.finished[u].generated for u in (1, 2)]

    assert run(None) == run(Tracer())
