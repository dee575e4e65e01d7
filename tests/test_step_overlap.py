"""One step in flight (docs/SERVING.md "A step in flight"): the scheduler
dispatches step n+1 before it has read step n's tokens, which the forward
drew on the device and the next forward takes from there. Held here: an
overlapped run is the run with nothing in flight, token for token; an EOS
stops a request where it falls although the row behind it has flown; a
cancel, an evacuation and a preemption find their sequence at rest; a
scheduler that needs its tokens on the host (a sampler, a proposer) leaves
nothing in flight; and the counter that says how often it engaged reaches
the registry. A tiny dense model and a tiny hybrid, float32 on the CPU."""

import dataclasses
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.engine_v2 import (
    DEVICE_TOKEN, InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.inference.v2.testing import share_forward
from deepspeed_tpu.inference.v2.spec import NGramProposer
from deepspeed_tpu.models.transformer import (
    TINY_TEST, CausalLM, TransformerConfig)
from deepspeed_tpu.telemetry import Tracer

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
#: chunks of 16 and a budget of 48: a prompt of 40 spans three chunks, and
#: the hybrid parts every put with a chunk row beside one-token rows
SIZING = dict(max_ragged_sequence_count=4, max_chunk_tokens=16,
              max_ragged_batch_size=48, kv_blocks=96, kv_block_size=8)
MODELS = {}


def model_and_params(kind):
    """Weights off their initial values: a freshly initialised tiny model
    repeats one token, and a wrong token would then go unseen."""
    if kind not in MODELS:
        model = CausalLM(DENSE if kind == "dense" else HYBRID)
        flat, tree = jax.tree_util.tree_flatten(
            model.init(jax.random.PRNGKey(0)))
        keys = jax.random.split(jax.random.PRNGKey(1), len(flat))
        noise = 0.3 if kind == "dense" else 0.05
        MODELS[kind] = model, jax.tree_util.tree_unflatten(tree, [
            leaf + noise * jax.random.normal(k, leaf.shape, leaf.dtype)
            for leaf, k in zip(flat, keys)])
    return MODELS[kind]


#: engines of one kind at one sizing share one jitted forward
#: (``testing.share_forward``)
_FORWARDS = {}


def engine(kind, **sizing):
    model, params = model_and_params(kind)
    # as if 32 positions were free, not 128: a dense [2, 16] runs padded,
    # a chunk row beside two or three decodes merged, [1, 16 + 4]
    with mock.patch.object(engine_v2, "_FREE_POSITIONS", 32):
        eng = InferenceEngineV2(model, params=params,
                                config=RaggedInferenceEngineConfig(
                                    **dict(SIZING, **sizing)))
    return share_forward(eng, _FORWARDS,
                         (kind, tuple(sorted(sizing.items()))))


def host_argmax(logits):
    return int(np.argmax(logits))


def prompt(seed, n, vocab=128):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


#: (prompt length, max_new_tokens, the step it is submitted before)
TRAFFIC = [(40, 12, 0), (5, 20, 0), (17, 6, 2), (3, 9, 5), (33, 7, 9),
           (9, 15, 9), (16, 1, 14), (1, 5, 20)]


def run(sched, traffic=TRAFFIC, eos=None, max_steps=400, prompts=None):
    """Drive ``traffic`` through ``sched``, each request submitted before
    its step (``prompts``: the prompts of some uids, in place of the
    seeded ones); returns ({uid: streamed tokens}, the finished
    requests)."""
    streamed = {}
    waiting = sorted(enumerate(traffic), key=lambda t: t[1][2])
    for step in range(max_steps):
        while waiting and waiting[0][1][2] <= step:
            uid, (n, new, _) = waiting.pop(0)
            streamed[uid] = []
            sched.submit(uid, (prompts or {}).get(uid) or prompt(100 + uid, n),
                         max_new_tokens=new,
                         eos_token_id=eos,
                         on_token=lambda u, t: streamed[u].append(t))
        if not waiting and not sched.has_work:
            break
        sched.step()
    assert not sched.has_work and not waiting
    return streamed, sched.finished


def engine_greedy(eng, uid, tokens, steps):
    """Greedy decode by the engine alone: prefill in chunks, then one
    token a put, each drawn on the host from the put's logits."""
    chunk = eng.config.max_chunk_tokens
    for at in range(0, len(tokens), chunk):
        out = eng.put([uid], [tokens[at:at + chunk]])
    got = [int(np.argmax(np.asarray(out)[0]))]
    for _ in range(steps - 1):
        got.append(int(np.argmax(np.asarray(eng.put([uid], [[got[-1]]]))[0])))
    eng.flush(uid)
    return got


# ------------------------------------------------- the same tokens, ahead

@pytest.mark.parametrize("kind,prefix_cache", [
    ("dense", False), ("dense", True), ("hybrid", False)])
def test_an_overlapped_run_is_the_run_at_depth_0_token_for_token(
        kind, prefix_cache):
    sizing = {"enable_prefix_cache": True} if prefix_cache else {}
    ahead = ContinuousBatchingScheduler(engine(kind, **sizing))
    streamed, finished = run(ahead)
    host = ContinuousBatchingScheduler(engine(kind, **sizing),
                                       sample_fn=host_argmax)
    streamed0, finished0 = run(host)
    assert streamed == streamed0
    for uid, (n, new, _) in enumerate(TRAFFIC):
        req = finished[uid]
        assert req.generated == streamed[uid] and len(req.generated) == new
        assert req.finish_reason == "length"
    # and both are what the engine alone decodes, one put a token
    alone = engine(kind)
    for uid in (0, 3):
        n, new, _ = TRAFFIC[uid]
        assert streamed[uid] == engine_greedy(alone, uid,
                                              prompt(100 + uid, n), new)
    stats, stats0 = ahead.step_stats(), host.step_stats()
    assert stats0["steps_overlapped"] == 0 < stats0["steps"]
    assert stats["steps_overlapped"] >= 0.8 * stats["steps"]
    totals = ahead.engine.put_totals
    assert totals["puts_split"] > 0                     # a parted put
    assert (totals.get("forwards_merged", 0) > 0) == (kind == "dense")
    for sched in (ahead, host):
        sm = sched.engine.state_manager
        assert sm.tracked_sequences == []
        assert sm.available_blocks == SIZING["kv_blocks"]
        assert len(sm._free_id_slots) == sm.id_slots
    if prefix_cache:
        # what was drawn on the device reached the index when its id came
        # back: the same blocks are registered either way
        a, b = (s.engine.state_manager for s in (ahead, host))
        assert len(a._index) == len(b._index) > 0
        assert set(a._index) == set(b._index)


def test_a_row_fed_on_the_device_is_the_row_fed_from_the_host():
    """``DEVICE_TOKEN`` reads the slot the sequence's last forward wrote:
    the same logits as the host's value, through the same program."""
    fed, host = engine("dense"), engine("dense")
    tokens = prompt(7, 13)
    first = fed.put([1, 2], [tokens, [3]]).next_tokens()
    host.put([1, 2], [tokens, [3]])
    a = fed.put([2, 1], [[DEVICE_TOKEN], [DEVICE_TOKEN]])
    b = host.put([2, 1], [[int(first[1])], [int(first[0])]])
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(a.next_tokens(), np.argmax(np.asarray(b), -1))
    assert fed.paged.forward._cache_size() == host.paged.forward._cache_size()


# -------------------------------------------------------------- an EOS

@pytest.mark.parametrize("kind", ["dense", "hybrid"])
@pytest.mark.parametrize("at", [0, 4])
def test_an_eos_ends_its_request_where_it_falls(kind, at):
    """The row behind an EOS has flown before the host sees it: it is
    dropped at its retirement, nothing after the EOS is delivered, and
    every block and slot comes back."""
    free, _ = run(ContinuousBatchingScheduler(engine(kind)),
                  traffic=[(19, 12, 0), (6, 12, 0)])
    eos = free[0][at]
    stop = {u: (s.index(eos) + 1 if eos in s else len(s))
            for u, s in free.items()}
    assert stop[0] == at + 1

    sched = ContinuousBatchingScheduler(engine(kind))
    streamed, finished = run(sched, traffic=[(19, 12, 0), (6, 12, 0)],
                             eos=eos)
    for uid in (0, 1):
        assert streamed[uid] == free[uid][:stop[uid]]
        assert finished[uid].generated == streamed[uid]
        assert finished[uid].finish_reason == \
            ("eos" if stop[uid] < 12 or free[uid][-1] == eos else "length")
    sm = sched.engine.state_manager
    assert sm.available_blocks == SIZING["kv_blocks"]
    assert sm.tracked_sequences == []
    assert sm.free_state_slots == sm.state_slots


def test_has_work_holds_while_a_step_is_unread():
    """A lone request that ends at an EOS leaves its overrun row in
    flight: nothing is pending or running, and the step is still there
    to be retired."""
    free, _ = run(ContinuousBatchingScheduler(engine("dense")),
                  traffic=[(6, 8, 0)])
    sched = ContinuousBatchingScheduler(engine("dense"))
    assert free[0][2] not in free[0][:2]
    sched.submit(0, prompt(100, 6), max_new_tokens=8, eos_token_id=free[0][2])
    seen = []
    while sched.has_work:
        done = sched.step()
        seen.append((bool(sched.running), sched._flight is not None, done))
    assert (False, True, [0]) in seen           # ended, a row still out
    assert seen[-1] == (False, False, [])       # ... and retired, unread
    assert sched.finished[0].generated == free[0][:3]
    sched.submit(1, prompt(101, 6), max_new_tokens=3)
    assert sched.run_to_completion()[1].generated == \
        engine_greedy(engine("dense"), 1, prompt(101, 6), 3)
    assert sched._flight is None


# -------------------------------------- edits from outside find it at rest

def _ahead_with_a_step_in_flight(kind="dense", **sizing):
    sched = ContinuousBatchingScheduler(engine(kind, **sizing))
    streamed = {0: [], 1: []}
    for uid, n in ((0, 21), (1, 4)):
        sched.submit(uid, prompt(100 + uid, n), max_new_tokens=14,
                     on_token=lambda u, t: streamed[u].append(t))
    for _ in range(5):
        sched.step()
    assert sched._flight is not None and streamed[0] and streamed[1]
    return sched, streamed


@pytest.fixture(scope="module")
def reference():
    free, _ = run(ContinuousBatchingScheduler(engine("dense")),
                  traffic=[(21, 14, 0), (4, 14, 0)])
    return free


@pytest.mark.parametrize("edit", ["cancel", "evacuate", "preempt"])
def test_an_edit_from_outside_first_retires_what_is_in_flight(edit,
                                                              reference):
    sizing = dict(admission_reservation=True,
                  admission_preemption_enabled=True,
                  admission_oversubscription_factor=3.0) \
        if edit == "preempt" else {}
    sched, streamed = _ahead_with_a_step_in_flight(**sizing)
    eng = sched.engine
    if edit == "cancel":
        assert sched.cancel(0)
        assert sched._flight is None
        assert sched.finished[0].finish_reason == "cancelled"
        assert eng.state_manager.get_sequence(0) is None
    elif edit == "evacuate":
        payload = sched.evacuate(0)
        assert sched._flight is None and 0 not in sched.running
        delivered = list(streamed[0])
        # the KV holds all that was delivered but the last token, which
        # had been drawn and not fed; the destination feeds it
        assert payload["seen_tokens"] == 21 + len(delivered) - 1
        assert isinstance(payload["last_logits"], np.ndarray)
        there = ContinuousBatchingScheduler(engine("dense"))
        resume = prompt(100, 21) + delivered
        there.engine.import_sequence(
            0, payload, tokens=resume[:payload["seen_tokens"]])
        there.submit_prefilled(
            0, resume, payload["last_logits"], 14 - len(delivered),
            on_token=lambda u, t: streamed[u].append(t))
        there.run_to_completion()
        assert streamed[0] == reference[0]
        assert there.engine.state_manager.available_blocks == \
            SIZING["kv_blocks"]
    else:
        victim = sched.running[0]
        sched._preempt(victim)
        assert sched._flight is None and 0 in sched.preempted
        entry = sched.preempted[0]
        assert entry["tokens"] == prompt(100, 21) + streamed[0]
        assert eng.state_manager.get_sequence(0) is None
    sched.run_to_completion()
    assert streamed[1] == reference[1]
    if edit == "cancel":
        assert streamed[0] == reference[0][:len(streamed[0])]
    elif edit == "preempt":
        assert streamed[0] == reference[0]
        assert sched.preempt_stats() == {"preempted": 1, "resumed": 1}
    assert eng.state_manager.available_blocks == SIZING["kv_blocks"]
    assert eng.state_manager.tracked_sequences == []


# ------------------------------------ what cannot run ahead runs as it did

def _phases(tracer, label):
    spans = tracer.export()
    steps = [s for s in spans if s["name"] == "step"
             and s["trace_id"] == label]
    kids = {s["span_id"]: [] for s in steps}
    for s in spans:
        if s["parent_id"] in kids:
            kids[s["parent_id"]].append(s["name"])
    return [(s["attrs"].get("overlapped"), kids[s["span_id"]])
            for s in steps]


@pytest.mark.parametrize("needs_host", ["sample_fn", "proposer"])
def test_a_scheduler_that_needs_its_tokens_on_the_host_keeps_depth_0(
        needs_host):
    traffic = [(24, 14, 0), (5, 10, 0), (18, 8, 3)]
    # a prompt that repeats itself, so that the n-gram proposer drafts
    repeats = {0: [5, 6, 7, 8] * 6}
    free, _ = run(ContinuousBatchingScheduler(engine("dense")),
                  traffic=traffic, prompts=repeats)
    tracer = Tracer()
    kw = {"sample_fn": host_argmax} if needs_host == "sample_fn" \
        else {"proposer": NGramProposer(), "max_draft_tokens": 3}
    sched = ContinuousBatchingScheduler(engine("dense"), tracer=tracer,
                                        trace_label="s", **kw)
    streamed, finished = run(sched, traffic=traffic, prompts=repeats)
    assert streamed == free
    stats = sched.step_stats()
    assert stats["steps_overlapped"] == 0 < stats["steps"]
    # every step holds its own four phases, in today's order
    phases = _phases(tracer, "s")
    assert len(phases) == stats["steps"]
    for overlapped, names in phases:
        assert overlapped is False
        assert [n for n in names if n != "forward"] == \
            ["pack", "stage", "fetch", "commit"]
    if needs_host == "proposer":
        spec = sched.spec_stats()
        # drafts were verified (and refuted: rows trimmed), and a decode
        # row emits the tokens after the one it was fed
        assert spec["proposed"] > 0
        assert spec["decode_rows"] <= spec["emitted"] == \
            sum(new - 1 for _, new, _ in traffic)
        assert spec["emitted"] - spec["decode_rows"] == spec["accepted"]


def test_an_overlapped_step_retires_the_step_before_it():
    """``pack`` and ``stage`` of step n+1, then ``fetch`` and ``commit``
    of step n; the first step only dispatches, the last only retires."""
    tracer = Tracer()
    sched = ContinuousBatchingScheduler(engine("dense"), tracer=tracer,
                                        trace_label="s")
    run(sched, traffic=[(20, 6, 0)])
    phases = _phases(tracer, "s")
    assert phases[0] == (False, ["pack", "stage"])
    assert phases[-1] == (False, ["pack", "fetch", "commit"])
    assert all(p == (True, ["pack", "stage", "fetch", "commit"])
               for p in phases[1:-1]) and len(phases) > 4
    forwards = [s for s in tracer.export() if s["name"] == "forward"]
    stages = [s for s in tracer.export() if s["name"] == "stage"]
    assert len(forwards) == len(stages) == sched.step_stats()["steps"]
    # a forward's span runs from its dispatch to the end of its fetch,
    # one step later: it holds the next step's stage
    for fwd, nxt in zip(forwards, stages[1:]):
        assert fwd["t_start"] < nxt["t_start"] < nxt["t_end"] < fwd["t_end"]


# ------------------------------------------------- the counter, published

def test_steps_overlapped_reaches_the_registry():
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    fe = ServingFrontend([engine("dense")], ServingConfig(max_queue_depth=8))
    try:
        handles = [fe.submit(prompt(u, 9), max_new_tokens=12)
                   for u in range(3)]
        assert fe.wait_all(handles, timeout=300)
        sched = fe.router.replicas[0].scheduler
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and (
                fe.metrics_snapshot().get("scheduler_steps", 0)
                < sched.step_stats()["steps"] or sched.has_work):
            time.sleep(0.005)
        snap, stats = fe.metrics_snapshot(), sched.step_stats()
    finally:
        fe.shutdown(drain=False, timeout=5)
    assert all(len([ev.token for ev in h.drain()]) == 12 for h in handles)
    assert snap["scheduler_steps"] == stats["steps"] > 10
    assert snap["steps_overlapped"] == stats["steps_overlapped"]
    assert snap["steps_overlapped"] >= 0.7 * snap["scheduler_steps"]
