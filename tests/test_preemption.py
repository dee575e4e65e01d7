"""Reservation-aware admission + preemptive KV spill (ISSUE 11;
docs/SERVING.md "Admission and preemption").

Invariants under test: the chunked-admission deadlock (kv_blocks=14,
8 seqs, 76-token prompts) reproduces on the old path and is structurally
impossible under reservation admission; the ledger credits prefix-cache
hits and releases on cancel/finish; victim selection orders by urgency
class, then blocks, then progress; a preempted sequence's KV round-trips
the spill store byte-for-byte (fp32 AND int8 + scale planes) and its
greedy stream is byte-identical to an uncontended run; preemption
composes with cancel and the disaggregated handoff; the
``max_preemptions_per_seq`` starvation cap holds; and the all-default
``admission`` block is byte-for-byte the historical scheduler."""

import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.scheduler import (ContinuousBatchingScheduler,
                                                  Request)
from deepspeed_tpu.inference.v2.testing import assert_greedy_parity
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.serving.config import AdmissionConfig

VOCAB = 128
BS = 8          # kv block size used throughout


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(vocab_size=VOCAB, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=2,
                            max_seq_len=128, norm="rmsnorm",
                            activation="silu", position="rope")
    model = CausalLM(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def make_engine(model, params, kv_blocks=14, max_seqs=8, prefix=False,
                quant=False, qdtype="int8", tier=False, reservation=False,
                preempt=False, factor=1.0, policy="lowest_class",
                max_preempts=2):
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=256, max_ragged_sequence_count=max_seqs,
        max_chunk_tokens=32, kv_blocks=kv_blocks, kv_block_size=BS,
        max_tracked_sequences=64, enable_prefix_cache=prefix,
        kv_quant_enabled=quant, kv_quant_dtype=qdtype,
        admission_reservation=reservation,
        admission_oversubscription_factor=factor,
        admission_preemption_enabled=preempt,
        admission_victim_policy=policy,
        admission_max_preemptions_per_seq=max_preempts)
    eng = InferenceEngineV2(model, params=params, config=vcfg)
    if tier:
        eng.configure_kv_tier(True, host_bytes=64 << 20)
    return eng


def rand_prompt(rng, n):
    return rng.integers(0, VOCAB, size=n).tolist()


def reference_streams(model, params, jobs, uid_base=90_000,
                      quant=False, qdtype="int8"):
    """Uncontended sequential greedy streams (big pool, old admission)
    — the parity baseline, at the SAME KV representation as the engine
    under test (spill/resume is lossless relative to its own pools).
    ``jobs`` = [(prompt, max_new), ...]."""
    eng = make_engine(model, params, kv_blocks=256, max_seqs=8,
                      quant=quant, qdtype=qdtype)
    sched = ContinuousBatchingScheduler(eng)
    out = []
    for i, (p, mn) in enumerate(jobs):
        sched.submit(uid_base + i, p, max_new_tokens=mn)
        sched.run_to_completion()
        out.append(sched.finished[uid_base + i].generated)
    return out


# -------------------------------------------------- deadlock regression
def test_chunked_admission_deadlock_regression(model_and_params):
    """The ROADMAP-confirmed production killer, on the exact regime
    that surfaced it (kv_blocks=14, 8 sequences, 76-token prompts):
    chunk-by-chunk admission part-prefills every sequence until the
    pool is exhausted with none able to finish — bounded steps, zero
    completions, blocks stranded. Under reservation admission the same
    traffic completes, with greedy streams byte-identical to an
    uncontended run."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    prompts = [rand_prompt(rng, 76) for _ in range(8)]

    # old path: wedged forever (bounded-time assert, not a hang)
    eng = make_engine(model, params, reservation=False)
    sched = ContinuousBatchingScheduler(eng)
    for i, p in enumerate(prompts):
        sched.submit(100 + i, p, max_new_tokens=4)
    for _ in range(40):
        sched.step()
    assert not sched.finished, "expected the historical wedge"
    assert sched.has_work
    assert eng.free_blocks < 4        # the pool is stranded, not idle

    # reservation admission: same pool, same traffic, all complete
    eng2 = make_engine(model, params, reservation=True)
    sched2 = ContinuousBatchingScheduler(eng2)
    for i, p in enumerate(prompts):
        sched2.submit(200 + i, p, max_new_tokens=4)
    fin = sched2.run_to_completion(max_steps=2000)
    assert len(fin) == 8
    assert eng2.free_blocks == 14     # everything reclaimed
    ref = reference_streams(model, params, [(p, 4) for p in prompts])
    assert_greedy_parity(ref, [fin[200 + i].generated for i in range(8)],
                         "reservation admission")


# ------------------------------------------------------ reservation ledger
def test_ledger_reserve_release_and_headroom(model_and_params):
    model, params = model_and_params
    eng = make_engine(model, params, kv_blocks=14)
    sm = eng.state_manager
    assert eng.reservation_headroom() == 14
    assert eng.try_reserve(1, 10)
    assert eng.reservation_headroom() == 4
    assert not eng.try_reserve(2, 5)          # shortfall: wait
    assert eng.try_reserve(2, 4)
    assert eng.reservation_headroom() == 0
    # re-reserving the same uid replaces, never double-counts
    assert eng.try_reserve(2, 4)
    assert sm.reserved_sequences == 2
    # flush releases the reservation with the state
    eng.flush(1)
    assert eng.reservation_headroom() == 10
    eng.release_reservation(2)
    assert eng.reservation_headroom() == 14
    # force_reserve records over-commitments (the import path)
    eng.force_reserve(3, 20)
    assert eng.reservation_headroom() == -6


def test_ledger_unfilled_tracks_allocation(model_and_params):
    """A reserved sequence's claim shrinks as it allocates: headroom is
    available minus UNFILLED needs, so admitted work never double-counts
    blocks it already holds."""
    model, params = model_and_params
    rng = np.random.default_rng(1)
    eng = make_engine(model, params, kv_blocks=14)
    assert eng.try_reserve(700, 6)
    assert eng.reservation_headroom() == 8
    eng.put([700], [rand_prompt(rng, 24)])    # 3 blocks allocated
    # available dropped by 3, but so did the unfilled claim
    assert eng.reservation_headroom() == 8


def test_prefix_hit_credits_reservation(model_and_params):
    """Blocks served from the prefix cache count toward the reservation:
    a warm-cache request reserves only its unfilled tail, so cache hits
    buy admission slots, not just prefill time."""
    model, params = model_and_params
    rng = np.random.default_rng(2)
    sys_prompt = rand_prompt(rng, 32)         # 4 full blocks
    eng = make_engine(model, params, kv_blocks=14, prefix=True,
                      reservation=True)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(300, sys_prompt + rand_prompt(rng, 6), max_new_tokens=2)
    sched.run_to_completion()
    # 4 cached blocks + a second request whose prefix matches them:
    # its 40+2-token need (6 blocks) reserves only the uncached tail
    eng.try_reserve(998, eng.reservation_headroom())   # squeeze the pool
    matched = eng.match_prefix(301, sys_prompt + rand_prompt(rng, 6))
    assert matched == 32
    # unfilled = 6 total - 4 matched = 2; grant exactly that much room
    eng.release_reservation(998)
    eng.try_reserve(998, eng.reservation_headroom() - 2)
    assert eng.try_reserve(301, 6)


def test_reservation_released_on_cancel(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(3)
    eng = make_engine(model, params, reservation=True)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(310, rand_prompt(rng, 60), max_new_tokens=20)
    for _ in range(2):
        sched.step()
    assert eng.state_manager.reserved_sequences == 1
    assert sched.cancel(310)
    assert eng.state_manager.reserved_sequences == 0
    assert eng.free_blocks == 14


# ------------------------------------------------------- victim selection
def _req(uid, shed_rank, fed=0, gen=0):
    r = Request(uid, [0] * 10, 8, shed_rank=shed_rank)
    r.prompt_fed = fed
    r.generated = [0] * gen
    return r


def test_victim_policy_ordering(model_and_params):
    model, params = model_and_params
    eng = make_engine(model, params, preempt=True)
    sched = ContinuousBatchingScheduler(eng)
    a = _req(1, shed_rank=0, fed=10, gen=4)   # interactive, 4 blocks
    b = _req(2, shed_rank=1, fed=10, gen=1)   # batch, few blocks
    c = _req(3, shed_rank=1, fed=10, gen=6)   # batch, most blocks
    # lowest_class: batch before interactive; most blocks wins the tie;
    # least progress breaks block ties
    order = sorted([(a, 4), (b, 2), (c, 6)],
                   key=lambda t: sched._victim_order(*t), reverse=True)
    assert [r.uid for r, _ in order] == [3, 2, 1]
    sched.victim_policy = "most_blocks"
    order = sorted([(a, 4), (b, 2), (c, 6)],
                   key=lambda t: sched._victim_order(*t), reverse=True)
    assert [r.uid for r, _ in order] == [3, 1, 2]
    sched.victim_policy = "least_progress"
    order = sorted([(a, 4), (b, 2), (c, 6)],
                   key=lambda t: sched._victim_order(*t), reverse=True)
    assert [r.uid for r, _ in order] == [2, 1, 3]


def test_admission_preempts_only_lower_urgency(model_and_params):
    """Admission-driven preemption requires a STRICTLY lower-urgency
    victim — same-class overload waits (preempting peer work to admit
    identical work is churn), lower-class work is spilled."""
    model, params = model_and_params
    rng = np.random.default_rng(4)
    eng = make_engine(model, params, reservation=True, preempt=True,
                      factor=3.0)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(400, rand_prompt(rng, 60), max_new_tokens=20, shed_rank=1)
    for _ in range(4):
        sched.step()
    assert 400 in sched.running
    # same class: no preemption, the newcomer waits
    sched.submit(401, rand_prompt(rng, 60), max_new_tokens=4, shed_rank=1)
    sched.step()
    assert sched.preempt_stats()["preempted"] == 0
    assert sched.pending and sched.pending[0].uid == 401
    assert sched.reserve_shortfall_blocks() > 0
    # higher urgency: the batch resident is spilled
    sched.submit(402, rand_prompt(rng, 60), max_new_tokens=4, shed_rank=0)
    sched.step()
    assert sched.preempt_stats()["preempted"] == 1
    assert 400 in sched.preempted
    fin = sched.run_to_completion(max_steps=2000)
    assert sorted(fin) == [400, 401, 402]
    assert sched.preempt_stats()["resumed"] == 1


# -------------------------------------------------- spill/resume round-trip
@pytest.mark.parametrize("quant", [False, True],
                         ids=["fp32", "int8+scales"])
@pytest.mark.parametrize("qdtype", ["int8", "fp8_e4m3"])
def test_preempt_spill_resume_byte_roundtrip(model_and_params, quant,
                                             qdtype):
    """A preempted sequence's KV round-trips the spill store exactly —
    pool slabs (and the int8/fp8 scale planes under kv_quant) byte-equal
    after resume, and the resumed greedy stream is byte-identical to an
    uncontended run at the same representation (the spilled logits are
    the decode state). The ISSUE 13 dtype axis rides this same test."""
    if not quant and qdtype != "int8":
        pytest.skip("dtype axis only exists under kv_quant")
    model, params = model_and_params
    rng = np.random.default_rng(5)
    prompts = [rand_prompt(rng, 60), rand_prompt(rng, 60)]
    eng = make_engine(model, params, prefix=True, tier=True, quant=quant,
                      qdtype=qdtype, reservation=True, preempt=True,
                      factor=3.0)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(500, prompts[0], max_new_tokens=16, shed_rank=1)
    for _ in range(4):
        sched.step()
    seq = eng.state_manager.get_sequence(500)
    ids_before = list(seq.kv_blocks)
    before = {name: np.asarray(np.take(np.asarray(pool), ids_before, axis=1))
              for name, pool in eng.state_manager.kv_cache.items()}
    seen_before = seq.seen_tokens
    sched.submit(501, prompts[1], max_new_tokens=4, shed_rank=0)
    sched.step()
    assert 500 in sched.preempted
    assert eng.state_manager.get_sequence(500) is None   # blocks freed
    # drive until 500 resumes, then compare its re-imported slabs
    for _ in range(2000):
        sched.step()
        if 500 in sched.running:
            break
    assert 500 in sched.running, "preempted sequence never resumed"
    seq2 = eng.state_manager.get_sequence(500)
    # the step that resumed the sequence may also have packed its next
    # decode row — the import itself restored exactly seen_before tokens
    assert seq2.seen_tokens in (seen_before, seen_before + 1)
    after = {name: np.asarray(np.take(np.asarray(pool),
                                      list(seq2.kv_blocks), axis=1))
             for name, pool in eng.state_manager.kv_cache.items()}
    assert set(after) == set(before)
    if quant:
        assert {"k_scale", "v_scale"} <= set(after)
    # compare the blocks that were FULL at capture time — the partial
    # tail block legitimately gained the post-resume decode token
    n_full = seen_before // BS
    assert n_full >= 7
    for name in before:
        np.testing.assert_array_equal(before[name][:, :n_full],
                                      after[name][:, :n_full],
                                      err_msg=f"slab {name} diverged "
                                              "across spill/resume")
    fin = sched.run_to_completion(max_steps=2000)
    ref = reference_streams(model, params,
                            [(prompts[0], 16), (prompts[1], 4)],
                            quant=quant, qdtype=qdtype)
    assert_greedy_parity(ref, [fin[500].generated, fin[501].generated],
                         f"preempt round-trip (quant={quant}/{qdtype})")


def test_resume_falls_back_to_reprefill_when_payload_dropped(
        model_and_params):
    """A spilled payload the tier lost (byte bounds, corruption) cannot
    crash the resume: the sequence re-prefills prompt + delivered tokens
    and the greedy stream stays byte-identical (failover semantics)."""
    model, params = model_and_params
    rng = np.random.default_rng(6)
    prompts = [rand_prompt(rng, 60), rand_prompt(rng, 60)]
    eng = make_engine(model, params, reservation=True, preempt=True,
                      factor=3.0)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(510, prompts[0], max_new_tokens=16, shed_rank=1)
    for _ in range(4):
        sched.step()
    sched.submit(511, prompts[1], max_new_tokens=4, shed_rank=0)
    sched.step()
    assert 510 in sched.preempted
    # simulate the tier dropping the payload
    eng.preempt_discard(510)
    fin = sched.run_to_completion(max_steps=2000)
    ref = reference_streams(model, params,
                            [(prompts[0], 16), (prompts[1], 4)])
    assert_greedy_parity(ref, [fin[510].generated, fin[511].generated],
                         "dropped-payload re-prefill")


# ------------------------------------------------------------------- races
def test_preempt_vs_cancel_race(model_and_params):
    """Cancelling a PARKED sequence settles terminally: the spilled
    payload is discarded, on_finish fires with "cancelled", and the
    sequence never resurrects on resume."""
    model, params = model_and_params
    rng = np.random.default_rng(7)
    eng = make_engine(model, params, prefix=True, tier=True,
                      reservation=True, preempt=True, factor=3.0)
    sched = ContinuousBatchingScheduler(eng)
    finished = []
    sched.submit(520, rand_prompt(rng, 60), max_new_tokens=16, shed_rank=1,
                 on_finish=lambda r, why: finished.append((r.uid, why)))
    for _ in range(4):
        sched.step()
    sched.submit(521, rand_prompt(rng, 60), max_new_tokens=4, shed_rank=0)
    sched.step()
    assert 520 in sched.preempted
    assert sched.cancel(520)
    assert (520, "cancelled") in finished
    assert 520 not in sched.preempted
    assert eng.state_manager.preempted_parked == 0
    fin = sched.run_to_completion(max_steps=2000)
    assert 520 not in sched.running and 521 in fin
    assert sched.preempt_stats()["resumed"] == 0


def test_preempt_composes_with_disagg_handoff(model_and_params):
    """Preemption on a role-split fleet: staged KV imports land on the
    decode replica (force-reserved), batch decodes get preempted for
    interactive bursts, and every stream still matches the uncontended
    reference — handoff, reservation, and preemption compose."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    model, params = model_and_params
    rng = np.random.default_rng(8)
    batch_p = [rand_prompt(rng, 40) for _ in range(3)]
    inter_p = [rand_prompt(rng, 40) for _ in range(3)]

    def factory(i):
        return make_engine(model, params, kv_blocks=20, max_seqs=8)

    cfg = ServingConfig(
        max_queue_depth=64,
        disaggregation={"enabled": True, "roles": ["prefill", "decode"]},
        admission={"reservation": True, "oversubscription_factor": 3.0,
                   "preemption": {"enabled": True}})
    fe = ServingFrontend.from_engine_factory(
        factory, cfg.model_copy(update={"num_replicas": 2}))
    try:
        hb = [fe.submit(p, max_new_tokens=16, request_class="batch")
              for p in batch_p]
        time.sleep(0.5)
        hi = [fe.submit(p, max_new_tokens=4, request_class="interactive")
              for p in inter_p]
        assert fe.wait_all(hb + hi, timeout=240)
        got = [[ev.token for ev in h.drain()] for h in hb + hi]
    finally:
        fe.shutdown(drain=False, timeout=5)
    ref = reference_streams(model, params,
                            [(p, 16) for p in batch_p]
                            + [(p, 4) for p in inter_p])
    assert_greedy_parity(ref, got, "disagg + preemption")


# --------------------------------------------------------- starvation cap
def test_max_preemptions_per_seq_starvation_cap(model_and_params):
    """A sequence spilled ``max_preemptions_per_seq`` times becomes
    immune: later higher-urgency arrivals wait instead of starving it,
    and it still completes."""
    model, params = model_and_params
    rng = np.random.default_rng(9)
    eng = make_engine(model, params, reservation=True, preempt=True,
                      factor=4.0, max_preempts=1)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(530, rand_prompt(rng, 60), max_new_tokens=30, shed_rank=1)
    for _ in range(4):
        sched.step()
    sched.submit(531, rand_prompt(rng, 60), max_new_tokens=2, shed_rank=0)
    sched.step()
    assert sched.preempt_stats()["preempted"] == 1        # spill #1
    # run until 530 is resident again, then hit it with another burst
    for _ in range(2000):
        sched.step()
        if 530 in sched.running and 531 in sched.finished:
            break
    sched.submit(532, rand_prompt(rng, 60), max_new_tokens=2, shed_rank=0)
    fin = sched.run_to_completion(max_steps=3000)
    assert sched.preempt_stats()["preempted"] == 1        # cap held
    assert sorted(fin) == [530, 531, 532]                 # nobody starved


# ------------------------------------------------------- disabled parity
def test_disabled_admission_byte_parity(model_and_params):
    """``admission`` all-default through the serving config surface is
    byte-for-byte a config that never heard of the block."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    model, params = model_and_params
    rng = np.random.default_rng(10)
    reqs = [rand_prompt(rng, 24) for _ in range(6)]

    def run(extra):
        fe = ServingFrontend(
            [make_engine(model, params, kv_blocks=64, max_seqs=4)],
            ServingConfig(max_queue_depth=64, **extra))
        try:
            hs = [fe.submit(p, max_new_tokens=4) for p in reqs]
            assert fe.wait_all(hs, timeout=240)
            return [[ev.token for ev in h.drain()] for h in hs]
        finally:
            fe.shutdown(drain=False, timeout=5)

    absent = run({})
    disabled = run({"admission": {"reservation": False,
                                  "preemption": {"enabled": False}}})
    assert disabled == absent


def test_scheduler_defaults_keep_old_admission(model_and_params):
    """A default-config scheduler still takes the historical
    chunk-by-chunk path: no ledger entries, no preemption state."""
    model, params = model_and_params
    rng = np.random.default_rng(11)
    eng = make_engine(model, params, kv_blocks=64)
    sched = ContinuousBatchingScheduler(eng)
    assert not sched.reservation and not sched.preempt_enabled
    sched.submit(540, rand_prompt(rng, 24), max_new_tokens=4)
    sched.run_to_completion()
    assert eng.state_manager.reserved_sequences == 0
    assert sched.preempt_stats() == {"preempted": 0, "resumed": 0}


# --------------------------------------------------------- serving surface
def test_queue_counts_preempt_pressure_sheds():
    """Overload sheds during a preemption-pressure window count
    ``requests_shed_preempt_pressure``; brownout sheds never do."""
    from deepspeed_tpu.serving import Rejected
    from deepspeed_tpu.serving.metrics import serving_metrics
    from deepspeed_tpu.serving.queue import AdmissionQueue
    from deepspeed_tpu.serving.request import ServingRequest

    def req(prio=1):
        return ServingRequest([1, 2], 4, prio, None, None)

    m = serving_metrics()
    q = AdmissionQueue(1, m)
    q.offer(req())
    with pytest.raises(Rejected):
        q.offer(req())                       # no pressure: plain overload
    assert m.counter("requests_shed_preempt_pressure").value == 0
    q.set_preempt_pressure(True)
    with pytest.raises(Rejected):
        q.offer(req())
    assert m.counter("requests_shed_preempt_pressure").value == 1
    q.set_preempt_pressure(False)
    with pytest.raises(Rejected):
        q.offer(req())
    assert m.counter("requests_shed_preempt_pressure").value == 1


def test_frontend_publishes_preempt_metrics_and_journal(model_and_params):
    """The serving surface of a preempting fleet: sequences_preempted /
    sequences_resumed counters, spill/resume histograms, the
    ``sequence_preempted`` journal event (schema-valid), and the
    health-report occupancy/counter integration."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend
    from deepspeed_tpu.telemetry import validate_events

    model, params = model_and_params
    rng = np.random.default_rng(12)

    def factory(i):
        return make_engine(model, params, kv_blocks=14, max_seqs=8)

    cfg = ServingConfig(
        max_queue_depth=64, prefix_cache={"enabled": True},
        kv_tier={"enabled": True},
        admission={"reservation": True, "oversubscription_factor": 3.0,
                   "preemption": {"enabled": True}})
    fe = ServingFrontend.from_engine_factory(factory, cfg)
    try:
        hb = [fe.submit(rand_prompt(rng, 60), max_new_tokens=24,
                        request_class="batch") for _ in range(4)]
        # the interactive wave goes out when the pool holds a batch
        # sequence 23 tokens from its end, not after a pause the batch
        # wave can finish inside (tests/test_fabric.py's
        # test_preempt_resume_parity waits the same way)
        next(hb[0].stream(timeout=120))
        hi = [fe.submit(rand_prompt(rng, 60), max_new_tokens=4,
                        request_class="interactive") for _ in range(8)]
        assert fe.wait_all(hb + hi, timeout=240)
        snap = fe.metrics_snapshot()
        assert snap["sequences_preempted"] > 0
        assert snap["sequences_resumed"] > 0
        assert snap["preempt_spill_s"]["count"] > 0
        assert snap["preempt_resume_s"]["count"] > 0
        evs = fe.journal.events(kinds=("sequence_preempted",))
        assert evs and evs[0]["detail"]["blocks"] > 0
        assert not validate_events(fe.journal.events())
        rep = fe.health_report()
        assert rep["counters"]["sequences_preempted"] > 0
        assert "preempted_resident_blocks" in rep["occupancy"]
        assert "queue_wait_blocks" in rep["occupancy"]
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_freeable_blocks_excludes_shared_prefix(model_and_params):
    """Victim selection counts only blocks a flush would actually free:
    prefix blocks another live sequence shares return nothing, so a
    mostly-shared victim must not be spilled for headroom that never
    materializes."""
    model, params = model_and_params
    rng = np.random.default_rng(13)
    sys_prompt = rand_prompt(rng, 32)          # 4 shareable full blocks
    eng = make_engine(model, params, kv_blocks=32, prefix=True)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(600, sys_prompt + rand_prompt(rng, 6), max_new_tokens=2)
    sched.run_to_completion()                  # prefix now cached
    # two live sequences sharing the cached prefix
    for uid in (601, 602):
        sched.submit(uid, sys_prompt + rand_prompt(rng, 6),
                     max_new_tokens=30)
        for _ in range(3):
            sched.step()
    sm = eng.state_manager
    for uid in (601, 602):
        total = sm.get_sequence(uid).cur_allocated_blocks
        free = eng.freeable_blocks_of(uid)
        assert free < total            # the 4 shared blocks don't count
        assert total - free == 4
    # a sequence holding only private + cache-only blocks frees them all
    sched.cancel(602)
    assert eng.freeable_blocks_of(601) == \
        sm.get_sequence(601).cur_allocated_blocks


def test_preemption_requires_reservation():
    """preemption without reservation would be silently inert (every
    preemption entry point lives on the reservation branch) — rejected
    at config validation and at the engine hook."""
    import pydantic

    from deepspeed_tpu.serving import ServingConfig

    with pytest.raises(pydantic.ValidationError):
        ServingConfig(admission={"reservation": False,
                                 "preemption": {"enabled": True}})
    with pytest.raises(ValueError):
        AdmissionConfig(preemption={"enabled": True})


def test_engine_configure_admission_guard(model_and_params):
    model, params = model_and_params
    eng = make_engine(model, params)
    with pytest.raises(ValueError):
        eng.configure_admission(False, preemption_enabled=True)


def test_config_wiring():
    """``admission:`` mounts on ServingConfig AND DeepSpeedTpuConfig,
    and ``AdmissionConfig.apply`` stamps a ragged engine config."""
    from deepspeed_tpu.runtime.config import DeepSpeedTpuConfig
    from deepspeed_tpu.serving import ServingConfig

    sc = ServingConfig(admission={"reservation": True,
                                  "oversubscription_factor": 2.5,
                                  "preemption": {"enabled": True,
                                                 "victim_policy":
                                                     "most_blocks",
                                                 "max_preemptions_per_seq":
                                                     3}})
    assert sc.admission.active
    ecfg = RaggedInferenceEngineConfig()
    sc.admission.apply(ecfg)
    assert ecfg.admission_reservation
    assert ecfg.admission_oversubscription_factor == 2.5
    assert ecfg.admission_preemption_enabled
    assert ecfg.admission_victim_policy == "most_blocks"
    assert ecfg.admission_max_preemptions_per_seq == 3
    ds = DeepSpeedTpuConfig(**{
        "train_micro_batch_size_per_gpu": 1,
        "admission": {"reservation": True},
        "serving": {"admission": {"reservation": True,
                                  "preemption": {"enabled": True}}}})
    assert ds.admission.reservation
    assert ds.serving.admission.preemption.enabled
    assert not AdmissionConfig().active
