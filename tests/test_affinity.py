"""Fleet-wide KV locality tests (ISSUE 17, docs/SERVING.md "Fleet KV
locality").

Four layers:

- **Hash/digest layer**: ``chain_hashes`` must agree with the chain the
  engine's prefix index actually holds (``record_tokens``), and the
  digest/export/import trio must round-trip KV *content* — a warmed
  replica has to produce byte-identical greedy tokens, not just index
  hits.
- **Scoring layer**: ``AffinityState.choose`` unit tests — leading-run
  overlap credit, load-vs-credit arbitration, the share cap, and the
  None fallback that keeps the caller's cache-blind pick reachable.
- **Router layer**: the pick path hashes the prompt ONCE per pick at
  fleet size 16, the ``req=None`` free-slot probe never hashes, and a
  router without affinity is the historical least-loaded pick.
- **Policy/frontend layer**: predictive scaling grows strictly earlier
  than the watermark baseline (reason ``predicted_pressure``) without
  adding flapping, shrink never acts on a forecast, grow-path warm-up
  populates the new replica (journal + histogram + digest), and the
  disabled path is the historical stack — no AffinityState, no
  predicted signal, same greedy tokens.
"""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import deepspeed_tpu.serving.affinity as affinity_mod
from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.testing import greedy_generate
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.serving import (AutoscalerConfig, ServingConfig,
                                   ServingFrontend, serving_metrics)
from deepspeed_tpu.serving.affinity import AffinityState, chain_hashes
from deepspeed_tpu.serving.autoscaler import (FleetController, FleetSignals,
                                              ReplicaInfo)
from deepspeed_tpu.serving.config import AffinityConfig
from deepspeed_tpu.serving.queue import AdmissionQueue
from deepspeed_tpu.serving.replica import ReplicaState
from deepspeed_tpu.serving.request import ServingRequest
from deepspeed_tpu.serving.router import ReplicaRouter

VOCAB = 128
BS = 8          # kv block size used throughout

_model = None
_params = None


def tiny_engine(i=0, **cfg_over):
    global _model, _params
    import jax

    if _model is None:
        _model = CausalLM(TransformerConfig(
            vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=2, max_seq_len=256, norm="rmsnorm",
            activation="silu", position="rope"))
        _params = _model.init(jax.random.PRNGKey(0))
    base = dict(max_ragged_batch_size=128, max_ragged_sequence_count=4,
                max_chunk_tokens=32, kv_blocks=64, kv_block_size=BS,
                max_tracked_sequences=32, enable_prefix_cache=True)
    base.update(cfg_over)
    return InferenceEngineV2(_model, params=_params,
                             config=RaggedInferenceEngineConfig(**base))


def prompts_shared(n, seed, shared_len=24, tail=6):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, VOCAB, size=shared_len).tolist()
    return shared, [shared + rng.integers(0, VOCAB, size=tail).tolist()
                    for _ in range(n)]


# ================================================== hash / digest layer
class TestChainHashes:
    def test_agrees_with_recorded_index(self):
        """Every full-block chain hash of a served prompt must appear in
        the engine's digest — the router predicts cache hits from the
        prompt alone, so disagreement here silently zeroes all credit."""
        eng = tiny_engine()
        p = list(range(30))
        greedy_generate(eng, [p], max_new_tokens=4)
        digest = set(eng.prefix_digest())
        want = chain_hashes(p, BS)
        assert want, "prompt long enough for full blocks"
        assert set(want) <= digest

    def test_last_token_excluded_like_match_walk(self):
        # 16 tokens, block 8: the match walk caps at len-1=15, so only
        # the first block is hashable — exactly one chain entry
        assert len(chain_hashes(list(range(16)), BS)) == 1
        assert len(chain_hashes(list(range(17)), BS)) == 2

    def test_short_prompt_has_no_hashes(self):
        assert chain_hashes(list(range(BS)), BS) == []
        assert chain_hashes([], BS) == []

    def test_digest_bounded_and_off_when_cache_off(self):
        eng = tiny_engine()
        ps = [list(range(i, i + 20)) for i in range(6)]
        greedy_generate(eng, ps, max_new_tokens=4)
        assert len(eng.prefix_digest(max_entries=3)) == 3
        cold = tiny_engine(enable_prefix_cache=False)
        assert cold.prefix_digest() == []


class TestWarmupRoundTrip:
    def test_export_import_preserves_tokens(self):
        """The content test: a replica warmed from a donor's exported
        blocks must (a) report the donor's chain hashes in its digest,
        (b) take prefix-cache hits on the donor's traffic, and (c) still
        produce byte-identical greedy tokens — imported KV is real KV,
        not just index entries."""
        shared, ps = prompts_shared(3, seed=11)
        donor = tiny_engine()
        ref = greedy_generate(donor, ps, max_new_tokens=5)

        entries = donor.export_prefix_blocks(max_blocks=32)
        assert entries, "donor exported nothing"

        warmed = tiny_engine()
        assert warmed.prefix_digest() == []
        n = warmed.import_prefix_blocks(entries)
        assert n == len(entries)
        assert set(warmed.prefix_digest()) >= {
            hash(key) for key, _ in entries}

        got = greedy_generate(warmed, ps, max_new_tokens=5, uid_base=100)
        assert got == ref, "warmed replica broke greedy parity"
        assert warmed.prefix_stats()["tokens_saved"] > 0, \
            "warm-up produced no first-request prefix hits"

    def test_import_respects_budget_and_dedup(self):
        donor = tiny_engine()
        _, ps = prompts_shared(2, seed=12)
        greedy_generate(donor, ps, max_new_tokens=4)
        entries = donor.export_prefix_blocks(max_blocks=32)
        warmed = tiny_engine()
        n = warmed.import_prefix_blocks(entries)
        assert n == len(entries)
        assert warmed.import_prefix_blocks(entries) == 0  # all dedup'd
        cold = tiny_engine(enable_prefix_cache=False)
        assert cold.import_prefix_blocks(entries) == 0    # cache off


# ======================================================= scoring layer
def _rep(rid, load=0):
    return SimpleNamespace(replica_id=rid, outstanding_tokens=load)


def _cost(r):
    return (r.outstanding_tokens, r.replica_id)


def _aff(**over):
    base = dict(enabled=True, share_window=8, max_share=0.5,
                refresh_interval_s=1e-9)
    base.update(over)
    return AffinityState(AffinityConfig(**base))


def _req(tokens):
    return ServingRequest(list(tokens), max_new_tokens=4, priority=1,
                          deadline_s=None, eos_token_id=None)


class TestAffinityChoose:
    def test_steers_to_warm_replica_and_counts_tokens(self):
        aff = _aff()
        p = list(range(24))
        hashes = chain_hashes(p, BS)
        r0, r1 = _rep(0), _rep(1)
        aff._digests = {1: frozenset(hashes)}
        best = aff.choose(_req(p), [r0, r1], _cost, BS)
        assert best is r1
        st = aff.stats()
        assert st["hits"] == 1
        assert st["tokens_saved"] == len(hashes) * BS

    def test_no_digest_anywhere_falls_back_none(self):
        aff = _aff()
        assert aff.choose(_req(range(24)), [_rep(0), _rep(1)],
                          _cost, BS) is None
        assert aff.stats()["misses"] == 1

    def test_short_prompt_falls_back_none(self):
        aff = _aff()
        aff._digests = {0: frozenset([1, 2, 3])}
        assert aff.choose(_req(range(BS)), [_rep(0)], _cost, BS) is None

    def test_leading_run_only_no_credit_for_trailing_hits(self):
        # digest holds every hash EXCEPT the first block's: the match
        # walk would stop immediately, so affinity must score zero
        aff = _aff()
        p = list(range(33))
        hashes = chain_hashes(p, BS)
        assert len(hashes) >= 3
        aff._digests = {1: frozenset(hashes[1:])}
        assert aff.choose(_req(p), [_rep(0), _rep(1)], _cost, BS) is None

    def test_load_overrules_small_credit(self):
        # one warm block (8 tokens credit) vs 1000 outstanding tokens:
        # the load term wins and the fleet counts it a miss
        aff = _aff()
        p = list(range(12))
        hashes = chain_hashes(p, BS)
        r0, r1 = _rep(0, load=0), _rep(1, load=1000)
        aff._digests = {1: frozenset(hashes)}
        best = aff.choose(_req(p), [r0, r1], _cost, BS)
        assert best is r0
        assert aff.stats() == {"hits": 0, "misses": 1, "tokens_saved": 0}

    def test_share_cap_diverts_to_second_warmest(self):
        aff = _aff(share_window=8, max_share=0.5)
        p = list(range(24))
        hashes = chain_hashes(p, BS)
        # r1 fully warm, r2 warm for one block, equal load
        aff._digests = {1: frozenset(hashes), 2: frozenset(hashes[:1])}
        reps = [_rep(0), _rep(1), _rep(2)]
        picks = [aff.choose(_req(p), reps, _cost, BS).replica_id
                 for _ in range(8)]
        # r1 takes wins until it owns max_share of the window capacity
        # (4 of 8), then credit zeroes and r2's single block wins
        assert picks[:4] == [1, 1, 1, 1]
        assert set(picks[4:]) == {2}
        counts = aff.share_counts()
        cap = aff.cfg.max_share * aff._recent.maxlen
        assert all(c <= cap for c in counts.values()), counts

    def test_digestless_candidate_is_cache_blind_not_error(self):
        aff = _aff()
        p = list(range(24))
        aff._digests = {1: frozenset(chain_hashes(p, BS))}
        # replica 0 has no digest entry at all: zero credit, no raise
        best = aff.choose(_req(p), [_rep(0), _rep(1)], _cost, BS)
        assert best.replica_id == 1

    def test_refresh_tolerates_sick_replicas(self):
        aff = _aff()

        class Sick:
            replica_id = 0

            def prefix_digest(self, n):
                raise RuntimeError("transport down")

        warm = SimpleNamespace(
            replica_id=1, prefix_digest=lambda n: frozenset([7, 8]))
        bare = SimpleNamespace(replica_id=2)     # no digest surface
        aff.refresh([Sick(), warm, bare], now=1.0)
        assert aff.digest_of(0) == frozenset()
        assert aff.digest_of(1) == frozenset([7, 8])
        assert aff.digest_of(2) == frozenset()


# ========================================================= router layer
class _FakeReplica:
    """Just enough surface for ReplicaRouter.pick: healthy, accepting,
    with a settable load and digest."""

    def __init__(self, rid, load=0, digest=()):
        self.replica_id = rid
        self.model_id = "default"
        self.role = "mixed"
        self.engine = SimpleNamespace(
            config=SimpleNamespace(kv_block_size=BS))
        self.state = ReplicaState.HEALTHY
        self.outstanding_tokens = load
        self.outstanding_prefill_tokens = load
        self.outstanding_decode_tokens = 0
        self.accepting = True
        self.has_capacity = True
        self._digest = frozenset(digest)

    def check_health(self):
        return ReplicaState.HEALTHY

    def prefix_digest(self, max_entries=512):
        return self._digest


def _router(reps, affinity=None):
    return ReplicaRouter(reps, AdmissionQueue(64), affinity=affinity)


class TestRouterPickPath:
    def test_one_hash_pass_per_pick_fleet16(self, monkeypatch):
        """Micro-benchmark of the satellite claim: at fleet size 16 the
        pick path runs exactly ONE chain-hash pass per pick — overlap
        scoring against all 16 digests reuses the memoized hashes."""
        p = list(range(40))
        hashes = chain_hashes(p, BS)
        reps = [_FakeReplica(i, digest=hashes[:1 + i % 3])
                for i in range(16)]
        aff = _aff(share_window=64)
        aff.refresh(reps, now=1.0)
        router = _router(reps, affinity=aff)

        calls = {"n": 0}
        real = affinity_mod.chain_hashes

        def counting(tokens, bs):
            calls["n"] += 1
            return real(tokens, bs)

        monkeypatch.setattr(affinity_mod, "chain_hashes", counting)
        for k in range(10):
            calls["n"] = 0
            assert router.pick(_req(p)) is not None
            assert calls["n"] == 1, f"pick {k} hashed {calls['n']} times"

    def test_free_slot_probe_never_hashes(self, monkeypatch):
        reps = [_FakeReplica(i) for i in range(4)]
        aff = _aff()
        router = _router(reps, affinity=aff)
        calls = {"n": 0}

        def counting(tokens, bs):
            calls["n"] += 1
            return []

        monkeypatch.setattr(affinity_mod, "chain_hashes", counting)
        assert router.pick() is not None            # the _loop probe shape
        assert calls["n"] == 0

    def test_affinity_none_is_least_loaded_pick(self):
        """The disabled path: no AffinityState means pick is the
        historical min-cost selection, even when replicas would have
        had digest overlap."""
        p = list(range(24))
        reps = [_FakeReplica(0, load=10, digest=chain_hashes(p, BS)),
                _FakeReplica(1, load=0)]
        router = _router(reps, affinity=None)
        assert router.pick(_req(p)).replica_id == 1

    def test_affinity_beats_load_tie_and_respects_fallback(self):
        p = list(range(24))
        warm = chain_hashes(p, BS)
        reps = [_FakeReplica(0, load=5), _FakeReplica(1, load=5,
                                                      digest=warm)]
        aff = _aff()
        aff.refresh(reps, now=1.0)
        router = _router(reps, affinity=aff)
        assert router.pick(_req(p)).replica_id == 1
        # a prompt with no hashable prefix falls through to least-loaded
        assert router.pick(_req(range(4))).replica_id == 0


# ================================================ predictive scaling
class _PredictiveFleet:
    """Minimal actuation surface: a mixed fleet whose signals carry a
    settable actual queue depth and predicted depth."""

    def __init__(self, n=1):
        self.n = n
        self.queue_depth = 0
        self.predicted = None
        self.actions = []

    def fleet_signals(self):
        infos = tuple(ReplicaInfo(i, "mixed", True, False, 0, 0)
                      for i in range(self.n))
        return FleetSignals(queue_depth=self.queue_depth, replicas=infos,
                            predicted_queue_depth=self.predicted)

    def add_replica(self, role):
        self.n += 1
        self.actions.append(("add", role))
        return self.n - 1

    def remove_replica(self, rid, reason="scale_down"):
        self.n -= 1
        self.actions.append(("remove", rid, reason))
        return True

    def set_replica_role(self, rid, role):
        return True

    def set_proactive_brownout(self, frac):
        pass


def _controller(fleet, **cfg):
    base = dict(enabled=True, min_replicas=1, max_replicas=4,
                scale_up_queue_per_replica=4.0,
                scale_down_queue_per_replica=0.25,
                scale_down_tokens_per_replica=8.0,
                up_stable_ticks=2, down_stable_ticks=3,
                scale_up_cooldown_s=5.0, scale_down_cooldown_s=10.0,
                tick_interval_s=1.0)
    base.update(cfg)
    return FleetController(AutoscalerConfig(**base), fleet,
                           async_actions=False)


class TestPredictiveScaling:
    def _ramp(self, fleet, ctl, predictive):
        """A load ramp: actual depth crosses the watermark (4/replica)
        only at t=6, but the trend projection sees it from t=0."""
        t = 0.0
        depths = [1, 2, 2, 3, 3, 4, 6, 8, 10, 12]
        first_up = None
        for d in depths:
            fleet.queue_depth = d
            fleet.predicted = 8.0 if predictive else None
            ctl.tick(t)
            if first_up is None and fleet.actions:
                first_up = t
            t += 1.0
        return first_up

    def test_predictive_grows_strictly_earlier_than_watermark(self):
        base_fleet = _PredictiveFleet()
        base_t = self._ramp(base_fleet, _controller(base_fleet),
                            predictive=False)
        pred_fleet = _PredictiveFleet()
        pred_ctl = _controller(pred_fleet)
        pred_t = self._ramp(pred_fleet, pred_ctl, predictive=True)
        assert base_t is not None and pred_t is not None
        assert pred_t < base_t, (pred_t, base_t)
        ups = [d for d in pred_ctl.decision_log
               if d["action"] == "scale_up"]
        assert ups[0]["reason"] == "predicted_pressure"

    def test_watermark_grow_keeps_historical_reason(self):
        fleet = _PredictiveFleet()
        ctl = _controller(fleet)
        fleet.queue_depth = 50          # actual pressure, prediction too
        fleet.predicted = 60.0
        ctl.tick(0.0)
        ctl.tick(1.0)
        ups = [d for d in ctl.decision_log if d["action"] == "scale_up"]
        assert ups and ups[0]["reason"] == "queue_pressure"

    def test_prediction_none_is_watermark_byte_for_byte(self):
        a, b = _PredictiveFleet(), _PredictiveFleet()
        ca, cb = _controller(a), _controller(b)
        for t, d in enumerate([1, 3, 5, 6, 2, 1, 0, 0, 0, 0, 0, 0]):
            a.queue_depth = b.queue_depth = d
            a.predicted = None          # affinity off
            b.predicted = None
            ca.tick(float(t))
            cb.tick(float(t))
        assert a.actions == b.actions
        strip = lambda log: [{k: v for k, v in d.items() if k != "t"}
                             for d in log]
        assert strip(ca.decision_log) == strip(cb.decision_log)

    def test_forecast_never_shrinks_and_never_flaps(self):
        """A spiky prediction over calm actuals may grow (that is its
        job) but must never cause a shrink, and a predicted grow must
        not be immediately reverted (no add->remove->add churn)."""
        fleet = _PredictiveFleet(n=2)
        ctl = _controller(fleet, min_replicas=1)
        t = 0.0
        for step in range(20):
            fleet.queue_depth = 1       # calm actuals, never down_cond
            fleet.predicted = 12.0 if step in (2, 3) else None
            ctl.tick(t)
            t += 1.0
        kinds = [a[0] for a in fleet.actions]
        assert "remove" not in kinds, fleet.actions
        assert kinds.count("add") <= 1
        # and a LOW forecast over genuinely idle actuals still shrinks
        # on the actual watermark only — prediction adds no down force
        for step in range(8):
            fleet.queue_depth = 0
            fleet.predicted = 0.0
            ctl.tick(t)
            t += 1.0
        downs = [d for d in ctl.decision_log
                 if d["action"] == "scale_down"]
        assert all(d["reason"] == "idle" for d in downs)


# ================================================ frontend integration
def _serving_cfg(enabled=True, **aff_over):
    aff = dict(enabled=enabled, refresh_interval_s=0.05,
               warmup_enabled=True, warmup_max_blocks=16)
    aff.update(aff_over)
    return ServingConfig(num_replicas=2, max_queue_depth=64, affinity=aff)


def _run(fe, ps, max_new=4):
    hs = [fe.submit(p, max_new_tokens=max_new) for p in ps]
    assert fe.wait_all(hs, timeout=300), [h.state for h in hs]
    return [[ev.token for ev in h.drain()] for h in hs]


class TestFrontendIntegration:
    def test_disabled_builds_none_of_it(self):
        fe = ServingFrontend.from_engine_factory(tiny_engine,
                                                 _serving_cfg(enabled=False))
        try:
            assert fe._affinity is None
            assert fe.router.affinity is None
            assert fe.fleet_signals().predicted_queue_depth is None
            _, ps = prompts_shared(3, seed=5)
            assert all(len(g) for g in _run(fe, ps))
            snap = fe.metrics.snapshot()
            assert snap.get("router_affinity_hits", 0) == 0
            assert not [e for e in fe.journal.events()
                        if e.get("kind") == "replica_warmup"]
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_enabled_parity_and_hits(self):
        """Affinity changes placement, never tokens: enabled vs disabled
        fleets produce identical greedy streams, and the enabled fleet
        accrues digest-steered hits on shared-prefix traffic."""
        _, ps = prompts_shared(8, seed=6)
        fe_off = ServingFrontend.from_engine_factory(
            tiny_engine, _serving_cfg(enabled=False))
        try:
            ref = _run(fe_off, ps)
        finally:
            fe_off.shutdown(drain=False, timeout=5)

        fe = ServingFrontend.from_engine_factory(tiny_engine,
                                                 _serving_cfg())
        try:
            got = _run(fe, ps)
            assert got == ref, "affinity broke greedy parity"
            time.sleep(0.3)             # a router tick refreshes digests
            got2 = _run(fe, ps)
            assert got2 == ref
            st = fe._affinity.stats()
            assert st["hits"] > 0 and st["tokens_saved"] > 0, st
            snap = fe.metrics.snapshot()
            assert snap["router_affinity_hits"] == st["hits"]
            assert snap["prefix_tokens_saved_fleet"] == st["tokens_saved"]
            cap = (fe.config.affinity.max_share
                   * fe._affinity._recent.maxlen)
            assert all(c <= cap
                       for c in fe._affinity.share_counts().values())
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_add_replica_warms_from_fleet(self):
        fe = ServingFrontend.from_engine_factory(tiny_engine,
                                                 _serving_cfg())
        try:
            _, ps = prompts_shared(6, seed=7)
            _run(fe, ps)
            rid = fe.add_replica()
            evs = [e for e in fe.journal.events()
                   if e.get("kind") == "replica_warmup"]
            assert evs, "no replica_warmup journal event"
            d = evs[-1]["detail"]
            assert d["replica"] == rid and d["blocks"] > 0
            assert d["warmup_s"] >= 0
            new_rep = next(r for r in fe.router.replicas
                           if r.replica_id == rid)
            assert len(new_rep.prefix_digest()) > 0, \
                "warm-up left the grown replica cold"
            snap = fe.metrics.snapshot()
            assert snap["replica_warmup_s"]["count"] >= 1
            assert snap["replicas_warming"] == 0   # inc/dec balanced
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_warmup_off_leaves_grown_replica_cold(self):
        fe = ServingFrontend.from_engine_factory(
            tiny_engine, _serving_cfg(warmup_enabled=False))
        try:
            _, ps = prompts_shared(4, seed=8)
            _run(fe, ps)
            rid = fe.add_replica()
            assert not [e for e in fe.journal.events()
                        if e.get("kind") == "replica_warmup"]
            new_rep = next(r for r in fe.router.replicas
                           if r.replica_id == rid)
            assert new_rep.prefix_digest() == frozenset()
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_predicted_signal_tracks_submission_trend(self):
        fe = ServingFrontend.from_engine_factory(tiny_engine,
                                                 _serving_cfg())
        try:
            _, ps = prompts_shared(6, seed=9)
            _run(fe, ps)
            # (the windowed ring has a rate once it holds two snapshots:
            # a warm run of six prompts can end before the second)
            deadline = time.monotonic() + 10.0
            while (sig := fe.fleet_signals()).predicted_queue_depth is None \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sig.predicted_queue_depth is not None
            assert sig.predicted_queue_depth >= 0
            assert fe.metrics.snapshot()["predicted_load"] >= 0
        finally:
            fe.shutdown(drain=False, timeout=5)
