"""Elastic fleet autoscaling tests (ISSUE 12, docs/SERVING.md "Elastic
autoscaling").

Two layers:

- **Deterministic policy tests**: a :class:`FleetController` driven by a
  fake clock against a fake fleet — hysteresis/cooldown boundaries,
  min/max clamps, shrink-prefers-parked, decode-capability floors,
  re-role flap suppression, proactive-brownout hysteresis. No threads,
  no engines, no sleeps.
- **Live-stack tests** over tiny engines: dynamic membership under
  traffic (add/remove with resident KV — losslessness asserted against
  uncontended greedy references), the supervisor retirement race guard
  (a pending/in-flight restart must never resurrect a removed slot),
  and an end-to-end elastic frontend whose journal matches the
  controller's decision log exactly.
"""

import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.inference.v2.testing import greedy_generate
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.serving import (AutoscalerConfig, ServingConfig,
                                   ServingFrontend, serving_metrics)
from deepspeed_tpu.serving.autoscaler import (FleetController, FleetSignals,
                                              ReplicaInfo)
from deepspeed_tpu.serving.router import DECODE_CAPABLE, PREFILL_CAPABLE
from deepspeed_tpu.telemetry import OpsJournal, validate_events

VOCAB = 128

_model = None
_params = None


def tiny_engine(i=0, kv_blocks=64, max_seqs=4):
    global _model, _params
    if _model is None:
        _model = CausalLM(TransformerConfig(
            vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=2, max_seq_len=256, norm="rmsnorm",
            activation="silu", position="rope"))
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=max_seqs,
        max_chunk_tokens=32, kv_blocks=kv_blocks, kv_block_size=8,
        max_tracked_sequences=32)
    eng = InferenceEngineV2(_model, params=_params, config=vcfg)
    _params = eng.params
    return eng


def prompts(n, seed, lo=8, hi=20):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=int(length)).tolist()
            for length in rng.integers(lo, hi, size=n)]


# ------------------------------------------------------------ policy layer
class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


class FakeFleet:
    """Actuation surface the policy tests drive the controller against."""

    def __init__(self, replicas, disaggregated=False,
                 prefill_cost=1.0, decode_cost=8.0):
        # rid -> dict(role, accepting, parked, pre, dec)
        self.replicas = {rid: dict(spec) for rid, spec in replicas.items()}
        self.next_id = max(replicas) + 1 if replicas else 0
        self.disaggregated = disaggregated
        self.prefill_cost = prefill_cost
        self.decode_cost = decode_cost
        self.queue_depth = 0
        self.burn_slow = 0.0
        self.brownout = None
        self.actions = []

    @staticmethod
    def rep(role="mixed", accepting=True, parked=False, pre=0, dec=0):
        return dict(role=role, accepting=accepting, parked=parked,
                    pre=pre, dec=dec)

    def fleet_signals(self):
        infos = tuple(
            ReplicaInfo(rid, r["role"], r["accepting"], r["parked"],
                        r["pre"], r["dec"])
            for rid, r in sorted(self.replicas.items()))
        return FleetSignals(queue_depth=self.queue_depth, replicas=infos,
                            burn_slow_max=self.burn_slow,
                            prefill_token_cost=self.prefill_cost,
                            decode_token_cost=self.decode_cost,
                            disaggregated=self.disaggregated)

    def add_replica(self, role):
        rid = self.next_id
        self.next_id += 1
        self.replicas[rid] = self.rep(role=role)
        self.actions.append(("add", rid, role))
        return rid

    def remove_replica(self, rid, reason="scale_down"):
        del self.replicas[rid]
        self.actions.append(("remove", rid, reason))
        return True

    def set_replica_role(self, rid, role):
        self.actions.append(("rerole", rid, self.replicas[rid]["role"],
                             role))
        self.replicas[rid]["role"] = role
        return True

    def set_proactive_brownout(self, frac):
        self.brownout = frac
        self.actions.append(("brownout", frac))


def make_controller(fleet, clock, journal=None, **cfg):
    base = dict(enabled=True, min_replicas=1, max_replicas=4,
                scale_up_queue_per_replica=4.0,
                scale_down_queue_per_replica=0.25,
                scale_down_tokens_per_replica=8.0,
                up_stable_ticks=2, down_stable_ticks=3,
                scale_up_cooldown_s=5.0, scale_down_cooldown_s=10.0,
                tick_interval_s=1.0, rerole_ratio=4.0,
                rerole_stable_ticks=3, rerole_cooldown_s=10.0,
                brownout_burn_threshold=2.0, brownout_fraction=0.5)
    base.update(cfg)
    return FleetController(AutoscalerConfig(**base), fleet, clock=clock,
                           journal=journal, async_actions=False)


class TestScalePolicy:
    def test_scale_up_needs_stable_ticks_then_respects_cooldown(self):
        clock = FakeClock()
        fleet = FakeFleet({0: FakeFleet.rep()})
        ctl = make_controller(fleet, clock, up_stable_ticks=2,
                              scale_up_cooldown_s=5.0)
        fleet.queue_depth = 50                      # way past the watermark
        ctl.tick(clock.t)                           # streak 1: no action
        assert len(fleet.replicas) == 1
        ctl.tick(clock.advance(1.0))                # streak 2: scale up
        assert len(fleet.replicas) == 2
        # pressure persists but the up cooldown gates the next step
        for _ in range(4):
            ctl.tick(clock.advance(1.0))            # t = 2..5 (< 1 + 5)
        assert len(fleet.replicas) == 2
        ctl.tick(clock.advance(1.0))                # t = 6: cooled + stable
        assert len(fleet.replicas) == 3
        ups = [d for d in ctl.decision_log if d["action"] == "scale_up"]
        assert len(ups) == 2
        assert all(d["reason"] == "queue_pressure" for d in ups)

    def test_one_calm_tick_resets_the_up_streak(self):
        clock = FakeClock()
        fleet = FakeFleet({0: FakeFleet.rep()})
        ctl = make_controller(fleet, clock, up_stable_ticks=3)
        fleet.queue_depth = 50
        ctl.tick(clock.t)
        ctl.tick(clock.advance(1.0))
        fleet.queue_depth = 0                       # blip clears
        ctl.tick(clock.advance(1.0))
        fleet.queue_depth = 50
        ctl.tick(clock.advance(1.0))
        ctl.tick(clock.advance(1.0))
        assert len(fleet.replicas) == 1             # streak restarted
        ctl.tick(clock.advance(1.0))
        assert len(fleet.replicas) == 2

    def test_max_replicas_clamp(self):
        clock = FakeClock()
        fleet = FakeFleet({i: FakeFleet.rep() for i in range(3)})
        ctl = make_controller(fleet, clock, max_replicas=3,
                              up_stable_ticks=1, scale_up_cooldown_s=0.0)
        fleet.queue_depth = 500
        for _ in range(10):
            ctl.tick(clock.advance(1.0))
        assert len(fleet.replicas) == 3
        assert list(ctl.decision_log) == []

    def test_scale_down_to_min_and_not_past_it(self):
        clock = FakeClock()
        fleet = FakeFleet({i: FakeFleet.rep() for i in range(3)})
        ctl = make_controller(fleet, clock, min_replicas=1,
                              down_stable_ticks=3,
                              scale_down_cooldown_s=4.0)
        # idle fleet: queue empty, no outstanding work
        for _ in range(30):
            ctl.tick(clock.advance(1.0))
        assert len(fleet.replicas) == 1
        downs = [d for d in ctl.decision_log
                 if d["action"] == "scale_down"]
        assert len(downs) == 2
        # cooldown respected between the two removals
        assert downs[1]["t"] - downs[0]["t"] >= 4.0

    def test_below_min_repairs_regardless_of_load(self):
        clock = FakeClock()
        fleet = FakeFleet({0: FakeFleet.rep()})
        ctl = make_controller(fleet, clock, min_replicas=2)
        ctl.tick(clock.t)                           # no streak needed
        assert len(fleet.replicas) == 2
        assert ctl.decision_log[0]["reason"] == "below_min"

    def test_shrink_prefers_parked_slots(self):
        clock = FakeClock()
        fleet = FakeFleet({
            0: FakeFleet.rep(pre=0, dec=0),
            1: FakeFleet.rep(accepting=False, parked=True),
            2: FakeFleet.rep(pre=0, dec=5)})
        ctl = make_controller(fleet, clock, down_stable_ticks=1,
                              scale_down_cooldown_s=0.0,
                              scale_down_tokens_per_replica=100.0)
        ctl.tick(clock.advance(1.0))
        assert 1 not in fleet.replicas              # the corpse went first
        assert len(fleet.replicas) == 2

    def test_shrink_skips_last_decode_capable(self):
        clock = FakeClock()
        fleet = FakeFleet({
            0: FakeFleet.rep(role="prefill", pre=3),
            1: FakeFleet.rep(role="decode", dec=0),     # least loaded!
            2: FakeFleet.rep(role="prefill", pre=9)},
            disaggregated=True)
        ctl = make_controller(fleet, clock, down_stable_ticks=1,
                              scale_down_cooldown_s=0.0,
                              scale_down_queue_per_replica=100.0,
                              scale_down_tokens_per_replica=100.0)
        ctl.tick(clock.advance(1.0))
        # replica 1 is idler but is the only decode-capable: replica 0
        # (least-loaded prefill) is removed instead
        assert 1 in fleet.replicas and 0 not in fleet.replicas

    def test_pressure_at_max_evicts_parked_corpse_then_grows(self):
        """At max_replicas with a parked corpse aboard, sustained queue
        pressure first evicts the corpse (zero-cost seat) and then
        grows live capacity into the freed seat — the fleet is never
        pinned below max by a circuit-broken slot."""
        clock = FakeClock()
        fleet = FakeFleet({
            0: FakeFleet.rep(accepting=False, parked=True),
            1: FakeFleet.rep(), 2: FakeFleet.rep()})
        ctl = make_controller(fleet, clock, max_replicas=3,
                              up_stable_ticks=1,
                              scale_up_cooldown_s=1.0)
        fleet.queue_depth = 100
        ctl.tick(clock.advance(1.0))
        assert 0 not in fleet.replicas          # corpse evicted first
        assert ctl.decision_log[-1]["reason"] == "evict_parked"
        ctl.tick(clock.advance(1.0))            # cooled: grow into seat
        assert len(fleet.replicas) == 3
        assert ctl.decision_log[-1]["action"] == "scale_up"

    def test_grow_role_follows_dominant_phase_load(self):
        clock = FakeClock()
        fleet = FakeFleet({0: FakeFleet.rep(role="prefill", pre=100),
                           1: FakeFleet.rep(role="decode", dec=1)},
                          disaggregated=True, prefill_cost=1.0,
                          decode_cost=8.0)
        ctl = make_controller(fleet, clock, up_stable_ticks=1,
                              scale_up_cooldown_s=0.0)
        fleet.queue_depth = 100
        ctl.tick(clock.advance(1.0))
        assert fleet.actions[-1] == ("add", 2, "prefill")


class TestRerolePolicy:
    def _fleet(self, pre, dec, n_decode=2):
        reps = {0: FakeFleet.rep(role="prefill", pre=pre)}
        for i in range(n_decode):
            reps[1 + i] = FakeFleet.rep(role="decode", dec=dec)
        return FakeFleet(reps, disaggregated=True, prefill_cost=1.0,
                         decode_cost=1.0)

    def test_stable_imbalance_reroles_once_then_cools_down(self):
        clock = FakeClock()
        fleet = self._fleet(pre=100, dec=1)
        ctl = make_controller(fleet, clock, rerole_stable_ticks=3,
                              rerole_cooldown_s=10.0,
                              scale_up_queue_per_replica=1e9)
        for _ in range(2):
            ctl.tick(clock.advance(1.0))
        assert not any(a[0] == "rerole" for a in fleet.actions)
        ctl.tick(clock.advance(1.0))                # 3rd stable tick
        reroles = [a for a in fleet.actions if a[0] == "rerole"]
        # ties on load break toward the NEWEST replica (highest id)
        assert reroles == [("rerole", 2, "decode", "prefill")]
        # imbalance persists, but the cooldown holds the next flip
        for _ in range(8):
            ctl.tick(clock.advance(1.0))
        assert len([a for a in fleet.actions if a[0] == "rerole"]) == 1

    def test_oscillating_imbalance_never_reroles(self):
        clock = FakeClock()
        fleet = self._fleet(pre=100, dec=1)
        ctl = make_controller(fleet, clock, rerole_stable_ticks=2,
                              rerole_cooldown_s=0.0,
                              scale_up_queue_per_replica=1e9)
        for i in range(12):
            # flip the dominant phase every tick: the signed streak
            # resets on every direction change
            pre, dec = (100, 1) if i % 2 == 0 else (1, 100)
            fleet.replicas[0].update(pre=pre, dec=0)
            for rid in (1, 2):
                fleet.replicas[rid].update(dec=dec, pre=0)
            ctl.tick(clock.advance(1.0))
        assert not any(a[0] == "rerole" for a in fleet.actions)

    def test_rerole_never_strands_decode(self):
        clock = FakeClock()
        fleet = self._fleet(pre=100, dec=1, n_decode=1)
        ctl = make_controller(fleet, clock, rerole_stable_ticks=1,
                              rerole_cooldown_s=0.0,
                              scale_up_queue_per_replica=1e9)
        for _ in range(5):
            ctl.tick(clock.advance(1.0))
        # the only decode replica may never flip to prefill
        assert not any(a[0] == "rerole" for a in fleet.actions)


class TestProactiveBrownout:
    def test_activates_before_alert_and_deactivates_with_hysteresis(self):
        clock = FakeClock()
        fleet = FakeFleet({0: FakeFleet.rep()})
        journal = OpsJournal(capacity=64)
        ctl = make_controller(fleet, clock, journal=journal,
                              brownout_burn_threshold=2.0,
                              brownout_fraction=0.5)
        fleet.burn_slow = 1.9
        ctl.tick(clock.advance(1.0))
        assert fleet.brownout is None               # below threshold
        fleet.burn_slow = 2.1
        ctl.tick(clock.advance(1.0))
        assert fleet.brownout == 0.5                # proactive, pre-breach
        fleet.burn_slow = 1.5                       # above thr/2: held
        ctl.tick(clock.advance(1.0))
        assert fleet.brownout == 0.5
        fleet.burn_slow = 0.9                       # below thr/2: released
        ctl.tick(clock.advance(1.0))
        assert fleet.brownout is None
        evs = journal.events(kinds=("brownout_proactive",))
        assert [e["detail"]["active"] for e in evs] == [True, False]
        assert validate_events(journal.events()) == []


class TestConfigValidation:
    def test_min_replicas_floor(self):
        with pytest.raises(Exception, match="min_replicas"):
            AutoscalerConfig(min_replicas=0)

    def test_max_at_least_min(self):
        with pytest.raises(Exception, match="max_replicas"):
            AutoscalerConfig(min_replicas=3, max_replicas=2)

    def test_brownout_fraction_range(self):
        with pytest.raises(Exception, match="brownout_fraction"):
            AutoscalerConfig(brownout_fraction=0.0)

    def test_role_constants_agree_with_router(self):
        from deepspeed_tpu.serving.autoscaler import _DECODE_CAPABLE

        assert tuple(_DECODE_CAPABLE) == tuple(DECODE_CAPABLE)
        assert set(PREFILL_CAPABLE) == {"prefill", "mixed"}


# ------------------------------------------------------- metrics satellite
class TestFleetShapeObservability:
    def test_registry_predeclares_fleet_gauges(self):
        names = serving_metrics().names()
        for g in ("replicas_target", "replicas_role_prefill",
                  "replicas_role_decode", "replicas_role_mixed",
                  "brownout_proactive_active"):
            assert g in names["gauges"], g
        assert "requests_evacuated" in names["counters"]

    def test_role_census_and_gauges_live(self):
        scfg = ServingConfig(
            max_queue_depth=16,
            disaggregation={"enabled": True,
                            "roles": ["prefill", "decode"]})
        fe = ServingFrontend([tiny_engine(0), tiny_engine(1)], scfg)
        try:
            assert fe.router.role_census() == {"prefill": 1, "decode": 1,
                                               "mixed": 0}
            hs = [fe.submit(p, max_new_tokens=3) for p in prompts(3, 0)]
            assert fe.wait_all(hs, timeout=120)
            deadline = time.monotonic() + 10
            snap = {}
            while time.monotonic() < deadline:
                snap = fe.metrics_snapshot()
                if snap.get("replicas_role_prefill") == 1.0:
                    break
                time.sleep(0.02)
            assert snap["replicas_role_prefill"] == 1.0
            assert snap["replicas_role_decode"] == 1.0
            assert snap["replicas_role_mixed"] == 0.0
            assert snap["replicas_target"] == 2.0
        finally:
            fe.shutdown(drain=False, timeout=5)


# ------------------------------------------------------------- live stack
class TestDynamicMembership:
    def test_add_replica_serves_traffic(self):
        fe = ServingFrontend([tiny_engine(0)],
                             ServingConfig(max_queue_depth=64),
                             engine_factory=tiny_engine)
        try:
            rid = fe.add_replica()
            assert rid == 1
            assert len(fe.router.replicas) == 2
            hs = [fe.submit(p, max_new_tokens=4) for p in prompts(8, 1)]
            assert fe.wait_all(hs, timeout=300)
            snap = fe.metrics_snapshot()
            assert snap["requests_completed"] == 8
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_remove_last_replica_impossible(self):
        fe = ServingFrontend([tiny_engine(0)],
                             ServingConfig(max_queue_depth=16))
        try:
            with pytest.raises(ValueError, match="last"):
                fe.remove_replica(0)
            assert len(fe.router.replicas) == 1
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_autoscaler_requires_engine_factory(self):
        with pytest.raises(ValueError, match="engine_factory"):
            ServingFrontend([tiny_engine(0)], ServingConfig(
                max_queue_depth=16, autoscaler={"enabled": True}))

    def test_remove_busy_replica_is_lossless(self):
        """Drain-with-resident-KV (the acceptance criterion): removing
        a replica with in-flight sequences mid-decode completes them
        losslessly on the survivor — every stream byte-identical to an
        uncontended greedy run — with at least one request actually
        evacuated (KV export + staged re-import or re-prefill)."""
        ps = prompts(6, 7, lo=10, hi=16)
        max_new = 48
        # uncontended greedy reference, one sequence at a time
        ref_sched = ContinuousBatchingScheduler(tiny_engine(90))
        ref = []
        for i, p in enumerate(ps):
            ref_sched.submit(500 + i, p, max_new_tokens=max_new)
            ref_sched.run_to_completion()
            ref.append(ref_sched.finished[500 + i].generated)

        fe = ServingFrontend([tiny_engine(0), tiny_engine(1)],
                             ServingConfig(max_queue_depth=64),
                             engine_factory=tiny_engine)
        try:
            hs = [fe.submit(p, max_new_tokens=max_new) for p in ps]
            # wait until replica 0 is genuinely mid-flight, then pull it
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                r0 = fe.router.replica_by_id(0)
                if r0 is not None and len(r0._active) > 0 and \
                        fe.metrics.counter("tokens_generated").value > 0:
                    break
                time.sleep(0.002)
            fe.remove_replica(0)
            assert len(fe.router.replicas) == 1
            assert fe.wait_all(hs, timeout=300)
            gens = [[ev.token for ev in h.drain()] for h in hs]
            assert gens == ref, "evacuation broke greedy byte-parity"
            snap = fe.metrics_snapshot()
            assert snap["requests_evacuated"] >= 1
            assert snap["requests_completed"] == len(ps)
            assert snap["requests_failed"] == 0
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_rerole_live_flips_scheduler_shape(self):
        scfg = ServingConfig(
            max_queue_depth=32,
            disaggregation={"enabled": True,
                            "roles": ["prefill", "decode"],
                            "handoff": {"enabled": True}})
        fe = ServingFrontend([tiny_engine(0), tiny_engine(1)], scfg,
                             engine_factory=tiny_engine)
        try:
            hs = [fe.submit(p, max_new_tokens=4) for p in prompts(4, 3)]
            assert fe.wait_all(hs, timeout=300)
            assert fe.set_replica_role(0, "mixed") is True
            r0 = fe.router.replica_by_id(0)
            assert r0.role == "mixed"
            assert r0.scheduler.prefill_only is False
            assert fe.router.role_census()["mixed"] == 1
            # the re-roled fleet still serves, losslessly
            hs = [fe.submit(p, max_new_tokens=4) for p in prompts(4, 5)]
            assert fe.wait_all(hs, timeout=300)
            assert fe.set_replica_role(0, "mixed") is False  # no-op
        finally:
            fe.shutdown(drain=False, timeout=5)


class TestRetirementRaceGuard:
    def test_retire_cancels_pending_restart(self):
        """A slot whose restart is still in backoff is retired: the
        restart must never fire — no replacement, no journal event, no
        resurrected capacity (the PR 5 shutdown-race guard extended to
        per-slot retirement)."""
        scfg = ServingConfig(
            max_queue_depth=32,
            fault_tolerance={"enabled": True, "restart_backoff_s": 0.4,
                             "restart_backoff_jitter": 0.0,
                             "supervisor_poll_s": 0.01,
                             "max_restarts_in_window": 10},
            faults={"enabled": True, "schedule": [
                {"kind": "crash", "replica": 0, "at_step": 0}]})
        fe = ServingFrontend([tiny_engine(0), tiny_engine(1)], scfg,
                             engine_factory=tiny_engine)
        try:
            hs = []
            for p in prompts(6, 11):
                try:
                    hs.append(fe.submit(p, max_new_tokens=4))
                except Exception:
                    pass
            # wait for the crash to be noticed (restart scheduled)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if fe.supervisor.recovery_pending():
                    break
                time.sleep(0.005)
            assert fe.supervisor.recovery_pending()
            fe.remove_replica(0)                 # retires the slot
            assert not fe.supervisor.recovery_pending()
            fe.wait_all(hs, timeout=120)
            time.sleep(1.0)                      # past the backoff
            assert fe.router.replica_by_id(0) is None
            assert len(fe.router.replicas) == 1
            assert fe.supervisor.restart_log == []
            assert fe.journal.count("replica_restart") == 0
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_retire_mid_build_drops_replacement(self):
        """Retirement landing while the restart's engine build is IN
        FLIGHT: the finished replacement must be dropped, never
        installed or started."""
        release = threading.Event()
        building = threading.Event()

        def slow_factory(i):
            building.set()
            assert release.wait(30)
            return tiny_engine(i)

        scfg = ServingConfig(
            max_queue_depth=32,
            fault_tolerance={"enabled": True, "restart_backoff_s": 0.01,
                             "restart_backoff_jitter": 0.0,
                             "supervisor_poll_s": 0.01,
                             "max_restarts_in_window": 10},
            faults={"enabled": True, "schedule": [
                {"kind": "crash", "replica": 0, "at_step": 0}]})
        fe = ServingFrontend([tiny_engine(0), tiny_engine(1)], scfg,
                             engine_factory=slow_factory)
        try:
            for p in prompts(4, 13):
                try:
                    fe.submit(p, max_new_tokens=3)
                except Exception:
                    pass
            assert building.wait(30), "restart build never started"
            fe.remove_replica(0)                 # retire mid-build
            release.set()
            time.sleep(0.5)                      # let the build finish
            assert fe.router.replica_by_id(0) is None
            assert len(fe.router.replicas) == 1
            assert fe.supervisor.restart_log == []
            assert not any(t.name == "serving-replica-0" and t.is_alive()
                           for t in threading.enumerate())
        finally:
            release.set()
            fe.shutdown(drain=False, timeout=5)


class TestElasticEndToEnd:
    def test_controller_scales_up_and_down_with_journal_parity(self):
        """A 1-replica fleet under a queue burst grows; once idle it
        shrinks back to min. Journal events match the controller's
        decision log exactly — one scale_up/scale_down event per
        completed action."""
        scfg = ServingConfig(
            max_queue_depth=256,
            autoscaler={"enabled": True, "min_replicas": 1,
                        "max_replicas": 3,
                        "scale_up_queue_per_replica": 2.0,
                        "scale_down_queue_per_replica": 0.25,
                        "scale_down_tokens_per_replica": 1.0,
                        "up_stable_ticks": 1, "down_stable_ticks": 2,
                        "scale_up_cooldown_s": 0.1,
                        "scale_down_cooldown_s": 0.2,
                        "tick_interval_s": 0.05})
        fe = ServingFrontend(
            [tiny_engine(0, max_seqs=2)],
            scfg, engine_factory=lambda i: tiny_engine(i, max_seqs=2))
        try:
            ps = prompts(24, 17)
            ref = greedy_generate(tiny_engine(90), ps, uid_base=500,
                                  max_new_tokens=24)
            hs = [fe.submit(p, max_new_tokens=24) for p in ps]
            assert fe.wait_all(hs, timeout=600)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                stats = fe.autoscaler.stats()
                if stats["scale_ups"] >= 1 and \
                        len(fe.router.replicas) == 1:
                    break
                time.sleep(0.05)
            stats = fe.autoscaler.stats()
            assert stats["scale_ups"] >= 1, "burst never grew the fleet"
            assert stats["scale_downs"] >= 1, "idle never shrank it"
            assert len(fe.router.replicas) == 1
            assert stats["peak_replicas"] >= 2
            assert stats["replica_seconds"] > 0
            # journal <-> decision-log exact-once parity
            log = [d for d in fe.autoscaler.decision_log
                   if d["action"] in ("scale_up", "scale_down")]
            evs = fe.journal.events(kinds=("scale_up", "scale_down"))
            assert [(e["kind"], e["detail"]["replica"]) for e in evs] \
                == [(d["action"], d["replica"]) for d in log]
            assert validate_events(fe.journal.events()) == []
            snap = fe.metrics_snapshot()
            assert snap["requests_completed"] == 24
            # every elastic stream (evacuated-and-resumed ones included)
            # is the uncontended greedy stream of its prompt
            assert [[ev.token for ev in h.drain()] for h in hs] == ref
            # the actuation surface reaches the health report too
            rep = fe.health_report()
            assert rep["autoscaler"]["scale_ups"] >= 1
            assert rep["autoscaler"]["replicas_target"] == 1.0
            assert "autoscaler: target=1" in fe.health_report_text()
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_disabled_block_is_the_static_stack(self):
        """``autoscaler: {enabled: false}`` is byte-for-byte a config
        that never heard of the block: no controller, the same greedy
        streams."""
        ps = prompts(6, 23)

        def gens(extra):
            fe = ServingFrontend([tiny_engine(0)],
                                 ServingConfig(max_queue_depth=64, **extra))
            try:
                assert fe.autoscaler is None
                hs = [fe.submit(p, max_new_tokens=8) for p in ps]
                assert fe.wait_all(hs, timeout=300)
                return [[ev.token for ev in h.drain()] for h in hs]
            finally:
                fe.shutdown(drain=False, timeout=5)

        assert gens({"autoscaler": {"enabled": False}}) == gens({})
