"""Training chaos suite: preemption-safe self-healing training
(runtime/resilience.py, docs/TRAINING.md "Fault tolerance").

The training counterpart of tests/test_fault_tolerance.py: a seeded
fault injector kills/wedges/poisons a supervised train run at scripted
steps and the suite asserts recovery — including the hard contract that
an interrupted+resumed run reproduces the uninterrupted loss curve
byte-for-byte and lands on identical final params.
`TIER1_ARGS=tests/test_train_resilience.py bash scripts/tier1.sh` runs
exactly this file.
"""

import os

import jax
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.parallel.topology as topo
from deepspeed_tpu.models import build_model
from deepspeed_tpu.runtime.dataloader import DeepSpeedTpuDataLoader
from deepspeed_tpu.runtime.resilience import (InjectedTrainFault,
                                              ResilienceConfig, StepWatchdog,
                                              TrainFaultInjector,
                                              TrainingSupervisor)

N_STEPS = 8


def tiny_data(n=64, seq=32, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(n, seq + 1),
                                      dtype=np.int64)}


def make_config(save_dir, faults=None, **res_over):
    res = {"enabled": True, "save_dir": str(save_dir),
           "save_interval_steps": 2, "restart_backoff_s": 0.01,
           "restart_backoff_jitter": 0.0, "watchdog_enabled": False,
           "faults": faults or {"enabled": False}}
    res.update(res_over)
    return {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_max_lr": 1e-3,
                                 "warmup_num_steps": 5}},
        "zero_optimization": {"stage": 1},
        "mesh": {"data": -1, "fsdp": 2},
        "steps_per_print": 10**9,
        "resilience": res,
    }


def build_engine(save_dir, faults=None, data=None, **res_over):
    topo.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=build_model("tiny"),
        config=make_config(save_dir, faults, **res_over),
        training_data=data if data is not None else tiny_data())
    return engine


def params_of(engine):
    return [np.asarray(l) for l in jax.tree.leaves(engine.state.params)]


def assert_same_params(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """Uninterrupted supervised run: per-step losses + final params —
    the parity baseline every chaos scenario is judged against."""
    d = tmp_path_factory.mktemp("ref")
    engine = build_engine(d)
    sup = TrainingSupervisor(engine=engine)
    r = sup.run(N_STEPS)
    assert r["status"] == "completed" and r["completed_steps"] == N_STEPS
    return {"losses": sup.losses_by_step(), "params": params_of(engine)}


# ------------------------------------------------------------- injector units
class TestInjector:
    def test_schedule_validation(self):
        with pytest.raises(ValueError, match="unknown train fault kind"):
            TrainFaultInjector([{"kind": "meteor", "at_step": 1}])
        with pytest.raises(ValueError, match="needs at_step"):
            TrainFaultInjector([{"kind": "crash"}])

    def test_crash_raises_and_counts(self):
        inj = TrainFaultInjector([{"kind": "crash", "at_step": 3}])
        assert inj.on_step(2) == []
        with pytest.raises(InjectedTrainFault):
            inj.on_step(3)
        # count=1: fired once, never again (the restarted run passes)
        assert inj.on_step(4) == []
        assert [e[:2] for e in inj.fired_events()] == [("crash", 3)]

    def test_seeded_step_range_is_deterministic(self):
        a = TrainFaultInjector([{"kind": "crash",
                                 "at_step_range": [10, 100]}], seed=7)
        b = TrainFaultInjector([{"kind": "crash",
                                 "at_step_range": [10, 100]}], seed=7)
        c = TrainFaultInjector([{"kind": "crash",
                                 "at_step_range": [10, 100]}], seed=8)
        assert a.events[0].at_step == b.events[0].at_step
        assert 10 <= a.events[0].at_step <= 100
        assert (a.events[0].at_step != c.events[0].at_step
                or a.seed != c.seed)

    def test_coscheduled_events_survive_a_crash(self):
        """A crash raises LAST: sigterm/nan_grads scheduled at the same
        step are delivered via the handler first, not silently consumed
        by the raise (their fired count would otherwise be burned)."""
        inj = TrainFaultInjector([
            {"kind": "sigterm", "at_step": 5},
            {"kind": "crash", "at_step": 5}])
        seen = []
        with pytest.raises(InjectedTrainFault):
            inj.on_step(5, handler=lambda ev: seen.append(ev.kind))
        assert seen == ["sigterm"]

    def test_count_zero_fires_every_time(self):
        inj = TrainFaultInjector([{"kind": "nan_grads", "at_step": 2,
                                   "count": 0}])
        for step in (2, 3, 4):
            evs = inj.on_step(step)
            assert [e.kind for e in evs] == ["nan_grads"]

    def test_disabled_config_builds_nothing(self):
        cfg = ResilienceConfig(faults={"enabled": False, "schedule": [
            {"kind": "crash", "at_step": 1}]})
        assert cfg.faults.build_injector() is None


# ------------------------------------------------------------ watchdog units
class TestWatchdog:
    def test_auto_baseline_arms_after_min_samples(self):
        wd = StepWatchdog(step_timeout_s=0.0, factor=10.0, min_samples=3)
        assert wd.timeout_s() is None
        for dt in (0.01, 0.02, 0.03):
            wd.step_end(dt)
        assert wd.timeout_s() == pytest.approx(0.2)

    def test_fixed_floor_combines_with_median(self):
        """The documented contract: max(step_timeout_s, factor x rolling
        median) — the fixed value is a floor, not an override that turns
        the adaptive threshold off."""
        wd = StepWatchdog(step_timeout_s=0.5, factor=10.0, min_samples=3)
        assert wd.timeout_s() == 0.5        # floor alone before arming
        for dt in (0.1, 0.1, 0.1):
            wd.step_end(dt)
        assert wd.timeout_s() == pytest.approx(1.0)     # max(0.5, 10x0.1)
        wd2 = StepWatchdog(step_timeout_s=5.0, factor=10.0, min_samples=3)
        for dt in (0.1, 0.1, 0.1):
            wd2.step_end(dt)
        assert wd2.timeout_s() == 5.0       # floor dominates a low median

    def test_fixed_timeout_detects_wedge(self):
        wd = StepWatchdog(poll_s=0.01, step_timeout_s=0.05)
        wd.start()
        try:
            wd.step_begin()
            assert wd.wedged.wait(2.0), "watchdog missed the wedged step"
        finally:
            wd.stop()

    def test_completed_steps_do_not_trip(self):
        wd = StepWatchdog(poll_s=0.01, step_timeout_s=0.05)
        wd.start()
        try:
            for _ in range(5):
                wd.step_begin()
                wd.step_end(0.001)
            import time
            time.sleep(0.15)
            assert not wd.wedged.is_set()
        finally:
            wd.stop()

    def test_curriculum_recompile_step_is_exempt(self):
        """A step that changes the curriculum difficulty recompiles
        (minutes vs a sub-second rolling median): the supervisor exempts
        exactly that step from the wedge bracket so a healthy run is not
        parked mid-compile."""
        class Sched:
            def get_difficulty(self, step):
                return 8 if step < 5 else 16

        class WithCurriculum:
            curriculum_scheduler = Sched()

        class NoCurriculum:
            curriculum_scheduler = None

        expect = TrainingSupervisor._expect_recompile
        assert expect(WithCurriculum(), 4)          # 4 -> 5 boundary
        assert not expect(WithCurriculum(), 3)      # steady difficulty
        assert not expect(WithCurriculum(), 6)
        assert not expect(NoCurriculum(), 4)
        assert not expect(object(), 4)              # no scheduler attr

        class Broken:
            class curriculum_scheduler:             # noqa: N801
                @staticmethod
                def get_difficulty(step):
                    raise RuntimeError("no custom fn")

        # a broken schedule fails inside train_batch with its real
        # error, never inside the probe
        assert not expect(Broken(), 4)


# ------------------------------------------------------- shared restart policy
class TestRestartPolicy:
    def test_backoff_breaker_and_window(self):
        import random

        from deepspeed_tpu.utils.restart import RestartPolicy

        p = RestartPolicy(1.0, 8.0, 0.0, 3, 100.0, random.Random(0))
        assert p.record_failure(0.0) == (1, 1.0)    # base
        assert p.record_failure(1.0) == (2, 2.0)    # doubled
        assert p.record_failure(2.0) == (3, None)   # breaker trips

        # failures age out of the sliding window
        p2 = RestartPolicy(1.0, 8.0, 0.0, 3, 10.0, random.Random(0))
        p2.record_failure(0.0)
        p2.record_failure(1.0)
        assert p2.record_failure(50.0) == (1, 1.0)  # first two aged out
        assert p2.count() == 1 and p2.last_failure_time() == 50.0

        # backoff is capped
        p3 = RestartPolicy(1.0, 2.5, 0.0, 10, 100.0, random.Random(0))
        p3.record_failure(0.0)
        p3.record_failure(0.1)
        assert p3.record_failure(0.2)[1] == 2.5     # min(4.0, cap)

    def test_jitter_is_seeded(self):
        import random

        from deepspeed_tpu.utils.restart import RestartPolicy

        a = RestartPolicy(1.0, 8.0, 0.5, 10, 100.0, random.Random(7))
        b = RestartPolicy(1.0, 8.0, 0.5, 10, 100.0, random.Random(7))
        seq_a = [a.record_failure(t)[1] for t in (0.0, 1.0, 2.0)]
        seq_b = [b.record_failure(t)[1] for t in (0.0, 1.0, 2.0)]
        assert seq_a == seq_b                       # deterministic
        assert 1.0 <= seq_a[0] <= 1.5               # jitter in [0, 50%]


# ------------------------------------------------------- dataloader resume
class TestDataloaderState:
    def _loader(self, **kw):
        kw.setdefault("batch_size", 8)
        kw.setdefault("seed", 11)
        return DeepSpeedTpuDataLoader(tiny_data(n=40, seq=8), **kw)

    def test_mid_epoch_resume_continues_exactly(self):
        a = self._loader()
        it = iter(a)
        consumed = [next(it) for _ in range(3)]
        del consumed
        sd = a.state_dict()
        assert sd["batches_yielded"] == 3
        b = self._loader()
        b.load_state_dict(sd)
        cont_a = [next(it)["input_ids"] for _ in range(2)]
        it_b = iter(b)
        cont_b = [next(it_b)["input_ids"] for _ in range(2)]
        for x, y in zip(cont_a, cont_b):
            np.testing.assert_array_equal(x, y)

    def test_resume_across_epoch_boundary(self):
        a = self._loader()
        stream_a = []
        it = iter(a)
        for _ in range(7):       # 5 batches/epoch: crosses into epoch 1
            try:
                stream_a.append(next(it)["input_ids"])
            except StopIteration:
                it = iter(a)
                stream_a.append(next(it)["input_ids"])
        sd = a.state_dict()
        b = self._loader()
        b.load_state_dict(sd)
        it_b = iter(b)
        nxt_a = next(it)["input_ids"]
        nxt_b = next(it_b)["input_ids"]
        np.testing.assert_array_equal(nxt_a, nxt_b)
        # epochs shuffle differently (seed + epoch), so the resumed
        # epoch-1 batch must differ from the epoch-0 batch at that slot
        assert not np.array_equal(stream_a[6], stream_a[1])

    def test_plain_reiteration_unchanged(self):
        """No load_state_dict = historical behavior: a fresh __iter__
        restarts the epoch even after a partial pass."""
        a = self._loader()
        first = next(iter(a))["input_ids"]
        again = next(iter(a))["input_ids"]
        np.testing.assert_array_equal(first, again)

    def test_state_mismatch_rejected(self):
        a = self._loader()
        sd = a.state_dict()
        b = self._loader(seed=99)
        with pytest.raises(ValueError, match="seed"):
            b.load_state_dict(sd)
        # shard identity: a position over order[i::2] means nothing on a
        # 1-shard loader — changed process counts must fail loudly
        sd2 = dict(a.state_dict(), num_shards=2)
        with pytest.raises(ValueError, match="num_shards"):
            self._loader().load_state_dict(sd2)
        # a grown/shrunk dataset reshuffles into a different permutation:
        # the saved position would fast-forward through the wrong stream
        sd3 = dict(a.state_dict(), dataset_len=9999)
        with pytest.raises(ValueError, match="dataset_len"):
            self._loader().load_state_dict(sd3)
        # drop_last changes which batches an epoch yields: a position
        # saved under one setting silently forks under the other
        sd4 = dict(a.state_dict(), drop_last=False)
        with pytest.raises(ValueError, match="drop_last"):
            self._loader().load_state_dict(sd4)

    def test_unresumable_sources_raise(self):
        gen = ({"input_ids": np.zeros((2, 4), np.int64)} for _ in range(3))
        lo = DeepSpeedTpuDataLoader(gen, batch_size=2)
        with pytest.raises(NotImplementedError):
            lo.state_dict()
        # loading into an unresumable loader must fail loudly too — the
        # sampler/iterable __iter__ path would silently DISCARD the
        # restored position otherwise
        gen2 = ({"input_ids": np.zeros((2, 4), np.int64)} for _ in range(3))
        lo2 = DeepSpeedTpuDataLoader(gen2, batch_size=2)
        good_sd = self._loader().state_dict()
        with pytest.raises(NotImplementedError):
            lo2.load_state_dict(good_sd)


# ----------------------------------------------------------- resume parity
class TestResumeParity:
    def test_crash_auto_resume_byte_parity(self, tmp_path, reference):
        engine = build_engine(tmp_path, faults={
            "enabled": True,
            "schedule": [{"kind": "crash", "at_step": 5}]})
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "completed"
        assert r["train_restarts"] == 1
        # checkpoint every 2 steps, killed at 5 → exactly step 5 lost
        assert r["steps_lost"] == 1
        assert r["restart_log"][0]["resumed_step"] == 4
        assert sup.losses_by_step() == reference["losses"]
        assert_same_params(reference["params"], params_of(engine))

    def test_sigterm_urgent_save_then_resume_parity(self, tmp_path,
                                                    reference):
        engine = build_engine(tmp_path, faults={
            "enabled": True,
            "schedule": [{"kind": "sigterm", "at_step": 5}]})
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "preempted"
        assert r["completed_steps"] == 5        # stopped AT the notice
        assert r["preemptions"] == 1
        # the urgent save ran, was measured, and beat the grace window
        assert r["urgent_save_s"] is not None
        assert r["urgent_save_s"] < sup.config.preempt_grace_s
        assert (tmp_path / "latest").read_text().strip() == "global_step5"
        # "restart after preemption": a fresh engine + supervisor over the
        # same save_dir resumes from 'latest' — zero steps lost
        engine2 = build_engine(tmp_path)
        sup2 = TrainingSupervisor(engine=engine2)
        r2 = sup2.run(N_STEPS)
        assert r2["status"] == "completed" and r2["steps_lost"] == 0
        merged = dict(sup.losses_by_step())
        merged.update(sup2.losses_by_step())
        assert merged == reference["losses"]
        assert_same_params(reference["params"], params_of(engine2))

    def test_same_supervisor_resumes_after_preemption(self, tmp_path,
                                                      reference):
        """The docstring contract: calling run() AGAIN on the same
        instance after a preemption IS the resume path (the honored
        preempt flag must not poison the next run)."""
        engine = build_engine(tmp_path, faults={
            "enabled": True,
            "schedule": [{"kind": "sigterm", "at_step": 5}]})
        sup = TrainingSupervisor(engine=engine)
        assert sup.run(N_STEPS)["status"] == "preempted"
        r2 = sup.run(N_STEPS)
        assert r2["status"] == "completed"
        assert r2["completed_steps"] == N_STEPS
        assert sup.losses_by_step() == reference["losses"]
        assert_same_params(reference["params"], params_of(engine))

    def test_real_sigterm_signal_path(self, tmp_path):
        """The injector delivers a REAL SIGTERM through the installed
        handler when run() owns the main thread — the production signal
        machinery, not just the internal flag."""
        import threading
        assert threading.current_thread() is threading.main_thread()
        engine = build_engine(tmp_path, faults={
            "enabled": True,
            "schedule": [{"kind": "sigterm", "at_step": 3}]})
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert sup._signal_installed is False      # handler restored
        assert r["status"] == "preempted" and r["completed_steps"] == 3
        assert [e[0] for e in sup.injector.fired_events()] == ["sigterm"]

    def test_mid_epoch_dataloader_resume(self, tmp_path):
        """Crash mid-epoch: the dataloader position (not just the epoch)
        must restore, or the resumed run re-reads batches and the loss
        curve silently forks."""
        # 7 batches/epoch at gas=2 → steps straddle epoch boundaries and
        # checkpoints land mid-epoch
        data = tiny_data(n=224, seq=32)
        d_ref = tmp_path / "ref"
        e_ref = build_engine(d_ref, data=data)
        sup_ref = TrainingSupervisor(engine=e_ref)
        sup_ref.run(N_STEPS)
        d = tmp_path / "chaos"
        engine = build_engine(d, data=data, faults={
            "enabled": True,
            "schedule": [{"kind": "crash", "at_step": 5}]})
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "completed"
        st = engine.training_dataloader.state_dict()
        assert 0 < st["batches_yielded"] < 7    # genuinely mid-epoch
        assert sup.losses_by_step() == sup_ref.losses_by_step()
        assert_same_params(params_of(e_ref), params_of(engine))

    def test_crash_after_skipped_step_keeps_parity(self, tmp_path):
        """The host step counter counts overflow-SKIPPED steps the device
        counter excludes; both must round-trip the manifest or a resume
        after any skipped step replays one extra step and the loss curve
        forks (the two runs here share the same single nan injection, so
        their trajectories are comparable)."""
        skip_fault = {"kind": "nan_grads", "at_step": 3, "count": 1}
        d_ref = tmp_path / "ref"
        e_ref = build_engine(d_ref, faults={
            "enabled": True, "schedule": [dict(skip_fault)]},
            max_consecutive_anomalies=5)
        sup_ref = TrainingSupervisor(engine=e_ref)
        r_ref = sup_ref.run(N_STEPS)
        assert r_ref["status"] == "completed" and r_ref["train_restarts"] == 0
        assert int(e_ref.skipped_steps) == 1

        d = tmp_path / "chaos"
        engine = build_engine(d, faults={
            "enabled": True,
            "schedule": [dict(skip_fault),
                         {"kind": "crash", "at_step": 6}]},
            max_consecutive_anomalies=5)
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "completed" and r["train_restarts"] == 1
        # restored host counter includes the skipped step: resume replays
        # from the save at host-step 6, not one step earlier
        assert r["restart_log"][0]["resumed_step"] == 6
        assert sup.losses_by_step() == sup_ref.losses_by_step()
        assert_same_params(params_of(e_ref), params_of(engine))

    def test_injectors_off_byte_identical(self, tmp_path, reference):
        """All injectors off (and supervision on) = byte-identical
        training behavior vs the plain train loop."""
        engine = build_engine(tmp_path)
        plain = {}
        while engine.global_steps < N_STEPS:
            loss = float(engine.train_batch())
            plain[engine.global_steps] = loss
        assert plain == reference["losses"]
        assert_same_params(reference["params"], params_of(engine))


# -------------------------------------------------------- watchdog + wedge
class TestWedgeRecovery:
    def test_watchdog_detects_wedge_dumps_and_restarts(self, tmp_path):
        """Acceptance: the watchdog detects an injected wedged step,
        dumps the flight recorder, and the supervisor restarts from
        'latest' without human intervention."""
        def factory():
            return build_engine(
                tmp_path,
                faults={"enabled": True, "schedule": [
                    {"kind": "slow_step", "at_step": 5,
                     "duration_s": 30.0}]},
                watchdog_enabled=True, watchdog_factor=8.0,
                watchdog_min_steps=3, watchdog_poll_s=0.05)

        sup = TrainingSupervisor(engine_factory=factory)
        r = sup.run(N_STEPS)
        assert r["status"] == "completed"
        assert r["wedges"] == 1 and r["train_restarts"] == 1
        assert r["restart_log"][0]["reason"] == "wedge"
        # checkpointed at step 4, wedged at 5 → resumed at 4
        assert r["restart_log"][0]["resumed_step"] == 4
        assert r["dump_paths"], "wedge must dump the flight recorder"
        for paths in r["dump_paths"]:
            assert os.path.exists(paths["json"])
            assert os.path.exists(paths["chrome_trace"])

    def test_wedge_without_factory_parks(self, tmp_path):
        engine = build_engine(
            tmp_path,
            faults={"enabled": True, "schedule": [
                {"kind": "slow_step", "at_step": 3, "duration_s": 30.0}]},
            watchdog_enabled=True, watchdog_factor=8.0,
            watchdog_min_steps=2, watchdog_poll_s=0.05)
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        # the stuck thread owns the engine; no factory → no safe restart
        assert r["status"] == "parked" and r["parked"]
        assert r["wedges"] == 1


# ------------------------------------------------------- anomaly rollback
class TestAnomalyRollback:
    def test_nan_grads_skip_then_rollback(self, tmp_path):
        """One poisoned step is absorbed by the engine's overflow gate
        (bounded step-skip, every precision); K consecutive trigger a
        rollback to the last good checkpoint and training completes."""
        engine = build_engine(
            tmp_path,
            faults={"enabled": True, "schedule": [
                {"kind": "nan_grads", "at_step": 4, "count": 3}]},
            max_consecutive_anomalies=2)
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "completed"
        assert r["anomaly_rollbacks"] == 1
        assert r["train_restarts"] == 1
        # poisoned steps were SKIPPED by the update (params never saw NaN)
        assert int(sup.engine.skipped_steps) >= 1
        final = sup.losses_by_step()[N_STEPS]
        assert np.isfinite(final)
        for leaf in params_of(sup.engine):
            assert np.isfinite(leaf).all()

    def test_single_anomaly_does_not_roll_back(self, tmp_path):
        engine = build_engine(
            tmp_path,
            faults={"enabled": True, "schedule": [
                {"kind": "nan_grads", "at_step": 4, "count": 1}]},
            max_consecutive_anomalies=3)
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "completed"
        assert r["anomaly_rollbacks"] == 0 and r["train_restarts"] == 0
        assert int(sup.engine.skipped_steps) == 1

    def test_parked_anomaly_storm_counts_no_rollback(self, tmp_path):
        """An anomaly storm with no checkpoint and no factory parks —
        and must NOT report a rollback that never happened (the gauge
        operators alert on)."""
        engine = build_engine(
            tmp_path,
            faults={"enabled": True, "schedule": [
                {"kind": "nan_grads", "at_step": 0, "count": 0}]},
            max_consecutive_anomalies=2, save_interval_steps=0)
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "parked"
        assert r["anomaly_rollbacks"] == 0 and r["train_restarts"] == 0

    def test_preempt_mid_streak_skips_urgent_save(self, tmp_path):
        """A SIGTERM landing inside an open anomaly streak must NOT
        publish the anomalous state as 'latest': the urgent save is
        skipped (logged), and 'latest' keeps naming the last GOOD
        checkpoint — otherwise a later rollback would restore the spiked
        params permanently."""
        engine = build_engine(
            tmp_path,
            faults={"enabled": True, "schedule": [
                {"kind": "nan_grads", "at_step": 4, "count": 1},
                {"kind": "sigterm", "at_step": 5}]},
            max_consecutive_anomalies=5)
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "preempted"
        assert r["preemptions"] == 1
        assert r["urgent_save_s"] is None          # no urgent save ran
        # the poisoned step completed as global_step 5 (anomalous, odd —
        # never a periodic save); the urgent tag it would have written
        # must not exist and 'latest' still names the last good save
        assert not (tmp_path / "global_step5").exists()
        assert (tmp_path / "latest").read_text().strip() == "global_step4"

    def test_loss_spike_detection(self):
        from collections import deque
        cfg = ResilienceConfig(enabled=True, save_dir="/tmp/x",
                               loss_spike_factor=5.0)
        sup = TrainingSupervisor.__new__(TrainingSupervisor)
        sup.config = cfg

        class FakeEngine:
            _last_metrics = {"overflow": np.asarray(False)}

        good = deque([2.0, 2.1, 1.9], maxlen=10)
        assert not sup._is_anomaly(FakeEngine(), 2.5, good)
        assert sup._is_anomaly(FakeEngine(), 50.0, good)
        assert sup._is_anomaly(FakeEngine(), float("nan"), good)
        FakeEngine._last_metrics = {"overflow": np.asarray(True)}
        assert sup._is_anomaly(FakeEngine(), 2.0, good)


# --------------------------------------------------------- circuit breaker
class TestCircuitBreaker:
    def test_persistent_crash_parks(self, tmp_path):
        def factory():
            return build_engine(tmp_path, faults={
                "enabled": True,
                "schedule": [{"kind": "crash", "at_step": 2, "count": 0}]},
                max_restarts_in_window=3)

        sup = TrainingSupervisor(engine_factory=factory)
        r = sup.run(N_STEPS)
        assert r["status"] == "parked" and r["parked"]
        assert r["train_restarts"] == 2     # 3rd failure trips the breaker

    def test_crash_before_any_checkpoint_needs_factory(self, tmp_path):
        engine = build_engine(tmp_path, faults={
            "enabled": True,
            "schedule": [{"kind": "crash", "at_step": 1}]},
            save_interval_steps=0)
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "parked"      # no checkpoint, no factory


# ----------------------------------------------------------- config surface
class TestConfigSurface:
    def test_resilience_block_mounts_on_ds_config(self):
        from deepspeed_tpu.runtime.config import DeepSpeedTpuConfig
        cfg = DeepSpeedTpuConfig(resilience={
            "enabled": True, "save_dir": "/ckpt",
            "save_interval_steps": 50,
            "faults": {"enabled": True, "seed": 3, "schedule": [
                {"kind": "sigterm", "at_step": 100}]}})
        assert cfg.resilience.enabled and cfg.resilience.save_dir == "/ckpt"
        inj = cfg.resilience.faults.build_injector()
        assert inj.events[0].kind == "sigterm"
        # default = everything off
        assert not DeepSpeedTpuConfig().resilience.enabled

    def test_disabled_supervisor_refuses_to_run(self, tmp_path):
        engine = build_engine(tmp_path)
        engine.config.resilience.enabled = False
        sup = TrainingSupervisor(engine=engine,
                                 config=engine.config.resilience,
                                 save_dir=str(tmp_path))
        with pytest.raises(ValueError, match="resilience.enabled"):
            sup.run(2)

    def test_supervisor_requires_save_dir(self, tmp_path):
        engine = build_engine(tmp_path)
        engine.config.resilience.save_dir = None
        with pytest.raises(ValueError, match="save_dir"):
            TrainingSupervisor(engine=engine)


# ------------------------------------------- LR + ScaleState resume exactness
class TestScheduleAndScaleResume:
    def test_warmup_lr_continues_without_rewarmup(self, tmp_path,
                                                  reference):
        """The LR schedule is serialized in the manifest and keyed off the
        restored global_step: after resume the very next step's LR equals
        the uninterrupted run's — no re-warmup from step 0."""
        engine = build_engine(tmp_path, faults={
            "enabled": True,
            "schedule": [{"kind": "crash", "at_step": 5}]})
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(N_STEPS)
        assert r["status"] == "completed", r
        ref_engine = build_engine(tmp_path / "ref2")
        r_ref = TrainingSupervisor(engine=ref_engine).run(N_STEPS)
        assert r_ref["status"] == "completed", r_ref
        assert engine.get_lr() == ref_engine.get_lr()
        assert engine.lr_scheduler.state_dict() == \
            ref_engine.lr_scheduler.state_dict()

    def test_fp16_scale_state_continues_exactly(self, tmp_path):
        """ScaleState (scale, good-step window, hysteresis) round-trips
        the manifest: a resumed fp16 run continues the loss-scale window
        instead of resetting to the initial scale."""
        topo.reset_topology()
        cfg = make_config(tmp_path, save_interval_steps=0)
        cfg["fp16"] = {"enabled": True, "initial_scale_power": 8,
                       "loss_scale_window": 4}
        del cfg["resilience"]
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=build_model("tiny"), config=cfg,
            training_data=tiny_data())
        for _ in range(6):
            engine.train_batch()
        engine.save_checkpoint(str(tmp_path), client_state={
            "dataloader": engine.training_dataloader.state_dict()})
        want = (float(engine.state.scale_state.scale),
                int(engine.state.scale_state.good_steps),
                int(engine.state.scale_state.hysteresis))
        # the window moved off the initial state, so a reset would show
        assert want[1] != 0 or want[0] != 2.0 ** 8

        topo.reset_topology()
        engine2, _, _, _ = deepspeed_tpu.initialize(
            model=build_model("tiny"), config=dict(cfg),
            training_data=tiny_data())
        _, cs = engine2.load_checkpoint(str(tmp_path))
        engine2.training_dataloader.load_state_dict(cs["dataloader"])
        engine2.reset_data_iterator()
        got = (float(engine2.state.scale_state.scale),
               int(engine2.state.scale_state.good_steps),
               int(engine2.state.scale_state.hysteresis))
        assert got == want
        # and both engines continue with the same scale trajectory
        l1 = float(engine.train_batch())
        l2 = float(engine2.train_batch())
        assert float(engine.state.scale_state.scale) == \
            float(engine2.state.scale_state.scale)
        assert l1 == l2
