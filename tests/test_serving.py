"""Unit surface of deepspeed_tpu/serving/: metrics, admission queue,
router selection/health. No engine involved — these are the pieces the
load test (test_serving_load.py) composes end-to-end."""

import threading
import time

import pytest

from deepspeed_tpu.serving import (AdmissionQueue, FinishReason,
                                   MetricsRegistry, Priority, Rejected,
                                   RequestState, ServingConfig,
                                   ServingRequest, serving_metrics)
from deepspeed_tpu.serving.metrics import Counter, Gauge, Histogram


def _req(priority=Priority.NORMAL, deadline_s=None, prompt_len=4,
         max_new=4):
    return ServingRequest([1] * prompt_len, max_new, priority, deadline_s,
                          None)


# ------------------------------------------------------------------ metrics
def test_counter_gauge():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = Gauge()
    g.set(7)
    g.dec(3)
    assert g.value == 4.0


def test_histogram_percentiles():
    h = Histogram(buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.5, 3.0, 7.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(13.5)
    # p50 lands in the (1, 2] bucket, p99 in (4, 8]
    assert 1.0 <= h.percentile(50) <= 2.0
    assert 4.0 <= h.percentile(99) <= 8.0
    assert h.percentile(0) <= 1.0


def test_histogram_overflow_bucket():
    h = Histogram(buckets=(1.0, 2.0))
    h.observe(100.0)
    # overflow estimate floors at the last finite bound
    assert h.percentile(50) == 2.0
    assert h.mean == pytest.approx(100.0)


def test_registry_snapshot_and_events():
    reg = MetricsRegistry("serving")
    reg.counter("requests_completed").inc(3)
    reg.gauge("queue_depth").set(5)
    reg.histogram("ttft_s").observe(0.02)
    snap = reg.snapshot()
    assert snap["requests_completed"] == 3
    assert snap["queue_depth"] == 5
    assert snap["ttft_s"]["count"] == 1
    tags = {t for t, _, _ in reg.events(step=7)}
    assert "serving/requests_completed" in tags
    assert "serving/ttft_s/p95" in tags
    assert all(s == 7 for _, _, s in reg.events(step=7))


def test_registry_monitor_fanout(tmp_path):
    """Serving metrics flow through the existing monitor/ CSV backend."""
    from deepspeed_tpu.monitor import CSVMonitor

    reg = serving_metrics()
    reg.counter("requests_completed").inc(2)
    mon = CSVMonitor(str(tmp_path), job_name="serve")
    reg.publish(mon, step=1)
    out = tmp_path / "serve" / "serving_requests_completed.csv"
    assert out.exists()
    assert "2.0" in out.read_text()


def test_predeclared_serving_metrics():
    reg = serving_metrics()
    snap = reg.snapshot()
    assert snap["requests_shed"] == 0.0
    assert snap["ttft_s"]["count"] == 0.0


# ------------------------------------------------------------------- queue
def test_queue_sheds_when_full():
    reg = serving_metrics()
    q = AdmissionQueue(max_depth=2, metrics=reg)
    q.offer(_req())
    q.offer(_req())
    with pytest.raises(Rejected) as ei:
        q.offer(_req())
    assert ei.value.reason == "overloaded"
    assert reg.snapshot()["requests_shed"] == 1
    assert len(q) == 2           # bounded: the shed request never entered


def test_queue_shed_request_gets_terminal_state():
    q = AdmissionQueue(max_depth=1)
    q.offer(_req())
    shed = _req()
    with pytest.raises(Rejected):
        q.offer(shed)
    assert shed.state == RequestState.REJECTED
    assert shed.wait(0)          # stream terminated, not hanging


def test_queue_priority_then_deadline_order():
    q = AdmissionQueue(max_depth=10)
    low = _req(priority=Priority.LOW)
    high = _req(priority=Priority.HIGH)
    tight = _req(priority=Priority.NORMAL, deadline_s=10.0)
    loose = _req(priority=Priority.NORMAL, deadline_s=60.0)
    none = _req(priority=Priority.NORMAL, deadline_s=None)
    for r in (none, low, loose, tight, high):
        q.offer(r)
    order = [q.pop(timeout=0.1).uid for _ in range(5)]
    assert order == [high.uid, tight.uid, loose.uid, none.uid, low.uid]


def test_queue_expires_stale_requests_at_pop():
    reg = serving_metrics()
    q = AdmissionQueue(max_depth=10, metrics=reg)
    stale = _req(deadline_s=0.01)
    fresh = _req(deadline_s=60.0)
    q.offer(stale)
    q.offer(fresh)
    time.sleep(0.05)
    got = q.pop(timeout=0.5)
    assert got is fresh
    assert stale.state == RequestState.EXPIRED
    assert stale.finish_reason == FinishReason.DEADLINE
    assert reg.snapshot()["requests_expired"] == 1


def test_queue_pop_skips_cancelled():
    q = AdmissionQueue(max_depth=10)
    a, b = _req(), _req()
    q.offer(a)
    q.offer(b)
    a.cancel_requested.set()
    assert q.pop(timeout=0.5) is b
    assert a.state == RequestState.CANCELLED


def test_queue_pop_blocks_until_offer():
    q = AdmissionQueue(max_depth=4)
    got = []

    def popper():
        got.append(q.pop(timeout=5.0))

    t = threading.Thread(target=popper)
    t.start()
    time.sleep(0.05)
    r = _req()
    q.offer(r)
    t.join(5.0)
    assert got and got[0] is r


def test_queue_close_drains():
    q = AdmissionQueue(max_depth=4)
    r = _req()
    q.offer(r)
    left = q.close()
    assert left == [r]
    with pytest.raises(Rejected) as ei:
        q.offer(_req())
    assert ei.value.reason == "draining"
    assert q.pop(timeout=0.1) is None


def test_queue_wait_histogram_populated():
    reg = serving_metrics()
    q = AdmissionQueue(max_depth=4, metrics=reg)
    q.offer(_req())
    q.pop(timeout=0.5)
    assert reg.snapshot()["queue_wait_s"]["count"] == 1


# ------------------------------------------------------------------- router
class _FakeEngine:
    """Engine stand-in: enough surface for Replica/scheduler to exist."""

    class _Cfg:
        max_ragged_batch_size = 64
        max_ragged_sequence_count = 4
        max_chunk_tokens = 16

    class _MCfg:
        max_seq_len = 128

    class _Model:
        cfg = None

    def __init__(self):
        self.config = self._Cfg()
        self.model = self._Model()
        self.model.cfg = self._MCfg()
        self.flushed = []

    def flush(self, uid):
        self.flushed.append(uid)


def _router(n=2, start=False):
    from deepspeed_tpu.serving import ReplicaRouter
    from deepspeed_tpu.serving.replica import Replica

    reg = serving_metrics()
    q = AdmissionQueue(max_depth=16, metrics=reg)
    reps = [Replica(i, _FakeEngine(), reg) for i in range(n)]
    router = ReplicaRouter(reps, q, reg)
    if start:
        router.start()
    return router, reps, q, reg


def test_router_picks_least_outstanding_tokens():
    router, (r0, r1), _, _ = _router()
    r0.assign(_req(prompt_len=100, max_new=50))
    assert router.pick() is r1
    r1.assign(_req(prompt_len=100, max_new=100))
    assert router.pick() is r0


def test_router_skips_draining_and_dead():
    from deepspeed_tpu.serving import ReplicaState

    router, (r0, r1), _, reg = _router()
    r0.drain()
    assert router.pick() is r1
    r1.state = ReplicaState.DEAD
    assert router.pick() is None
    assert reg.snapshot()["replicas_healthy"] == 0


def test_router_fails_fast_with_no_replicas():
    from deepspeed_tpu.serving import ReplicaState

    router, reps, q, reg = _router()
    for r in reps:
        r.state = ReplicaState.DEAD
    req = _req()
    router._dispatch(req)
    assert req.state == RequestState.FAILED
    assert req.wait(0)
    assert reg.snapshot()["requests_failed"] == 1


def test_replica_wedge_detection():
    from deepspeed_tpu.serving import ReplicaState
    from deepspeed_tpu.serving.replica import Replica

    r = Replica(0, _FakeEngine(), wedge_timeout_s=0.01)
    # simulate: past warm-up, has work, no progress for > wedge_timeout
    r._steps_done = 1
    r._busy_since = time.monotonic() - 1.0
    r.last_progress_t = time.monotonic() - 1.0
    assert r.check_health() == ReplicaState.DEAD
    # idle replicas are never wedged
    r2 = Replica(1, _FakeEngine(), wedge_timeout_s=0.01)
    r2.last_progress_t = time.monotonic() - 1.0
    assert r2.check_health() == ReplicaState.HEALTHY
    # a cold replica stuck in its FIRST step is compiling, not wedged
    r3 = Replica(2, _FakeEngine(), wedge_timeout_s=0.01)
    r3._busy_since = time.monotonic() - 1.0
    r3.last_progress_t = time.monotonic() - 1.0
    assert r3.check_health() == ReplicaState.HEALTHY


def test_serving_config_in_runtime_config():
    from deepspeed_tpu.runtime.config import load_config

    cfg = load_config({"serving": {"max_queue_depth": 7, "num_replicas": 3,
                                   "default_deadline_ms": 250.0}})
    assert cfg.serving.max_queue_depth == 7
    assert cfg.serving.num_replicas == 3
    assert cfg.serving.default_deadline_ms == 250.0
    # defaults survive an absent block
    assert load_config({}).serving.shed_policy == "reject"


def test_replica_engine_fault_fails_requests_terminally():
    """A replica whose engine raises mid-step goes DEAD and every
    in-flight request reaches a terminal FAILED state (streams must not
    hang on a dead replica)."""
    from deepspeed_tpu.serving import ReplicaState
    from deepspeed_tpu.serving.replica import Replica

    class ExplodingEngine(_FakeEngine):
        def can_schedule(self, uids, lengths):
            raise RuntimeError("device wedged")

        def put(self, uids, tokens):
            raise RuntimeError("device wedged")

    reg = serving_metrics()
    r = Replica(0, ExplodingEngine(), reg)
    req = _req()
    assert r.assign(req)
    r.start()
    assert req.wait(10), "request never reached a terminal state"
    assert req.state == RequestState.FAILED
    assert r.state == ReplicaState.DEAD
    assert reg.snapshot()["requests_failed"] == 1
    r.stop(1.0)


def test_queue_blocking_offer_waits_for_room():
    """shed_policy="block": a full queue makes offer(block=True) wait for
    room, and the request is admitted ONCE, never shed-finished."""
    reg = serving_metrics()
    q = AdmissionQueue(max_depth=1, metrics=reg)
    first = _req()
    q.offer(first)
    blocked = _req()
    done = threading.Event()

    def offerer():
        q.offer(blocked, block=True, timeout=5.0)
        done.set()

    t = threading.Thread(target=offerer)
    t.start()
    time.sleep(0.05)
    assert not done.is_set(), "offer should still be blocked on a full queue"
    assert q.pop(timeout=1.0) is first      # frees the slot
    assert done.wait(5.0), "blocked offer never admitted"
    t.join(5.0)
    assert q.pop(timeout=1.0) is blocked
    assert blocked.state != RequestState.REJECTED
    assert reg.snapshot()["requests_shed"] == 0


def test_queue_blocking_offer_timeout_sheds_once():
    q = AdmissionQueue(max_depth=1)
    q.offer(_req())
    late = _req()
    with pytest.raises(Rejected) as ei:
        q.offer(late, block=True, timeout=0.05)
    assert ei.value.reason == "overloaded"
    assert late.state == RequestState.REJECTED


def test_wedged_replica_fails_inflight_requests():
    """check_health marking a replica DEAD (worker stuck in a device
    call) must terminate its in-flight requests — no stream may hang."""
    from deepspeed_tpu.serving import ReplicaState
    from deepspeed_tpu.serving.replica import Replica

    reg = serving_metrics()
    r = Replica(0, _FakeEngine(), reg, wedge_timeout_s=0.01)
    req = _req()
    # simulate a worker wedged mid-step with this request active
    r._steps_done = 1
    r._active[req.uid] = req
    r._busy_since = time.monotonic() - 1.0
    r.last_progress_t = time.monotonic() - 1.0
    assert r.check_health() == ReplicaState.DEAD
    assert req.wait(1.0), "wedged replica left the request hanging"
    assert req.state == RequestState.FAILED
    assert reg.snapshot()["requests_failed"] == 1


def test_custom_ttft_buckets_take_effect():
    """ServingConfig.ttft_buckets_s must actually re-bucket the
    pre-declared ttft histogram (registry reset path)."""
    reg = serving_metrics()
    h = reg.histogram("ttft_s", (0.5, 1.0), reset=True)
    assert h.bounds == (0.5, 1.0)
    assert reg.histogram("ttft_s") is h


def test_queue_remove_frees_slot():
    q = AdmissionQueue(max_depth=2)
    a, b = _req(), _req()
    q.offer(a)
    q.offer(b)
    assert q.remove(a) is True
    assert q.remove(a) is False          # already out
    assert len(q) == 1
    q.offer(_req())                      # freed slot is usable again
    assert q.pop(timeout=0.5) is b


def test_pop_sweeps_expired_entries_anywhere_in_heap():
    """Deadline sweep at pop time covers the WHOLE heap: an expired LOW
    request buried under fresh HIGH traffic is failed with DEADLINE on
    the next pop instead of occupying a depth slot until it surfaces."""
    reg = serving_metrics()
    q = AdmissionQueue(max_depth=10, metrics=reg)
    buried = _req(priority=Priority.LOW, deadline_s=0.01)
    tops = [_req(priority=Priority.HIGH, deadline_s=60.0) for _ in range(3)]
    q.offer(buried)
    for r in tops:
        q.offer(r)
    time.sleep(0.05)
    got = q.pop(timeout=0.5)
    assert got is tops[0]                # urgency order unchanged
    # the buried request was swept by that same pop, not left queued
    assert buried.state == RequestState.EXPIRED
    assert buried.finish_reason == FinishReason.DEADLINE
    assert buried.wait(0)                # stream terminated
    assert len(q) == 2
    assert reg.snapshot()["requests_expired"] == 1


# ------------------------------------------------------ replica lifecycle
class _CompletingFakeEngine(_FakeEngine):
    """Fake engine whose scheduler path actually completes requests:
    constant logits, every chunk schedulable — enough surface to run the
    worker loop end-to-end without JAX."""

    def can_schedule(self, uids, lengths):
        from deepspeed_tpu.inference.v2.scheduling_utils import (
            SchedulingResult)

        return SchedulingResult.Success

    def put(self, uids, chunks, **kw):
        import numpy as np

        class Logits(np.ndarray):       # what the scheduler asks of a put
            ran_dry = True

            def next_tokens(self):
                return np.argmax(self, axis=-1)

        return np.zeros((len(uids), 8), dtype=np.float32).view(Logits)

    def match_prefix(self, uid, prompt_tokens):
        return 0

    def commit_tokens(self, uid, tokens, in_flight=0):
        pass


def test_check_health_on_draining_replica():
    """DRAINING is not DEAD: check_health reports it untouched while the
    replica makes progress, but a WEDGED draining replica still crosses
    to DEAD (drain must not disable the watchdog)."""
    from deepspeed_tpu.serving import ReplicaState
    from deepspeed_tpu.serving.replica import Replica

    r = Replica(0, _FakeEngine(), wedge_timeout_s=0.01)
    r.drain()
    assert r.check_health() == ReplicaState.DRAINING
    # now simulate a wedge while draining: watchdog still fires
    r._steps_done = 1
    r._busy_since = time.monotonic() - 1.0
    r.last_progress_t = time.monotonic() - 1.0
    assert r.check_health() == ReplicaState.DEAD


def test_assign_racing_drain():
    """assign() after drain() refuses; an assign that WON the race (the
    request entered the inbox before DRAINING) still runs to completion
    — drain finishes accepted work, it never drops it."""
    from deepspeed_tpu.serving import ReplicaState
    from deepspeed_tpu.serving.replica import Replica

    reg = serving_metrics()
    r = Replica(0, _CompletingFakeEngine(), reg)
    won = _req(prompt_len=3, max_new=2)
    assert r.assign(won) is True         # accepted while HEALTHY
    r.drain()
    lost = _req()
    assert r.assign(lost) is False       # refused while DRAINING
    assert lost.state == RequestState.QUEUED   # untouched, router retries
    r.start()
    assert won.wait(10), "drain dropped an accepted request"
    assert won.state == RequestState.FINISHED
    r.thread.join(10)
    assert r.state == ReplicaState.STOPPED     # drained to completion
    r.stop(1.0)


def test_double_stop_idempotent():
    from deepspeed_tpu.serving import ReplicaState
    from deepspeed_tpu.serving.replica import Replica

    r = Replica(0, _CompletingFakeEngine(), serving_metrics())
    r.start()
    r.stop(2.0)
    assert r.state == ReplicaState.STOPPED
    r.stop(2.0)                          # second stop: no-op, no raise
    assert r.state == ReplicaState.STOPPED
    assert not r.thread.is_alive()


def test_fault_tolerance_config_in_runtime_config():
    from deepspeed_tpu.runtime.config import load_config

    cfg = load_config({"serving": {
        "fault_tolerance": {"enabled": True, "max_retries": 5,
                            "brownout_threshold": 0.5},
        "faults": {"enabled": True, "seed": 7, "schedule": [
            {"kind": "crash", "replica": 0, "at_step": 3}]}}})
    ft = cfg.serving.fault_tolerance
    assert ft.enabled and ft.max_retries == 5
    assert ft.brownout_threshold == 0.5
    inj = cfg.serving.faults.build_injector()
    assert inj is not None and inj.events[0].at_step == 3
    # defaults: both off, injector not built
    dflt = load_config({}).serving
    assert not dflt.fault_tolerance.enabled
    assert dflt.faults.build_injector() is None
