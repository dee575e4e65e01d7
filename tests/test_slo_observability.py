"""SLO observability suite (ISSUE 9): windowed metrics, burn-rate
alerting, the ops journal, and the health report
(docs/OBSERVABILITY.md "SLOs and burn-rate alerts").

Unit layers (no engines): histogram snapshot consistency under racing
``observe`` threads (the window-math-never-negative contract), the
windowed-metrics delta ring on a fake clock, journal schema/bounds/
ordering, and the AlertEngine state machine (fire needs evidence in
both windows; resolve needs evidence too — a data-less window must not
flap a firing alert). E2E layers (tiny CPU engines): a frontend whose
injected latency fault fires and resolves the interactive alert, the
health report's merged shape, and the training supervisor's journal.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.serving.metrics import (DEFAULT_LATENCY_BUCKETS,
                                           Histogram, MetricsRegistry,
                                           serving_metrics)
from deepspeed_tpu.telemetry import (AlertEngine, FlightRecorder,
                                     OpsJournal, SLOConfig, Tracer,
                                     WindowedMetrics, validate_events)

VOCAB = 128

_model = None
_params = None


def tiny_engine(i=0):
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    global _model, _params
    if _model is None:
        _model = CausalLM(TransformerConfig(
            vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=2, max_seq_len=128, norm="rmsnorm",
            activation="silu", position="rope"))
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=4,
        max_chunk_tokens=32, kv_blocks=64, kv_block_size=8,
        max_tracked_sequences=16)
    eng = InferenceEngineV2(_model, params=_params, config=vcfg)
    _params = eng.params
    return eng


def prompts(n, seed, lo=8, hi=20):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=int(l)).tolist()
            for l in rng.integers(lo, hi, size=n)]


# ------------------------------------------------- histogram consistency
class TestHistogramConsistency:
    def test_racing_observes_never_negative_deltas(self):
        """Satellite regression: two bucket snapshots taken around
        concurrent observes must have non-negative, mutually-consistent
        deltas (count delta == sum of bucket deltas; sum delta covers
        exactly the counted observations)."""
        h = Histogram((0.1, 1.0, 10.0))
        stop = threading.Event()

        def pound():
            while not stop.is_set():
                h.observe(0.05)
                h.observe(5.0)

        threads = [threading.Thread(target=pound) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            prev = h.buckets_snapshot()
            for _ in range(300):
                cur = h.buckets_snapshot()
                d_counts = [a - b for a, b in zip(cur[1], prev[1])]
                assert all(d >= 0 for d in d_counts), d_counts
                d_count = cur[3] - prev[3]
                d_sum = cur[2] - prev[2]
                assert d_count == sum(d_counts)
                assert d_count >= 0 and d_sum >= 0.0
                # every observation is 0.05 or 5.0: the sum delta must
                # equal the per-bucket composition exactly
                assert d_sum == pytest.approx(
                    d_counts[0] * 0.05 + d_counts[2] * 5.0)
                prev = cur
        finally:
            stop.set()
            for t in threads:
                t.join()

    def test_snapshot_internally_consistent_under_races(self):
        """Histogram.snapshot derives count/sum/mean/percentiles from ONE
        locked read — count always equals the percentile sample size."""
        h = Histogram((0.1, 1.0))
        stop = threading.Event()

        def pound():
            while not stop.is_set():
                h.observe(0.05)

        t = threading.Thread(target=pound)
        t.start()
        try:
            for _ in range(200):
                s = h.snapshot()
                if s["count"]:
                    assert s["sum"] == pytest.approx(s["count"] * 0.05)
                    assert s["mean"] == pytest.approx(0.05)
        finally:
            stop.set()
            t.join()

    def test_percentile_from_matches_cumulative(self):
        h = Histogram(DEFAULT_LATENCY_BUCKETS)
        rng = np.random.default_rng(0)
        for v in rng.uniform(0.001, 5.0, size=500):
            h.observe(float(v))
        bounds, counts, _, _ = h.buckets_snapshot()
        for q in (50, 90, 95, 99):
            assert h.percentile(q) == Histogram.percentile_from(
                bounds, counts, q)


# ---------------------------------------------------- windowed metrics
class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class TestWindowedMetrics:
    def _setup(self):
        reg = MetricsRegistry("serving")
        clock = FakeClock()
        w = WindowedMetrics(reg, bucket_s=1.0, history_s=60.0, clock=clock)
        return reg, w, clock

    def test_window_percentile_sees_only_the_window(self):
        reg, w, clock = self._setup()
        h = reg.histogram("ttft_s", DEFAULT_LATENCY_BUCKETS)
        w.tick()
        for _ in range(20):
            h.observe(0.01)
        clock.t = 10.0
        w.tick()
        for _ in range(20):
            h.observe(0.4)
        clock.t = 12.0
        w.tick()
        # short window: only the slow batch; long window: both
        assert w.window_percentile("ttft_s", 95, 3.0) > 0.25
        assert w.window_percentile("ttft_s", 50, 100.0) < 0.1
        assert w.window_count("ttft_s", 3.0) == 20
        assert w.window_count("ttft_s", 100.0) == 40

    def test_window_agrees_with_cumulative_over_full_history(self):
        reg, w, clock = self._setup()
        h = reg.histogram("ttft_s", DEFAULT_LATENCY_BUCKETS)
        w.tick()
        rng = np.random.default_rng(1)
        for v in rng.uniform(0.001, 2.0, size=300):
            h.observe(float(v))
        clock.t = 5.0
        w.tick()
        for q in (50, 95, 99):
            assert w.window_percentile("ttft_s", q, 1e9) == \
                h.percentile(q)

    def test_window_rate_and_delta(self):
        reg, w, clock = self._setup()
        c = reg.counter("tokens_generated")
        w.tick()
        c.inc(100)
        clock.t = 4.0
        w.tick()
        assert w.window_delta("tokens_generated", 10.0) == 100
        assert w.window_rate("tokens_generated", 10.0) == pytest.approx(25.0)

    def test_fraction_over_threshold(self):
        reg, w, clock = self._setup()
        h = reg.histogram("ttft_s", DEFAULT_LATENCY_BUCKETS)
        w.tick()
        for _ in range(30):
            h.observe(0.01)
        for _ in range(10):
            h.observe(0.4)
        clock.t = 1.0
        w.tick()
        assert w.window_fraction_over("ttft_s", 0.1, 10.0) == \
            pytest.approx(0.25)
        # threshold beyond the largest bound: only +Inf overflow is over
        assert w.window_fraction_over("ttft_s", 1e6, 10.0) == 0.0

    def test_no_data_reads_none_not_zero(self):
        reg, w, clock = self._setup()
        reg.histogram("ttft_s", DEFAULT_LATENCY_BUCKETS)
        assert w.window_percentile("ttft_s", 95, 10.0) is None
        w.tick()
        clock.t = 1.0
        w.tick()
        assert w.window_percentile("ttft_s", 95, 10.0) is None
        assert w.window_fraction_over("ttft_s", 0.1, 10.0) is None

    def test_reset_histogram_clamps_never_negative(self):
        reg, w, clock = self._setup()
        h = reg.histogram("ttft_s", DEFAULT_LATENCY_BUCKETS)
        for _ in range(50):
            h.observe(0.01)
        w.tick()
        # re-declare with fresh counts (reset=True): deltas vs the old
        # baseline would be negative — must clamp to "window restarts"
        h2 = reg.histogram("ttft_s", DEFAULT_LATENCY_BUCKETS, reset=True)
        h2.observe(0.01)
        clock.t = 1.0
        w.tick()
        d = w.window_hist("ttft_s", 10.0)
        assert d[3] >= 0 and all(c >= 0 for c in d[1]) and d[2] >= 0.0

    def test_stalled_ticks_read_no_data_not_stale(self):
        """If ticks stall longer than the window, there is no baseline
        inside it: the answer is None (no data), NOT a silently
        over-spanned window that smuggles a long-cleared incident back
        into a 'fast' burn rate."""
        reg, w, clock = self._setup()
        h = reg.histogram("ttft_s", DEFAULT_LATENCY_BUCKETS)
        w.tick()
        for _ in range(10):
            h.observe(0.5)                  # the incident
        clock.t = 1.0
        w.tick()
        clock.t = 50.0                      # ticker stalled 49s
        w.tick()
        assert w.window_hist("ttft_s", 2.0) is None
        assert w.window_percentile("ttft_s", 95, 2.0) is None
        # the full-history window still answers
        assert w.window_count("ttft_s", 1e9) == 10

    def test_ring_is_bounded(self):
        reg, w, clock = self._setup()
        for i in range(500):
            clock.t = float(i)
            w.tick()
        assert len(w) <= w.max_snapshots

    def test_fast_tickers_refresh_head_not_history(self):
        """A dashboard polling tick() at 10x the cadence must not evict
        old snapshots (shrinking the slow window): faster-than-cadence
        ticks replace the ring head, persistent entries stay ~bucket_s
        apart, and the newest snapshot is still the freshest data."""
        reg, w, clock = self._setup()     # bucket_s = 1.0
        h = reg.histogram("ttft_s", DEFAULT_LATENCY_BUCKETS)
        w.tick()
        for i in range(1, 600):           # 60s of 10 Hz ticks
            clock.t = i * 0.1
            if i == 595:
                h.observe(0.01)           # lands just before the head
            w.tick()
        # ~60s span needs ~60-120 entries, nowhere near 600
        assert len(w) <= 125
        # old history survived AND the head saw the last observation
        assert w.window_count("ttft_s", 1e9) == 1
        assert w.window_count("ttft_s", 1.0) == 1

    def test_out_of_order_tick_dropped(self):
        reg, w, clock = self._setup()
        w.tick(5.0)
        w.tick(8.0)
        w.tick(6.0)                       # racing ticker lost the race
        pair = w._window_pair(100.0)
        assert pair[1]["t"] == 8.0 and len(w) == 2


# ------------------------------------------------------------ journal
class TestOpsJournal:
    def test_emit_and_validate(self):
        j = OpsJournal(capacity=16)
        j.emit("replica_restart", replica=0, attempt=1, recovery_s=0.5)
        j.emit("brownout_enter", healthy_fraction=0.4)
        assert validate_events(j.events()) == []
        assert [e["kind"] for e in j.events()] == ["replica_restart",
                                                   "brownout_enter"]

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="unknown journal event"):
            OpsJournal().emit("meteor_strike", where="everywhere")

    def test_missing_required_field_raises(self):
        with pytest.raises(ValueError, match="missing required"):
            OpsJournal().emit("replica_restart", replica=0)

    def test_unserializable_detail_raises(self):
        with pytest.raises(ValueError, match="JSON-serializable"):
            OpsJournal().emit("brownout_enter",
                              healthy_fraction=object())

    def test_bounded_capacity_and_total(self):
        j = OpsJournal(capacity=5)
        for i in range(20):
            j.emit("train_wedge", step=i)
        assert len(j) == 5
        assert j.total_emitted == 20
        assert [e["detail"]["step"] for e in j.events()] == list(range(15, 20))

    def test_seq_and_timestamps_monotonic(self):
        j = OpsJournal(capacity=64)
        for i in range(30):
            j.emit("checkpoint_saved", step=i, urgent=False)
        evs = j.events()
        assert validate_events(evs) == []
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)

    def test_filtering_and_count(self):
        j = OpsJournal()
        j.emit("train_wedge", step=1)
        j.emit("checkpoint_saved", step=1, urgent=False)
        j.emit("train_wedge", step=2)
        assert j.count("train_wedge") == 2
        assert len(j.events(kinds=("checkpoint_saved",))) == 1
        assert len(j.events(limit=1)) == 1

    def test_jsonl_sink_is_byte_capped(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        j = OpsJournal(capacity=1000, path=path, max_file_bytes=600)
        for i in range(50):
            j.emit("train_wedge", step=i)
        size = os.path.getsize(path)
        assert size <= 600
        # the in-memory ring kept everything; the sink just stopped
        assert len(j) == 50
        lines = [json.loads(l) for l in open(path)]
        assert all(l["kind"] == "train_wedge" for l in lines)

    def test_jsonl_sink_seq_ordered_under_concurrent_emits(self, tmp_path):
        """The durable sink must pass validate_events during exactly the
        multi-threaded incidents it exists to capture: lines land in seq
        order even with racing emitters."""
        path = str(tmp_path / "j.jsonl")
        j = OpsJournal(capacity=4096, path=path, max_file_bytes=10**7)

        def emit_many():
            for _ in range(200):
                j.emit("train_wedge", step=1)

        threads = [threading.Thread(target=emit_many) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lines = [json.loads(l) for l in open(path)]
        seqs = [l["seq"] for l in lines]
        assert seqs == list(range(1, 801))
        assert validate_events(lines) == []

    def test_dump_writes_ring_as_jsonl(self, tmp_path):
        j = OpsJournal()
        j.emit("brownout_enter", healthy_fraction=0.3)
        path = str(tmp_path / "dump.jsonl")
        assert j.dump(path) == 1
        assert json.loads(open(path).read())["kind"] == "brownout_enter"

    def test_render_text(self):
        j = OpsJournal()
        j.emit("replica_parked", replica=3, crashes_in_window=5)
        text = j.render_text()
        assert "replica_parked" in text and "replica=3" in text


# --------------------------------------------------------- alert engine
def make_alert_engine(classes=None, recorder=None, **over):
    reg = serving_metrics()
    clock = FakeClock()
    cfg = SLOConfig(enabled=True,
                    classes=classes or {"interactive":
                                        {"ttft_p95_ms": 100.0}},
                    fast_window_s=2.0, slow_window_s=6.0,
                    burn_rate_threshold=4.0, min_window_count=2,
                    eval_interval_s=0.0, **over)
    w = WindowedMetrics(reg, bucket_s=1.0, history_s=120.0, clock=clock)
    j = OpsJournal()
    eng = AlertEngine(cfg, w, metrics=reg, journal=j, recorder=recorder,
                      clock=clock)
    return reg, w, j, eng, clock


class TestAlertEngine:
    def test_rules_built_and_gauges_predeclared(self):
        reg, _, _, eng, _ = make_alert_engine(
            classes={"interactive": {"ttft_p95_ms": 100.0,
                                     "tpot_p95_ms": 20.0,
                                     "availability": 0.999}})
        names = {r.name for r in eng.rules}
        assert names == {"slo_ttft_interactive", "slo_tpot_interactive",
                         "slo_availability_interactive"}
        gauges = reg.names()["gauges"]
        assert "alerts_firing" in gauges
        for n in names:
            assert f"alert_firing_{n}" in gauges

    def test_fires_on_both_windows_then_resolves_on_evidence(self):
        reg, w, j, eng, clock = make_alert_engine()
        h = reg.histogram("ttft_s_class_interactive")
        w.tick()
        assert eng.evaluate(0.0) == []          # no data: no transitions
        for _ in range(10):
            h.observe(0.5)                      # all over the 100ms target
        clock.t = 1.0
        w.tick()
        trs = eng.evaluate(1.0)
        assert [t["transition"] for t in trs] == ["firing"]
        assert eng.firing() == ["slo_ttft_interactive"]
        assert reg.gauge("alerts_firing").value == 1.0
        assert reg.gauge("alert_firing_slo_ttft_interactive").value == 1.0
        # recovery: fresh fast traffic, the bad batch ages out of the
        # fast window while still inside the slow one
        for t_new in (2.0, 3.0, 4.0):
            clock.t = t_new
            for _ in range(10):
                h.observe(0.001)
            w.tick()
        trs = eng.evaluate(4.0)
        assert [t["transition"] for t in trs] == ["resolved"]
        assert eng.firing() == []
        assert reg.gauge("alerts_firing").value == 0.0
        kinds = [e["kind"] for e in j.events()]
        assert kinds == ["alert_firing", "alert_resolved"]
        assert validate_events(j.events()) == []

    def test_no_evidence_neither_fires_nor_resolves(self):
        reg, w, j, eng, clock = make_alert_engine()
        h = reg.histogram("ttft_s_class_interactive")
        w.tick()
        h.observe(9.0)                          # ONE terrible request
        clock.t = 1.0
        w.tick()
        assert eng.evaluate(1.0) == []          # below min_window_count
        # now a real breach...
        for _ in range(10):
            h.observe(9.0)
        clock.t = 2.0
        w.tick()
        assert [t["transition"] for t in eng.evaluate(2.0)] == ["firing"]
        # ...then total silence: empty fast windows must NOT flap it
        for t_new in (5.0, 9.0, 20.0):
            clock.t = t_new
            w.tick()
            assert eng.evaluate(t_new) == []
        assert eng.firing() == ["slo_ttft_interactive"]

    def test_slow_window_guards_against_blips(self):
        """A burst that breaches the fast window but not the slow one
        (diluted by history) must not fire."""
        reg, w, j, eng, clock = make_alert_engine()
        h = reg.histogram("ttft_s_class_interactive")
        w.tick()
        for t_new in (1.0, 2.0, 3.0, 4.0):      # 4s of good history
            clock.t = t_new
            for _ in range(50):
                h.observe(0.001)
            w.tick()
        for _ in range(30):                     # short bad blip
            h.observe(0.5)
        clock.t = 5.0
        w.tick()
        trs = eng.evaluate(5.0)
        st = eng.status()["slo_ttft_interactive"]
        assert st["burn_fast"] > 4.0            # fast window IS breached
        assert st["burn_slow"] < 4.0            # slow one absorbs the blip
        assert trs == [] and eng.firing() == []

    def test_availability_rule(self):
        reg, w, j, eng, clock = make_alert_engine(
            classes={"batch": {"availability": 0.99}})
        sub = reg.counter("requests_submitted_class_batch")
        shed = reg.counter("requests_shed_class_batch")
        w.tick()
        sub.inc(20)
        shed.inc(10)                            # 50% shed vs 1% budget
        clock.t = 1.0
        w.tick()
        trs = eng.evaluate(1.0)
        assert [t["transition"] for t in trs] == ["firing"]
        assert eng.firing() == ["slo_availability_batch"]

    def test_status_reports_budget_spend(self):
        reg, w, j, eng, clock = make_alert_engine()
        h = reg.histogram("ttft_s_class_interactive")
        for _ in range(95):
            h.observe(0.001)
        for _ in range(5):
            h.observe(0.5)
        st = eng.status()["slo_ttft_interactive"]
        # 5% bad on a 5% budget: the whole budget is spent, exactly
        assert st["budget_spent_frac"] == pytest.approx(1.0)
        assert st["target_ms"] == 100.0

    def test_new_firing_dumps_flight_recorder_rate_limited(self, tmp_path):
        tracer = Tracer(enabled=True)
        rec = FlightRecorder(tracer, dump_dir=str(tmp_path),
                             max_error_dumps=1, error_dump_window_s=3600.0)
        reg, w, j, eng, clock = make_alert_engine(recorder=rec)
        h = reg.histogram("ttft_s_class_interactive")
        w.tick()

        def breach_then_recover(t0):
            clock.t = t0 - 1.0
            w.tick()              # keep tick cadence inside the window
            clock.t = t0
            for _ in range(10):
                h.observe(0.5)
            w.tick()
            eng.evaluate(clock.t)
            for dt in (2.0, 4.0):
                clock.t = t0 + dt
                for _ in range(10):
                    h.observe(0.001)
                w.tick()
            eng.evaluate(clock.t)

        breach_then_recover(1.0)
        breach_then_recover(10.0)
        states = eng.status()["slo_ttft_interactive"]
        assert states["fire_count"] == 2
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flightrec_")]
        assert len(dumps) == 1                  # second firing rate-limited
        assert "alert_slo_ttft_interactive" in dumps[0]


# ---------------------------------------------- registry pre-declaration
class TestRegistryPredeclaration:
    def test_custom_classes_expose_zero_series_before_traffic(self):
        reg = serving_metrics(["interactive", "batch", "bulk_eval"])
        text = reg.render_prometheus()
        for cls in ("interactive", "batch", "bulk_eval"):
            assert f"serving_requests_shed_class_{cls} 0" in text
            assert f"serving_requests_submitted_class_{cls} 0" in text
            assert f"serving_queue_depth_class_{cls} 0" in text
            assert f"serving_ttft_s_class_{cls}_count 0" in text
            assert f"serving_tpot_s_class_{cls}_count 0" in text

    def test_stock_registry_has_alerts_firing(self):
        assert "alerts_firing" in serving_metrics().names()["gauges"]

    def test_frontend_declares_configured_classes(self):
        from deepspeed_tpu.serving import ServingConfig, ServingFrontend

        fe = ServingFrontend([tiny_engine()], ServingConfig(
            max_queue_depth=8,
            classes={"realtime": {"priority": 0, "deadline_ms": 500.0}}))
        try:
            assert "ttft_s_class_realtime" in \
                fe.metrics.names()["histograms"]
            assert "serving_requests_shed_class_realtime 0" in \
                fe.render_prometheus()
        finally:
            fe.shutdown(drain=False, timeout=5)


def _doc_metric_table():
    """Parse docs/OBSERVABILITY.md's metric-name reference table into
    {name: kind} (the satellite audit surface)."""
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "OBSERVABILITY.md")
    doc = open(path).read()
    assert "## Metric name reference" in doc, \
        "docs/OBSERVABILITY.md lost its '## Metric name reference' section"
    section = doc.split("## Metric name reference", 1)[1]
    section = section.split("\n## ", 1)[0]
    table = {}
    for line in section.splitlines():
        line = line.strip()
        if not line.startswith("| `"):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        name = cells[0].strip("`")
        kind = cells[1]
        if "<" in name:          # templated rows (per-rule gauges etc.)
            continue
        table[name] = kind
    return table


class TestMetricNameAudit:
    def test_docs_and_registry_agree_both_ways(self):
        """Every metric a fresh registry declares is documented, and
        every documented (non-templated) name exists in a fresh registry
        — docs/OBSERVABILITY.md cannot drift from the code."""
        doc = _doc_metric_table()
        reg = serving_metrics().names()
        actual = {}
        for kind, names in (("counter", reg["counters"]),
                            ("gauge", reg["gauges"]),
                            ("histogram", reg["histograms"])):
            for n in names:
                actual[n] = kind
        missing_in_docs = sorted(set(actual) - set(doc))
        assert not missing_in_docs, \
            f"registry metrics undocumented in OBSERVABILITY.md: " \
            f"{missing_in_docs}"
        ghosts = sorted(set(doc) - set(actual))
        assert not ghosts, \
            f"OBSERVABILITY.md documents metrics no registry declares: " \
            f"{ghosts}"
        wrong_kind = {n: (doc[n], actual[n]) for n in doc
                      if doc[n] != actual[n]}
        assert not wrong_kind, f"kind mismatches: {wrong_kind}"


# --------------------------------------------------------- e2e serving
class TestServingE2E:
    def test_health_report_shape_with_everything_off(self):
        from deepspeed_tpu.serving import ServingConfig, ServingFrontend

        fe = ServingFrontend([tiny_engine()],
                             ServingConfig(max_queue_depth=16))
        try:
            hs = [fe.submit(p, max_new_tokens=3) for p in prompts(4, 0)]
            assert fe.wait_all(hs, timeout=120)
            rep = fe.health_report(window_s=30.0)
            assert rep["slo"] is None and rep["alerts_firing"] == []
            assert rep["counters"]["requests_completed"] == 4
            assert rep["replicas"][0]["state"] == "healthy"
            assert "interactive" in rep["queue"]["per_class"]
            assert rep["window"]["ttft_s"]["count"] >= 1
            text = fe.health_report_text(window_s=30.0)
            assert "serving health" in text and "submitted=4" in text
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_latency_fault_fires_and_resolves_alert(self):
        """The alerting story end to end: a
        slow_forward fault inflates interactive TTFT past the target,
        the burn-rate alert fires (gauge + journal), and once the fault
        clears and fresh traffic repopulates the fast window it
        resolves."""
        from deepspeed_tpu.serving import ServingConfig, ServingFrontend

        scfg = ServingConfig(
            max_queue_depth=32,
            slo={"enabled": True,
                 "classes": {"interactive": {"ttft_p95_ms": 50.0}},
                 "fast_window_s": 0.6, "slow_window_s": 1.5,
                 "window_bucket_s": 0.15, "eval_interval_s": 0.1,
                 "burn_rate_threshold": 4.0, "min_window_count": 2},
            faults={"enabled": True, "schedule": [
                {"kind": "slow_forward", "replica": 0, "at_put": 6,
                 "count": 12, "duration_s": 0.08}]})
        fe = ServingFrontend([tiny_engine()], scfg)
        try:
            ps = prompts(40, 3)
            # warmup compiles outside the fault window (at_put=6)
            fe.wait_all([fe.submit(ps[0], max_new_tokens=2)], timeout=120)
            fired = resolved = False
            deadline = time.monotonic() + 30.0
            i = 0
            while time.monotonic() < deadline and not (fired and resolved):
                h = fe.submit(ps[i % len(ps)], max_new_tokens=3,
                              request_class="interactive")
                h.result(timeout=60)
                i += 1
                fired = fired or fe.journal.count("alert_firing") > 0
                resolved = fired and fe.journal.count("alert_resolved") > 0
            assert fired, "injected latency never fired the alert"
            assert resolved, "alert never resolved after the fault cleared"
            assert fe.metrics.snapshot()["alerts_firing"] == 0.0
            evs = fe.journal.events(kinds=("alert_firing",
                                           "alert_resolved"))
            assert [e["kind"] for e in evs] == ["alert_firing",
                                                "alert_resolved"]
            assert validate_events(fe.journal.events()) == []
            rep = fe.health_report()
            assert rep["slo"]["slo_ttft_interactive"]["fire_count"] == 1
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_disabled_slo_block_is_byte_identical(self):
        """``slo: {enabled: false}`` builds no alert engine and serves the
        greedy streams of a config with no ``slo:`` block at all."""
        from deepspeed_tpu.serving import ServingConfig, ServingFrontend

        ps = prompts(6, 11)

        def gens(extra):
            fe = ServingFrontend([tiny_engine()],
                                 ServingConfig(max_queue_depth=16, **extra))
            try:
                assert fe.alerts is None
                hs = [fe.submit(p, max_new_tokens=6) for p in ps]
                assert fe.wait_all(hs, timeout=120)
                return [[ev.token for ev in h.drain()] for h in hs]
            finally:
                fe.shutdown(drain=False, timeout=5)

        assert gens({"slo": {"enabled": False}}) == gens({})

    def test_windowed_ring_fed_by_router_tick(self):
        from deepspeed_tpu.serving import ServingConfig, ServingFrontend

        fe = ServingFrontend([tiny_engine()], ServingConfig(
            max_queue_depth=16,
            slo={"enabled": False, "window_bucket_s": 0.05}))
        try:
            hs = [fe.submit(p, max_new_tokens=3) for p in prompts(3, 5)]
            assert fe.wait_all(hs, timeout=120)
            deadline = time.monotonic() + 10.0
            while len(fe.windowed) < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert len(fe.windowed) >= 3, \
                "router tick never fed the windowed ring"
        finally:
            fe.shutdown(drain=False, timeout=5)


# -------------------------------------------------------- e2e training
class TestTrainingHealthReport:
    def _build(self, tmp_path, faults=None):
        import deepspeed_tpu
        import deepspeed_tpu.parallel.topology as topo
        from deepspeed_tpu.models import build_model

        topo.reset_topology()
        rng = np.random.default_rng(0)
        cfg = {
            "train_micro_batch_size_per_gpu": 4,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "mesh": {"data": -1, "fsdp": 1},
            "steps_per_print": 10**9,
            "resilience": {"enabled": True, "save_dir": str(tmp_path),
                           "save_interval_steps": 2,
                           "restart_backoff_s": 0.01,
                           "restart_backoff_jitter": 0.0,
                           "watchdog_enabled": False,
                           "faults": faults or {"enabled": False}},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=build_model("tiny"), config=cfg,
            training_data={"input_ids": rng.integers(
                0, 256, size=(64, 33), dtype=np.int64)})
        return engine

    def test_journal_records_checkpoints_restarts_and_report(self, tmp_path):
        from deepspeed_tpu.runtime.resilience import TrainingSupervisor

        engine = self._build(tmp_path, faults={
            "enabled": True,
            "schedule": [{"kind": "crash", "at_step": 3}]})
        sup = TrainingSupervisor(engine=engine)
        r = sup.run(5)
        assert r["status"] == "completed"
        assert sup.journal.count("train_restart") == 1
        assert sup.journal.count("checkpoint_saved") >= 2
        assert validate_events(sup.journal.events()) == []
        rep = sup.health_report()
        assert rep["global_step"] == 5
        assert rep["counters"]["train_restarts"] == 1
        assert any(e["kind"] == "train_restart" for e in rep["events"])
        text = sup.health_report_text()
        assert "training health" in text and "restarts=1" in text
