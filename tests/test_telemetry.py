"""Unified telemetry (ISSUE 4): tracer core, chrome-trace export, flight
recorder, request-trace chain through the serving stack, step profiling in
the training engine, and the observability satellites (MonitorMaster
per-backend isolation, Prometheus exposition, ThroughputTimer memory)."""

import json
import logging
import threading
import time
import tracemalloc

import numpy as np
import pytest

from deepspeed_tpu.telemetry import (NOOP_SPAN, NOOP_TRACER, FlightRecorder,
                                     TelemetryConfig, Tracer, chrome_trace,
                                     trace_coverage, validate_chrome_trace)

VOCAB = 128


def tiny_engine(max_seqs=4, **cfg_over):
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=VOCAB, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=2,
                            max_seq_len=128, norm="rmsnorm",
                            activation="silu", position="rope")
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=max_seqs,
        max_chunk_tokens=32, kv_blocks=64, kv_block_size=8,
        max_tracked_sequences=16, **cfg_over)
    return InferenceEngineV2(CausalLM(cfg), config=vcfg)


# ------------------------------------------------------------- tracer core
def test_span_nesting_and_ordering():
    tr = Tracer()
    with tr.span("outer", trace_id="t") as outer:
        time.sleep(0.001)
        with tr.span("inner") as inner:
            assert tr.current() is inner
            time.sleep(0.001)
        assert tr.current() is outer
    assert tr.current() is None
    spans = tr.export()
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["inner"]["trace_id"] == "t"    # inherited from parent
    # completion order: inner ends first; interval containment holds
    assert spans[0]["name"] == "inner"
    assert by_name["outer"]["t_start"] <= by_name["inner"]["t_start"]
    assert by_name["inner"]["t_end"] <= by_name["outer"]["t_end"]


def test_begin_end_cross_thread():
    tr = Tracer()
    sp = tr.begin("xthread", trace_id="req-1", attrs={"k": 1})

    t = threading.Thread(target=sp.end)
    t.start()
    t.join()
    (d,) = tr.export()
    assert d["name"] == "xthread" and d["t_end"] is not None
    assert d["attrs"]["k"] == 1
    sp.end()                       # idempotent: no double record
    assert len(tr.export()) == 1


def test_ring_buffer_eviction():
    tr = Tracer(max_spans=4)
    for i in range(10):
        tr.begin(f"s{i}").end()
    spans = tr.export()
    assert [s["name"] for s in spans] == ["s6", "s7", "s8", "s9"]


def test_open_spans_visible_and_capped():
    tr = Tracer(max_spans=4)
    spans = [tr.begin(f"open{i}") for i in range(6)]
    ex = tr.export(include_open=True)
    assert all(s["t_end"] is None and s["attrs"]["open"] for s in ex)
    assert len(ex) == 4            # leak cap at max_spans
    assert tr.export(include_open=False) == []
    for sp in spans:
        sp.end()


def test_disabled_is_noop_singleton():
    tr = Tracer(enabled=False)
    assert tr.span("a") is NOOP_SPAN
    assert tr.begin("b") is NOOP_SPAN
    with tr.span("c") as sp:
        assert sp.set("k", 1) is sp
    assert tr.export() == []
    assert NOOP_TRACER.span("d") is NOOP_SPAN


def test_disabled_hot_path_allocation_free():
    """The disabled span() path must not allocate per call — the
    guarantee that lets the scheduler/engine keep tracer calls on their
    hot paths. A transient constant residual (the in-flight bound-method
    object tracemalloc catches) is tolerated; per-iteration growth over
    2000 spans is not."""
    tr = Tracer(enabled=False)
    with tr.span("warm"):          # warm any lazy state
        pass
    here = __file__
    tracer_file = Tracer.__init__.__code__.co_filename
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(2000):
            with tr.span("hot"):
                pass
            tr.begin("hot2").set("k", 1).end()
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    leaked_objects = sum(
        st.count_diff for st in after.compare_to(before, "lineno")
        if st.traceback and st.traceback[0].filename in (here, tracer_file)
        and st.count_diff > 0)
    # 2000 iterations × 2 spans would leave thousands of objects if the
    # disabled path allocated; tracemalloc catches at most a handful of
    # in-flight bound-method objects regardless of the iteration count
    assert leaked_objects <= 8, (
        f"disabled tracer leaked {leaked_objects} objects over 2000 spans")
    assert tr.export() == []


# ------------------------------------------------- profiler annotations
class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records what a span
    does with it (no profiler session is needed to see that)."""
    log = []

    def __init__(self, name, **kwargs):
        self.name = name
        self.log.append(("init", name, kwargs))

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def set_metadata(self, **kwargs):
        self.log.append(("metadata", self.name, kwargs))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture()
def fake_annotation(monkeypatch):
    from deepspeed_tpu.telemetry import tracer as tracer_mod

    monkeypatch.setattr(tracer_mod, "_trace_annotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    return _FakeAnnotation.log


def test_context_span_is_mirrored_as_ds_annotation(fake_annotation):
    """Every context-manager span of an enabled tracer enters and leaves a
    TraceAnnotation named ds:<name>; the attrs it holds when it closes
    (set at creation or later) become the annotation's stats. begin()
    spans belong to no thread and are not mirrored."""
    tr = Tracer()
    with tr.span("step", trace_id="replica-0"):
        with tr.span("stage", attrs={"n": 1}) as sp:
            sp.set("bucket_seqs", 16)
    tr.begin("forward", trace_id="replica-0").end()
    assert fake_annotation == [
        ("init", "ds:step", {}), ("enter", "ds:step"),
        ("init", "ds:stage", {}), ("enter", "ds:stage"),
        ("metadata", "ds:stage", {"n": 1, "bucket_seqs": 16}),
        ("exit", "ds:stage"), ("exit", "ds:step")]
    assert [s["name"] for s in tr.export()] == ["stage", "step", "forward"]


def test_annotation_failure_is_not_swallowed(fake_annotation, monkeypatch):
    def boom(self):
        raise RuntimeError("profiler broke")

    monkeypatch.setattr(_FakeAnnotation, "__enter__", boom)
    with pytest.raises(RuntimeError, match="profiler broke"):
        with Tracer().span("x"):
            pass


def test_disabled_tracer_constructs_no_annotation(fake_annotation):
    tr = Tracer(enabled=False)
    with tr.span("a"):
        pass
    with NOOP_TRACER.span("b"):
        pass
    assert fake_annotation == [] and tr._annotation is None


def test_tracer_works_without_jax_profiler(monkeypatch):
    """Where jax.profiler cannot be imported the absence is recorded once
    (not retried and swallowed per span) and spans are still recorded."""
    import builtins

    from deepspeed_tpu.telemetry import tracer as tracer_mod

    real_import, attempts = builtins.__import__, []

    def no_profiler(name, *args, **kwargs):
        if name == "jax.profiler":
            attempts.append(name)
            raise ImportError("no jax here")
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(tracer_mod, "_trace_annotation", tracer_mod._UNSET)
    monkeypatch.setattr(builtins, "__import__", no_profiler)
    tr = Tracer()
    for _ in range(3):
        with tr.span("a"):
            pass
    Tracer()
    assert tr._annotation is None and len(attempts) == 1
    assert [s["name"] for s in tr.export()] == ["a"] * 3


# ------------------------------------------------------------ chrome trace
def test_chrome_trace_schema_valid():
    tr = Tracer()
    with tr.span("a", trace_id="req-1", attrs={"x": 3}):
        with tr.span("b"):
            pass
    tr.begin("other", trace_id="replica-0").end()
    open_span = tr.begin("inflight", trace_id="req-1")
    obj = chrome_trace(tr.export(), meta={"reason": "test"})
    assert validate_chrome_trace(obj) == []
    # JSON round-trip stays valid (what lands on disk is what's checked)
    assert validate_chrome_trace(json.dumps(obj)) == []
    events = obj["traceEvents"]
    procs = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"req-1", "replica-0"} <= procs
    assert any(e["ph"] == "B" and e["name"] == "inflight" for e in events)
    xs = [e for e in events if e["ph"] == "X"]
    assert xs and all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    open_span.end()


def test_validate_chrome_trace_catches_garbage():
    assert validate_chrome_trace("not json{")
    assert validate_chrome_trace({"no_events": 1})
    bad = {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1,
                            "ts": 0.0}]}          # X without dur
    assert any("dur" in p for p in validate_chrome_trace(bad))
    assert validate_chrome_trace({"traceEvents": [
        {"name": "", "ph": "Z", "pid": "a", "tid": 1, "ts": 0}]})


def test_trace_coverage():
    mk = lambda a, b: {"t_start": a, "t_end": b}  # noqa: E731
    assert trace_coverage([mk(0, 1)], 0, 1) == pytest.approx(1.0)
    # overlap is not double counted; gaps subtract
    assert trace_coverage([mk(0, 0.6), mk(0.4, 1.0)], 0, 1) \
        == pytest.approx(1.0)
    assert trace_coverage([mk(0, 0.25), mk(0.75, 1.0)], 0, 1) \
        == pytest.approx(0.5)
    # open span counts to the window end; out-of-window clipped
    assert trace_coverage([{"t_start": 0.5, "t_end": None}], 0, 1) \
        == pytest.approx(0.5)
    assert trace_coverage([], 0, 1) == 0.0


# --------------------------------------------------------- flight recorder
def test_flight_recorder_dump_and_snapshots(tmp_path):
    tr = Tracer()
    rec = FlightRecorder(tr, max_snapshots=3, dump_dir=str(tmp_path))
    rec.add_metrics_provider("m", lambda: {"v": 7})
    rec.add_metrics_provider("boom", lambda: 1 / 0)   # guarded provider
    for _ in range(5):
        rec.snapshot_metrics()
    with tr.span("work", trace_id="t"):
        pass
    paths = rec.dump(reason="unit")
    record = json.load(open(paths["json"]))
    assert record["format"].startswith("deepspeed_tpu.flight_recorder")
    assert len(record["metric_snapshots"]) == 3          # ring bounded
    assert record["metric_snapshots"][0]["m"] == {"v": 7}
    assert "error" in record["metric_snapshots"][0]["boom"]
    assert [s["name"] for s in record["spans"]] == ["work"]
    assert validate_chrome_trace(json.load(open(paths["chrome_trace"]))) == []


def test_flight_recorder_on_error_rate_limited(tmp_path):
    """Error dumps are limited per sliding window, not per lifetime —
    a burst consumes the slots, but a later incident (after the window)
    is captured again."""
    clock = [100.0]
    tr = Tracer(clock=lambda: clock[0])
    rec = FlightRecorder(tr, dump_dir=str(tmp_path), max_error_dumps=2,
                         error_dump_window_s=60.0)
    outs = [rec.on_error("replica-0", RuntimeError(f"e{i}"))
            for i in range(4)]
    assert [o is not None for o in outs] == [True, True, False, False]
    clock[0] += 61.0                   # window expires → slots free again
    assert rec.on_error("replica-0", RuntimeError("later")) is not None
    # disabled telemetry: error dumps are a no-op, not a file
    rec2 = FlightRecorder(NOOP_TRACER, dump_dir=str(tmp_path))
    assert rec2.on_error("x", RuntimeError()) is None


def test_telemetry_config_builders():
    tc = TelemetryConfig()
    assert tc.build_tracer() is NOOP_TRACER
    tc_on = TelemetryConfig(enabled=True, max_spans=16)
    tr = tc_on.build_tracer()
    assert tr.enabled and tr.max_spans == 16
    # mirroring into the profiler is what an enabled tracer does, not a
    # knob: the config has no field for it and the tracer takes none
    from jax.profiler import TraceAnnotation

    assert tr._annotation is TraceAnnotation
    assert set(TelemetryConfig.model_fields) == {
        "enabled", "max_spans", "max_metric_snapshots", "dump_on_error",
        "max_error_dumps", "error_dump_window_s", "dump_dir"}
    rec = tc_on.build_recorder(tr)
    assert isinstance(rec, FlightRecorder)


# ------------------------------------------------- satellites: prometheus
def test_render_prometheus_counters_gauges():
    from deepspeed_tpu.serving import MetricsRegistry

    reg = MetricsRegistry("serving")
    reg.counter("requests_completed").inc(3)
    reg.gauge("queue_depth").set(5)
    text = reg.render_prometheus()
    lines = text.splitlines()
    assert "# TYPE serving_requests_completed counter" in lines
    assert "serving_requests_completed 3" in lines
    assert "# TYPE serving_queue_depth gauge" in lines
    assert "serving_queue_depth 5" in lines
    assert text.endswith("\n")


def test_render_prometheus_histogram_buckets():
    from deepspeed_tpu.serving import MetricsRegistry

    reg = MetricsRegistry("serving")
    h = reg.histogram("lat_s", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 99.0):    # one over-range sample
        h.observe(v)
    text = reg.render_prometheus()
    lines = text.splitlines()
    assert "# TYPE serving_lat_s histogram" in lines
    # cumulative counts per le, with +Inf covering the overflow sample
    assert 'serving_lat_s_bucket{le="0.1"} 1' in lines
    assert 'serving_lat_s_bucket{le="1"} 3' in lines
    assert 'serving_lat_s_bucket{le="10"} 4' in lines
    assert 'serving_lat_s_bucket{le="+Inf"} 5' in lines
    assert "serving_lat_s_count 5" in lines
    (sum_line,) = [ln for ln in lines if ln.startswith("serving_lat_s_sum")]
    assert float(sum_line.split()[1]) == pytest.approx(105.05)


def test_percentile_clamps_to_largest_finite_bound():
    from deepspeed_tpu.serving.metrics import Histogram

    h = Histogram(buckets=(0.1, 1.0))
    for _ in range(10):
        h.observe(50.0)           # everything over-range
    for q in (1, 50, 99, 100):
        p = h.percentile(q)
        assert np.isfinite(p) and p == 1.0
    assert Histogram(buckets=()).percentile(50) == 0.0


# ---------------------------------------------- satellites: monitor master
def test_monitor_master_isolates_backend_failures(tmp_path, monkeypatch):
    from deepspeed_tpu.monitor import monitor as mon
    from deepspeed_tpu.runtime.config import DeepSpeedTpuConfig

    class Boom(mon.Monitor):
        def __init__(self, *a, **k):
            raise RuntimeError("backend exploded")

    # an early backend failing must not take down the later ones
    monkeypatch.setattr(mon, "CSVMonitor", Boom)
    seen = []

    class Fake(mon.Monitor):
        def __init__(self, *a, **k):
            pass

        def write_events(self, events):
            seen.extend(events)

    monkeypatch.setattr(mon, "TensorBoardMonitor", Fake)
    cfg = DeepSpeedTpuConfig(
        csv_monitor={"enabled": True, "output_path": str(tmp_path)},
        tensorboard={"enabled": True, "output_path": str(tmp_path)})
    # the package logger does not propagate; attach a capture handler
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture(level=logging.WARNING)
    mon.logger.addHandler(handler)
    try:
        mm = mon.MonitorMaster(cfg)
    finally:
        mon.logger.removeHandler(handler)
    assert len(mm.backends) == 1 and isinstance(mm.backends[0], Fake)
    assert any("csv_monitor" in m and "failed to initialize" in m
               for m in records)
    mm.write_events([("a/b", 1.0, 0)])
    assert seen == [("a/b", 1.0, 0)]


def test_monitor_master_all_backends_ok(tmp_path):
    from deepspeed_tpu.monitor import monitor as mon
    from deepspeed_tpu.runtime.config import DeepSpeedTpuConfig

    cfg = DeepSpeedTpuConfig(
        csv_monitor={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "j"})
    mm = mon.MonitorMaster(cfg)
    assert len(mm.backends) == 1
    mm.write_events([("Train/loss", 1.5, 3)])
    out = tmp_path / "j" / "Train_loss.csv"
    assert out.exists() and "1.5" in out.read_text()


# ------------------------------------------ satellites: throughput memory
def test_throughput_timer_monitor_memory():
    import jax.numpy as jnp

    from deepspeed_tpu.utils.timer import ThroughputTimer

    keepalive = jnp.ones((256, 256))      # ensure live device bytes exist
    logs = []
    t = ThroughputTimer(batch_size=4, start_step=1, steps_per_output=1,
                        monitor_memory=True, logging_fn=logs.append)
    for _ in range(2):
        t.start()
        time.sleep(0.001)
        t.stop()
    assert t.memory_bytes is not None
    assert t.memory_bytes >= keepalive.nbytes
    assert any("device_mem=" in m for m in logs)
    # off by default: no memory sampling, no log decoration
    t2 = ThroughputTimer(batch_size=4, start_step=1, steps_per_output=1,
                         logging_fn=logs.append)
    t2.start()
    t2.stop()
    assert t2.memory_bytes is None
    del keepalive


# ----------------------------------------------- engine step profiling
@pytest.mark.parametrize("via", ["wall_clock_breakdown", "telemetry"])
def test_engine_step_profiling(via):
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.utils.timer import (FORWARD_MICRO_TIMER,
                                           STEP_GLOBAL_TIMER)

    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=2,
                            max_seq_len=64, norm="rmsnorm",
                            activation="silu", position="rope")
    ds = {"train_micro_batch_size_per_gpu": 2,
          "gradient_accumulation_steps": 2,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "steps_per_print": 1, "mesh": {"data": -1, "fsdp": 1}}
    ds[via] = {"enabled": True} if via == "telemetry" else True
    engine, _, _, _ = deepspeed_tpu.initialize(model=CausalLM(cfg),
                                               config=ds)
    rng = np.random.default_rng(0)
    gb = 2 * engine.topology.get_data_parallel_world_size()
    data = {"input_ids": rng.integers(0, 64, size=(gb, 33), dtype=np.int64)}
    syncs = []
    real_sync = engine.timers._sync_fn
    engine.timers._sync_fn = lambda: (syncs.append(1), real_sync())
    engine.train_batch(iter([data, data]))
    # flops_per_sample auto-populated from the flops profiler (satellite)
    from deepspeed_tpu.profiling import train_step_flops

    assert engine.tput_timer.flops_per_sample \
        == pytest.approx(train_step_flops(cfg, 1, 32))
    if via == "telemetry":
        # tracing alone records the spans round the dispatches and
        # synchronizes nothing: it must not change what it traces
        assert not engine._profile_steps and not syncs
        assert not engine.timers.timers
        spans = engine.tracer.export()
        names = [s["name"] for s in spans]
        assert names.count("fwd_bwd") == 2       # gas=2 micro steps
        assert names.count("optimizer_step") == 1
        # (the process's program builds and full collections ride along
        # under an id of their own)
        assert all(s["trace_id"] == "train" for s in spans
                   if s["name"] not in ("program_build", "gc"))
        assert [s["attrs"] for s in spans if s["name"] == "fwd_bwd"] == \
            [{"micro_step": 0}, {"micro_step": 1}]
    else:
        # synchronized timers recorded both phases
        assert engine._profile_steps and syncs
        assert engine.timers.has(FORWARD_MICRO_TIMER)
        assert engine.timers.has(STEP_GLOBAL_TIMER)
        assert engine.timers(FORWARD_MICRO_TIMER).mean() > 0
        assert not engine.tracer.enabled


def test_engine_profiling_off_by_default():
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=2,
                            max_seq_len=64, norm="rmsnorm",
                            activation="silu", position="rope")
    ds = {"train_micro_batch_size_per_gpu": 2,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "steps_per_print": 10**9, "mesh": {"data": -1, "fsdp": 1}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=CausalLM(cfg),
                                               config=ds)
    rng = np.random.default_rng(0)
    gb = 2 * engine.topology.get_data_parallel_world_size()
    data = {"input_ids": rng.integers(0, 64, size=(gb, 17), dtype=np.int64)}
    engine.train_batch(iter([data]))
    assert not engine._profile_steps
    assert engine.tracer is NOOP_TRACER
    assert not engine.timers.timers       # no timers touched off the path


# --------------------------------------------------- end-to-end serving
def _stage_spans(spans, trace_id):
    return {s["name"]: s for s in spans if s["trace_id"] == trace_id}


def test_e2e_request_span_chain():
    """An end-to-end serving request produces the complete
    queue→route→admit→prefill→decode chain under one trace id, with
    prefix-cache and speculation attributes, covering ≥95% of TTFT."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    eng = tiny_engine(enable_prefix_cache=True)
    fe = ServingFrontend([eng], ServingConfig(
        max_queue_depth=8,
        speculative={"enabled": True, "mode": "ngram", "ngram_max": 3,
                     "max_draft_tokens": 3},
        telemetry={"enabled": True}))
    try:
        rng = np.random.default_rng(0)
        motif = rng.integers(0, VOCAB, size=8).tolist()
        prompt = motif * 4                       # 32 tokens, 4 full blocks
        h1 = fe.submit(prompt, max_new_tokens=6)
        assert fe.wait_all([h1], timeout=120)
        # same prompt again: the prefix cache now has blocks to hit
        h2 = fe.submit(prompt, max_new_tokens=6)
        assert fe.wait_all([h2], timeout=120)

        spans = fe.tracer.export()
        for h in (h1, h2):
            req = h._req
            chain = _stage_spans(spans, req.trace_id)
            assert {"request", "queue", "route", "admit", "prefill",
                    "decode"} <= set(chain)
            # stage ordering: each stage starts no earlier than the last
            order = ["queue", "route", "admit", "prefill", "decode"]
            for a, b in zip(order, order[1:]):
                assert chain[a]["t_start"] <= chain[b]["t_start"] + 1e-9
                assert chain[a]["t_end"] <= chain[b]["t_end"] + 1e-9
            root = chain["request"]
            assert root["attrs"]["state"] == "finished"
            assert root["attrs"]["finish_reason"] == "length"
            assert root["attrs"]["generated"] == 6
            # TTFT coverage ≥ 95% (the acceptance criterion, in-test)
            stages = [chain[n] for n in order[:-1]]
            cov = trace_coverage(stages, req.arrival_t, req.first_token_t)
            assert cov >= 0.95, f"span chain covers only {cov:.1%} of TTFT"
            # speculation attrs live on the decode span (repetitive
            # prompt → the n-gram proposer must have proposed)
            assert chain["decode"]["attrs"].get("spec_proposed", 0) > 0
        # prefix attrs: first request misses, second hits full blocks
        c1 = _stage_spans(spans, h1._req.trace_id)["prefill"]["attrs"]
        c2 = _stage_spans(spans, h2._req.trace_id)["prefill"]["attrs"]
        assert c1["prefix_matched_tokens"] == 0
        assert c2["prefix_matched_tokens"] > 0
        # per-forward spans recorded under the replica trace
        fwd = [s for s in spans if s["trace_id"] == "replica-0"
               and s["name"] == "forward"]
        assert fwd and all(s["attrs"]["n_seqs"] >= 1 for s in fwd)
        assert any(s["name"] == "spec_verify" for s in spans
                   if s["trace_id"] == "replica-0")
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_serving_telemetry_off_records_nothing():
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    fe = ServingFrontend([tiny_engine()], ServingConfig(max_queue_depth=8))
    try:
        rng = np.random.default_rng(1)
        h = fe.submit(rng.integers(0, VOCAB, size=12).tolist(),
                      max_new_tokens=3)
        assert fe.wait_all([h], timeout=120)
        assert not fe.tracer.enabled
        assert fe.tracer.export() == []
        assert h._req.spans is None and h._req.trace_id is None
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_greedy_parity_telemetry_on_vs_off():
    """Tracing must never change the token streams (prefix cache +
    speculation active) — byte-identical on vs off."""
    from deepspeed_tpu.inference.v2.scheduler import (
        ContinuousBatchingScheduler)
    from deepspeed_tpu.inference.v2.spec import NGramProposer
    from deepspeed_tpu.inference.v2.testing import (assert_greedy_parity,
                                                    greedy_generate)

    rng = np.random.default_rng(2)
    motif = rng.integers(0, VOCAB, size=6).tolist()
    prompts = [motif * 3 + rng.integers(0, VOCAB, size=4).tolist()
               for _ in range(3)]

    def run(tracer):
        eng = tiny_engine(enable_prefix_cache=True)
        sched = ContinuousBatchingScheduler(
            eng, proposer=NGramProposer(ngram_max=3), max_draft_tokens=3,
            tracer=tracer, trace_label="parity")
        return greedy_generate(prompts=prompts, uid_base=500,
                               max_new_tokens=8, scheduler=sched)

    ref = run(None)
    traced = run(Tracer())
    assert_greedy_parity(ref, traced, label="telemetry")


def test_scheduler_step_phase_spans():
    """One step is a ``step`` span whose children are pack, stage, fetch
    and commit in that order — pack and stage of the step it dispatches,
    fetch and commit of the step before it, which ran meanwhile (docs/
    SERVING.md "A step in flight"); ``stage`` (and the kept ``forward``)
    carry the engine's own record of the put."""
    from deepspeed_tpu.inference.v2.scheduler import (
        ContinuousBatchingScheduler)

    eng = tiny_engine()
    tr = Tracer()
    sched = ContinuousBatchingScheduler(eng, tracer=tr,
                                        trace_label="replica-7")
    sched.submit(1, list(range(1, 12)), max_new_tokens=4)
    sched.submit(2, list(range(20, 25)), max_new_tokens=4)
    sched.step()
    record = dict(eng.last_put)
    assert record == {"bucket_seqs": 2, "bucket_chunk": 16, "rows": 2,
                      "valid_tokens": 16, "kv_read_tokens": 16,
                      "qk_pairs": 11 * 12 // 2 + 5 * 6 // 2,
                      # 11 and 5 tokens in 8-token blocks, of 2 tables of
                      # 128 / 8 slots
                      "kv_blocks_live": 2 + 1, "kv_table_slots": 2 * 16,
                      "free_blocks": eng.state_manager.available_blocks}
    # the first step dispatches and leaves its forward in flight
    first = {s["name"]: s for s in tr.export()}
    assert set(first) == {"step", "pack", "stage", "dispatch", "forward"}
    assert first["forward"]["t_end"] is None
    assert first["step"]["attrs"] == {"overlapped": False, "starved": False}
    assert first["dispatch"]["parent_id"] == first["stage"]["span_id"]
    tr.clear()
    # the second dispatches a decode step and then retires the first
    sched.step()
    decode_put = dict(eng.last_put)
    spans = {s["name"]: s for s in tr.export() if s["t_end"] is not None}
    # (the first step's forward ends with its fetch, here)
    assert set(spans) == {"step", "pack", "stage", "dispatch", "fetch",
                          "commit", "forward"}
    assert spans["forward"]["span_id"] == first["forward"]["span_id"]
    assert spans["forward"]["t_end"] == spans["fetch"]["t_end"] or \
        spans["fetch"]["t_end"] <= spans["forward"]["t_end"] \
        <= spans["commit"]["t_start"]
    step = spans["step"]
    assert step["parent_id"] is None and step["trace_id"] == "replica-7"
    assert step["attrs"]["overlapped"] is True
    assert set(step["attrs"]) == {"overlapped", "starved"}
    phases = [spans[n] for n in ("pack", "stage", "fetch", "commit")]
    for a, b in zip(phases, phases[1:]):
        assert a["t_end"] <= b["t_start"]
    for ph in phases:
        assert ph["parent_id"] == step["span_id"]
        assert ph["trace_id"] == "replica-7"
        assert step["t_start"] <= ph["t_start"] and ph["t_end"] <= step["t_end"]
    spans["stage"] = first["stage"]
    # ``stage`` carries the record without the walk's two counts (the
    # benchmark's agreement test pins its keys); ``forward`` carries all
    walk = {"kv_blocks_live", "kv_table_slots"}
    assert spans["stage"]["attrs"] == {k: v for k, v in record.items()
                                       if k not in walk}
    assert spans["forward"]["attrs"] == dict(record, n_seqs=2, n_tokens=16)
    # a decode step: one position a row, every key seen so far read
    assert eng.last_put == decode_put
    assert eng.last_put["bucket_chunk"] == 1
    assert eng.last_put["kv_read_tokens"] == 12 + 6
    assert eng.last_put["qk_pairs"] == 12 + 6
    assert eng.last_put["kv_blocks_live"] == 2 + 1
    assert eng.put_totals == {"forwards": 2, "tokens_valid": 18,
                              "positions_computed": 2 * 16 + 2,
                              "puts_split": 0, "forwards_qkv_fused": 2,
                              "forwards_merged": 0}
    # a step with nothing to run is a step with a pack and no more
    idle = ContinuousBatchingScheduler(tiny_engine(), tracer=Tracer())
    assert idle.step() == []
    assert sorted(s["name"] for s in idle.tracer.export()) == \
        ["pack", "step"]


def test_tokens_and_finish_order_identical_traced_or_not():
    """Plain decoding (no speculation), requests of different lengths:
    the streamed tokens and the order requests finish in do not depend on
    the tracer."""
    from deepspeed_tpu.inference.v2.scheduler import (
        ContinuousBatchingScheduler)

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, size=n).tolist() for n in (9, 40, 3)]

    def run(tracer):
        sched = ContinuousBatchingScheduler(tiny_engine(), tracer=tracer)
        events = []
        for uid, (p, n) in enumerate(zip(prompts, (7, 3, 5))):
            sched.submit(uid, p, max_new_tokens=n,
                         on_token=lambda u, t: events.append(("tok", u, t)),
                         on_finish=lambda r, why: events.append(
                             ("fin", r.uid, why)))
        sched.run_to_completion()
        return json.dumps(events).encode()

    assert run(None) == run(Tracer())


def test_replica_loop_spans_and_put_counters():
    """The worker names what it does between steps (admit_inbox when
    there was something to admit, one idle_wait per idle period) and
    publishes the engine's cumulative put counters."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    eng = tiny_engine()
    fe = ServingFrontend([eng], ServingConfig(
        max_queue_depth=8, telemetry={"enabled": True}))
    try:
        h = fe.submit(list(range(1, 10)), max_new_tokens=3)
        assert fe.wait_all([h], timeout=120)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not any(
                s["name"] == "idle_wait" and s["attrs"].get("open")
                for s in fe.tracer.export()):
            time.sleep(0.002)
        spans = [s for s in fe.tracer.export()
                 if s["trace_id"] == "replica-0"]
        names = [s["name"] for s in spans]
        assert names.count("admit_inbox") == 1
        assert names.count("step") == names.count("publish_stats") >= 3
        # idle before the request and after it: two periods, not one span
        # per 5 ms wait
        assert names.count("idle_wait") == 2
        snap = fe.metrics_snapshot()
        assert snap["forwards"] == eng.put_totals["forwards"] >= 3
        # (the last of the three tokens is drawn and never fed)
        assert snap["tokens_valid"] == eng.put_totals["tokens_valid"] == 9 + 2
        assert snap["positions_computed"] == \
            eng.put_totals["positions_computed"] >= 16 + 2
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_frontend_debug_dump_and_prometheus(tmp_path):
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    fe = ServingFrontend([tiny_engine()], ServingConfig(
        max_queue_depth=8,
        telemetry={"enabled": True, "dump_dir": str(tmp_path)}))
    try:
        rng = np.random.default_rng(3)
        h = fe.submit(rng.integers(0, VOCAB, size=10).tolist(),
                      max_new_tokens=3)
        assert fe.wait_all([h], timeout=120)
        paths = fe.debug_dump()
        record = json.load(open(paths["json"]))
        assert record["reason"] == "debug"
        assert any(s["name"] == "request" for s in record["spans"])
        assert record["metric_snapshots"], "debug dump must snapshot metrics"
        snap = record["metric_snapshots"][-1]["serving"]
        assert snap["requests_completed"] == 1
        assert validate_chrome_trace(
            json.load(open(paths["chrome_trace"]))) == []
        # Prometheus rendering of the same registry, via the frontend
        text = fe.render_prometheus()
        assert "serving_requests_completed 1" in text.splitlines()
        assert 'serving_ttft_s_bucket{le="+Inf"} 1' in text.splitlines()
    finally:
        fe.shutdown(drain=False, timeout=5)


def test_replica_error_writes_flight_record(tmp_path):
    """A replica death (engine fault) leaves a flight-recorder dump with
    the in-flight span evidence."""
    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    eng = tiny_engine()
    fe = ServingFrontend([eng], ServingConfig(
        max_queue_depth=8,
        telemetry={"enabled": True, "dump_dir": str(tmp_path)}))
    try:
        def boom(*a, **k):
            raise RuntimeError("engine exploded")

        eng.put = boom
        rng = np.random.default_rng(4)
        h = fe.submit(rng.integers(0, VOCAB, size=10).tolist(),
                      max_new_tokens=3)
        assert h._req.wait(60)
        assert h._req.state.value == "failed"
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            dumps = list(tmp_path.glob("flightrec_*_error_replica-0_*.json"))
            if dumps:
                break
            time.sleep(0.05)
        assert dumps, "no flight-recorder dump after replica death"
        record = json.load(open(dumps[0]))
        assert record["reason"] == "error_replica-0"
        # the doomed request's spans are in the record (open or closed)
        assert any(s["trace_id"] == h._req.trace_id
                   for s in record["spans"])
    finally:
        fe.shutdown(drain=False, timeout=5)
