"""The recorder of program builds and full collections
(deepspeed_tpu/telemetry/builds.py): what JAX's monitoring events and the
collector's callbacks are turned into, on the CPU — records, sums, the
union over threads, the spans an enabled tracer is fed and the registry
counters a replica publishes."""

import gc
import sys
import threading
import time

import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401  (its import starts the recorder)
from deepspeed_tpu.telemetry import (NOOP_TRACER, TelemetryConfig,
                                     chrome_trace, validate_chrome_trace)
from deepspeed_tpu.telemetry import builds
from deepspeed_tpu.telemetry.builds import RECORDER, BuildRecorder

TRACE, LOWER, COMPILE = builds.STAGE_OF_EVENT     # the three event names


def _jitted(name):
    """A jitted function of a name of its own (a program JAX has not built
    in this process)."""
    import jax

    def fn(x):
        return x * 2.0 + 1.0

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _build(name, width=8):
    return np.asarray(_jitted(name)(np.ones((width,), np.float32)))


def _as_jax_names(name):
    """The name each stage's event carries: the function's for its trace,
    the module's for its lowering and its compile."""
    return {"trace": name, "lower": f"jit({name})", "compile": f"jit({name})"}


def _stages_of(snap, name):
    return {stage: snap["by_fun_name"][stage].get(jax_name,
                                                  {"count": 0})["count"]
            for stage, jax_name in _as_jax_names(name).items()}


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


# ------------------------------------------------------- JAX's own events

def test_a_first_call_is_one_stage_of_each_kind_and_a_second_call_none():
    fn = _jitted("bt_first_call")
    x = np.ones((8,), np.float32)
    np.asarray(fn(x))
    first = RECORDER.snapshot()
    assert _stages_of(first, "bt_first_call") == {
        "trace": 1, "lower": 1, "compile": 1}
    t1 = time.monotonic()
    np.asarray(fn(x))
    second = RECORDER.snapshot(since=t1)
    assert _stages_of(second, "bt_first_call") == {
        "trace": 1, "lower": 1, "compile": 1}
    assert [second[s]["count"] for s in builds.STAGES] == [0, 0, 0]
    for stage, jax_name in _as_jax_names("bt_first_call").items():
        entry = first["by_fun_name"][stage][jax_name]
        assert 0 <= entry["self_seconds"] <= entry["seconds"]
    # ``x * 2.0 + 1.0`` traced a jit of ``multiply`` and one of ``add``
    # inside it: announced, their seconds the trace's own no more
    fn_trace = first["by_fun_name"]["trace"]["bt_first_call"]
    assert fn_trace["self_seconds"] < fn_trace["seconds"]


def test_records_lie_on_the_monotonic_clock_and_until_cuts_them():
    t0 = time.monotonic()
    _build("bt_before_the_cut")
    cut = time.monotonic()
    _build("bt_behind_the_cut")
    t1 = time.monotonic()
    with RECORDER._lock:
        mine = [r for r in RECORDER._records if "_the_cut" in r[1]]
    assert len(mine) == 6
    assert all(t0 <= r[2] <= r[3] <= t1 for r in mine)
    early = RECORDER.snapshot(since=t0, until=cut)
    # the nested traces of ``multiply`` and ``add`` count with their holder
    assert [early[s]["count"] for s in builds.STAGES] == [3, 1, 1]
    assert 0 < early["build_wall_seconds"] <= cut - t0
    both = RECORDER.snapshot(since=t0)
    assert [both[s]["count"] for s in builds.STAGES] == [6, 2, 2]
    assert both["build_wall_seconds"] >= early["build_wall_seconds"]


def test_four_threads_compiling_at_once_lose_nothing():
    names = [f"bt_thread_{i}" for i in range(4)]
    gate = threading.Barrier(4)

    def work(name):
        gate.wait(timeout=30)
        _build(name, width=16)

    threads = [threading.Thread(target=work, args=(n,)) for n in names]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    t1 = time.monotonic()
    assert not any(t.is_alive() for t in threads)
    snap = RECORDER.snapshot(since=t0)
    longest = 0.0
    for name in names:
        assert _stages_of(snap, name) == {"trace": 1, "lower": 1,
                                          "compile": 1}
        longest = max(longest, *(
            snap["by_fun_name"][stage][jax_name]["seconds"]
            for stage, jax_name in _as_jax_names(name).items()))
    # the union: no longer than the time the four took, no shorter than
    # the longest single record, no longer than the thread seconds
    assert longest <= snap["build_wall_seconds"] <= t1 - t0
    assert snap["build_wall_seconds"] <= sum(
        snap[s]["self_seconds"] for s in builds.STAGES) + 1e-9


def test_the_total_equals_a_second_listeners():
    """``benchmark.device.CompileWatch`` hears the same events from the
    benchmark's side: ``compile_seconds`` of a run's ``extra`` line."""
    from benchmark.device import CompileWatch

    watch = CompileWatch()
    before = RECORDER.snapshot()["announced"]["compile"]
    t0 = time.monotonic()
    for i in range(3):
        _build(f"bt_watched_{i}")
    snap = RECORDER.snapshot(since=t0)
    after = snap["announced"]["compile"]
    assert after["count"] - before["count"] == watch.count >= 3
    assert after["seconds"] - before["seconds"] == pytest.approx(
        watch.seconds, rel=1e-9)
    assert snap["compile"]["count"] == watch.count
    assert snap["cache_hits"] == watch.hits


def test_a_second_build_behind_clear_caches_is_a_cache_hit(tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    tracer = TelemetryConfig(enabled=True, max_spans=1 << 16).build_tracer()
    try:
        for k, v in zip(keys, (str(tmp_path), 0.0, 0)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        t0 = time.monotonic()
        _build("bt_cached")
        jax.clear_caches()
        _build("bt_cached")
        snap = RECORDER.snapshot(since=t0)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    mine = [s["attrs"] for s in tracer.export()
            if s["name"] == "program_build"
            and s["attrs"]["fun_name"] == "jit(bt_cached)"
            and s["attrs"]["stage"] == "compile"]
    assert len(mine) == 2
    if "cache_hit" not in mine[0]:
        pytest.skip("this backend keeps no persistent compile cache: "
                    "neither a hit nor a miss was announced")
    assert [a["cache_hit"] for a in mine] == [False, True]
    assert snap["cache_hits"] >= 1 and snap["cache_misses"] >= 1
    assert RECORDER.counters()["compile_cache_misses"] >= 1


def test_starting_twice_registers_one_listener():
    from jax._src import monitoring

    RECORDER.start()
    RECORDER.start()
    mine = [fn for fn in monitoring.get_event_duration_listeners()
            if getattr(fn, "__self__", None) is RECORDER]
    assert len(mine) == 1
    for listeners in (monitoring.get_event_listeners(),
                      monitoring._scalar_listeners):
        assert [getattr(fn, "__self__", None)
                for fn in listeners].count(RECORDER) == 1
    assert gc.callbacks.count(RECORDER._on_gc) == 1


# ------------------------------------------------- a recorder of its own

def test_a_full_list_folds_into_the_sums():
    clock = _Clock()
    rec = BuildRecorder(max_records=4, clock=clock)
    for i in range(10):
        clock.t += 1.0
        rec._on_duration(COMPILE, 0.5, fun_name=f"f{i}")
    snap = rec.snapshot()
    assert snap["dropped"] == 6 and len(rec._records) == 4
    assert snap["compile"] == {"self_seconds": 5.0, "count": 10}
    assert snap["announced"]["compile"] == {
        "seconds": 5.0, "self_seconds": 5.0, "count": 10}
    # the union sees the four records that are left
    assert snap["build_wall_seconds"] == pytest.approx(2.0)
    assert [r[1] for r in rec._records] == ["f6", "f7", "f8", "f9"]
    assert rec.counters()["program_builds"] == 10
    # a cut behind the folded records leaves them out
    assert rec.snapshot(since=107.5)["compile"]["count"] == 3


def test_a_stage_inside_a_stage_counts_once():
    """A jit called inside a function being traced announces its trace
    inside its caller's: the announced seconds count those seconds twice,
    the self seconds once, and only the outermost stage is a record."""
    clock = _Clock()
    rec = BuildRecorder(clock=clock)
    rec._on_scalar(TRACE, 0.0, fun_name="outer")         # 100.00
    for t_end in (101.0, 101.5):                         # two of 0.25 s
        rec._on_scalar(TRACE, 0.0, fun_name="inner")
        clock.t = t_end
        rec._on_duration(TRACE, 0.25, fun_name="inner")
    clock.t = 102.0
    rec._on_duration(TRACE, 2.0, fun_name="outer")       # 100.00 .. 102.00
    rec._on_scalar(LOWER, 0.0, fun_name="jit(outer)")
    clock.t = 103.0
    rec._on_duration(LOWER, 1.0, fun_name="jit(outer)")  # 102.00 .. 103.00
    snap = rec.snapshot()
    assert snap["announced"]["trace"] == {
        "seconds": 2.5, "self_seconds": 2.0, "count": 3}
    assert snap["by_fun_name"]["trace"]["outer"]["self_seconds"] == 1.5
    assert snap["trace"] == {"self_seconds": 2.0, "count": 3}
    assert snap["lower"] == {"self_seconds": 1.0, "count": 1}
    assert snap["build_wall_seconds"] == pytest.approx(3.0)
    assert [(r[0], r[1]) for r in rec._records] == [
        ("trace", "outer"), ("lower", "jit(outer)")]
    assert rec.counters()["program_build_seconds"] == pytest.approx(3.0)


def test_threads_hammering_the_listeners_lose_no_update():
    rec = BuildRecorder(max_records=64)
    n_threads, n_events = 16, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(n_events):
                if i % 4 == 0:
                    rec._on_event("/jax/compilation_cache/cache_hits")
                rec._on_duration(COMPILE if i % 4 == 0 else TRACE,
                                 0.001, fun_name=f"t{k}")

        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    snap = rec.snapshot()
    assert snap["trace"]["count"] + snap["compile"]["count"] == \
        n_threads * n_events
    assert snap["compile"]["count"] == snap["cache_hits"] == \
        n_threads * n_events // 4
    assert snap["dropped"] == n_threads * n_events - 64


def test_a_registry_is_told_each_build_once():
    rec = BuildRecorder()
    rec._on_duration(COMPILE, 0.5, fun_name="f")

    class Registry:
        pass

    one, other = Registry(), Registry()
    assert rec.unpublished(one)["program_builds"] == 1
    assert rec.unpublished(one)["program_builds"] == 0     # a second replica
    rec._on_duration(COMPILE, 0.5, fun_name="g")
    assert rec.unpublished(one)["program_builds"] == 1
    assert rec.unpublished(other)["program_builds"] == 2
    assert tuple(rec.counters()) == builds.COUNTER_NAMES


def test_the_serving_registry_declares_the_counters():
    from deepspeed_tpu.serving.metrics import serving_metrics

    snap = serving_metrics().snapshot()
    for name in builds.COUNTER_NAMES:
        assert snap[name] == 0


# ------------------------------------------------------------------ spans

def test_an_enabled_tracer_is_fed_the_builds_and_a_disabled_one_nothing():
    _build("bt_before_the_tracer")
    fed = len(RECORDER._tracers)
    assert TelemetryConfig(enabled=False).build_tracer() is NOOP_TRACER
    assert len(RECORDER._tracers) == fed and NOOP_TRACER.export() == []
    tracer = TelemetryConfig(enabled=True, max_spans=1 << 16).build_tracer()
    _build("bt_behind_the_tracer")
    spans = [s for s in tracer.export() if s["name"] == "program_build"]
    for name in ("bt_before_the_tracer", "bt_behind_the_tracer"):
        mine = [s for s in spans
                if s["attrs"]["fun_name"] in (name, f"jit({name})")]
        assert sorted(s["attrs"]["stage"] for s in mine) == [
            "compile", "lower", "trace"]
        assert all(s["trace_id"] == "startup" and s["t_start"] <= s["t_end"]
                   and s["attrs"]["thread"] == "MainThread" for s in mine)
    assert len({s["span_id"] for s in spans}) == len(spans)
    assert validate_chrome_trace(chrome_trace(tracer.export())) == []


class _Annotation:
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.fixture()
def collecting():
    """A recorder of its own on the collector's callbacks, taken off them
    again; what it mirrors into the profiler goes to ``_Annotation``."""
    from deepspeed_tpu.telemetry import Tracer

    rec, tracer = BuildRecorder(), Tracer(max_spans=1 << 10)
    rec.feed(tracer)
    rec._annotation, _Annotation.log = _Annotation, []
    gc.callbacks.append(rec._on_gc)
    try:
        yield rec, tracer
    finally:
        gc.callbacks.remove(rec._on_gc)


def _gc_spans(rec, tracer):
    rec.counters()          # the recorder's next turn adopts what ended
    return [s for s in tracer.export() if s["name"] == "gc"]


def test_a_full_collection_is_one_gc_span_and_a_young_one_none(collecting):
    rec, tracer = collecting
    gc.collect(0)
    gc.collect(1)
    assert _gc_spans(rec, tracer) == [] and _Annotation.log == []
    t0 = time.monotonic()
    gc.collect()
    t1 = time.monotonic()
    span, = _gc_spans(rec, tracer)
    assert span["trace_id"] == "startup" and span["parent_id"] is None
    assert t0 <= span["t_start"] <= span["t_end"] <= t1
    assert span["attrs"]["generation"] == 2
    # mirrored into the profiler's trace there and then
    assert _Annotation.log == [("enter", "ds:gc"), ("exit", "ds:gc")]
    snap = rec.snapshot()
    assert snap["gc"]["count"] == 1
    assert snap["gc"]["self_seconds"] == span["t_end"] - span["t_start"]
    assert rec.counters()["gc_full_collections"] == 1
    assert validate_chrome_trace(chrome_trace(tracer.export())) == []


def test_a_collection_inside_a_span_is_that_spans_child(collecting):
    rec, tracer = collecting
    with tracer.span("commit", trace_id="replica-0") as commit:
        gc.collect()
    span, = _gc_spans(rec, tracer)
    assert span["parent_id"] == commit.span_id
    assert span["trace_id"] == "replica-0"


def test_without_a_fed_tracer_a_collection_is_counted_and_not_mirrored():
    rec = BuildRecorder()
    rec._annotation = _Annotation
    _Annotation.log = []
    gc.callbacks.append(rec._on_gc)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(rec._on_gc)
    assert rec.counters()["gc_full_collections"] == 1
    assert rec.counters()["gc_full_seconds"] > 0
    assert _Annotation.log == []
