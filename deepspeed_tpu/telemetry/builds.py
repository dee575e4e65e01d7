"""Start-up telemetry: every program the process builds and every full
collection of the garbage collector, recorded where JAX and the collector
report them themselves (docs/OBSERVABILITY.md "Start-up: program builds
and full collections").

JAX announces each stage of each program it builds through
``jax.monitoring`` — the trace to a jaxpr, the lowering to MLIR and the
backend's compile (a read of the persistent cache included), each at its
start and at its end, with the function's name, on the thread that does
the work — and the interpreter announces each collection through
``gc.callbacks``. One process-wide :data:`RECORDER` listens to both from
the package's import on, so no line on a forward's or the build path's
Python stack is there for the measurement: the stack rides in every
Mosaic kernel's compile-cache key, and
``InferenceEngineV2._compile_ahead`` overlaps its lowering with compiles
on threads, which a timer in that path would undo.

**Stages nest.** A jit called inside a function that is being traced —
every ``jnp`` function is one — announces its own trace inside its
caller's: a set-up announces some 10⁴ stages for some 10² programs, and
the plain sum of the announced seconds counts the nested ones twice. The
recorder follows the nesting from the announcements of a stage's start
and end (a stack a thread), books each stage's *self* seconds — its
length less what ran inside it — to that stage, and keeps a **record**
for the outermost stages only: ``(stage, fun_name, t_start, t_end,
thread, cache hit or miss)`` on ``time.monotonic``, the tracer's clock,
with the self seconds and counts of everything that ran inside it.

What it keeps, under one lock (compile events arrive on the
compile-ahead threads): seconds, self seconds and count of each stage, in
all and by the name the event carried; the persistent cache's seconds; a
bounded list of records whose oldest entries fold into sums when it is
full; the same for generation-2 collections.
:meth:`BuildRecorder.snapshot` adds the length of the union of the
records' intervals over all threads: the wall seconds in which the
process was building some program.

An enabled :class:`~deepspeed_tpu.telemetry.tracer.Tracer` built by
``TelemetryConfig.build_tracer`` is fed (:meth:`BuildRecorder.feed`):
the records held are replayed into it as ``program_build`` spans under
the trace id ``startup`` and later ones follow as they end; a disabled
tracer is never fed. Stdlib at import; ``jax.monitoring`` is imported
when the recorder starts listening.
"""

from __future__ import annotations

import gc
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils.locks import RankedLock
from .tracer import (ANNOTATION_PREFIX, Tracer, _annotation_class,
                     union_seconds)

#: JAX's events (jax/_src/dispatch.py) by the stage they time: a scalar
#: of this name at the stage's start, a duration at its end
STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
STAGES = ("trace", "lower", "compile")
#: the persistent cache's own events (jax/_src/compiler.py,
#: compilation_cache.py); a hit or a miss is announced inside the
#: ``compile`` stage it belongs to, on that stage's thread
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_seconds",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_time_saved_seconds",
}
#: the trace id of the spans the recorder feeds: the process's own track
TRACE_ID = "startup"
#: outermost stages only: three a program and one an eager operation's
#: first call, a few hundred a set-up
MAX_RECORDS = 1 << 13
#: the registry counters a replica publishes (serving/metrics.py,
#: serving/replica.py), in the order of :meth:`BuildRecorder.counters`
COUNTER_NAMES = ("program_builds", "program_build_seconds",
                 "compile_cache_misses", "gc_full_collections",
                 "gc_full_seconds")

# A record: (stage, fun_name, t_start, t_end, tid, thread_name, cache,
# inside) — stage one of STAGES or "gc"; cache None, "hit" or "miss";
# ``inside`` what the record's interval holds, itself included, as
# {key: [self seconds, count]} with a key a stage, "hit" or "miss".


def _merge(into: Dict[str, list], other: Dict[str, list]) -> None:
    for key, (seconds, count) in other.items():
        entry = into.setdefault(key, [0.0, 0])
        entry[0] += seconds
        entry[1] += count


class BuildRecorder:
    """Listener for JAX's build events and the collector's callbacks.
    The process has one, :data:`RECORDER`; a test makes its own and calls
    its listeners itself."""

    # ``_tracers`` is rebound under the lock and read without it by the
    # collector's callback, which may take none (below)
    _GUARDED_BY = {"_records": "_lock", "_announced": "_lock",
                   "_by_name": "_lock", "_folded": "_lock",
                   "_folded_until": "_lock", "_cache_seconds": "_lock",
                   "_cache_misses": "_lock",
                   "_tracers": "_lock:writes", "_annotation": "_lock:writes",
                   "_published": "_lock", "_listening": "_lock",
                   "dropped": "_lock"}

    def __init__(self, max_records: int = MAX_RECORDS,
                 clock=time.monotonic):
        self.max_records = int(max_records)
        self.clock = clock
        self._lock = RankedLock("telemetry.builds")
        self._records: deque = deque()
        # every stage JAX announced, nested or not, and every full
        # collection: stage -> [seconds, self seconds, count], and the
        # same by (stage, the name the event carried)
        self._announced = {s: [0.0, 0.0, 0] for s in STAGES + ("gc",)}
        self._by_name: Dict[tuple, list] = {}
        # the ``inside`` of the records that left the full list, summed,
        # and the end of the newest of them
        self._folded: Dict[str, list] = {}
        self._folded_until = float("-inf")
        self.dropped = 0
        self._cache_seconds = dict.fromkeys(_CACHE_SECONDS.values(), 0.0)
        self._cache_misses = 0
        # a thread's open stages, innermost last: [seconds of the stages
        # that ended inside it, their ``inside`` summed]; and the cache's
        # word on the compile this thread is in
        self._local = threading.local()
        self._tracers: List["weakref.ref"] = []
        # jax.profiler.TraceAnnotation, looked up when a tracer is fed
        self._annotation = None
        self._published: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._listening = False
        # the collector's side takes no lock: a collection can start at
        # any bytecode of any thread, under whatever lock that thread
        # holds (this one and a tracer's among them), and only one runs
        # at a time. What it finished waits in ``_gc_done`` for the next
        # call that holds the lock.
        self._gc_open: Optional[tuple] = None
        self._gc_done: deque = deque()

    # ------------------------------------------------------------ listening
    def start(self) -> None:
        """Register the listeners, once however often it is called."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        gc.callbacks.append(self._on_gc)
        try:
            import jax.monitoring as mon
        except ImportError:     # the collector's half needs no JAX
            return
        mon.register_scalar_listener(self._on_scalar)
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_scalar(self, event: str, value, **kwargs) -> None:
        """A stage begins on this thread."""
        if event in STAGE_OF_EVENT:
            try:
                self._local.open.append([0.0, {}])
            except AttributeError:
                self._local.open = [[0.0, {}]]

    def _on_duration(self, event: str, secs: float, **kwargs) -> None:
        stage = STAGE_OF_EVENT.get(event)
        if stage is None:
            key = _CACHE_SECONDS.get(event)
            if key is not None:
                with self._lock:
                    self._cache_seconds[key] += secs
            return
        t_end = self.clock()
        local = self._local
        open_ = getattr(local, "open", None)
        # (a stage that began before the recorder listened has no entry)
        inner, inside = open_.pop() if open_ else (0.0, {})
        own = max(0.0, secs - inner)
        _merge(inside, {stage: [own, 1]})
        cache = None
        if stage == "compile":
            cache, local.cache = getattr(local, "cache", None), None
            if cache is not None:
                _merge(inside, {cache: [0.0, 1]})
        fun_name = str(kwargs.get("fun_name", ""))
        if open_:               # nested: its seconds are its holder's too
            open_[-1][0] += secs
            _merge(open_[-1][1], inside)
        with self._lock:
            for entry in (self._announced[stage], self._by_name.setdefault(
                    (stage, fun_name), [0.0, 0.0, 0])):
                entry[0] += secs
                entry[1] += own
                entry[2] += 1
            self._cache_misses += cache == "miss"
            if not open_:
                thread = threading.current_thread()
                self._add((stage, fun_name, t_end - secs, t_end,
                           thread.ident or 0, thread.name, cache, inside))
            self._adopt_collections()

    def _on_event(self, event: str, **kwargs) -> None:
        kind = _CACHE_EVENTS.get(event)
        if kind is not None:
            self._local.cache = kind

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            tracers = [t for t in (r() for r in self._tracers)
                       if t is not None]
            mirror = None
            if tracers and self._annotation is not None:
                mirror = self._annotation(ANNOTATION_PREFIX + "gc")
                mirror.__enter__()
            # the span each tracer would have made the parent: the one
            # this thread has entered (a thread-local read, no lock)
            self._gc_open = (self.clock(), mirror,
                             [(weakref.ref(t), t.current()) for t in tracers])
            return
        opened, self._gc_open = self._gc_open, None
        if opened is None:      # registered between a start and its stop
            return
        t_start, mirror, parents = opened
        if mirror is not None:
            mirror.__exit__(None, None, None)
        thread = threading.current_thread()
        self._gc_done.append((t_start, self.clock(), thread.ident or 0,
                              thread.name, int(info.get("collected", 0)),
                              parents))

    # ------------------------------------------------------------ recording
    def _add(self, record: tuple) -> None:
        """An outermost stage or a collection that ended: into the list
        and, a stage, into the fed tracers. Caller holds the lock."""
        self._records.append(record)
        while len(self._records) > self.max_records:
            old = self._records.popleft()
            _merge(self._folded, old[7])
            self._folded_until = max(self._folded_until, old[3])
            self.dropped += 1
        if record[0] != "gc":
            for tracer in self._live_tracers():
                tracer.ingest(_build_span(tracer, record))

    def _live_tracers(self) -> list:
        live = [(r, r()) for r in self._tracers]
        if any(t is None for _, t in live):
            self._tracers = [r for r, t in live if t is not None]
        return [t for _, t in live if t is not None]

    def _adopt_collections(self) -> None:
        """Take what the collector's callback finished since the last
        call: records, and the ``gc`` span of each tracer that was fed
        when the collection began. Caller holds the lock."""
        while self._gc_done:
            t_start, t_end, tid, name, collected, parents = \
                self._gc_done.popleft()
            seconds = t_end - t_start
            total = self._announced["gc"]
            total[0] += seconds
            total[1] += seconds
            total[2] += 1
            self._add(("gc", "", t_start, t_end, tid, name, None,
                       {"gc": [seconds, 1]}))
            for ref, parent in parents:
                tracer = ref()
                if tracer is None:
                    continue
                tracer.ingest({
                    "name": "gc", "span_id": next(tracer._ids),
                    "trace_id": parent.trace_id if parent is not None
                    else TRACE_ID,
                    "parent_id": parent.span_id if parent is not None
                    else None,
                    "t_start": t_start, "t_end": t_end, "tid": tid,
                    "attrs": {"generation": 2, "collected": collected,
                              "thread": name}})

    def feed(self, tracer: Tracer) -> None:
        """Replay the records held into ``tracer`` — the newest of them,
        up to an eighth of its ring: the ring is the flight recorder's
        recent history, and a process that has built thousands of
        programs must not fill it (and every error dump) with them — and
        deliver later ones as they end. The tracer is held weakly; a
        disabled one is left alone."""
        if not tracer.enabled:
            return
        with self._lock:
            self._adopt_collections()
            builds = [r for r in self._records if r[0] != "gc"]
            for record in builds[-max(1, tracer.max_spans // 8):]:
                tracer.ingest(_build_span(tracer, record))
            self._tracers.append(weakref.ref(tracer))
            self._annotation = _annotation_class()

    # -------------------------------------------------------------- reading
    def counters(self) -> Dict[str, float]:
        """The cumulative totals behind :data:`COUNTER_NAMES`: programs
        built (``compile`` stages, cache reads among them), the thread
        seconds of all three stages (self seconds: each counted once),
        persistent-cache misses, full collections and their seconds."""
        with self._lock:
            self._adopt_collections()
            return self._counters()

    def _counters(self) -> Dict[str, float]:
        a = self._announced
        return dict(zip(COUNTER_NAMES, (
            a["compile"][2], sum(a[s][1] for s in STAGES),
            self._cache_misses, a["gc"][2], a["gc"][0])))

    def unpublished(self, registry) -> Dict[str, float]:
        """What :meth:`counters` has gained since the last call for this
        ``registry`` (held weakly): several replicas of one process that
        publish into one registry count each build once."""
        with self._lock:
            self._adopt_collections()
            now = self._counters()
            last = self._published.get(registry, {})
            self._published[registry] = now
        return {k: v - last.get(k, 0) for k, v in now.items()}

    def snapshot(self, since: Optional[float] = None,
                 until: Optional[float] = None) -> Dict[str, Any]:
        """Two views. **Cut to ``[since, until]``** (the monotonic clock;
        open on a side left None), from the records that ended there: for
        each of ``trace``, ``lower``, ``compile`` and ``gc`` its
        ``self_seconds`` and ``count`` (nested stages with the record
        that holds them), ``cache_hits`` / ``cache_misses`` among the
        compiles, and ``build_wall_seconds`` — the length of the union
        over all threads of the build records' intervals, cut to the
        bounds. Records that left the full list count in the sums if the
        newest of them ended inside the bounds; their intervals are lost
        to the union, and ``dropped`` says how many they are. **Of the
        whole process**, under ``announced``: for each stage the plain
        ``seconds`` JAX announced (nested ones counted again in their
        holders), ``self_seconds`` and ``count``, the same under
        ``by_fun_name`` by the name the event carried (the function's
        for a trace, the module's — ``jit(<name>)`` — for its lowering
        and its compile), and the persistent cache's own seconds."""
        lo = float("-inf") if since is None else float(since)
        hi = float("inf") if until is None else float(until)

        def as_dict(entry):
            return {"seconds": entry[0], "self_seconds": entry[1],
                    "count": entry[2]}

        with self._lock:
            self._adopt_collections()
            records = list(self._records)
            sums = {k: list(v) for k, v in self._folded.items()} \
                if lo <= self._folded_until <= hi else {}
            by_name: Dict[str, dict] = {s: {} for s in STAGES}
            for (stage, name), entry in self._by_name.items():
                by_name[stage][name] = as_dict(entry)
            out: Dict[str, Any] = dict(
                self._cache_seconds, dropped=self.dropped,
                by_fun_name=by_name, announced={
                    s: as_dict(e) for s, e in self._announced.items()})
        intervals = []
        for record in records:
            if not lo <= record[3] <= hi:
                continue
            _merge(sums, record[7])
            if record[0] != "gc":
                intervals.append((max(record[2], lo), record[3]))
        for key in STAGES + ("gc",):
            seconds, count = sums.get(key, (0.0, 0))
            out[key] = {"self_seconds": seconds, "count": count}
        out.update(cache_hits=sums.get("hit", (0.0, 0))[1],
                   cache_misses=sums.get("miss", (0.0, 0))[1],
                   build_wall_seconds=union_seconds(intervals))
        return out


def _build_span(tracer: Tracer, record: tuple) -> Dict[str, Any]:
    stage, fun_name, t_start, t_end, tid, thread_name, cache, inside = record
    attrs = {"stage": stage, "fun_name": fun_name, "thread": thread_name,
             "nested": sum(inside[s][1] for s in STAGES if s in inside) - 1}
    if cache is not None:
        attrs["cache_hit"] = cache == "hit"
    return {"name": "program_build", "trace_id": TRACE_ID,
            "span_id": next(tracer._ids), "parent_id": None,
            "t_start": t_start, "t_end": t_end, "tid": tid, "attrs": attrs}


#: the process's recorder; ``deepspeed_tpu/__init__.py`` starts it
RECORDER = BuildRecorder()
