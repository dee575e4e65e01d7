"""Every forward of a traced run, matched to its run on the device by the
order it was dispatched in, and what follows from that: a device time a
forward that does not move when the program does not, the device's idle
time by cause, and a request's share of the device while it waits.

The program names the unit of device work where it hands it over
(``InferenceEngineV2._forward_rows``: one ``dispatch`` span a forward,
mirrored as ``ds:dispatch`` on the profiler's clock, with its ``ordinal``,
its ``[S, C]`` bucket, its valid tokens and its rows' request uids). One
engine feeds one device queue, so the k-th dispatch is the k-th forward
module of the device's ``XLA Modules`` line — by order, not by time: with
a step in flight a forward starts on the device about a step after its
dispatch (``trace.module_seconds`` pairs by nearness, and books a forward
under its neighbour's bucket wherever the bucket changes).

``pair`` finds the one offset between the two sequences that a trace which
opens in mid-run leaves unknown; ``clock`` estimates the offset between
the annotations' clock and the device's as two-way time transfer does;
``idle_by_cause`` splits each idle interval between two modules at the
moment the next forward's dispatch ended (before it the host had handed
nothing over, after it the work was on its way); the readers below are
what the files under ``layer_metrics/`` name. Everything returns None off
the chip, on a trace without ``ds:dispatch`` (a checkout from before the
span existed) and where no offset fits: a wrong number is worse than none.

``python3 -m benchmark.dispatch_readers <xplane.pb>`` prints the pairing,
the clock offset with its uncertainty and the idle table, or says why the
trace cannot be paired.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional

from . import arithmetic as ar
from . import scopes, trace

DISPATCH, STAGE, FETCH, STEP = "ds:dispatch", "ds:stage", "ds:fetch", "ds:step"
NO_WORK_SPAN = "ds:idle_wait"
#: the paged forward's two entry points on the ``XLA Modules`` line
FORWARD_MODULE = re.compile(r"^jit_(_forward|forward_verify)\b")
PROGRAM_ID = re.compile(r"\((\d+)\)$")
#: forwards a trace may open behind — one put's, already handed over —
#: and, the other way, dispatches whose module the device's side of the
#: profiler was not yet up for
LEADS = range(-2, 9)
#: what the two clocks of one trace may differ by, either way: twice what
#: a trace has been seen to (1.8 ms, chat; PERF.md §7)
CLOCK_SLACK_S = 4e-3
CAUSES = ("no_work", "starved", "unspanned", "launch", "in_module")


def _end(e):
    return e["start"] + e["dur"]


def _set(value) -> bool:
    """A bool attr as an annotation's stat holds it (``1`` / ``0``, or
    the word)."""
    return str(value) in ("1", "True", "true")


# ------------------------------------------------------------------ pairing

def forward_modules(events: List[dict]) -> List[dict]:
    """The forward programs the first device plane ran, by start. Other
    programs run between them (a slice of the logits, a copy) and are
    busy time, not forwards."""
    planes = scopes._planes(events, 1)
    if not planes:
        return []
    return sorted((e for e in planes[0] if e["line"] == trace.MODULES_LINE
                   and FORWARD_MODULE.match(e["name"])),
                  key=lambda e: e["start"])


def dispatches(events: List[dict]) -> List[dict]:
    """The ``ds:dispatch`` annotations that say which forward they are,
    by ordinal."""
    found = [e for e in events if e["name"] == DISPATCH
             and isinstance(e.get("stats", {}).get("ordinal"), int)]
    return sorted(found, key=lambda e: e["stats"]["ordinal"])


def bucket_of(stats: dict) -> tuple:
    """What names a compiled forward: its ``[S, C]`` bucket and, for a
    verification, the width."""
    return (stats.get("bucket_seqs"), stats.get("bucket_chunk"),
            stats.get("verify_width", 0))


def program_of(module: dict) -> str:
    """The id a module's name carries (``jit__forward(5057…)``): what
    tells one compiled program from another. (This profiler's module
    events hold no ``program_id`` stat to fall back on — ``run_id``,
    ``device_offset_ps``, ``queue_id`` and the like — so a name without
    an id is no program, and pairs with nothing.)"""
    found = PROGRAM_ID.search(module["name"])
    return found[1] if found else ""


# -------------------------------------------------------------------- clock

def _step_events(events):
    """The annotations ``_retired`` walks, in the order they began (an
    enclosing one first)."""
    return sorted((e for e in events
                   if e["name"] in (STEP, STAGE, FETCH, DISPATCH)),
                  key=lambda e: (e["start"], -e["dur"]))


def _retired(kids, paired):
    """(last module of a put, the ``ds:fetch`` that read it back) for the
    puts the trace saw whole, from ``_step_events``. Puts are read back
    in the order they were staged; a step that was dispatched
    ``overlapped`` with nothing known in flight says that a put from
    before the trace is (what the trace holds of a step it opened inside
    of is left out)."""
    module_of = {d["stats"]["ordinal"]: m for m, d in paired}
    out, queue, step, put = [], [], None, None
    for e in kids:
        if e["name"] == STEP:
            step, put = e, None
        elif step is None or e["start"] >= _end(step):
            put = None                  # of a step the trace did not see
        elif e["name"] == STAGE:
            if not queue and _set(step.get("stats", {}).get("overlapped")):
                queue.append(None)      # in flight since before the trace
            put = {"last": None}
            queue.append(put)
        elif e["name"] == DISPATCH:
            if put is not None:
                put["last"] = module_of.get(e["stats"].get("ordinal"))
        elif queue:
            read = queue.pop(0)
            if read is not None and read["last"] is not None:
                out.append((read["last"], e))
    return out


def clock(kids: List[dict], paired) -> dict:
    """Seconds the device's clock is ahead of the annotations' in this
    trace, as two-way time transfer estimates it: a module cannot start
    before its dispatch began (the tightest such pair bounds the offset
    from above) and a ``ds:fetch`` cannot end before the module it waits
    for has (the tightest bounds it from below); the estimate is the
    middle, its uncertainty half the distance. A trace in which no put
    was read back is bounded from one side: no estimate. ``kids``:
    ``_step_events``."""
    above = min(m["start"] - d["start"] for m, d in paired)
    retired = _retired(kids, paired)
    if not retired:
        return {"offset_s": 0.0, "uncertainty_s": None,
                "dispatch_to_start_min_s": above, "end_to_fetch_min_s": None}
    below = min(_end(f) - _end(m) for m, f in retired)
    return {"offset_s": (above - below) / 2,
            "uncertainty_s": (above + below) / 2,
            "dispatch_to_start_min_s": above, "end_to_fetch_min_s": below}


def pair(events: List[dict]) -> dict:
    """Module k of the trace is dispatch ``o0 + k``. Returns ``{"o0",
    "lead", "pairs": [(module, dispatch or None)], "programs": {bucket:
    program}, "clock"}`` or ``{"why": …}``. ``lead`` modules were handed
    over before the trace opened (no annotation: their bucket is told from
    their program where a later forward shares it); a negative ``lead``
    says that so many dispatches have no module in the trace. The offset
    is the one under which every bucket maps to one program and no two
    buckets to the same, and for which one clock exists — within
    ``CLOCK_SLACK_S`` of the trace's — on which no module starts before
    its dispatch began and none ends after the ``ds:fetch`` that waited
    for it."""
    modules, found = forward_modules(events), dispatches(events)
    if not found:
        return {"why": "the trace holds no ds:dispatch annotation with an "
                       "ordinal (a program from before the span existed, "
                       "or telemetry off)"}
    if not modules:
        return {"why": "no forward module on the first device plane"}
    first = found[0]["stats"]["ordinal"]
    if [d["stats"]["ordinal"] for d in found] != \
            list(range(first, first + len(found))):
        return {"why": "the ds:dispatch ordinals are not consecutive: an "
                       "annotation was lost"}
    fits, why_not, kids = [], {}, _step_events(events)
    for lead in LEADS:
        pairs = list(zip(modules[max(lead, 0):], found[max(-lead, 0):]))
        if not pairs:
            continue
        programs, buckets = defaultdict(set), defaultdict(set)
        for m, d in pairs:
            programs[bucket_of(d["stats"])].add(program_of(m))
            buckets[program_of(m)].add(bucket_of(d["stats"]))
        split = sum(len(p) > 1 for p in programs.values())
        shared = sum(len(b) > 1 for b in buckets.values())
        clk = clock(kids, pairs)
        if clk["uncertainty_s"] is None:
            off = clk["dispatch_to_start_min_s"] < -CLOCK_SLACK_S
        else:
            off = clk["uncertainty_s"] < 0 \
                or abs(clk["offset_s"]) > CLOCK_SLACK_S
        if split or shared or off:
            why_not[lead] = (
                f"{split} buckets on several programs, {shared} programs "
                f"under several buckets" + (
                    ", and no clock within "
                    f"{CLOCK_SLACK_S * 1e3:g} ms of the trace's orders "
                    f"dispatch, run and fetch (tightest dispatch->start "
                    f"{clk['dispatch_to_start_min_s'] * 1e3:.3f} ms, "
                    f"end->fetch "
                    f"{(clk['end_to_fetch_min_s'] or 0) * 1e3:.3f} ms)"
                    if off else ""))
            continue
        fits.append({"o0": first - lead, "lead": lead, "clock": clk,
                     "programs": {b: next(iter(p))
                                  for b, p in programs.items()},
                     "pairs": [(m, None)
                               for m in modules[:max(lead, 0)]] + pairs,
                     "unpaired_dispatches": len(found) - len(pairs)})
    if len(fits) == 1:
        return fits[0]
    if fits:
        return {"why": f"{len(fits)} offsets fit (leads "
                       f"{[f['lead'] for f in fits]}): the trace has no "
                       "moment and no change of bucket that tells them "
                       "apart"}
    return {"why": "no offset fits: " + "; ".join(
        f"lead {k}: {v}" for k, v in why_not.items())}


def monotonic_offset(found: List[dict], spans: List[dict]) -> Optional[float]:
    """Seconds the annotations' clock is ahead of ``time.monotonic``: the
    ``dispatch`` spans are in both (a span's start is taken just before
    its annotation's)."""
    start = {s["attrs"].get("ordinal"): s["t_start"] for s in spans
             if s["name"] == "dispatch"}
    deltas = [d["start"] - start[d["stats"]["ordinal"]] for d in found
              if d["stats"]["ordinal"] in start]
    return ar.median(deltas) if deltas else None


# --------------------------------------------------------------------- idle

class _Innermost:
    """The innermost ``ds:*`` span open at a time, by bisection over the
    spans' edges (``trace._host_at`` scans them all for every piece of
    every gap)."""

    def __init__(self, host: List[dict]):
        self.edges, self.names = [], []
        stack = []
        for e in sorted(host, key=lambda e: (e["start"], -e["dur"])):
            while stack and _end(stack[-1]) <= e["start"]:
                self._cut(_end(stack.pop()), stack)
            stack.append(e)
            self._cut(e["start"], stack)
        while stack:
            self._cut(_end(stack.pop()), stack)

    def _cut(self, t, stack):
        name = stack[-1]["name"] if stack else None
        if self.edges and self.edges[-1] == t:
            self.names[-1] = name
        else:
            self.edges.append(t)
            self.names.append(name)

    def pieces(self, a, b):
        """(lo, hi, name) over [a, b), cut where the innermost changes."""
        i = bisect.bisect_right(self.edges, a)
        cuts = [a] + self.edges[i:bisect.bisect_left(self.edges, b)] + [b]
        for k, (lo, hi) in enumerate(zip(cuts, cuts[1:])):
            at = i + k - 1
            yield lo, hi, self.names[at] if at >= 0 else None


def idle_by_cause(events: List[dict], paired, offset_s: float = 0.0,
                  host=None) -> dict:
    """The window's device idle seconds (``XLA Ops`` busy of the first
    plane, as ``scopes.summarize`` cuts it) by cause. Idle inside a
    running module is the program's own (``in_module``). An interval
    between two modules is split at the moment the next forward's
    dispatch ended: before it the host had handed nothing over —
    ``no_work`` where ``ds:idle_wait`` was open, ``starved`` where any
    other ``ds:*`` span was (``starved_by_phase``), ``unspanned``
    otherwise — after it, ``launch``: the work was on its way.
    ``offset_s`` (``clock``) moves the host's times onto the device's
    clock (the window stays the mark's, so the causes sum to the idle
    seconds ``scopes.summarize`` reads); ``host``: the host's phases,
    where the ``ds:*`` annotations are not all there is to know
    (``host_phases``).
    ``gaps``: for each forward module of the window but the first (by
    its place among the trace's forwards), the idle seconds before it by
    cause."""
    w0, w1 = scopes._window(events)
    plane = scopes._planes(events, 1)[0]

    def device(line):
        return ar.clip_intervals(
            ((e["start"], _end(e)) for e in plane if e["line"] == line),
            w0, w1)

    running = sorted(device(trace.MODULES_LINE))
    starts = [a for a, _ in running]
    # each forward module with the end of its dispatch (-inf: handed over
    # before the trace opened), by the module's start
    forwards = [(m["start"],
                 _end(d) + offset_s if d else float("-inf"))
                for m, d in paired]
    forward_starts = [t for t, _ in forwards]
    if host is None:
        host = [e for e in events if e["name"].startswith(scopes.ANNOTATION)]
    innermost = _Innermost([dict(e, start=e["start"] + offset_s)
                            for e in host])
    total = dict.fromkeys(CAUSES, 0.0)
    phases: Dict[str, float] = defaultdict(float)
    gaps = defaultdict(lambda: dict.fromkeys(CAUSES, 0.0))

    def book(lo, hi):
        """[lo, hi) is idle and under no module."""
        k = bisect.bisect_left(forward_starts, lo)
        handed = forwards[k][1] if k < len(forwards) else float("inf")
        cut = min(max(handed, lo), hi)
        for a, b, name in innermost.pieces(lo, cut) if cut > lo else ():
            cause = "unspanned" if name is None else \
                "no_work" if name == NO_WORK_SPAN else "starved"
            total[cause] += b - a
            gaps[k][cause] += b - a
            if cause == "starved":
                phases[name] += b - a
        total["launch"] += hi - cut
        gaps[k]["launch"] += hi - cut

    idle = trace._gaps(device(trace.OPS_LINE), w0, w1)
    for a, b in idle:
        # the part of the gap under a module, and the rest
        at = a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(running) and running[i][0] < b:
            lo, hi = max(running[i][0], at), min(running[i][1], b)
            if hi > lo:
                if lo > at:
                    book(at, lo)
                total["in_module"] += hi - lo
                at = hi
            i += 1
        if b > at:
            book(at, b)
    in_window = [k for k, t in enumerate(forward_starts) if w0 <= t < w1]
    return {"window_s": w1 - w0, "idle_s": sum(total.values()),
            "by_cause": total, "intervals": idle,
            "starved_by_phase": sorted(phases.items(), key=lambda kv: -kv[1]),
            "gaps": {k: gaps[k] for k in in_window[1:]}}


def host_phases(events: List[dict], spans, shift: Optional[float]):
    """The host's phases from the program's spans, on the trace's clock:
    every ``ds:*`` annotation mirrors a span, but a span still open when
    the profiler stopped (the ``idle_wait`` a window ends in) left no
    annotation. The spans of the names the trace mirrors, moved by
    ``shift`` (``monotonic_offset``); None without spans to read."""
    mirrored = {e["name"] for e in events
                if e["name"].startswith(scopes.ANNOTATION)}
    if shift is None or not mirrored:
        return None
    w0, w1 = scopes._window(events)
    out = []
    for s in spans:
        end = float("inf") if s.get("t_end") is None else s["t_end"] + shift
        if scopes.ANNOTATION + s["name"] in mirrored \
                and s["t_start"] + shift < w1 and end > w0:
            out.append({"name": scopes.ANNOTATION + s["name"],
                        "start": s["t_start"] + shift,
                        "dur": end - s["t_start"] - shift})
    return out


# ------------------------------------------------------------------ reading

def _attrs(k: int, found: dict, by_ordinal: dict) -> dict:
    """Forward k's own counts: the program's ``dispatch`` span of that
    ordinal (the spans are of the whole run), else the annotation's
    stats, else — handed over before the trace opened — the bucket its
    program runs wherever the trace saw it dispatched."""
    m, d = found["pairs"][k]
    if found["o0"] + k in by_ordinal:
        return by_ordinal[found["o0"] + k]
    if d is not None:
        return d["stats"]
    for (seqs, chunk, width), program in found["programs"].items():
        if program == program_of(m):
            return {"bucket_seqs": seqs, "bucket_chunk": chunk,
                    **({"verify_width": width} if width else {})}
    return {}


def reduce_trace(events: List[dict], spans=()) -> dict:
    """Everything the readers share, from one trace and the program's
    spans: ``{"why": …}`` where the trace cannot be paired."""
    found = pair(events)
    if "why" in found:
        return found
    paired, o0, clk = found["pairs"], found["o0"], found["clock"]
    by_ordinal = {s["attrs"]["ordinal"]: s["attrs"] for s in spans
                  if s["name"] == "dispatch" and "ordinal" in s["attrs"]}
    w0, w1 = scopes._window(events)
    shift = monotonic_offset(dispatches(events), spans)
    forwards = []
    for k, (m, _) in enumerate(paired):
        forwards.append({"start": m["start"], "end": _end(m),
                         "dur": m["dur"], "ordinal": o0 + k,
                         "in_window": w0 <= m["start"] < w1,
                         "attrs": _attrs(k, found, by_ordinal)})
    return {"o0": o0, "lead": found["lead"], "programs": found["programs"],
            "unpaired_dispatches": found["unpaired_dispatches"],
            "clock": clk, "window": (w0, w1), "forwards": forwards,
            "idle": idle_by_cause(events, paired, clk["offset_s"],
                                  host_phases(events, spans, shift)),
            "monotonic_offset_s": shift}


def _reduced(ctx) -> Optional[dict]:
    """The reduced trace of a traced run on the chip (once a context)."""
    if ctx.trace is None:
        return None
    if getattr(ctx, "_dispatch", None) is None:
        ctx._dispatch = reduce_trace(scopes.load(ctx.result["xplane"]),
                                     ctx.result.get("program_spans", []))
    return None if "why" in ctx._dispatch else ctx._dispatch


def _window_forwards(r, wide: bool):
    return [f for f in r["forwards"] if f["in_window"]
            and isinstance(f["attrs"].get("bucket_chunk"), int)
            and (f["attrs"]["bucket_chunk"] > 1) == wide]


def dev_decode_ms_per_forward(ctx):
    """Device ms a one-token forward (``bucket_chunk`` = 1) of the window:
    the sum over them all ÷ their count."""
    r = _reduced(ctx)
    fw = _window_forwards(r, wide=False) if r else []
    return 1e3 * sum(f["dur"] for f in fw) / len(fw) if fw else None


def dev_prefill_us_per_token(ctx):
    """Device µs a valid token of the window's forwards wider than one
    token (a joint ``[S, C]`` forward counts whole, its decode rows'
    tokens too)."""
    r = _reduced(ctx)
    fw = _window_forwards(r, wide=True) if r else []
    tokens = sum(f["attrs"].get("valid_tokens", 0) for f in fw)
    return 1e6 * sum(f["dur"] for f in fw) / tokens if tokens else None


def forward_device_ms(ctx, mixed: bool):
    """Median device ms of the window's forwards at their most frequent
    bucket: among the one-token forwards (``bucket_chunk`` = 1), or —
    ``mixed`` — among those of the widest chunk the window ran. None
    where the trace cannot be paired or the window holds no such
    forward."""
    r = _reduced(ctx)
    fw = _window_forwards(r, wide=mixed) if r else []
    if mixed and fw:
        widest = max(f["attrs"]["bucket_chunk"] for f in fw)
        fw = [f for f in fw if f["attrs"]["bucket_chunk"] == widest]
    by_bucket = defaultdict(list)
    for f in fw:
        by_bucket[bucket_of(f["attrs"])].append(f["dur"])
    if not by_bucket:
        return None
    return 1e3 * ar.median(max(by_bucket.values(), key=len))


def idle_share(ctx, cause: str):
    """Idle seconds of one cause ÷ the traced window, in percent."""
    r = _reduced(ctx)
    if r is None:
        return None
    return 100.0 * r["idle"]["by_cause"][cause] / r["idle"]["window_s"]


def _on_device_clock(r, spans, name):
    """[start, end) of the program's ``name`` spans on the device's clock,
    clipped to the traced window (an open span runs to its end)."""
    shift = r["monotonic_offset_s"]
    if shift is None:
        return []
    shift += r["clock"]["offset_s"]
    w0, w1 = r["window"]
    out = []
    for s in spans:
        if s["name"] != name:
            continue
        a = s["t_start"] + shift
        b = w1 if s.get("t_end") is None else s["t_end"] + shift
        a, b = max(a, w0), min(b, w1)
        if b > a:
            out.append((a, b, s))
    return out


def _overlap(intervals, cover) -> float:
    """Seconds of ``intervals`` (disjoint) inside the union of ``cover``."""
    intervals = list(intervals)
    return ar.union_seconds(intervals) - ar.subtract_seconds(intervals, cover)


def decode_time_share(ctx, what: str):
    """Of the traced time in which some request is between its first and
    its last token (the union of the ``decode`` spans), the share (%) the
    device spent in forwards wider than one token (``chunk``) or idle
    (``idle``); the rest is one-token forwards."""
    r = _reduced(ctx)
    if r is None:
        return None
    decoding = [(a, b) for a, b, _ in _on_device_clock(
        r, ctx.result.get("program_spans", []), "decode")]
    total = ar.union_seconds(decoding)
    if not total:
        return None
    if what == "idle":
        mine = r["idle"]["intervals"]
    else:
        mine = [(f["start"], f["end"]) for f in r["forwards"]
                if f["attrs"].get("bucket_chunk", 1) > 1]
    return 100.0 * _overlap(mine, decoding) / total


def prefill_own_share(ctx):
    """Over the requests whose ``prefill`` span overlaps the traced time:
    device seconds, inside that overlap, of the forwards whose ``uids``
    hold the request ÷ the overlaps' lengths, in percent. 100 less it is
    the share of its prefill a request spends behind other requests'
    forwards or an idle device."""
    r = _reduced(ctx)
    if r is None:
        return None
    fed = defaultdict(list)             # uid -> the forwards that held it
    for f in r["forwards"]:
        for uid in str(f["attrs"].get("uids", "")).split():
            fed[uid].append((f["start"], f["end"]))
    own = waited = 0.0
    for a, b, s in _on_device_clock(r, ctx.result.get("program_spans", []),
                                   "prefill"):
        own += ar.union_seconds(ar.clip_intervals(
            fed[str(s["attrs"].get("uid"))], a, b))
        waited += b - a
    return 100.0 * own / waited if waited else None


def steps_share(ctx, attr: str):
    """Of the program's ``step`` spans of the window that dispatched (a
    ``stage`` is their child), the share (%) with ``attr`` set
    (``overlapped``, ``starved``). None for a program without the attr."""
    if ctx.trace is None:
        return None
    w0, w1 = ctx.result["window"]
    spans = ctx.result.get("program_spans", [])
    dispatched = {s["parent_id"] for s in spans if s["name"] == "stage"}
    steps = [s for s in spans if s["name"] == "step"
             and s["span_id"] in dispatched and w0 <= s["t_start"] < w1]
    if not steps or not any(attr in s["attrs"] for s in steps):
        return None
    return 100.0 * sum(bool(s["attrs"].get(attr)) for s in steps) / len(steps)


def starved_steps(events: List[dict], r: dict) -> Optional[dict]:
    """The two readings of one fact, over the window's steps that
    dispatched: the program's (``ds:step``'s ``starved`` stat:
    ``is_ready()`` on the step ahead as the step is handed over) and the
    trace's — of the steps dispatched ``overlapped``, those whose first
    forward's dispatch ended after the last forward handed over before it
    had ended on the device (both on the device's clock), in percent."""
    w0, w1 = r["window"]
    end_of = {f["ordinal"]: f["end"] for f in r["forwards"]}
    first_of = {}       # the stage's place in time -> its first dispatch
    stages = sorted((e for e in events if e["name"] == STAGE),
                    key=lambda e: e["start"])
    starts = [e["start"] for e in stages]
    for d in dispatches(events):
        k = bisect.bisect_right(starts, d["start"]) - 1
        if k >= 0 and d["start"] < _end(stages[k]):
            first_of.setdefault(k, d)
    program = trace_side = steps = 0
    for step in (e for e in events if e["name"] == STEP
                 and w0 <= e["start"] < w1):
        k = bisect.bisect_left(starts, step["start"])
        if k not in first_of or starts[k] >= _end(step) \
                or "starved" not in step.get("stats", {}):
            continue
        steps += 1
        program += _set(step["stats"]["starved"])
        ahead = end_of.get(first_of[k]["stats"]["ordinal"] - 1)
        trace_side += _set(step["stats"].get("overlapped")) \
            and ahead is not None \
            and ahead < _end(first_of[k]) + r["clock"]["offset_s"]
    if not steps:
        return None
    return {"steps": steps, "program": 100.0 * program / steps,
            "trace": 100.0 * trace_side / steps}


# ----------------------------------------------------------------- __main__

def describe(events: List[dict]) -> str:
    r = reduce_trace(events)
    if "why" in r:
        return "cannot pair this trace: " + r["why"]
    fw = [f for f in r["forwards"] if f["in_window"]]
    unpaired = sum(not f["attrs"] for f in fw)
    lines = [f"{len(fw)} forward modules in the window, {unpaired} unpaired "
             f"(o0 = {r['o0']}; {r['lead']} handed over before the trace "
             f"opened; {r['unpaired_dispatches']} dispatches whose module "
             f"the trace did not reach)"]
    clk = r["clock"]
    if clk["uncertainty_s"] is None:
        lines.append("clock: no put of the trace was read back inside it: "
                     "bounded from one side, no offset applied")
    else:
        lines.append(f"clock: device ahead of the annotations by "
                     f"{clk['offset_s'] * 1e6:+.1f} us +- "
                     f"{clk['uncertainty_s'] * 1e6:.1f} us (tightest "
                     f"dispatch->start {clk['dispatch_to_start_min_s'] * 1e6:.1f}"
                     f" us, end->fetch {clk['end_to_fetch_min_s'] * 1e6:.1f} us)")
    lines.append("bucket [S, C] (verify) -> program: forwards, median ms, "
                 "sum s, valid tokens")
    by_bucket = defaultdict(list)
    for f in fw:
        if f["attrs"]:
            by_bucket[bucket_of(f["attrs"])].append(f)
    for bucket, fs in sorted(by_bucket.items(), key=lambda kv: str(kv[0])):
        s, c, w = bucket
        lines.append(
            f"  [{s}, {c}]{f' ({w})' if w else ''} -> "
            f"{r['programs'].get(bucket, '?')}: {len(fs)}, "
            f"{ar.median(f['dur'] for f in fs) * 1e3:.3f}, "
            f"{sum(f['dur'] for f in fs):.4f}, "
            f"{sum(f['attrs'].get('valid_tokens', 0) for f in fs)}")
    idle = r["idle"]
    lines.append(f"idle {idle['idle_s']:.4f} s of a window of "
                 f"{idle['window_s']:.4f} s "
                 f"({100 * idle['idle_s'] / idle['window_s']:.2f}%), by cause:")
    for cause in CAUSES:
        lines.append(f"  {cause:10s} {idle['by_cause'][cause]:.4f} s "
                     f"{100 * idle['by_cause'][cause] / idle['window_s']:6.2f}%")
    for name, secs in idle["starved_by_phase"]:
        lines.append(f"    starved under {name}: {secs:.4f} s")
    both = starved_steps(events, r)
    if both is not None:
        lines.append(f"steps handed to a device that had run dry: "
                     f"{both['program']:.1f}% by the program's is_ready, "
                     f"{both['trace']:.1f}% by the trace, of "
                     f"{both['steps']} that dispatched")
    return "\n".join(lines)


if __name__ == "__main__":
    import sys

    print(describe(scopes.load(sys.argv[1])))
