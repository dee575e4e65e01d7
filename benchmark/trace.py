"""From the profiler's trace to numbers: device busy and idle time, time
per operation and per XLA module, time in collectives and how much of it
no compute hides, and what the host was doing in each idle gap.

Two steps, so the arithmetic can be checked on a small recorded trace
(``testdata/``) without the profiler: ``load_xplane`` flattens an
``.xplane.pb`` into plain events, ``summarize`` reduces events.

An event is ``{"plane", "line", "name", "start", "dur"}``, seconds. On a
TPU the profiler writes one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line holds every HLO operation as it ran and whose
``XLA Modules`` line holds every executed program; host threads are lines
of the ``/host:CPU`` plane and carry the benchmark's ``bench:*``
annotations (``probe.py``).
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List

from .arithmetic import clip_intervals, subtract_seconds, union_seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
WINDOW = "bench:window"


def load_xplane(path: str) -> List[dict]:
    """Flatten an ``.xplane.pb``: device planes whole, of the host planes
    only the ``bench:*`` annotations (the rest is the interpreter's)."""
    from jax.profiler import ProfileData

    events = []
    data = ProfileData.from_file(path)
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                if not device and not ev.name.startswith("bench:"):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name,
                               "start": ev.start_ns * 1e-9,
                               "dur": ev.duration_ns * 1e-9})
    return events


KERNEL = " custom-call("      # a Pallas (Mosaic) kernel, in HLO text


def op_family(name: str) -> str:
    """``%fusion.123 = …`` → ``fusion``: the operation without its
    instance number, so that the layers of a scan add up. A custom call
    is marked as one: a Pallas kernel's instruction takes the kernel's
    ``name=`` (``%paged_attention.8`` → ``kernel:paged_attention``)."""
    head = name.split(" = ")[0].lstrip("%")
    family = re.sub(r"\.\d+(\.\d+)*$", "", head)
    return f"kernel:{family}" if KERNEL in name else family


def exclusive(events: List[dict]) -> List[tuple]:
    """(event, self seconds, encloses others) for the events of one line:
    an operation that encloses others (a ``while`` and its body) keeps
    only the time in which none of them ran."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e["start"], -e["dur"])):
        end = e["start"] + e["dur"]
        while stack and stack[-1][1] <= e["start"]:
            stack.pop()
        if stack:
            stack[-1][2][1] -= min(end, stack[-1][1]) - e["start"]
            stack[-1][2][2] = True
        cell = [e, e["dur"], False]
        out.append(cell)
        stack.append((e["start"], end, cell))
    return [(e, max(0.0, own), outer) for e, own, outer in out]


def _span(e):
    return (e["start"], e["start"] + e["dur"])


def summarize(events: List[dict], chips: int) -> dict:
    """Reduce events to the numbers the metrics read. Times are averaged
    over the ``chips`` lowest-numbered device planes that ran anything."""
    marks = [e for e in events if e["name"] == WINDOW]
    device_events = [e for e in events if DEVICE_PLANE.match(e["plane"])]
    if marks:
        w0, w1 = _span(max(marks, key=lambda e: e["dur"]))
    elif device_events:
        w0 = min(e["start"] for e in device_events)
        w1 = max(e["start"] + e["dur"] for e in device_events)
    else:
        raise ValueError("the trace holds no device event and no window")

    by_plane: Dict[str, List[dict]] = defaultdict(list)
    for e in device_events:
        by_plane[e["plane"]].append(e)
    planes = sorted(by_plane, key=lambda p: int(DEVICE_PLANE.match(p)[1]))
    planes = [p for p in planes
              if any(e["line"] == OPS_LINE for e in by_plane[p])][:chips]
    if not planes:
        raise ValueError("no operation ran on a device in this trace")

    host = sorted((e for e in events
                   if e["name"].startswith("bench:") and e["name"] != WINDOW),
                  key=lambda e: e["start"])
    # what the host was doing between one annotation edge and the next
    # (doing[i] holds before edges[i]; the last entry after the last edge)
    edges = sorted({t for e in host for t in _span(e)})
    mids = ([edges[0] - 1e-9]
            + [(a + b) / 2 for a, b in zip(edges, edges[1:])]
            + [edges[-1] + 1e-9]) if edges else [w0]
    doing = [_host_at(host, t) for t in mids]
    busy, coll, exposed = [], [], []
    op_seconds: Dict[str, float] = defaultdict(float)
    gap_seconds: Dict[str, float] = defaultdict(float)
    modules, kernels = [], []
    kernel_seconds: Dict[str, float] = defaultdict(float)
    for p in planes:
        ops = [e for e in by_plane[p] if e["line"] == OPS_LINE
               or "Async" in e["line"]]
        on_core = [e for e in ops if e["line"] == OPS_LINE]
        core_iv = clip_intervals(map(_span, on_core), w0, w1)
        busy.append(union_seconds(core_iv))
        # by the operation's own name, not its operands' (a fusion that
        # reads %all-gather.3 is compute)
        is_coll = lambda e: bool(COLLECTIVE.search(e["name"].split(" = ")[0]))
        nested = exclusive([e for e in on_core if w0 <= e["start"] < w1])
        coll_iv = clip_intervals((_span(e) for e in ops if is_coll(e)),
                                 w0, w1)
        # compute = the operations that do the work themselves: a while
        # that encloses a collective is not compute that hides it
        compute_iv = clip_intervals((_span(e) for e, _, outer in nested
                                     if not outer and not is_coll(e)),
                                    w0, w1)
        coll.append(union_seconds(coll_iv))
        exposed.append(subtract_seconds(coll_iv, compute_iv))
        for e, own, _ in nested:
            family = op_family(e["name"])
            op_seconds[family] += own / len(planes)
            if KERNEL in e["name"]:
                kernel_seconds[family] += e["dur"] / len(planes)
                kernels.append(dict(e, device=p, family=family))
        for a, b in _gaps(core_iv, w0, w1):
            # cut the gap where an annotation begins or ends, and give
            # each piece to what the host was doing in it
            i, j = bisect.bisect_right(edges, a), bisect.bisect_left(edges, b)
            cuts = [a] + edges[i:j] + [b]
            for k, (lo, hi) in enumerate(zip(cuts, cuts[1:])):
                gap_seconds[doing[i + k]] += (hi - lo) / len(planes)
        modules += [dict(e, device=p) for e in by_plane[p]
                    if e["line"] == MODULES_LINE
                    and w0 <= e["start"] < w1]

    n = len(planes)
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])
    return {
        "window_s": w1 - w0, "window": (w0, w1), "devices": n,
        "busy_s": sum(busy) / n,
        "collective_s": sum(coll) / n,
        "collective_exposed_s": sum(exposed) / n,
        # custom calls: device seconds by name (``kernel:<name>``), their
        # total, and the events themselves
        "kernel_seconds": dict(kernel_seconds),
        "kernel_s": sum(kernel_seconds.values()), "kernels": kernels,
        "device_ops": top(op_seconds), "idle_gaps": top(gap_seconds),
        "modules": modules, "host": host,
    }


def _gaps(busy_intervals, w0, w1):
    """The idle intervals of [w0, w1) between the busy ones."""
    out, at = [], w0
    for a, b in sorted(busy_intervals):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if w1 > at:
        out.append((at, w1))
    return out


def _host_at(host: List[dict], t: float) -> str:
    """The innermost ``bench:*`` annotation open at time t (the shortest
    that covers it), without its shape tag; ``host:other`` when none is:
    the host was outside every call the benchmark wraps (the frontend's
    threads, the router, idle waiting for a request)."""
    best = None
    for e in host:
        if e["start"] > t:
            break
        if e["start"] + e["dur"] >= t and (best is None
                                           or e["dur"] < best["dur"]):
            best = e
    return "host:other" if best is None else best["name"].split("[")[0]


def module_seconds(summary: dict, name_part: str, tag: str = None):
    """Device seconds of each executed XLA module whose name contains
    ``name_part``; with ``tag``, only those dispatched under a
    ``bench:*[tag]`` annotation. A module belongs to the tagged annotation
    that began nearest to its own start: the program's steps are
    synchronous, so a dispatch and its execution start within a
    millisecond of each other, while the device's and the host's clocks
    in a trace can differ by about as much either way."""
    out = []
    tagged = [e for e in summary["host"] if "[" in e["name"]]
    for m in summary["modules"]:
        if name_part not in m["name"]:
            continue
        if tag is not None:
            if not tagged:
                continue
            near = min(tagged, key=lambda e: abs(e["start"] - m["start"]))
            if not near["name"].endswith(f"[{tag}]"):
                continue
        out.append(m["dur"])
    return out


def describe(path: str, top: int = 12) -> dict:
    """What a trace holds, for a look by hand before code is written
    against it: every plane and line, their event counts, and the most
    frequent event names of each line with the stats they carry."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names, seconds, stats = Counter(), Counter(), {}
            n = 0
            for ev in line.events:
                n += 1
                names[ev.name] += 1
                seconds[ev.name] += ev.duration_ns * 1e-9
                if ev.name not in stats:
                    stats[ev.name] = [str(k) for k, _ in ev.stats][:12]
            lines[line.name] = {"events": n, "top": [
                [k, c, round(seconds[k], 6), stats[k]]
                for k, c in names.most_common(top)]}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(describe(sys.argv[1]), indent=1))


def load_recorded(path: str) -> List[dict]:
    """A small recorded trace kept as JSON (``testdata/``): the same
    events ``load_xplane`` gives, stored compactly (tables of plane and
    line names, times in ns)."""
    import json

    with open(path) as f:
        doc = json.load(f)
    events = [{"plane": doc["planes"][p], "line": doc["lines"][ln],
               "name": name, "start": start * 1e-9, "dur": dur * 1e-9}
              for p, ln, name, start, dur in doc["events"]]
    events.append({"plane": "/host:CPU", "line": "python3", "name": WINDOW,
                   "start": 0.0, "dur": doc["window_ns"] * 1e-9})
    return events
