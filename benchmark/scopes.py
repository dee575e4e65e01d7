"""The program's own names, read out of the profiler's trace.

The program says which of its parts caused each device operation
(``jax.named_scope`` in the paged forward, the model and the train step:
the scope rides in the operation's HLO ``op_name``) and what its host
threads were doing (``ds:<name>`` annotations, one per context-manager span
of ``deepspeed_tpu.telemetry``). ``trace.load_xplane`` drops event stats
and every host event that is not ``bench:*``, so this module decodes the
``.xplane.pb`` itself and gives

- each ``XLA Ops`` event with its ``op_name`` (self time by ``trace.exclusive``),
- the ``ds:*`` annotations with their stats,
- the device's idle intervals of the traced window, cut at ``ds:*`` edges and
  given to the innermost ``ds:*`` span open at the time (``trace._host_at``'s
  rule).

The vocabulary of scope names is the program's shared one (below) plus
what a configuration's block adds under ``layers`` (``SCOPES`` in
``blocks/<block>.py``: a sparse block's ``router`` and ``experts`` inside
``mlp``); every function that reads an op_name takes those as
``block_scopes``, and the readers take them from ``ctx.info["block"]``.

``python3 -m benchmark.scopes <xplane.pb> [chips [scope ...]]`` prints the
whole table (further words: a block's scopes).
Every reader returns None off the chip (``ctx.trace is None``) and where the
program carries no such name (a checkout from before the names existed).
"""

from __future__ import annotations

import bisect
import re
import struct
from collections import defaultdict
from typing import Dict, List, Optional

from . import arithmetic as ar
from . import trace

#: the stat of an ``XLA Ops`` event that holds the HLO op_name on this
#: profiler (``python3 -m benchmark.trace <file>`` lists them)
OP_NAME_STATS = ("tf_op", "hlo_op_name", "op_name")
ANNOTATION = "ds:"

#: the scope vocabulary every block shares (docs/OBSERVABILITY.md "XLA
#: alignment"); a block module's ``SCOPES`` extends it
BLOCK = ("attn_norm", "qkv", "kv_write", "attend", "attn_out", "mlp")
MODEL = ("embed", "layers", "final_norm", "logits", "loss")
STEP = ("loss_and_grad", "grad_accumulate", "grad_norm_clip", "optimizer")
VOCABULARY = frozenset(BLOCK + MODEL + STEP)
SCAN_OVERHEAD, UNSCOPED, UNSPANNED = "layers (scan)", "(unscoped)", "host:other"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


# ------------------------------------------------------------------ loading

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint or fixed field, the bytes for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _stats(message, field, stat_names):
    """The XStats in ``field`` of an XEvent or XEventMetadata, by name."""
    out = {}
    for number, value in _fields(message):
        if number != field:
            continue
        key = got = None
        for k, v in _fields(value):
            if k == 1:
                key = stat_names.get(v, str(v))
            elif k in (3, 4):           # uint64, int64
                got = v - (1 << 64) if k == 4 and v >> 63 else v
            elif k in (5, 6):           # str, bytes
                got = bytes(v).decode("utf-8", "replace")
            elif k == 7:                # a string kept as a stat's name
                got = stat_names.get(v, "")
            elif k == 2:                # double
                got = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        out[key] = got
    return out


def load(path: str) -> List[dict]:
    """Flatten an ``.xplane.pb``: of each device plane the ``XLA Ops`` line,
    every event with the ``op_name`` its HLO instruction carries, and the
    ``XLA Modules`` line; of the host planes the ``ds:*`` annotations (with
    their stats) and the benchmark's window mark. The file is decoded here
    (tensorflow's ``XSpace`` message, field numbers from xplane.proto):
    the op_name is a stat of the event's *metadata*, which
    ``jax.profiler.ProfileData`` does not hand out."""
    with open(path, "rb") as f:
        space = f.read()
    events = []
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, lines, metadata, stat_names = "", [], {}, {}
        for k, v in _fields(plane):
            if k == 2:
                name = bytes(v).decode()
            elif k == 3:
                lines.append(v)
            elif k in (4, 5):           # map entries: 1 = key, 2 = value
                entry = dict(_fields(v))
                if k == 4:
                    metadata[entry[1]] = entry[2]
                else:
                    stat_names[entry[1]] = bytes(
                        dict(_fields(entry[2])).get(2, b"")).decode()
        device = bool(trace.DEVICE_PLANE.match(name))
        names: Dict[int, str] = {}
        op_names: Dict[int, str] = {}
        for line in lines:
            head, packed = {}, []
            for k, v in _fields(line):
                if k == 4:
                    packed.append(v)
                else:
                    head[k] = v
            line_name = bytes(head.get(2, b"")).decode()
            if device and line_name not in (trace.OPS_LINE,
                                            trace.MODULES_LINE):
                continue
            t0 = head.get(3, 0) * 1e-9
            for v in packed:
                ev = {a: b for a, b in _fields(v) if a != 4}
                mid = ev.get(1, 0)
                if mid not in names:
                    meta = metadata.get(mid, b"")
                    names[mid] = bytes(dict(_fields(meta)).get(2, b"")).decode()
                    if device and line_name == trace.OPS_LINE:
                        found = _stats(meta, 5, stat_names)
                        op_names[mid] = next(
                            (str(found[s]) for s in OP_NAME_STATS
                             if s in found), "")
                ev_name = names[mid]
                host = ev_name.startswith(ANNOTATION)
                if not device and not host and ev_name != trace.WINDOW:
                    continue
                e = {"plane": name, "line": line_name, "name": ev_name,
                     "start": t0 + ev.get(2, 0) * 1e-12,
                     "dur": ev.get(3, 0) * 1e-12}
                if mid in op_names:
                    e["op_name"] = op_names[mid]
                elif host:
                    e["stats"] = _stats(v, 4, stat_names)
                events.append(e)
    return events


def load_recorded(path: str) -> List[dict]:
    """A small recorded trace kept as JSON (``testdata/``): what ``load``
    gives, stored compactly — tables of plane, line and op_name strings,
    times in ps (rounded to ns, back-to-back operations would overlap and
    ``trace.exclusive`` would nest them); a host event's last field is its
    stats, a device operation's an index into ``op_names``."""
    import json

    with open(path) as f:
        doc = json.load(f)
    events = []
    for p, ln, name, start, dur, extra in doc["events"]:
        e = {"plane": doc["planes"][p], "line": doc["lines"][ln],
             "name": name, "start": start * 1e-12, "dur": dur * 1e-12}
        if isinstance(extra, dict):
            e["stats"] = extra
        elif extra is not None:
            e["op_name"] = doc["op_names"][extra]
        events.append(e)
    events.append({"plane": "/host:CPU", "line": "python3",
                   "name": trace.WINDOW, "start": 0.0,
                   "dur": doc["window_ps"] * 1e-12})
    return events


# ------------------------------------------------------------------- scopes

def scope_path(op_name: str, block_scopes=()) -> tuple:
    """The program scopes an operation sits under, outermost first:
    ``jit(micro)/loss_and_grad/transpose(jvp(layers))/while/body/
    checkpoint/mlp/dot_general`` → (loss_and_grad, layers, mlp). JAX wraps
    a scope in the transformation it was traced under (``jvp(layers)``),
    so the words are looked for inside each component. ``block_scopes``
    are the names the configuration's block adds to the vocabulary."""
    return tuple(w for part in op_name.split("/")
                 for w in _WORD.findall(part)
                 if w in VOCABULARY or w in block_scopes)


def scope_of(op_name: str, block_scopes=()) -> str:
    """One row of the table per operation: its innermost program scope
    (``…/mlp/experts/dot_general`` is ``experts`` for a block that lists
    it and ``mlp`` for one that does not); ``layers`` alone (under the
    scan, under none of the block's scopes) is the scan's own plumbing —
    slices of the stacked weights, carry copies; no word of the vocabulary
    at all is what the names do not yet explain."""
    path = scope_path(op_name, block_scopes)
    if not path:
        return UNSCOPED
    return SCAN_OVERHEAD if path[-1] == "layers" else path[-1]


def train_pass(op_name: str, module: str) -> str:
    """forward / backward / recomputed forward / optimizer, from the
    program an operation ran in and the prefixes JAX writes into op_name:
    the ``update`` program whole is the optimizer; in ``micro`` the
    recomputed forward carries ``rematted_computation`` (under
    ``checkpoint``), the backward ``transpose(jvp(…))`` — gradient
    accumulation is counted with it — and what else has a program scope is
    the forward."""
    if "update" in module:
        return "opt"
    if "rematted_computation" in op_name:
        return "remat"
    path = scope_path(op_name)
    if "transpose(" in op_name or "grad_accumulate" in path:
        return "bwd"
    return "fwd" if path else "unscoped"


# ----------------------------------------------------------------- reducing

def _span(e):
    return (e["start"], e["start"] + e["dur"])


def _window(events):
    marks = [e for e in events if e["name"] == trace.WINDOW]
    if not marks:
        raise ValueError("the trace holds no bench:window mark")
    return _span(max(marks, key=lambda e: e["dur"]))


def _planes(events, chips):
    by_plane: Dict[str, List[dict]] = defaultdict(list)
    for e in events:
        if trace.DEVICE_PLANE.match(e["plane"]):
            by_plane[e["plane"]].append(e)
    names = sorted(by_plane, key=lambda p: int(trace.DEVICE_PLANE.match(p)[1]))
    return [by_plane[p] for p in names
            if any(e["line"] == trace.OPS_LINE for e in by_plane[p])][:chips]


def _module_at(modules, starts, t):
    """Name of the executed program that holds time t on this plane."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i]["start"] + modules[i]["dur"]:
        return modules[i]["name"]
    return ""


def summarize(events: List[dict], chips: int = 1, block_scopes=()) -> dict:
    """Device self seconds by program scope (``by_scope``, over the shared
    vocabulary and ``block_scopes``; the XLA operation families under
    each in ``ops_by_scope``, all of those that carry no scope in
    ``unscoped_ops``) and by training pass (``by_pass``); idle seconds of
    the window by ``ds:*`` phase (``idle_by_phase``). Averaged over the
    ``chips`` lowest-numbered device planes that ran anything. ``scoped``
    says whether any operation carried a scope of the program at all,
    ``spanned`` whether the trace holds any ``ds:*`` annotation."""
    w0, w1 = _window(events)
    planes = _planes(events, chips)
    if not planes:
        raise ValueError("no operation ran on a device in this trace")
    host = sorted((e for e in events if e["name"].startswith(ANNOTATION)),
                  key=lambda e: e["start"])
    edges = sorted({t for e in host for t in _span(e)})
    by_scope: Dict[str, float] = defaultdict(float)
    by_pass: Dict[str, float] = defaultdict(float)
    ops_of: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    idle: Dict[str, float] = defaultdict(float)
    n = len(planes)
    for plane in planes:
        ops = [e for e in plane if e["line"] == trace.OPS_LINE]
        modules = sorted((e for e in plane if e["line"] == trace.MODULES_LINE),
                         key=lambda e: e["start"])
        starts = [m["start"] for m in modules]
        in_window = [e for e in ops if w0 <= e["start"] < w1]
        for e, own, _ in trace.exclusive(in_window):
            op_name = e.get("op_name", "")
            scope = scope_of(op_name, block_scopes)
            by_scope[scope] += own / n
            by_pass[train_pass(op_name, _module_at(
                modules, starts, e["start"]))] += own / n
            ops_of[scope][trace.op_family(e["name"])] += own / n
        busy = ar.clip_intervals(map(_span, ops), w0, w1)
        for a, b in trace._gaps(busy, w0, w1):
            cuts = [a] + edges[bisect.bisect_right(edges, a):
                               bisect.bisect_left(edges, b)] + [b]
            for lo, hi in zip(cuts, cuts[1:]):
                idle[trace._host_at(host, (lo + hi) / 2)] += (hi - lo) / n
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])
    return {"window_s": w1 - w0, "devices": n,
            "busy_s": sum(by_scope.values()), "idle_s": sum(idle.values()),
            "by_scope": top(by_scope), "by_pass": top(by_pass),
            "unscoped_ops": top(ops_of[UNSCOPED]),
            "ops_by_scope": {k: top(v)[:6] for k, v in ops_of.items()},
            "idle_by_phase": top(idle),
            "scoped": any(k != UNSCOPED for k in by_scope),
            "spanned": bool(host)}


# ------------------------------------------------------------------ readers

def _summary(ctx) -> Optional[dict]:
    """The reduced trace of a traced run on the chip (once per context)."""
    if ctx.trace is None:
        return None
    if getattr(ctx, "_scopes", None) is None:
        ctx._scopes = summarize(
            load(ctx.result["xplane"]), chips=ctx.result["chips"],
            block_scopes=getattr(ctx.info.get("block"), "SCOPES", ()))
    return ctx._scopes


def _share(table, key, total):
    return 100.0 * dict(table).get(key, 0.0) / total if total else None


def device_share(ctx, scope: str):
    """Share (%) of device busy time spent under ``scope`` (self time)."""
    s = _summary(ctx)
    if s is None or not s["scoped"]:
        return None
    return _share(s["by_scope"], scope, s["busy_s"])


def train_share(ctx, which: str):
    """Share (%) of device busy time in one pass of the train step."""
    s = _summary(ctx)
    if s is None or not s["scoped"]:
        return None
    return _share(s["by_pass"], which, s["busy_s"])


def idle_unspanned_share(ctx):
    """Share (%) of the window's device idle seconds under no ``ds:*``
    span: what the host was doing then, the program does not say."""
    s = _summary(ctx)
    if s is None or not s["spanned"]:
        return None
    return _share(s["idle_by_phase"], UNSPANNED, s["idle_s"])


def span_median_ms(ctx, name: str):
    """Median duration (ms) of the program's ``name`` spans that began in
    the window (``ctx.result["program_spans"]``) of a traced run on the
    chip."""
    if ctx.trace is None:
        return None
    w0, w1 = ctx.result["window"]
    ms = [(s["t_end"] - s["t_start"]) * 1e3
          for s in ctx.result.get("program_spans", [])
          if s["name"] == name and s.get("t_end") is not None
          and w0 <= s["t_start"] < w1]
    return ar.median(ms) if ms else None


if __name__ == "__main__":
    import json
    import sys

    chips = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    print(json.dumps(summarize(load(sys.argv[1]), chips, sys.argv[3:]),
                     indent=1))
