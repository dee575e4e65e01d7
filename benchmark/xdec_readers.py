"""What the readers of a decoder-hybrid-decoder block share (the pattern of
``hybrid_readers.py`` and ``ssm_readers.py``, which are not edited): device
time under a scope that encloses others (``cross_attn`` round its ``qkv``,
``attend`` and ``attn_out``; ``xdec`` round every layer behind the
forward's exit), over the traced window or over one kind of forward's
program executions alone, and the program's counters of the exit:
``xdec_rows`` on each forward's ``dispatch`` span, the positions that ran
the layers behind the last layer that writes a cache, and
``shared_kv_read_tokens`` on the ``forward`` span (``engine.last_put``),
the K/V positions the cross layers' walks of that layer's pool rows
read. Everything returns None when there is
nothing to read: an untraced run, a rehearsal off the chip, a block or a
program without the names (the parent's)."""

from __future__ import annotations

import bisect

from . import arithmetic as ar
from . import hybrid_readers, kv_group_readers, peaks, readers, scopes, trace


def _block_scopes(ctx):
    return getattr(ctx.info.get("block"), "SCOPES", ())


def _forward_executions(ctx, mixed: bool):
    """The device operations of each execution of the paged forward's
    program that belongs to a chunk forward (``mixed``: dispatched wider
    than one token) or to a one-token one — ``hybrid_readers
    .scope_ms_per_forward``'s rule for which is which —, as lists of
    ``(path, self seconds)``: the program scopes an operation sits under
    (``scopes.scope_path``) and its self time."""
    if ctx.trace is None:
        return []
    if getattr(ctx, "_xdec_executions", None) is None:
        ctx._xdec_executions = {}
    if mixed in ctx._xdec_executions:
        return ctx._xdec_executions[mixed]
    tags = hybrid_readers._forward_tags(ctx)
    out = []
    if tags:
        starts = [t for t, _ in tags]
        words = _block_scopes(ctx)
        ops_of = {}
        for e in scopes.load(ctx.result["xplane"]):
            if e["line"] == trace.OPS_LINE:
                ops_of.setdefault(e["plane"], []).append(e)
        op_starts = {}
        for plane, ops in ops_of.items():
            ops.sort(key=lambda e: e["start"])
            op_starts[plane] = [e["start"] for e in ops]
        for m in ctx.trace["modules"]:
            if "forward" not in m["name"] or m["device"] not in ops_of:
                continue
            i = bisect.bisect_left(starts, m["start"])
            near = min((j for j in (i - 1, i) if 0 <= j < len(tags)),
                       key=lambda j: abs(starts[j] - m["start"]))
            if (tags[near][1] > 1) != mixed:
                continue
            at = op_starts[m["device"]]
            ops = ops_of[m["device"]][
                bisect.bisect_left(at, m["start"]):
                bisect.bisect_left(at, m["start"] + m["dur"])]
            out.append([(scopes.scope_path(e.get("op_name", ""), words), own)
                        for e, own, _ in trace.exclusive(ops)])
    ctx._xdec_executions[mixed] = out
    return out


def path_share(ctx, word: str, mixed=None):
    """Share (%) of device busy time of the operations that sit under the
    scope ``word``, whatever their innermost scope: over the traced
    window's forwards of one kind (``mixed`` True: the chunk forwards',
    False: the one-token ones') or of both (None)."""
    if word not in _block_scopes(ctx):
        return None
    kinds = (True, False) if mixed is None else (mixed,)
    under = busy = 0.0
    for kind in kinds:
        for ops in _forward_executions(ctx, kind):
            for path, own in ops:
                busy += own
                under += own * (word in path)
    return 100.0 * under / busy if under else None


def ms_per_forward(ctx, outer: str, inner: str, mixed: bool):
    """Median device self time (ms) of the operations whose innermost
    scope is ``inner`` under the scope ``outer``, inside one execution of
    the forward's program of the given kind."""
    totals = [sum(own for path, own in ops
                  if path and path[-1] == inner and outer in path)
              for ops in _forward_executions(ctx, mixed)]
    totals = [t for t in totals if t]
    return ar.median(totals) * 1e3 if totals else None


def _traced_spans(ctx, name: str):
    """The attrs of the program's ``name`` spans that began inside the
    traced marks: ``forward`` holds a put's record (summed over its
    forwards where it ran as several), ``dispatch`` one forward's own."""
    marks = ctx.result.get("trace_marks")
    if not marks:
        return []
    return [s["attrs"] for s in ctx.result.get("program_spans", [])
            if s["name"] == name and marks[0] <= s["t_start"] < marks[1]
            and "attrs" in s]


def xdec_rows_share(ctx):
    """Of the positions the traced window's chunk forwards were fed (those
    with a row wider than one token; each forward's own ``dispatch``
    span, not its put's sums), the share (%) that ran the layers behind
    the exit: ``xdec_rows`` over ``valid_tokens``. One a row — 100 / the
    chunk's width — where the forward has its exit; 100 where the tail
    runs on every position."""
    chunks = [a for a in _traced_spans(ctx, "dispatch")
              if a.get("bucket_chunk", 1) > 1 and "xdec_rows" in a
              and a.get("valid_tokens")]
    if not chunks:
        return None
    return 100.0 * sum(a["xdec_rows"] for a in chunks) \
        / sum(a["valid_tokens"] for a in chunks)


def shared_kv_read_gbps(ctx):
    """GB/s at which the one-token forwards of the traced window read the
    shared K/V: their median ``shared_kv_read_tokens`` (every cross
    layer's walk of the writer's pool rows) at the block's bytes a
    position (``shared_kv_read_bytes``) over the median device time of one
    such forward under ``cross_attn``'s ``attend``. A rate beside the
    chip's memory bandwidth, as ``ssm_state_gbps`` is."""
    block = ctx.info.get("block")
    if ctx.trace is None or not hasattr(block, "shared_kv_read_bytes"):
        return None
    read = [a["shared_kv_read_tokens"] for a in _traced_spans(ctx, "forward")
            if a.get("bucket_chunk") == 1 and a.get("shared_kv_read_tokens")]
    spent_ms = ms_per_forward(ctx, "cross_attn", "attend", mixed=False)
    if not read or not spent_ms:
        return None
    return block.shared_kv_read_bytes(ctx.result["arch"], ar.median(read)) \
        / (spent_ms * 1e-3) / 1e9


def paged_attention_roofline(ctx):
    """The paged kernel's share (%) of its roofline over the traced
    window (``kv_group_readers.paged_attention_roofline``'s form), with
    the block's cost function — differential attention's least work —
    and the program's own counts, layer group by layer group
    (``paged_calls``): each of a group's reading layers is one call, of
    one query a row where the group is read behind the forward's exit
    (the put's ``rows``), else of every fed position."""
    block = ctx.info.get("block")
    marks = ctx.result.get("trace_marks")
    if ctx.trace is None or not marks or not hasattr(block, "paged_calls"):
        return None
    arch, kind = ctx.result["arch"], ctx.device["kind"]
    calls = block.paged_calls(arch)
    least = 0.0
    for r in kv_group_readers.forward_records(ctx, *marks):
        for g, (window, layers, one_query) in enumerate(calls):
            if r.get(f"kv_g{g}_window") != window:
                return None     # the program's groups are not the block's
            least += layers * peaks.roofline_seconds(
                block.paged_attention_cost(
                    arch, r["rows" if one_query else "valid_tokens"],
                    r[f"kv_g{g}_read_tokens"], r[f"kv_g{g}_qk_pairs"]), kind)
    return readers.kernel_roofline(ctx, ("kernel:paged_attention",), least)
