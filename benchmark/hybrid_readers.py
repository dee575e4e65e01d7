"""What the readers of a hybrid block's metrics share (the pattern of
``readers.py``, which is not edited): shares of device time under the
scopes the block adds, one scope's device time per forward, the
program's routing and state-slot counters, and the rooflines whose cost
functions the block module brings (``blocks/<block>.py``:
``paged_attention_cost`` at the stated head size over the attention
layers only, ``gmm_cost`` for the held experts' grouped matmuls).
Everything returns None when there is nothing to read: an untraced run, a
rehearsal off the chip, a block or a program without the name."""

from __future__ import annotations

import bisect

from . import arithmetic as ar
from . import peaks, readers, scopes, trace


def _block(ctx):
    return ctx.info.get("block")


def gdn_scopes(ctx):
    return tuple(getattr(_block(ctx), "GDN_SCOPES", ()))


def scopes_share(ctx, names):
    """Share (%) of device busy time whose innermost program scope is one
    of ``names`` (self time); None where the trace holds none of them."""
    s = scopes._summary(ctx)
    if s is None or not s["scoped"] or not names:
        return None
    table = dict(s["by_scope"])
    if not any(n in table for n in names):
        return None
    return 100.0 * sum(table.get(n, 0.0) for n in names) / s["busy_s"]


def _forward_tags(ctx):
    """(start, chunk width) of each ``bench:forward[SxC]`` annotation."""
    out = []
    for e in ctx.trace["host"]:
        if e["name"].startswith("bench:forward["):
            _, c = e["name"][len("bench:forward["):-1].split("x")
            out.append((e["start"], int(c)))
    return sorted(out)


def scope_ms_per_forward(ctx, scope: str, mixed: bool):
    """Median device self time (ms) under ``scope`` inside one execution
    of the paged forward's program, over the traced window's mixed steps
    (the forward was dispatched at a chunk wider than one token) or its
    decode steps. A program execution belongs to the ``bench:forward``
    annotation that began nearest to its start (``trace.module_seconds``'
    rule)."""
    if ctx.trace is None:
        return None
    tags = _forward_tags(ctx)
    if not tags:
        return None
    starts = [t for t, _ in tags]
    block_scopes = getattr(_block(ctx), "SCOPES", ())
    # each device's operations in order of their start, so that a program
    # execution finds its own by bisection and not by a pass over them all
    ops_of = {}
    for e in scopes.load(ctx.result["xplane"]):
        if e["line"] == trace.OPS_LINE:
            ops_of.setdefault(e["plane"], []).append(e)
    op_starts = {}
    for plane, ops in ops_of.items():
        ops.sort(key=lambda e: e["start"])
        op_starts[plane] = [e["start"] for e in ops]
    totals = []
    for m in ctx.trace["modules"]:
        if "forward" not in m["name"] or m["device"] not in ops_of:
            continue
        i = bisect.bisect_left(starts, m["start"])
        near = min((j for j in (i - 1, i) if 0 <= j < len(tags)),
                   key=lambda j: abs(starts[j] - m["start"]))
        if (tags[near][1] > 1) != mixed:
            continue
        at = op_starts[m["device"]]
        ops = ops_of[m["device"]][bisect.bisect_left(at, m["start"]):
                                  bisect.bisect_left(at, m["start"] + m["dur"])]
        own = sum(own for e, own, _ in trace.exclusive(ops)
                  if scopes.scope_of(e.get("op_name", ""), block_scopes)
                  == scope)
        if own:
            totals.append(own)
    return ar.median(totals) * 1e3 if totals else None


def _forward_attrs(ctx, key):
    w0, w1 = ctx.result["window"]
    return [s["attrs"][key] for s in ctx.result.get("program_spans", [])
            if s["name"] == "forward" and w0 <= s["t_start"] < w1
            and key in s.get("attrs", {})]


def moe_rows_per_expert(ctx):
    """Rows one held expert is given in one forward of one layer: the
    program's ``moe_rows_held`` a forward (the expectation under even
    routing, ``engine._count_routing``) over layers x experts held."""
    held = _forward_attrs(ctx, "moe_rows_held")
    arch = ctx.result["arch"]
    experts = (arch.get("moe_held_experts") or (0, 0))[1]
    if not held or not experts:
        return None
    return sum(held) / len(held) / (arch["num_layers"] * experts)


def state_slots_peak_share(ctx):
    used = _forward_attrs(ctx, "state_slots_used")
    slots = ctx.info["config"]["engine"].get("max_ragged_sequence_count")
    return 100.0 * max(used) / slots if used and slots else None


def _traced_forwards(ctx):
    marks = ctx.result.get("trace_marks")
    probe = ctx.result.get("probe")
    if ctx.trace is None or not marks or probe is None:
        return []
    return [a for *_, a in probe.named("forward", *marks)]


def paged_attention_roofline(ctx):
    """``readers.paged_attention_roofline`` with the block's own cost
    function: one call an *attention* layer, at the stated head size."""
    block = _block(ctx)
    forwards = _traced_forwards(ctx)
    if not forwards or not hasattr(block, "paged_attention_cost"):
        return None
    arch, kind = ctx.result["arch"], ctx.device["kind"]
    least = sum(block.attention_layers(arch) * peaks.roofline_seconds(
        block.paged_attention_cost(arch, a["valid_tokens"],
                                   a["kv_read_tokens"], a["qk_pairs"]), kind)
        for a in forwards)
    return readers.kernel_roofline(ctx, ("kernel:paged_attention",), least)


def gmm_roofline(ctx):
    """The grouped-matmul kernel's share (%) of its roofline over the
    traced window: the least time the chip could take for the held
    experts' GEMMs (the block's ``gmm_cost``: expected pairs and expected
    experts hit a forward) over the device time of ``kernel:gmm``'s own
    events (``readers.kernel_roofline``)."""
    block = _block(ctx)
    forwards = _traced_forwards(ctx)
    if not forwards or not hasattr(block, "gmm_cost"):
        return None
    least = sum(peaks.roofline_seconds(
        block.gmm_cost(ctx.result["arch"], a["valid_tokens"]),
        ctx.device["kind"]) for a in forwards)
    return readers.kernel_roofline(ctx, ("kernel:gmm",), least)
