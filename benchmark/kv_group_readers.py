"""What the readers of a block with K/V by layer group share (the pattern
of ``hybrid_readers.py``, which is not edited): device time under the
scope that names an attention kind, the program's counters of its layer
groups' pools (``engine.last_put`` on the ``forward`` span: blocks in use
of each group's pool, K/V bytes resident beside what the same sequences
would hold with nothing handed back, each group's window-bounded keys and
pairs), and the paged kernel's roofline with the block's own cost
function, group by group. Everything returns None when there is nothing
to read: an untraced run, a rehearsal off the chip, a block or a program
without the names (the parent's: its ``forward`` spans carry no
``kv_g<i>_*``)."""

from __future__ import annotations

from . import peaks, readers, scopes, trace


def _block(ctx):
    return ctx.info.get("block")


def path_share(ctx, kind: str):
    """Share (%) of device busy time of the operations that sit under the
    block's scope for attention layers of ``kind`` ("window" | "full"),
    whatever their innermost scope (``qkv``, ``kv_write``, ``attend``,
    ``attn_out`` keep theirs): self time over busy time. The trace is
    read once a context, for both kinds."""
    words = getattr(_block(ctx), "ATTN_SCOPES", {})
    s = scopes._summary(ctx)
    if s is None or not s["scoped"] or kind not in words or not s["busy_s"]:
        return None
    if getattr(ctx, "_attn_seconds", None) is None:
        events = scopes.load(ctx.result["xplane"])
        w0, w1 = scopes._window(events)
        planes = scopes._planes(events, ctx.result["chips"])
        block_scopes = getattr(_block(ctx), "SCOPES", ())
        under = dict.fromkeys(words.values(), 0.0)
        for plane in planes:
            ops = [e for e in plane if e["line"] == trace.OPS_LINE
                   and w0 <= e["start"] < w1]
            for e, own, _ in trace.exclusive(ops):
                for word in set(scopes.scope_path(e.get("op_name", ""),
                                                  block_scopes)) & set(under):
                    under[word] += own / len(planes)
        ctx._attn_seconds = under
    seconds = ctx._attn_seconds[words[kind]]
    return 100.0 * seconds / s["busy_s"] if seconds else None


def forward_records(ctx, t0=None, t1=None):
    """The attrs of the program's ``forward`` spans that began in the
    measured window (or in [t0, t1)) and say something of layer groups."""
    if t0 is None:
        t0, t1 = ctx.result["window"]
    return [s["attrs"] for s in ctx.result.get("program_spans", [])
            if s["name"] == "forward" and t0 <= s["t_start"] < t1
            and "kv_g0_total" in s.get("attrs", {})]


def _groups(record):
    g = 0
    while f"kv_g{g}_total" in record:
        yield g
        g += 1


def resident_ratio(ctx):
    """K/V bytes resident over the bytes the same sequences would hold
    had no block been handed back, mean over the window's forwards."""
    ratios = [r["kv_bytes_resident"] / r["kv_bytes_unreleased"]
              for r in forward_records(ctx) if r.get("kv_bytes_unreleased")]
    return sum(ratios) / len(ratios) if ratios else None


def group_peak_share(ctx, windowed: bool):
    """Largest share (%) of a layer group's pool in use at a forward of
    the window: the window groups' (``windowed``) or the whole-context
    group's."""
    shares = [100.0 * r[f"kv_g{g}_in_use"] / r[f"kv_g{g}_total"]
              for r in forward_records(ctx) for g in _groups(r)
              if bool(r[f"kv_g{g}_window"]) == windowed
              and r[f"kv_g{g}_total"]]
    return max(shares) if shares else None


def paged_attention_roofline(ctx):
    """The paged kernel's share (%) of its roofline over the traced
    window, with the block's own cost function and the program's own
    counts, layer group by layer group: a window group's calls read and
    multiply what the window leaves, the whole-context group's all of
    it; each at the stated head size, one call a layer."""
    block = _block(ctx)
    marks = ctx.result.get("trace_marks")
    if ctx.trace is None or not marks \
            or not hasattr(block, "attention_calls"):
        return None
    arch, kind = ctx.result["arch"], ctx.device["kind"]
    calls = block.attention_calls(arch)
    least = 0.0
    for r in forward_records(ctx, *marks):
        for g, (window, layers) in enumerate(calls):
            if r.get(f"kv_g{g}_window") != window:
                return None     # the program's groups are not the block's
            least += layers * peaks.roofline_seconds(
                block.paged_attention_cost(
                    arch, r["valid_tokens"], r[f"kv_g{g}_read_tokens"],
                    r[f"kv_g{g}_qk_pairs"]), kind)
    return readers.kernel_roofline(ctx, ("kernel:paged_attention",), least)
