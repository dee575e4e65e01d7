"""The share of the paged kernel's live grid steps whose first turn the grid step before them had fetched (the program's attn_steps_primed over attn_steps, summed over the forward spans that began inside the traced marks), in percent."""


def reduce(ctx):
    """None where there is nothing to read: an untraced run (no program
    spans), a program whose ``forward`` spans carry no ``attn_steps`` (the
    parent's, whose walk drains at every grid step's edge), a window in
    which no paged call walked a block."""
    marks = ctx.result.get("trace_marks")
    if not marks:
        return None
    counts = [s["attrs"] for s in ctx.result.get("program_spans", [])
              if s["name"] == "forward" and marks[0] <= s["t_start"] < marks[1]
              and "attn_steps" in s.get("attrs", {})]
    steps = sum(a["attn_steps"] for a in counts)
    if not steps:
        return None
    return 100.0 * sum(a["attn_steps_primed"] for a in counts) / steps
