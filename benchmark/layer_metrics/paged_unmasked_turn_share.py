"""The share of the paged kernel's loop turns that lie wholly inside every row's view and are folded without the mask's arithmetic (the program's attn_turns_unmasked over attn_turns, summed over the forward spans that began inside the traced marks), in percent."""


def reduce(ctx):
    """None where there is nothing to read: an untraced run (no program
    spans), a program whose ``forward`` spans carry no ``attn_turns`` (the
    parent's, which masks every turn), a window in which no paged call
    folded a turn."""
    marks = ctx.result.get("trace_marks")
    if not marks:
        return None
    counts = [s["attrs"] for s in ctx.result.get("program_spans", [])
              if s["name"] == "forward" and marks[0] <= s["t_start"] < marks[1]
              and "attn_turns" in s.get("attrs", {})]
    turns = sum(a["attn_turns"] for a in counts)
    if not turns:
        return None
    return 100.0 * sum(a["attn_turns_unmasked"] for a in counts) / turns
