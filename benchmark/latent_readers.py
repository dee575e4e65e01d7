"""What the readers of a latent-attention block's metrics share (the
pattern of ``hybrid_readers.py`` and ``kv_group_readers.py``, which are
not edited): the program's counters of which path its queries took
(``engine.last_put`` on the ``forward`` span: ``latent_q_absorbed`` with
the keys and pairs of those rows, ``latent_q_expanded``,
``latent_rows_expanded``, ``prefill_tokens``) and the two kernels'
rooflines with the block's own cost functions
(``blocks/<block>.py``: ``mla_decode_cost``, ``mla_prefill_cost``).
Everything returns None when there is nothing to read: an untraced run,
a rehearsal off the chip, a block without the cost functions, a program
whose ``forward`` spans carry no ``latent_*`` (the parent's)."""

from __future__ import annotations

from . import peaks, readers


def forward_records(ctx, t0=None, t1=None):
    """The attrs of the program's ``forward`` spans that began in the
    measured window (or in [t0, t1)) and say which path their queries
    took, in order of their start."""
    if t0 is None:
        t0, t1 = ctx.result["window"]
    spans = sorted((s for s in ctx.result.get("program_spans", [])
                    if s["name"] == "forward" and t0 <= s["t_start"] < t1
                    and "latent_q_absorbed" in s.get("attrs", {})),
                   key=lambda s: s["t_start"])
    return [s["attrs"] for s in spans]


def expand_ratio(ctx):
    """Context positions whose K/V the expanded path rebuilt over the
    prompt positions prefilled, over the window's forwards: about L / 2C
    + 1/2 for a prompt of L in chunks of C, each chunk rebuilding all
    that lies before it and itself."""
    records = forward_records(ctx)
    prefilled = sum(r.get("prefill_tokens", 0) for r in records)
    rebuilt = sum(r["latent_rows_expanded"] for r in records)
    return rebuilt / prefilled if prefilled and rebuilt else None


def _roofline(ctx, kernel: str, cost_name: str, counts):
    """``kernel``'s share (%) of its roofline over the traced window: the
    least time the chip could take for what the window's forwards asked
    of it (``counts(record)`` -> the cost function's query rows, keys and
    pairs; one call a layer) over the device time of the kernel's own
    events. The last forward that began inside the profiler's marks may
    still be running when it stops: it is left out of the least work, so
    the share errs low, never high."""
    block = ctx.info.get("block")
    marks = ctx.result.get("trace_marks")
    cost = getattr(block, cost_name, None)
    if ctx.trace is None or not marks or cost is None:
        return None
    arch, kind = ctx.result["arch"], ctx.device["kind"]
    least = sum(arch["num_layers"] * peaks.roofline_seconds(
        cost(arch, *counts(r)), kind)
        for r in forward_records(ctx, *marks)[:-1] if counts(r)[0])
    return readers.kernel_roofline(ctx, (kernel,), least)


def decode_roofline(ctx):
    return _roofline(
        ctx, "kernel:mla_decode", "mla_decode_cost",
        lambda r: (r["latent_q_absorbed"], r["latent_keys_absorbed"],
                   r["latent_pairs_absorbed"]))


def prefill_roofline(ctx):
    return _roofline(
        ctx, "kernel:mla_prefill", "mla_prefill_cost",
        lambda r: (r["latent_q_expanded"],
                   r["kv_read_tokens"] - r["latent_keys_absorbed"],
                   r["qk_pairs"] - r["latent_pairs_absorbed"]))
