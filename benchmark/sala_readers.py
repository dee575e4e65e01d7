"""What the readers of a block with lightning layers and block-sparse
layers share (the pattern of ``sparse_readers.py`` and
``hybrid_readers.py``, which are not edited): shares of device time under
the scopes the block adds (``blocks/<block>.py``: ``LIGHTNING_SCOPES``,
``SELECT_SCOPES``), the program's counters of what the selection kept
(``engine.last_put`` on the ``forward`` span: ``sparse_blocks_live`` /
``sparse_blocks_selected``, and the split by kernel — ``sparse_ones`` /
``sparse_blocks_ones`` for the one-token rows, ``sparse_q_chunk`` /
``sparse_keys_chunk`` / ``sparse_pairs_chunk`` for the chunk rows), the
two attention kernels' share of busy and their rooflines with the block's
own cost functions (``paged_select_cost``, ``paged_mask_cost``).
Everything returns None when there is nothing to read: an untraced run, a
rehearsal off the chip, a block without the names, a program whose
``forward`` spans carry no ``sparse_blocks_*`` (the parent's)."""

from __future__ import annotations

from . import hybrid_readers, peaks, readers, scopes


def _block(ctx):
    return ctx.info.get("block")


def forward_records(ctx, t0=None, t1=None):
    """The attrs of the program's ``forward`` spans that began in the
    measured window (or in [t0, t1)) and say what the selection kept, in
    order of their start."""
    if t0 is None:
        t0, t1 = ctx.result["window"]
    spans = sorted((s for s in ctx.result.get("program_spans", [])
                    if s["name"] == "forward" and t0 <= s["t_start"] < t1
                    and "sparse_blocks_live" in s.get("attrs", {})),
                   key=lambda s: s["t_start"])
    return [s["attrs"] for s in spans]


def lightning_share(ctx, scopes_=None):
    """Device self time under the lightning layers' scopes (all of them,
    or the ones named), share of busy in percent."""
    names = scopes_ or getattr(_block(ctx), "LIGHTNING_SCOPES", ())
    return hybrid_readers.scopes_share(ctx, names)


def select_share(ctx):
    """Device self time of the selection (compress, score, select),
    share of busy in percent."""
    return hybrid_readers.scopes_share(
        ctx, getattr(_block(ctx), "SELECT_SCOPES", ()))


def select_ratio(ctx):
    """Blocks the sparse layers attended over the blocks their query
    positions could see, over the window's forwards."""
    records = forward_records(ctx)
    live = sum(r["sparse_blocks_live"] for r in records)
    kept = sum(r["sparse_blocks_selected"] for r in records)
    return kept / live if live and kept else None


def _kernel_names(ctx):
    block = _block(ctx)
    names = (getattr(block, "SELECT_KERNEL", None),
             getattr(block, "MASK_KERNEL", None))
    return names if all(names) else None


def attend_share(ctx):
    """Device time of the sparse layers' two attention kernels' own
    events, share of busy in percent."""
    names = _kernel_names(ctx)
    s = scopes._summary(ctx)
    if ctx.trace is None or s is None or not names or not s["busy_s"]:
        return None
    spent = sum(ctx.trace["kernel_seconds"].get("kernel:" + n, 0.0)
                for n in names)
    return 100.0 * spent / s["busy_s"] if spent else None


def _roofline(ctx, kernel: str, cost_name: str, counts):
    """``kernel``'s share (%) of its roofline over the traced window
    (``latent_readers._roofline``'s rule: the last forward that began
    inside the marks is left out of the least work, so the share errs
    low), one call a sparse layer; ``counts(record)`` -> the cost
    function's arguments, or None for a forward that made no such call."""
    block = _block(ctx)
    marks = ctx.result.get("trace_marks")
    cost = getattr(block, cost_name, None)
    if ctx.trace is None or not marks or cost is None or not kernel:
        return None
    arch, chip = ctx.result["arch"], ctx.device["kind"]
    layers = block.layer_kinds(arch)["block_sparse"]
    least = 0.0
    for r in forward_records(ctx, *marks)[:-1]:
        args = counts(r)
        if args:
            least += layers * peaks.roofline_seconds(cost(arch, *args), chip)
    return readers.kernel_roofline(ctx, ("kernel:" + kernel,), least)


def select_roofline(ctx):
    """``paged_attention_select``: the selected blocks of the one-token
    rows, read once a row a K/V head."""
    return _roofline(
        ctx, getattr(_block(ctx), "SELECT_KERNEL", None),
        "paged_select_cost",
        lambda r: (r["sparse_ones"], r["sparse_blocks_ones"])
        if r.get("sparse_ones") else None)


def mask_roofline(ctx):
    """``paged_attention_mask``: the selected query-key pairs of the
    chunk rows, whatever the kernel walked."""
    return _roofline(
        ctx, getattr(_block(ctx), "MASK_KERNEL", None), "paged_mask_cost",
        lambda r: (r["sparse_q_chunk"], r["sparse_keys_chunk"],
                   r["sparse_pairs_chunk"])
        if r.get("sparse_q_chunk") else None)
