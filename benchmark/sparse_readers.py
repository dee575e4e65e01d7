"""What the readers of a block with a learned selection and window latent
layers share (the pattern of ``latent_readers.py`` and
``kv_group_readers.py``, which are not edited): the program's counters of
what the selection kept (``engine.last_put`` on the ``forward`` span:
``sparse_keys_live`` / ``sparse_keys_selected``, beside the ``latent_*``
and ``window_*`` path counters and the window group's ``kv_g<i>_*``) and
the rooflines of the kernels the block's layers run, with the block's own
cost functions (``blocks/<block>.py``: ``index_score_cost``,
``mla_sparse_decode_cost``, ``mla_window_cost``).
Everything returns None when there is nothing to read: an untraced run, a
rehearsal off the chip, a block without the cost functions, a program
whose ``forward`` spans carry no ``sparse_*`` (the parent's)."""

from __future__ import annotations

from . import hybrid_readers, latent_readers, peaks, readers


def index_share(ctx, scopes=None):
    """Device self time under the indexer's scopes (all of them, or the
    ones named), share of busy in percent."""
    names = getattr(ctx.info.get("block"), "INDEX_SCOPES", ())
    return hybrid_readers.scopes_share(ctx, scopes or names)


def select_ratio(ctx):
    """Keys the sparse layers attended over the keys their query
    positions could see, over the window's forwards."""
    records = [r for r in latent_readers.forward_records(ctx)
               if "sparse_keys_live" in r]
    live = sum(r["sparse_keys_live"] for r in records)
    kept = sum(r["sparse_keys_selected"] for r in records)
    return kept / live if live and kept else None


def _roofline(ctx, kernels, cost_name: str, kind: str, counts):
    """The named kernels' share (%) of their roofline over the traced
    window (``latent_readers._roofline``'s rule: the last forward that
    began inside the marks is left out of the least work), one call a
    layer of ``kind``; ``counts(record)`` -> the cost function's
    arguments, or None for a forward that made no such call."""
    block = ctx.info.get("block")
    marks = ctx.result.get("trace_marks")
    cost = getattr(block, cost_name, None)
    if ctx.trace is None or not marks or cost is None:
        return None
    arch, chip = ctx.result["arch"], ctx.device["kind"]
    layers = block.layer_kinds(arch)[kind]
    least = 0.0
    for r in latent_readers.forward_records(ctx, *marks)[:-1]:
        args = counts(r) if "sparse_keys_live" in r else None
        if args:
            least += layers * peaks.roofline_seconds(cost(arch, *args), chip)
    return readers.kernel_roofline(
        ctx, tuple("kernel:" + k for k in kernels), least)


def index_score_roofline(ctx):
    return _roofline(
        ctx, ("index_score",), "index_score_cost", "latent_sparse",
        lambda r: (r["valid_tokens"], r["kv_read_tokens"], r["qk_pairs"]))


def sparse_attention_roofline(ctx):
    """``mla_sparse_decode``'s share over the forwards that ran absorbed:
    the selected rows of each query position."""
    return _roofline(
        ctx, ("mla_sparse_decode",), "mla_sparse_decode_cost",
        "latent_sparse",
        lambda r: (r["latent_q_absorbed"], r["sparse_keys_absorbed"])
        if r["latent_q_absorbed"] else None)


def window_roofline(ctx, group: int = 1):
    """The window latent kernels' share, either path: the absorbed rows'
    keys and pairs under the window are the program's own counts, the
    expanded rows' the window group's less those (a put's record sums
    its forwards, which may have taken either path)."""
    def counts(r):
        keys, pairs = (r.get(f"kv_g{group}_read_tokens"),
                       r.get(f"kv_g{group}_qk_pairs"))
        if keys is None:
            return None
        return (r["window_q_absorbed"], r["window_keys_absorbed"],
                r["window_pairs_absorbed"], r["window_q_expanded"],
                keys - r["window_keys_absorbed"],
                pairs - r["window_pairs_absorbed"])

    return _roofline(ctx, ("mla_window_decode", "mla_window_prefill"),
                     "mla_window_cost", "latent_window", counts)
