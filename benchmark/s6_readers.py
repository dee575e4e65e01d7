"""What the readers of a block whose pace the S6 (Mamba) layers set share
(beside ``ssm_readers.py`` and ``ssm_step_readers.py``, which read a
Mamba-2 kernel and are not edited): the one-token step's share of its
roofline whatever implements it, the chunked scan's device time a token a
layer, and the share of the norms inside the layer. Rows and tokens are
each forward's own on its ``dispatch`` span (``rows``: the one-token rows
through the step, each of which the program's ``ssm_state_bytes`` counts
at its state once each way, every S6 layer; ``valid_tokens``: a chunk
forward's tokens through the chunked form); times are device self time
under the layer's scopes (``mamba_scan``, ``mamba_state_io``) inside the
same forwards' executions, matched by order (``dispatch_readers``).
Everything returns None when there is nothing to read: an untraced run, a
rehearsal off the chip, a block or a program without the names."""

from __future__ import annotations

import bisect

from . import dispatch_readers, peaks, scopes, trace
from .ssm_readers import STATE_SCOPES


def _forwards(ctx, wide: bool):
    """The traced window's forwards of one kind (``wide``: a chunk row;
    else one-token rows), each device execution matched to the program's
    ``dispatch`` of its ordinal by order (``dispatch_readers``: the rule
    that holds when the scheduler runs steps ahead of the device, as it
    does in a cell whose device is never idle — an execution then starts
    tens of milliseconds behind its own annotation and nearer a later
    one's), with that forward's own ``rows`` and ``valid_tokens``. The
    last is left out: the profiler may stop inside it."""
    r = dispatch_readers._reduced(ctx)
    if not r:
        return []
    return [f for f in dispatch_readers._window_forwards(r, wide)
            if "rows" in f["attrs"]][:-1]


def _seconds_under(ctx, forwards, names) -> float:
    """Device self time whose innermost scope is one of ``names``, inside
    the executions ``forwards``."""
    words = getattr(ctx.info.get("block"), "SCOPES", ())
    ops = sorted((e for e in scopes.load(ctx.result["xplane"])
                  if e["line"] == trace.OPS_LINE), key=lambda e: e["start"])
    starts = [e["start"] for e in ops]
    total = 0.0
    for f in forwards:
        inside = ops[bisect.bisect_left(starts, f["start"]):
                     bisect.bisect_left(starts, f["end"])]
        total += sum(own for e, own, _ in trace.exclusive(inside)
                     if scopes.scope_of(e.get("op_name", ""), words) in names)
    return total


def step_roofline(ctx):
    """The S6 one-token step's share (%) of its roofline over the traced
    window: the least bytes — what ``ssm_state_bytes`` counts for the
    stepped rows, their state read once and written once and nothing else
    (the step is a few FLOPs a state element, far under the ridge) — over
    the chip's memory bandwidth, over the device time under
    ``STATE_SCOPES`` in the same one-token forwards' executions. It reads
    the same work whatever implements the step (XLA round a gather and a
    scatter, a kernel that steps the state where it lies in its slots)."""
    block = ctx.info.get("block")
    if ctx.trace is None or not hasattr(block, "ssm_state_bytes"):
        return None
    arch = ctx.result["arch"]
    # what ``ssm_state_bytes`` counts a row: its state once each way, every
    # S6 layer
    a_row = 2 * block.layer_kinds(arch).get("mamba1", 0) \
        * block.ssm_state_bytes(arch)
    forwards = _forwards(ctx, wide=False)
    moved = a_row * sum(f["attrs"]["rows"] for f in forwards)
    spent = _seconds_under(ctx, forwards, STATE_SCOPES) if moved else 0.0
    if not moved or not spent:
        return None
    least = moved / peaks.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / spent


def scan_us_per_token(ctx):
    """Device time (µs) of the chunked recurrence a prompt token a layer:
    self time under ``mamba_scan`` in the traced window's chunk forwards'
    executions, over their valid tokens (a hybrid model's chunk forward is
    one row: its ``ssm_chunk_tokens``) and the block's S6 layers."""
    block = ctx.info.get("block")
    if ctx.trace is None or not hasattr(block, "layer_kinds"):
        return None
    layers = block.layer_kinds(ctx.result["arch"]).get("mamba1", 0)
    forwards = _forwards(ctx, wide=True)
    tokens = sum(f["attrs"].get("valid_tokens", 0) for f in forwards)
    spent = _seconds_under(ctx, forwards, ("mamba_scan",)) \
        if tokens and layers else 0.0
    if not spent:
        return None
    return 1e6 * spent / (tokens * layers)


def norm_share(ctx):
    """Device self time under ``mamba_norm`` (the three RMSNorms inside an
    S6 layer), share of busy in percent."""
    return scopes.device_share(ctx, "mamba_norm")
