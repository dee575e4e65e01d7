"""What the readers of a block with state-space (Mamba-2) layers and
experts in a latent share (the pattern of ``hybrid_readers.py`` and
``sala_readers.py``, which are not edited): shares of device time under
the scopes the block adds (``blocks/<block>.py``: ``MAMBA_SCOPES``,
``latent_proj``), and the rate at which the one-token step moves the
recurrent state — the program's ``ssm_state_bytes`` (``engine.last_put``
on the ``forward`` span: the state bytes a forward's rows read and write)
over the device time of the same kind of forward under ``mamba_scan`` (the
recurrence) plus ``mamba_state_io`` (the gather of the rows' state out of
the slots and the scatter back).
Everything returns None when there is nothing to read: an untraced run, a
rehearsal off the chip, a block without the names, a program whose
``forward`` spans carry no ``ssm_*`` (the parent's)."""

from __future__ import annotations

from . import arithmetic as ar
from . import hybrid_readers


def mamba_share(ctx, scopes_=None):
    """Device self time under the Mamba-2 layers' scopes (all of them, or
    the ones named), share of busy in percent."""
    names = scopes_ or getattr(hybrid_readers._block(ctx), "MAMBA_SCOPES", ())
    return hybrid_readers.scopes_share(ctx, names)


def latent_share(ctx):
    """Device self time of the two projections round the routed experts'
    latent, share of busy in percent."""
    return hybrid_readers.scopes_share(ctx, ("latent_proj",))


def stepped_forwards(ctx):
    """The attrs of the program's ``forward`` spans that began inside the
    traced marks and ran one-token rows through the step."""
    marks = ctx.result.get("trace_marks")
    if not marks:
        return []
    return [s["attrs"] for s in ctx.result.get("program_spans", [])
            if s["name"] == "forward" and marks[0] <= s["t_start"] < marks[1]
            and s.get("attrs", {}).get("ssm_rows_stepped")]


#: where the one-token step touches the state: the recurrence, and the
#: gather out of the slots and the scatter back round it
STATE_SCOPES = ("mamba_scan", "mamba_state_io")


def state_gbps(ctx):
    """GB/s at which the one-token forwards of the traced window moved
    the recurrent state: their median ``ssm_state_bytes`` (the rows' state
    read + written, every Mamba-2 layer) over the median device time of
    one such forward under ``STATE_SCOPES`` — all the time spent moving
    the state, not the recurrence's alone (``hybrid_readers``' rule for
    which forward a program's execution is). A rate beside the chip's
    memory bandwidth, not a share: the step is plain XLA, and what it
    moves besides the state (x, B, C, y) is a thousandth of it. None
    where either scope has no device time (a program without
    ``mamba_state_io`` would read the recurrence alone under this name)."""
    if ctx.trace is None:
        return None
    moved = [a["ssm_state_bytes"] for a in stepped_forwards(ctx)
             if a.get("ssm_state_bytes")]
    if not moved:
        return None
    spent_ms = [hybrid_readers.scope_ms_per_forward(ctx, name, mixed=False)
                for name in STATE_SCOPES]
    if not all(spent_ms):
        return None
    return ar.median(moved) / (sum(spent_ms) * 1e-3) / 1e9
