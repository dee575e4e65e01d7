"""The reader of the one-token Mamba-2 step's kernel (beside
``ssm_readers.py``, which is not edited): ``mamba2_step``'s share of its
roofline. The least work is the program's own counter, ``ssm_state_bytes``
on the ``forward`` spans: the live rows' state read once and written
once, every Mamba-2 layer, and nothing else — bytes only, the step is 5
FLOPs a state element and far under the ridge. A kernel that moves padded
rows or a copy besides reads low; none can read over 100 on this count.
None when there is nothing to read: an untraced run, a program whose
forwards carry no ``ssm_*``, a program without the kernel (the parent's,
whose step is plain XLA round a gather and a scatter)."""

from __future__ import annotations

from . import peaks, readers, ssm_readers

KERNEL = "kernel:mamba2_step"


def step_roofline(ctx):
    """``mamba2_step``'s share (%) of its roofline over the traced
    window: the state bytes its stepped forwards had to move over the
    chip's memory bandwidth, over the device time of the kernel's own
    events. The last forward that began inside the profiler's marks may
    still be running when it stops: it is left out of the least work, so
    the share errs low, never high (``latent_readers._roofline``)."""
    if ctx.trace is None:
        return None
    moved = sum(a.get("ssm_state_bytes", 0)
                for a in ssm_readers.stepped_forwards(ctx)[:-1])
    least = moved / peaks.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return readers.kernel_roofline(ctx, (KERNEL,), least)
