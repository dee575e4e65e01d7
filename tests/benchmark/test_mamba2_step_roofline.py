"""``mamba2_step_roofline`` (benchmark/ssm_step_readers.py) on hand-made
contexts: the bytes are the program's ``ssm_state_bytes`` over the
stepped forwards inside the marks but the last, the time is that of
``kernel:mamba2_step``'s events and no other operation's, a program
without the kernel (the parent) reads nothing, and a kernel that takes at
least bytes / 819 GB/s cannot read over 100."""

import pytest

from benchmark import manifest as mf
from benchmark import peaks, ssm_step_readers

STATE = 5 * 2 * 128 * 64 * 128 * 4      # a live row: five layers, in + out
HBM = peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"]


class _Ctx:
    def __init__(self, records, kernel_seconds, traced=True):
        self.device = {"kind": "TPU v5 lite"}
        self.result = {
            "window": (0.0, 100.0), "trace_marks": (10.0, 100.0),
            "program_spans": [{"name": "forward", "t_start": 5.0 + 10 * i,
                               "attrs": r} for i, r in enumerate(records)]}
        self.trace = {"kernel_seconds": kernel_seconds} if traced else None


def step(rows):
    return {"bucket_chunk": 1, "ssm_rows_stepped": rows,
            "ssm_chunk_tokens": 0, "ssm_state_bytes": rows * STATE}


CHUNK = {"bucket_chunk": 512, "ssm_rows_stepped": 0,
         "ssm_chunk_tokens": 300, "ssm_state_bytes": STATE}


def reduce(ctx):
    return mf.find_module(mf.HERE, "layer_metrics",
                          "mamba2_step_roofline").reduce(ctx)


def test_bytes_from_the_counter_time_from_the_kernels_events():
    # the first forward began before the marks, the chunk forward does
    # not step, the last stepped forward may still run: 20 + 24 rows
    records = [step(32), step(20), CHUNK, step(24), step(28)]
    least = 44 * STATE / HBM
    ctx = _Ctx(records, {"kernel:mamba2_step": 2 * least,
                         "kernel:gmm": 1.0, "fusion": 3.0})
    assert reduce(ctx) == pytest.approx(50.0)
    assert ssm_step_readers.step_roofline(ctx) == reduce(ctx)
    # another kernel's time, or XLA's, moves nothing here
    ctx.trace["kernel_seconds"]["kernel:gmm"] = 100.0
    assert reduce(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("slack", [1.0, 1.2, 3.0])
def test_never_over_100_when_the_events_take_at_least_the_least(slack):
    records = [step(18)] * 6
    least = 4 * 18 * STATE / HBM        # the first and the last left out
    got = reduce(_Ctx(records, {"kernel:mamba2_step": slack * least}))
    assert got == pytest.approx(100.0 / slack) and got <= 100.0 + 1e-9


@pytest.mark.parametrize("case", ["parent", "untraced", "chunks", "no_ssm",
                                  "one_forward"])
def test_nothing_to_read_is_none(case):
    records = [step(18)] * 4
    seconds = {"kernel:mamba2_step": 1.0}
    if case == "parent":        # plain XLA round a gather and a scatter
        seconds = {"kernel:gmm": 1.0, "fusion": 2.0}
    if case == "chunks":
        records = [CHUNK] * 4
    if case == "no_ssm":
        records = [{"bucket_chunk": 1, "valid_tokens": 4}] * 4
    if case == "one_forward":   # the one inside the marks may still run
        records = records[:2]
    assert reduce(_Ctx(records, seconds, traced=case != "untraced")) is None
