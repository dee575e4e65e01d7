"""Seconds of set-up (process start to window start) the process spent tracing functions to jaxprs — the forwards' and the kernels' Python, paid once a program on a warm start too — as JAX reports them (jaxpr_trace_duration), each second counted once to the innermost stage."""

from benchmark import setup_readers


def reduce(ctx):
    return setup_readers.read(ctx, "trace", "self_seconds")
