"""Seconds of set-up the process spent lowering jaxprs to MLIR, the Mosaic kernels' bodies among it, as JAX reports them (jaxpr_to_mlir_module_duration), each second counted once to the innermost stage."""

from benchmark import setup_readers


def reduce(ctx):
    return setup_readers.read(ctx, "lower", "self_seconds")
