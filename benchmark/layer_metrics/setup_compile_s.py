"""Thread-seconds of set-up spent in the backend's compile, reads of the persistent cache included, as JAX reports them (backend_compile_duration); under compile_ahead they run on threads and overlap the lowering and each other."""

from benchmark import setup_readers


def reduce(ctx):
    return setup_readers.read(ctx, "compile", "self_seconds")
