"""Set-up by part: what the program's own recorder of program builds and
full collections (``deepspeed_tpu/telemetry/builds.py``) heard between
the process's start and the window's start — the interval ``setup_s`` is
the length of. JAX reports each stage of each program it builds and the
collector each collection; the recorder keeps them on ``time.monotonic``,
the clock of ``window`` and ``setup_s``, so a reader cuts at the window's
start and sees neither the pre-roll's end nor the check's reference.

A stage's seconds are its *self* seconds: a jit called inside a function
that is being traced reports its own trace inside its caller's, and the
plain sum of the reports counts those seconds twice. Self seconds count
each second of a thread once, to the innermost stage, so on one thread
the three stages sum to ``setup_build_wall_s``; compiles on the
``compile_ahead`` threads overlap it and each other and add to more.
"""


def parts(ctx):
    """The recorder's snapshot over the run's set-up, read once a run;
    None for a program that has no recorder (the parent of the PR that
    added it) or whose recorder heard no build."""
    if not hasattr(ctx, "_setup_parts"):
        ctx._setup_parts = _read(ctx)
    return ctx._setup_parts


def _read(ctx):
    try:
        from deepspeed_tpu.telemetry import builds
    except ImportError:
        return None
    w0 = ctx.result["window"][0]
    snap = builds.RECORDER.snapshot(since=w0 - ctx.result["setup_s"],
                                    until=w0)
    return snap if snap["compile"]["count"] else None


def read(ctx, *keys):
    """The snapshot's number under ``keys``; None where there is no
    snapshot."""
    value = parts(ctx)
    for key in keys:
        if value is None:
            return None
        value = value[key]
    return value


def cache_hit_share(ctx):
    """None where no compile asked the persistent cache (a run that
    compiles uncached)."""
    snap = parts(ctx)
    asked = snap["cache_hits"] + snap["cache_misses"] if snap else 0
    return 100.0 * snap["cache_hits"] / asked if asked else None
