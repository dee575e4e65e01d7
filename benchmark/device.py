"""What the benchmark asks of the device and of JAX itself: which device
this is, its peak memory, where compiled programs are kept, how many
compiles happened, and the profiler's trace of a window."""

from __future__ import annotations

import os
import threading
import time

from .manifest import CHECKOUT


class CompileWatch:
    """Seconds JAX spent in backend compiles (cache reads included), their
    count, and persistent-cache hits — from JAX's own monitoring events.
    (A copy of ``chip_smoke._CompileWatch``; listeners cannot be taken
    back, so a process makes one.)"""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds, self.count, self.hits = 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "count": self.count,
                "hits": self.hits}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says if it is set (JAX reads it itself,
    no other directory is set in code), else ``<checkout>/.jax_cache`` —
    a fixed path, because the path is part of the cache's key. Every
    program is kept, the small ones too, so that a warm run compiles
    nothing."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class compile_cache_off:
    """Context manager: compile without reading or writing the persistent
    cache (for a program that a cached executable breaks)."""

    def _set(self, on: bool):
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()

    def __enter__(self):
        self._set(False)

    def __exit__(self, *exc):
        self._set(True)
        return False


def device_record() -> dict:
    """The device as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int, platform: str = "tpu") -> dict:
    """The device record, or SystemExit(2) with the reason on stderr when
    this is not the chip or there are too few: no result is printed, and
    nothing falls back to the CPU."""
    import sys

    try:
        rec = device_record()
    except RuntimeError as e:       # the backend could not start
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        raise SystemExit(2)
    if rec["platform"] != platform:
        print(f"benchmark: platform is {rec['platform']!r}, not "
              f"{platform!r}: a cell is measured on the chip only",
              file=sys.stderr)
        raise SystemExit(2)
    if rec["count"] < chips:
        print(f"benchmark: the cell needs {chips} chips, "
              f"{rec['count']} present", file=sys.stderr)
        raise SystemExit(2)
    return rec


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the chips used (cumulative over
    the process); None where the backend reports no memory statistics."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class TraceWindow:
    """The profiler's trace of [t_start, t_end) on the monotonic clock,
    taken from a thread of its own so that the load generator keeps its
    schedule. The window is marked inside the trace by a ``bench:window``
    annotation, so the reducer needs no clock of its own."""

    def __init__(self, out_dir: str, t_start: float, t_end: float):
        self.out_dir, self.t_start, self.t_end = out_dir, t_start, t_end
        self.error = None
        self.marks = None       # the annotation's span, monotonic clock
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-trace")
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(max(0.0, self.t_start - time.monotonic()))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0      # annotations, not frames
            jax.profiler.start_trace(self.out_dir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation("bench:window"):
                    m0 = time.monotonic()
                    time.sleep(max(0.0, self.t_end - m0))
                    self.marks = (m0, time.monotonic())
            finally:
                jax.profiler.stop_trace()
        except Exception as e:      # reported by the runner, not swallowed
            self.error = e

    def finish(self, timeout: float = 240.0) -> str:
        """Wait for the trace to be written; returns the .xplane.pb path."""
        import glob

        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop in time")
        if self.error is not None:
            raise RuntimeError(f"profiler trace failed: {self.error!r}")
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"no .xplane.pb under {self.out_dir}")
        return found[-1]
