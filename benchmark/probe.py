"""The benchmark's own spans and counters, recorded around the calls into
each layer — on the instance, from outside (the program's files are not
touched). Used in traced runs only: end-to-end numbers are taken with
nothing wrapped. Every span is also a ``jax.profiler.TraceAnnotation``
(``bench:<name>``), so it lands in the profiler's trace beside the device
operations and the reducer can say what the host was doing in an idle gap.
"""

from __future__ import annotations

import time


class Probe:
    def __init__(self):
        self.spans = []         # (name, t_start, t_end, attrs) monotonic s
        self.samples = []       # (t, name, value)

    def wrap(self, obj, method: str, name: str, attrs=None, after=None):
        """Replace ``obj.method`` on the instance by a timed call.
        ``attrs(*args, **kw)`` → dict recorded with the span (and put in
        the annotation's name when it has a ``"tag"``); ``after()`` runs
        when the call returns (to sample a counter)."""
        import jax

        inner = getattr(obj, method)
        spans = self.spans

        def timed(*args, **kwargs):
            a = attrs(*args, **kwargs) if attrs is not None else {}
            label = f"bench:{name}" + (f"[{a['tag']}]" if "tag" in a else "")
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(label):
                out = inner(*args, **kwargs)
            spans.append((name, t0, time.monotonic(), a))
            if after is not None:
                after()
            return out

        setattr(obj, method, timed)

    def sample(self, name: str, value) -> None:
        self.samples.append((time.monotonic(), name, value))

    def named(self, name: str, t0: float, t1: float):
        return [s for s in self.spans if s[0] == name and t0 <= s[1] < t1]
