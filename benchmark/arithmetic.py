"""Metric arithmetic, kept with the benchmark so every PR computes a number
the same way. Pure functions of plain lists, tested against hand counts;
at the end the two measures of disagreement between the program's output
and a block's reference, which every block's check shares."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Iterable[float], p: float) -> float:
    """The p-th percentile (0..100) by linear interpolation between the
    two nearest order statistics (rank p/100·(n−1)); ``inf`` stays ``inf``
    — a request that never got its token is worse than any that did."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    rank = p / 100.0 * (len(xs) - 1)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == math.inf:
        return xs[lo] if rank == lo else math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Iterable[float]) -> float:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median — the driver's
    measure of run-to-run noise."""
    return (percentile(values, 75) - percentile(values, 25)) / median(values)


def ttft_ms(due: float, first_token_t: Optional[float]) -> float:
    """Time to the first token, from when the request was *due*; a request
    with no first token missed every limit."""
    return math.inf if first_token_t is None else (first_token_t - due) * 1e3


def tpot_ms(token_times: Sequence[float]) -> Optional[float]:
    """Mean gap between a request's output tokens,
    ``(t_last − t_first)/(n − 1)``; None for fewer than two tokens."""
    if len(token_times) < 2:
        return None
    return (token_times[-1] - token_times[0]) / (len(token_times) - 1) * 1e3


def count_in_window(times: Iterable[float], t0: float, t1: float) -> int:
    """How many of ``times`` fall in the half-open window [t0, t1)."""
    return sum(1 for t in times if t0 <= t < t1)


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [a, b) intervals."""
    total, end = 0.0, -math.inf
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def clip_intervals(intervals: Iterable[Tuple[float, float]], t0: float,
                   t1: float) -> List[Tuple[float, float]]:
    out = []
    for a, b in intervals:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            out.append((a, b))
    return out


def subtract_seconds(intervals: Sequence[Tuple[float, float]],
                     cover: Sequence[Tuple[float, float]]) -> float:
    """Seconds of the union of ``intervals`` not covered by ``cover``."""
    both = union_seconds(list(intervals) + list(cover))
    return both - union_seconds(cover)


def longest_silence(times: Iterable[float], t0: float, t1: float) -> float:
    """The longest stretch of [t0, t1) in which none of ``times`` falls:
    while requests are in flight a server emits tokens every step, so a
    long silence is a stall."""
    inside = sorted(t for t in times if t0 <= t < t1)
    edges = [t0] + inside + [t1]
    return max(b - a for a, b in zip(edges, edges[1:]))


def rms_rel_err(got, want) -> float:
    """Root-mean-square disagreement over the reference's RMS: averages
    over the whole vocabulary, so it moves with the precision of the
    arithmetic and not with one unlucky logit."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / (np.sqrt(np.mean(want ** 2)) + 1e-12))


def max_rel_err(got, want) -> float:
    """Largest disagreement relative to the reference's range."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))
