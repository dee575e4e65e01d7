"""Shared correctness helpers for the v2 ragged engine.

One home for the greedy-token-parity machinery used by
``tests/test_prefix_cache.py``, ``tests/test_spec_decode.py`` and the
other feature suites under ``tests/``: every engine-level
optimization here (prefix caching, speculative decoding) carries the hard
guarantee that greedy token streams are byte-identical with the feature on
and off — this module is the single definition of "run these prompts
greedily and give me the streams".
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from .scheduler import ContinuousBatchingScheduler


def greedy_generate(engine=None, prompts: Sequence[Sequence[int]] = (),
                    uid_base: int = 0, max_new_tokens: int = 8,
                    eos_token_id: Optional[int] = None,
                    scheduler: Optional[ContinuousBatchingScheduler] = None,
                    sequential: bool = True,
                    **scheduler_kwargs) -> List[List[int]]:
    """Greedy-decode ``prompts`` through a ContinuousBatchingScheduler and
    return one generated-token list per prompt.

    ``sequential=True`` (default) runs each prompt to completion before
    submitting the next — the deterministic reference order parity checks
    compare against (it also warms prefix/speculation state in submission
    order). ``sequential=False`` submits everything up front and lets
    continuous batching interleave — same tokens, concurrent schedule.

    Pass ``scheduler`` to reuse one (e.g. to keep its engine's caches warm
    across passes), or ``scheduler_kwargs`` (``proposer=``,
    ``max_draft_tokens=``...) to build one on ``engine``.
    """
    if scheduler is None:
        if engine is None:
            raise ValueError("greedy_generate needs an engine or scheduler")
        scheduler = ContinuousBatchingScheduler(engine, **scheduler_kwargs)
    uids = []
    for i, p in enumerate(prompts):
        uid = uid_base + i
        uids.append(uid)
        scheduler.submit(uid, list(p), max_new_tokens=max_new_tokens,
                         eos_token_id=eos_token_id)
        if sequential:
            scheduler.run_to_completion()
    if not sequential:
        scheduler.run_to_completion()
    return [scheduler.finished[uid].generated for uid in uids]


def assert_greedy_parity(reference: Sequence[List[int]],
                         candidate: Sequence[List[int]],
                         label: str = "feature") -> None:
    """Byte-identical-stream check with a diagnostic that names the first
    diverging request and position (raw list comparison buries both)."""
    assert len(reference) == len(candidate), (
        f"{label}: {len(candidate)} streams vs {len(reference)} expected")
    for r, (ref, got) in enumerate(zip(reference, candidate)):
        if list(ref) == list(got):
            continue
        pos = next((j for j, (a, b) in enumerate(zip(ref, got)) if a != b),
                   min(len(ref), len(got)))
        raise AssertionError(
            f"greedy parity broken by {label}: request {r} diverges at "
            f"token {pos}: expected {list(ref)[max(0, pos - 2):pos + 3]}, "
            f"got {list(got)[max(0, pos - 2):pos + 3]} "
            f"(lens {len(ref)} vs {len(got)})")


def spec_summary(stats: Dict[str, int]) -> Dict[str, float]:
    """Derived speculative-decoding numbers from
    ``ContinuousBatchingScheduler.spec_stats()`` counters."""
    proposed = stats.get("proposed", 0)
    rows = stats.get("decode_rows", 0)
    return {
        "acceptance_rate": (stats.get("accepted", 0) / proposed
                            if proposed else 0.0),
        "tokens_per_forward": (stats.get("emitted", 0) / rows
                               if rows else 0.0),
    }


def share_forward(engine, cache: dict, key):
    """Give ``engine`` the jitted forward of the first engine that was
    registered in ``cache`` under ``key``, with what it has compiled, and
    return the engine. For suites that build many engines of one model at
    one sizing: each would otherwise trace and compile the same buckets
    again (a jit belongs to its ``PagedCausalLM``). ``key`` has to name
    everything a trace reads — the model, the sizing, any module switch a
    test has moved. An engine that compiled ahead keeps its own."""
    if not engine.config.compile_ahead:
        engine.paged.forward = engine._forward_jit = cache.setdefault(
            key, engine.paged.forward)
    return engine

