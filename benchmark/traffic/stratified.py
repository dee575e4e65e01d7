"""Requests on a stratified, fixed schedule: independent users with
lognormal prompt and output lengths, open loop (exponential gaps between
arrivals at the cell's rate) or closed loop (each caller sends its next
request when its last completes).

Two things are drawn, from two seeds. The *schedule* — when each request is
due, how long its prompt and its output are — comes from the mix's own
``schedule_seed``: the draws of one block of ``BLOCK`` requests are the
block's evenly spaced quantiles of the stated distribution in a seeded
order, so every block offers the same amount of work (and, in an open
loop, takes the same time: arrivals are not Poisson counts, the bursts of
a Poisson stream are ironed out). The *tokens* (and the weights, in the
runner) come from ``--seed``. A run's timing depends on the schedule and
not on the tokens (greedy, no EOS: every request runs to its length), so
runs with different seeds repeat to within the system's own noise; with
the order left to ``--seed``, p90 TTFT of the chat cell spread by 20% over
seeds (which request meets which; PERF.md, PR 25). Another sample of the
same traffic is another mix file with another ``schedule_seed`` — data.

Mix parameters (lengths in tokens):

``loop``           ``"open"`` (rate from the cell's file) or ``"closed"``
``clients``        closed loop: how many callers
``prompt_tokens``, ``output_tokens``
                   lognormal ``{"median", "sigma", "min", "max"}``
``schedule_seed``  the schedule's own seed
``preroll_s``      traffic before the measured window
``drain_s``        how long after the window unfinished requests may
                   still complete before they count as failed
"""

from statistics import NormalDist

import numpy as np

from benchmark.traffic import Request

#: requests to a block; each block holds the same lengths and gaps
BLOCK = 16


def _quantiles(rng) -> np.ndarray:
    """The BLOCK mid-point quantiles (i + ½)/BLOCK in a seeded order."""
    return rng.permutation((np.arange(BLOCK) + 0.5) / BLOCK)


def _lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def requests(mix: dict, vocab: int, seed: int, rate_rps=None):
    """An endless seeded stream of requests (take what the run needs)."""
    open_loop = mix["loop"] == "open"
    if open_loop and not rate_rps:
        raise ValueError("an open loop needs the cell's rate_rps")
    rng = np.random.default_rng([seed, 0x7261])             # tokens
    sched = np.random.default_rng([int(mix["schedule_seed"]),
                                   0x7363])                 # the schedule
    t, index = 0.0, 0
    while True:
        p_len = _lengths(mix["prompt_tokens"], _quantiles(sched))
        o_len = _lengths(mix["output_tokens"], _quantiles(sched))
        gaps = (-np.log1p(-_quantiles(sched)) / rate_rps if open_loop
                else np.zeros(BLOCK))
        for i in range(BLOCK):
            prompt = rng.integers(0, vocab, size=int(p_len[i])).tolist()
            t += float(gaps[i])
            yield Request(index, t if open_loop else None, prompt,
                          int(o_len[i]))
            index += 1
