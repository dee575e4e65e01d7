"""Traffic. A mix is a data file of parameters (``traffic/<mix>.json``).
It names its ``generator``: a module ``traffic/<generator>.py``, found by
that name, that turns the mix's parameters and ``--seed`` into requests
(``requests(mix, vocab, seed, rate_rps)``, an endless stream) or training
batches (``batches(mix, vocab, seed, chips)``). The program sees only what
is generated there.

Another mix over a generator that is there is data alone (other lengths,
another loop, another ``schedule_seed``). Another *kind* of traffic —
bursts, sessions with shared prefixes — is a generator file of its own
beside the others, with its own tests; nothing here is edited for it.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from .manifest import find_module


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: Optional[float]      # seconds after traffic start; None = closed
    prompt: List[int]
    new_tokens: int


def generator(info: dict):
    """The generator module the cell's traffic mix names."""
    return find_module(info["bench_dir"], "traffic",
                       info["traffic"]["generator"])
