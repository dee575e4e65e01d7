"""Two faults of a Jamba block's own, beside ``controls.py``'s (which plant
what a cache *holds*: a lost block, rounded weights; that file is not
edited), planted in the program and held against the cell's own check —

    python3 -m benchmark.jamba_controls --config <name> [--seed 1]
                                        [--prompt-tokens 2169]

(``controls.py``'s options and its line a control.)

``served``          the engine as the cell builds it: ``ok``.
``no_inner_norm``   the S6 layers run without the three norms between
                    ``W_x`` and ``W_dt`` (the layer as every other Mamba
                    model has it; the gains are there and unread). Not
                    ``ok``, or the norms are outside ``correct``.
``stale_slot``      a fresh row's state and conv tail are not zeroed: a
                    sequence starts from what its slot's last sequence
                    left there (the slots are used once before the check,
                    as a served engine's are). Not ``ok``, or the reuse of
                    a seat is outside ``correct``.

Each is ``controls.measure`` — ``serve_runner.check_logits`` over the
block's replay, at the file's tolerances — on one seeded prompt. The
served engine is let go before a faulted one is built on the same weights;
a faulted engine compiles the programs its replay runs and no others
(``compile_ahead`` 0). One JSON line a control, ``as_expected`` in each;
exit code 0 when every one is. It runs wherever JAX runs; a disagreement
is no device metric, say where it was read.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys

import numpy as np

from . import controls
from . import manifest as mf
from . import serve_runner as sr

KIND = "mamba1"
#: sequences run and flushed before a check, and the tokens of each (one
#: chunk at most): more than the replay's two sequences, so that both land
#: in a slot that has been used
DIRTY = (4, 96)


@contextlib.contextmanager
def never_fresh():
    """The program's ``mamba1`` kind building its serving layers over a
    forward in which no row is fresh, for as long as the context lasts
    (the registry's entry is frozen: the fault goes round that)."""
    from deepspeed_tpu.models.mixers import KINDS

    mixer, paged = KINDS[KIND], KINDS[KIND].paged
    object.__setattr__(
        mixer, "paged", lambda cfg, fwd: paged(cfg, fwd._replace(
            fresh=fwd.fresh & False)))
    try:
        yield
    finally:
        object.__setattr__(mixer, "paged", paged)


def use_slots(engine, seed: int) -> None:
    """``DIRTY`` sequences through the engine and out again: what a served
    engine's slots hold when a request takes a seat."""
    n, width = DIRTY[0], min(DIRTY[1], engine.config.max_chunk_tokens)
    vocab = engine.model.cfg.vocab_size
    rng = np.random.default_rng([seed, 0x736c])
    uids = [sr._OWN_UID + (1 << 22) + i for i in range(n)]
    for uid in uids:
        np.asarray(engine.put(
            [uid], [rng.integers(0, vocab, size=width).tolist()]))
    for uid in uids:
        engine.flush(uid)


def run(info: dict, seed: int, prompt):
    """(control, expected ok, ``check_logits`` record) for each control."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.transformer import CausalLM

    lazy = dict(info, config=dict(info["config"], engine=dict(
        info["config"]["engine"], compile_ahead=0)))
    cfg, params, engine = sr.build(lazy, seed)
    use_slots(engine, seed)
    yield "served", True, controls.measure(info, "served", engine, params,
                                           prompt)
    sizing = engine.config
    del engine
    gc.collect()
    bare = CausalLM(dataclasses.replace(cfg, mamba1_inner_norm=False))
    engine = InferenceEngineV2(bare, params=params, config=sizing)
    yield "no_inner_norm", False, controls.measure(
        info, "no_inner_norm", engine, params, prompt)
    del engine
    gc.collect()
    with never_fresh():
        engine = InferenceEngineV2(CausalLM(cfg), params=params,
                                   config=sizing)
        use_slots(engine, seed)
        yield "stale_slot", False, controls.measure(
            info, "stale_slot", engine, params, prompt)


def main(argv=None, root: str = mf.CHECKOUT) -> int:
    """``controls.main`` — its options, its prompt, its line a control —
    over this file's controls."""
    theirs = controls.run
    controls.run = lambda info, seed, prompt, rows_out: run(info, seed,
                                                            prompt)
    try:
        return controls.main(argv, root)
    finally:
        controls.run = theirs


if __name__ == "__main__":
    sys.exit(main())
