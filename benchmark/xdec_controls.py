"""Read-side faults for a decoder-hybrid-decoder block, beside
``controls.py``'s (which plant what a cache *holds*: a lost block, rounded
weights; that file is not edited): what the layers that read another
layer's K/V *compute* from it, planted in the program and held against the
cell's own check —

    python3 -m benchmark.xdec_controls --config <name> [--seed 1]
                                       [--prompt-tokens 3800]

(``controls.py``'s options and its line a control.)

``served``        the engine as the cell builds it: ``ok``.
``short_walk``    every ``cross`` layer's walk of the writer's pool rows
                  stops half way: a row's one query reads the first half
                  of its context. Not ``ok``, or how far those layers read
                  is outside ``correct``.
``run_depth``     every ``cross`` layer takes ``λ_init`` at its depth
                  within its own run of layers, not within the model
                  (layer 19 reads ``λ_init(1)``). Not ``ok``, or the
                  differential form's depth term is outside ``correct``.

Each is ``controls.measure`` — ``serve_runner.check_logits`` over the
block's replay, at the file's tolerances — on one seeded prompt. The
served engine is let go before a faulted one is built on the same weights
(two engines' pools do not fit beside the check's reference); a faulted
engine compiles the programs its replay runs and no others
(``compile_ahead`` 0). One JSON line a control, ``as_expected`` in each;
exit code 0 when every one is. It runs wherever JAX runs; a disagreement
is no device metric, say where it was read.
"""

from __future__ import annotations

import contextlib
import gc
import sys

from . import controls
from . import manifest as mf
from . import serve_runner as sr

KIND = "cross"


@contextlib.contextmanager
def planted(view_of):
    """The program's ``cross`` kind building its serving layers over
    ``view_of(fwd)`` in place of ``fwd``, for as long as the context
    lasts (the registry's entry is frozen: the fault goes round that)."""
    from deepspeed_tpu.models.mixers import KINDS

    mixer, paged = KINDS[KIND], KINDS[KIND].paged
    object.__setattr__(mixer, "paged",
                       lambda cfg, fwd: paged(cfg, view_of(fwd)))
    try:
        yield
    finally:
        object.__setattr__(mixer, "paged", paged)


def short_walk(fwd):
    return fwd._replace(start_pos=fwd.start_pos // 2)


def run_depth(fwd):
    return fwd._replace(depth_of=lambda kind, i: 2 * i + 1)


FAULTS = {"short_walk": short_walk, "run_depth": run_depth}


def run(info: dict, seed: int, prompt):
    """(control, expected ok, ``check_logits`` record) for each control."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    lazy = dict(info, config=dict(info["config"], engine=dict(
        info["config"]["engine"], compile_ahead=0)))
    _, params, engine = sr.build(lazy, seed)
    yield "served", True, controls.measure(info, "served", engine, params,
                                           prompt)
    model, sizing = engine.model, engine.config
    for name in FAULTS:
        del engine
        gc.collect()
        with planted(FAULTS[name]):
            engine = InferenceEngineV2(model, params=params, config=sizing)
            yield name, False, controls.measure(info, name, engine, params,
                                                prompt)


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
