"""The mixer kinds of a hybrid block (``TransformerConfig.layer_pattern``,
models/hybrid.py), one module a family: its sizes' checks, its weights,
its cache leaves, its reference and its serving forward, its counters —
stated as a ``base.Mixer``. ``KINDS`` is the registry, and the whole of
it: what reads a model's kinds (models/hybrid.py, models/transformer.py,
inference/v2/paged_model.py and engine_v2.py, serving/) folds over it and
names none. A new kind is a module here, a line below and its
``TransformerConfig`` fields (docs/SERVING.md "Adding a mixer kind")."""

from __future__ import annotations

from types import MappingProxyType
from typing import Dict, Mapping, Tuple

from . import (attention, block_sparse, cross, gdn, gmu, latent, lightning,
               mamba1, mamba2)
from .base import Fwd, Mixer  # noqa: F401

#: read-only: a kind is added here, in the source, and nowhere else
KINDS: Mapping[str, Mixer] = MappingProxyType({
    "full": attention.FULL,
    "linear": gdn.LINEAR,
    "window": attention.WINDOW,
    "latent": latent.LATENT,
    "latent_sparse": latent.LATENT_SPARSE,
    "latent_window": latent.LATENT_WINDOW,
    "lightning": lightning.LIGHTNING,
    "block_sparse": block_sparse.BLOCK_SPARSE,
    "mamba2": mamba2.MAMBA2,
    "mamba1": mamba1.MAMBA1,
    "gmu": gmu.GMU,
    "cross": cross.CROSS,
})


def kinds_of(cfg) -> Tuple[str, ...]:
    """The kinds a model has layers of, in the registry's order (none
    for a model that is not a hybrid block; a position of the pattern
    that is None is an FFN alone and has none)."""
    if cfg.layer_pattern is None:
        return ()
    have = set(cfg.layer_pattern + cfg.lead_layers)
    return tuple(kind for kind in KINDS if kind in have)


def own_rope_bases(cfg) -> Dict[float, int]:
    """The rope bases beside the model's own that its kinds rotate at
    (``Mixer.rope_base``): theta -> the rotated width."""
    bases = {}
    for kind in kinds_of(cfg):
        if KINDS[kind].rope_base is not None:
            width, theta = KINDS[kind].rope_base(cfg)
            if theta != cfg.rope_theta:
                bases.setdefault(theta, width)
    return bases


#: every name a kind adds to an engine's ``put_totals``: what a fleet's
#: registry declares and a replica publishes (serving/)
PUT_TOTALS: Tuple[str, ...] = tuple(dict.fromkeys(
    name for mixer in KINDS.values() for name in mixer.totals))
