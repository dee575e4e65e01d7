"""``"gmu"``: the gated memory unit of a decoder-hybrid-decoder (SambaY;
Ren et al. 2025) — ``out = (silu(u W_in) ⊙ m) W_out``, where ``m`` is the
**memory** an earlier state-space layer handed on: the same token's output
of that layer's recurrence, taken before its gate (``mamba1.py``;
``Fwd.carry["memory"]``). The layer mixes no tokens of its own: it keeps
no cache and no state, in serving as without one, and reads nothing but
its input and the forward's carry.

Scope (docs/OBSERVABILITY.md): ``gmu``."""

from __future__ import annotations

import jax

from ...parallel.sharding import spec
from ..transformer import _linear
from .base import Mixer

KIND = "gmu"


def check(cfg):
    if cfg.mamba1_inner_size <= 0:
        raise ValueError("\"gmu\" layers gate a memory mamba1_inner_size "
                         "wide: set it (> 0)")


def init(cfg, w, gain):
    h, ch = cfg.hidden_size, cfg.mamba1_inner_size
    return dict(gmu_w_in=w((h, ch)), gmu_w_out=w((ch, h), w.out_std))


def specs(cfg):
    return dict(gmu_w_in=spec("layers", "embed", None),
                gmu_w_out=spec("layers", None, "embed"))


def build(cfg, fwd):
    """The layer over the forward's carry: one function for both paths
    (there is no cache to tell them apart)."""
    dt = cfg.dtype

    def mixer(h1, lp, _):
        memory = fwd.carry["memory"]
        gate = jax.nn.silu(_linear(h1, lp["gmu_w_in"], None, dt))
        return _linear(gate * memory.astype(dt), lp["gmu_w_out"], None, dt)
    return mixer


GMU = Mixer(init=init, specs=specs, reference=build, paged=build,
            scope=KIND, check=check, takes=("memory",))
