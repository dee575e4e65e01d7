"""Shared Pallas imports and platform probe for the kernel modules.

Each kernel module (flash_attention, paged_attention, quantizer) keeps its
own ``_FORCE_INTERPRET`` test hook (tests monkeypatch per module); the
platform probe lives here so a detection fix lands once. Pallas ships with
the installed JAX — a failed import is an error, not "no kernels".
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl                    # noqa: F401
from jax.experimental.pallas import tpu as pltpu             # noqa: F401


@functools.cache
def on_tpu() -> bool:
    """Is the default backend a TPU? Decided once; a backend that fails to
    initialize raises here instead of reading as "not on TPU" (which would
    flip every kernel to interpret mode or its XLA formulation)."""
    return jax.default_backend() == "tpu"
