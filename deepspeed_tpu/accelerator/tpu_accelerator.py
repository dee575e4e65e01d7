"""TPU accelerator backed by the JAX TPU runtime.

Counterpart of the reference's ``accelerator/cuda_accelerator.py``: memory
stats come from PJRT ``device.memory_stats()``, devices from
``jax.devices()``; communication is ICI/DCN via XLA collectives rather than
NCCL, so ``communication_backend_name`` reports ``"xla"``.
"""

from __future__ import annotations

from typing import Any, Sequence

from .abstract_accelerator import Accelerator


class TpuAccelerator(Accelerator):
    _name = "tpu"
    _communication_backend_name = "xla"

    def devices(self) -> Sequence[Any]:
        import jax

        return jax.devices()

    def local_devices(self) -> Sequence[Any]:
        import jax

        return jax.local_devices()

    def current_platform(self) -> str:
        return "tpu"

    def is_available(self) -> bool:
        """A backend that fails to initialize raises; it does not read as
        "no TPU" (and so as a quiet fall to the CPU accelerator)."""
        import jax

        return jax.default_backend() == "tpu"

    def memory_stats(self, index: int = 0) -> dict:
        # PJRT reports None where a backend keeps no stats (the CPU's)
        return dict(self.local_devices()[index].memory_stats() or {})

    def supported_dtypes(self) -> list:
        import jax.numpy as jnp

        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.int32,
                jnp.float8_e4m3fn, jnp.float8_e5m2]
