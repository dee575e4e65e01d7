"""The one place this codebase names JAX's SPMD primitives.

Every module imports ``shard_map`` (and ``axis_size`` / ``pcast``) from
here instead of from ``jax`` directly — ``tests/test_marker_audit.py``
enforces that — so a future JAX move of these names is a one-file change.
They are plain re-exports of the installed JAX's (0.9.0) own.
"""

from jax import shard_map                                     # noqa: F401
from jax.lax import axis_size, pcast                          # noqa: F401
