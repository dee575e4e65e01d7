"""Where compiled programs are kept between processes.

A cold TPU process compiles a 24-layer model once per bucket shape; JAX's
persistent compilation cache turns the second process's compiles into file
reads. The cache's directory is part of its key, so it must not move:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing is done
  here and no other directory is set in code (the operator placed the
  cache, e.g. on a disk that outlives the machine);
- unset: ``<checkout>/.jax_cache`` — a fixed path under the repo, listed in
  ``.gitignore``; never ``tempfile``, a pid or a time.

Call before the first compile (``chip_smoke.py`` and
``scripts/serve_replica.py`` do).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
