"""Where jax's persistent compilation cache lives — the one definition.

The contract (tests/test_aux_subsystems.py pins it in a subprocess):

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment: jax itself keeps
  its cache there (it reads the variable at import) and this module
  sets nothing — not through ``jax.config`` either.  That is how a
  caller outside the program places the cache, e.g. on a disk that
  survives the machine.
* not set: the cache goes to ``<checkout>/.jax_cache`` (gitignored).
  The path holds no hostname, CPU hash, pid, time or temp name — the
  directory is part of how a later process finds the entries, so a
  directory that moves never hits.  The variable is also exported so
  child processes that import jax fresh land in the same place.

Call :func:`configure` before the first compile of the process: jax
decides once, at its first compile, whether the cache is in use.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["configure", "CHECKOUT_CACHE_DIR"]

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the default location: ``.jax_cache`` beside the ``paddle_tpu`` package
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure() -> Optional[str]:
    """Apply the contract above; returns the directory in use (None
    when the environment disabled the cache with an empty value)."""
    if _ENV in os.environ:
        return os.environ[_ENV] or None
    import jax

    os.environ[_ENV] = CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
