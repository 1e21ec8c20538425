"""Persistent XLA compile cache, placed from outside.

Every process that compiles for the chip — the runtime's workers leased
TPU chips — calls ``configure()`` once before it compiles. The directory is decided by the environment, not by
code:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable itself and
  caches there; this module sets no other path.
- unset: ONE fixed directory inside the checkout (git-ignored). The
  directory is part of the cache key, so it is never derived from a
  tempfile, a pid or the time — a path that moves never hits.

The variable is exported either way, so every child process (the
runtime's ``child_env`` copies the environment) compiles into the same
cache. Importing this module does not import jax.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache — where the cache goes when nobody placed it
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Make this process (and its children) use the persistent compile
    cache; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.environ[ENV_VAR] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the (then unset) variable when it was imported
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
