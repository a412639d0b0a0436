"""Where the persistent XLA compile cache lives.

A cold 1.3B train step is minutes of XLA + Mosaic compilation; a fresh
process on the same machine should read it back from disk. The cache key
covers the directory, so the directory must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX honours it by itself, and
  no directory is set here: whoever runs the program chose the place.
* unset, TPU backend — ``<checkout>/.jax_cache`` (git-ignored): one fixed
  path derived from this file's location, never a temp dir, pid or
  timestamp.
* unset, any other backend — no cache. The CPU backend is the test
  surface, and tier-1 asserts on XLA's compile-time diagnostics (the GSPMD
  involuntary-full-rematerialization warning), which a cache hit skips.

Wherever a cache is on, its key includes the program's metadata: what the
cache hands back then describes the source that is running.
"""

import os
from typing import Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — same answer from any cwd or process."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def ensure_compile_cache() -> Optional[str]:
    """Point JAX at a persistent compile cache before the first big
    compile; returns the directory in use, or None when no cache is on.
    Idempotent; called by ``initialize``, ``init_inference``, ``bench.py``
    and ``chip_smoke.py``."""
    env_dir = os.environ.get(CACHE_DIR_ENV)
    import jax

    if not env_dir and jax.default_backend() != "tpu":
        return None
    # JAX leaves metadata out of the cache's key by default, so an entry
    # compiled from other source lines or scope names would be handed back
    # with THEIR op_names, and program_scopes() (telemetry/scopes.py) reads
    # the scopes of the running program from the executable's own text
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if env_dir:
        return env_dir
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    return jax.config.jax_compilation_cache_dir
