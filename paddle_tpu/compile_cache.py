"""Where JAX's persistent compilation cache lives.

The cache directory is part of every entry's key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (jax reads the variable itself — nothing is set in code), else one
fixed git-ignored directory at the root of the checkout.  Entry points
(``paddle`` CLI, perf/run.py, chip_smoke.py, the tests' conftest) call
``configure()`` once, before first backend use.
"""

from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache")


def configure() -> str:
    """Point jax at the cache directory and return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
