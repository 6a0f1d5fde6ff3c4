"""The one place a process decides where XLA's persistent compile cache lives.

jax hashes the cache directory *string* into every cache key (measured on
jax 0.9.0: ``/x/cA`` and ``/x/cB/../cA`` give different keys for the same
program), so a directory that is respelled — or that moves with a pid, a
timestamp or a temp dir — never hits. Hence the rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax has already read it; set nothing.
- a directory already configured in this process (``tests/conftest.py``
  does that for the suite): leave it, so an entry point called in-process
  cannot respell the directory under a running caller.
- otherwise: ``<checkout>/.jax_cache``, one absolute spelling derived from
  this file's location.

The floor below which jax does not bother to cache a program follows the
same courtesy: jax's default of one second is lowered to zero, a floor the
process already chose (the suite keeps 0.5 s for XLA:CPU) is left alone. On
the TPU a model's eager init compiles dozens of programs of about half a
second each; under a 0.5 s floor they straddled it, so every warm run
compiled most of them again and wrote a few more (chip runs, PR 21).

Every entry point (``fedml_tpu/exp/*``, ``chip_smoke.py``,
``tools/shard_smoke.py``) calls :func:`configure_compile_cache` once before
its first compile.
"""

from __future__ import annotations

import os
from collections.abc import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — this file is <checkout>/fedml_tpu/core/compile_cache.py
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

JAX_DEFAULT_FLOOR_SECS = 1.0  # jax_persistent_cache_min_compile_time_secs


def cache_dir_to_set(env: Mapping[str, str], configured: str | None) -> str | None:
    """The directory to hand to jax, or ``None`` to leave jax's own setting
    alone (``env`` is the process environment, ``configured`` the current
    ``jax_compilation_cache_dir``)."""
    if env.get(ENV_VAR) or configured:
        return None
    return DEFAULT_CACHE_DIR


def floor_to_set(configured: float) -> float | None:
    """The compile-time floor to hand to jax, or ``None`` when the process
    already moved it off jax's default."""
    return 0.0 if configured == JAX_DEFAULT_FLOOR_SECS else None


def configure_compile_cache() -> str:
    """Apply the rules above; returns the directory in effect."""
    import jax

    target = cache_dir_to_set(os.environ, jax.config.jax_compilation_cache_dir)
    if target is not None:
        jax.config.update("jax_compilation_cache_dir", target)
    floor = floor_to_set(jax.config.jax_persistent_cache_min_compile_time_secs)
    if floor is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
    return jax.config.jax_compilation_cache_dir
