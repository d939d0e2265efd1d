"""Placement of JAX's persistent compilation cache.

Every process that reaches the chip starts with no compiled code, and
the 128^3 and forest step programs take most of a cold run to compile.
The entry points (``python -m cup3d_tpu``, ``bench.py``,
``chip_smoke.py``) call :func:`enable` once, before their first jit:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, so
  nothing is set in code and the operator's directory is the cache;
- unset: the cache lives at ``<checkout>/.jax_cache`` — a fixed path
  derived from the package location.  The directory is part of the
  cache key, so a path built from ``tempfile``, a pid or the clock
  would never hit.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` (gitignored); cup3d_tpu/utils/ is two levels
#: below the checkout root
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str:
    """Turn the persistent cache on and return the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def entries(directory: str) -> set:
    """Keys of the executables in ``directory`` (JAX writes one
    ``<key>-cache`` file per entry, next to an access-time sidecar);
    empty when it does not exist yet."""
    try:
        return {name[:-len("-cache")] for name in os.listdir(directory)
                if name.endswith("-cache")}
    except FileNotFoundError:
        return set()
