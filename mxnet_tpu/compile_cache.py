"""One place that decides where JAX's persistent compilation cache lives.

The entry points that run on the chip (``chip_smoke.py``, ``bench.py``,
``tools/bench_serving.py``) call :func:`enable` before their first
compile. The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; this module
  sets no other directory in code.
* not set: the cache goes to ONE fixed path inside the checkout,
  ``<repo>/.cache/jax`` (``.cache/`` is git-ignored). The directory is
  part of the cache key, so it is never derived from ``tempfile``, a pid
  or the time — a directory that moves never hits.

The library itself and the tests never turn the cache on.
"""
from __future__ import annotations

import os

__all__ = ["cache_dir", "enable"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_dir():
    """The directory the persistent cache uses: ``$JAX_COMPILATION_
    CACHE_DIR`` when set, else ``<repo>/.cache/jax``."""
    return os.environ.get(_ENV) or os.path.join(_REPO, ".cache", "jax")


def enable():
    """Turn the persistent compilation cache on at :func:`cache_dir`
    and return that path. Every program is cached, however quick its
    compile: a cold process on the chip pays for hundreds of small
    ones."""
    import jax

    path = cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
