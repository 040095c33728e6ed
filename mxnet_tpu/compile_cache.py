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

Whoever turns it on, the cache's key holds the programs' metadata
(:func:`names_in_key`): the names ``jax.named_scope`` gives the operations
(ops/fusion.py, parallel/trainer.py, parallel/decode.py) are metadata, and
by default JAX leaves metadata out of the key, so a cache warmed by a tree
with other names (or none) hands back ITS executable and a profiler then
shows that tree's names, silently. The price: metadata also carries source
lines, so on a cache shared between trees a program whose lines moved
compiles again.
"""
from __future__ import annotations

import os

__all__ = ["cache_dir", "enable", "names_in_key"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_dir():
    """The directory the persistent cache uses: ``$JAX_COMPILATION_
    CACHE_DIR`` when set, else ``<repo>/.cache/jax``."""
    return os.environ.get(_ENV) or os.path.join(_REPO, ".cache", "jax")


def names_in_key():
    """Key the persistent cache by the programs' metadata too, so a
    trace always shows the running tree's scope names. :func:`enable`
    calls it; ``import mxnet_tpu`` does as well, because ``benchmark/
    harness.py`` turns the cache on by itself and not through
    :func:`enable` (once it does, the call at import can go). Without a
    persistent cache it changes nothing."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def enable():
    """Turn the persistent compilation cache on at :func:`cache_dir`
    and return that path. Every program is cached, however quick its
    compile: a cold process on the chip pays for hundreds of small
    ones."""
    import jax

    names_in_key()
    path = cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
