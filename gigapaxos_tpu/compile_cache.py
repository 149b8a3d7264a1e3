"""Where compiled programs are kept between processes.

A plane of 1M groups takes minutes to compile; every entry point that starts
one (``chip_smoke.py``, ``bench.py``, ``server.main``, the cells worker, the
capacity probe's CLI) calls :func:`configure` first so the next process finds
the programs again.  The directory is part of JAX's cache key, so it must be
the same path in every process: never a temporary directory, a pid or a
timestamp.
"""

from __future__ import annotations

import os

#: the checkout that holds this package (``<checkout>/gigapaxos_tpu/``)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure() -> str:
    """Place JAX's persistent compilation cache; returns the directory in
    effect.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here — whoever runs the program placed the cache.
    Otherwise it is ``<checkout>/.jax_cache``, resolved from this package's
    own path, whatever the working directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
