"""Where the persistent XLA compilation cache lives — one definition.

Every entry point that compiles something worth keeping
(``chip_smoke.py``, ``bench.py``, ``python -m dist_keras_tpu.serving.bench``,
the examples) calls :func:`enable` before its first compile, so a second
process on the same machine skips the compiles the first one paid for.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable():
    """Turn on jax's persistent compilation cache -> its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set: jax reads that variable
    itself, so no path is set in code and the cache lands exactly where
    the caller's environment placed it.  Otherwise the cache is
    ``<checkout>/.jax_cache`` — a fixed path, never derived from a
    temporary name, a pid or the time, so the next process finds what
    this one wrote.  What gets kept is jax's own policy (programs that
    took at least a second to compile).
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
