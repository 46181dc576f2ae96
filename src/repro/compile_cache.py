"""Persistent XLA compile cache for the command-line entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise compiled programs go to
``<checkout>/.jax_cache``: a fixed path, so a later run of the same
checkout finds them again.  Only entry points call
:func:`enable_compile_cache`; importing this module changes nothing, so the
tests never turn the cache on.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, os.pardir))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
