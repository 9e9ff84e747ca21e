"""JAX's persistent compilation cache for the entry points.

Called from the ``main()`` of each entry point (never at import), so
library users and tests keep whatever cache setting they chose.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the repository checkout (src/repro/launch/ -> three levels up)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing else is set here.  Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, because a run only finds
    entries that an earlier run wrote to the same directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
