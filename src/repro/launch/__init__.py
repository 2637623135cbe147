"""Launchers: mesh construction, multi-pod dry-run, roofline, train, serve.

NOTE: never import .dryrun from tests — it force-sets the XLA device count.
"""

import os
from pathlib import Path

import jax

#: root of the checkout this package runs from (``src/repro/launch`` up 3)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache(checkout: str | os.PathLike = CHECKOUT) -> str:
    """Keep JAX's persistent compilation cache across runs of an entry
    point.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and no directory is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache`` — one fixed path, because the path is part
    of what a cache hit needs.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.fspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
