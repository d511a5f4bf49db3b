"""Where jax's persistent compilation cache lives.

The directory is part of the cache key's lookup path, so it must not
move between runs: never a tempdir, a pid or a timestamp. Whoever runs
the program may place the cache from outside with
``JAX_COMPILATION_CACHE_DIR`` (jax reads that variable itself); when
they do not, it sits at one fixed path inside the checkout.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Make this process and the workers it spawns share one persistent
    compile cache; returns its directory. Call before the first compile.

    With ``JAX_COMPILATION_CACHE_DIR`` set this does nothing: jax read
    the variable at import and no other directory is set in code.
    Otherwise the cache goes to ``<checkout>/.jax_cache``, exported
    through the same variable so spawned workers (hvdrun slots overlay
    ``os.environ``) land in the same place.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
