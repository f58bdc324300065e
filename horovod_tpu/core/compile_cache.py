"""Where JAX's persistent compilation cache lives.

A cold ResNet-50 train step costs tens of seconds of XLA compile, and a
run on a freshly provisioned machine pays it on every start unless the
cache sits where the machine's owner can keep it.  So the directory is
placed from outside: ``JAX_COMPILATION_CACHE_DIR``, which JAX reads by
itself.  Only when that is unset does this module pick one — a fixed
path next to the package (``<checkout>/.jax_cache``), because the path
is part of nothing but has to be the same on every start for the cache
to hit; never a temp dir, a pid or a timestamp.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The directory in effect: the environment's if set, else the
    checkout-relative default.  Touches neither JAX nor the disk (the
    launcher calls it to hand workers the same directory)."""
    return os.environ.get(_ENV) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first
    compile; returns the directory.  With ``JAX_COMPILATION_CACHE_DIR``
    set this changes nothing — JAX already reads it."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
