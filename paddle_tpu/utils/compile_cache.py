"""Where compiled programs are kept between processes.

A chip call starts with nothing compiled, and the 12-layer train step
and the two engine programs take minutes to compile.  JAX's persistent
compilation cache keeps them on disk; its directory is part of the
cache key, so it has to be the same path in every process.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory.  Call it from a program's ``main()`` before anything is
    compiled, never at import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    no directory is set in code; otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, never a
    temporary, per-process or dated one.

    JAX keeps only programs that took a second or more to compile, and
    that is left so: keeping the hundreds of per-op programs of a
    ``jit.to_static`` function's first call too was measured on the
    chip (PR 24) — a warm ``chip_smoke.py`` fell from 141 s of compile
    to 14 s, but a cold one rose from 270 s to 570 s (writing an entry
    costs more than compiling a small program) and one run left over a
    thousand files in the cache."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
