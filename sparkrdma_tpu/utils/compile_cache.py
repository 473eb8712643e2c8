"""Where JAX's persistent compilation cache lives — decided in one place.

A wide-record TeraSort program compiles for minutes, so every entry point
that compiles at bench scale (``chip_smoke.py``, ``bench.py``, the
``scripts/`` studies) turns the cache on through here. The directory is
part of what makes a later process hit, so it never moves: the
deployment's ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads the
variable itself, and no code here names another directory), else the
fixed ``<repo>/.jax_cache`` (gitignored).
"""

import os

import jax

#: the fixed fallback: ``<repo>/.jax_cache``, beside the package
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile. JAX's own thresholds (cache programs
    that took >= 1 s to compile, of any size) stay as they are."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]
