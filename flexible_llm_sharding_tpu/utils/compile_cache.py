"""Where JAX's persistent compilation cache lives.

One rule, applied by every entry point (``cli.main``, ``cli.serve_main``,
``chip_smoke.py``, ``benchmark/run.py``) before first JAX use: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no directory is
set in code; otherwise the cache sits at ONE fixed path inside the checkout.
The path is part of the cache key, so it is never a temp name, a pid or a
time — a directory that moves never hits.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (listed in .gitignore).
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """The directory this process's persistent cache uses."""
    return os.environ.get(ENV_VAR) or REPO_CACHE_DIR


def configure_compile_cache() -> str:
    """Place the persistent cache (idempotent); returns its directory."""
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return compile_cache_dir()


def compile_cache_entries() -> int:
    """Number of files in the cache directory (0 when it does not exist)."""
    try:
        return len(os.listdir(compile_cache_dir()))
    except FileNotFoundError:
        return 0
