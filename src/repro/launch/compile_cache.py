"""Where the entry points keep JAX's persistent compilation cache.

``serve.main`` and ``chip_smoke.py`` call :func:`enable_compile_cache`
before their first compile; importing a module never sets it.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable itself and
nothing is set here.  Otherwise the cache lives in a fixed, git-ignored
directory of the checkout: the path is part of the cache key, so a
directory that never moves keeps hitting from one run to the next.  On
the CPU backend nothing is set: its compiles are short, and a cached CPU
executable carries code for the host that compiled it.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

#: the checkout's own cache directory (listed in .gitignore)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache(platform: Optional[str] = None) -> Optional[str]:
    """Turn the persistent compilation cache on for ``platform`` (default:
    ``jax.default_backend()``); returns its directory, or None on CPU
    where the environment does not name one."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if (platform or jax.default_backend()) == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
