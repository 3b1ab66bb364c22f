"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module leaves it alone.  Otherwise the cache goes to ``<repo>/.jax_cache``:
the path is part of what a later run must find again, so it is fixed —
never built from a temporary name, a pid or the time.  Tests do not call
this (a compile for a described chip cannot be read back).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> Optional[Path]:
    """The directory to set, or None where the environment already names
    one."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return REPO_CACHE_DIR


def enable_compile_cache() -> Path:
    """Turn the persistent cache on; returns the directory in use."""
    path = compile_cache_dir()
    if path is None:
        path = Path(os.environ["JAX_COMPILATION_CACHE_DIR"])
    else:
        jax.config.update("jax_compilation_cache_dir", str(path))
    # Cache every executable, Pallas kernels included: the serving path
    # compiles dozens of small kernels that each take under a second.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
