"""JAX's persistent compilation cache, turned on by entry points.

Entry points (`chip_smoke.py`, `launch/lm_serve.py`'s `main`) call
`enable_compile_cache()` before they compile anything; importing this module
changes nothing.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already
reads it and the helper sets no other directory.  Otherwise the cache lives
at the fixed path `<repo>/.jax_cache`: the path is part of what makes a later
run find the entries, so it carries no temp name, pid or time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return REPO_CACHE_DIR


def cache_entries(path: Path) -> int:
    """Number of files in the cache directory (0 if it does not exist yet)."""
    if not path.is_dir():
        return 0
    return sum(1 for p in path.rglob("*") if p.is_file())
