"""Where JAX keeps its persistent compilation cache.

`use_compile_cache()` is called by the entry points that compile served
programs (chip_smoke.py, launch/serve.py, benchmarks/run.py) — never at
import. When JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and no
other directory is set here. Otherwise the cache lives at the fixed path
`.jax_cache/` of this checkout (listed in .gitignore): the directory is part
of the cache key, so a path built from a temp name, a pid or the time would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    import jax

    if os.environ.get(ENV):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT))
    return str(DEFAULT)
