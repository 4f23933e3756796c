"""Where JAX keeps its persistent compilation cache.

The cache directory is part of what makes an entry findable again, so it
never moves: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(and then no other path is set here), otherwise the fixed in-checkout
directory ``.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; call it
    before the first compile.  Returns the directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
