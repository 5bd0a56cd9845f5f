"""Where the entry points keep JAX's persistent compilation cache.

Called from ``main()`` of each entry point (``chip_smoke.py``,
``benchmarks/run.py``, ``repro.launch.*``), never at import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-checkout directory (ignored by git); a cache whose path moves
#: between runs never hits
CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads and writes
    there and nothing else is set; otherwise the cache goes to
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
