"""Launchers: training, serving, and the multi-pod compile dry-run.

``repro.launch.dryrun`` is import-order sensitive (it must set XLA flags
before jax initializes) and is therefore not imported here.
"""

import os
from pathlib import Path

#: the source checkout's root (``src/repro/launch`` → three levels up)
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile
    and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins: nothing else is set.  Otherwise the cache lives at the fixed
    ``<repo root>/.jax_cache`` — the directory is part of each entry's
    key, so it must not move between runs.  Entry points call this; an
    import of ``repro`` never does.
    """
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
