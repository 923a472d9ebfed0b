"""Where JAX keeps its persistent compilation cache for this repository.

A full-size engine compiles dozens of TPU programs, and the TPU
compiler takes tens of seconds for each large sort or scatter, so a
cold start pays minutes that a warm one does not. JAX keys its cache on
the directory path as well as the program, so the path must not move
between runs: never derive it from a temporary name, a PID or the time.

Call `enable` once at start-up (entry points do; importing this module
changes nothing).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — listed in .gitignore
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and the choice is left to JAX; otherwise the cache goes to the fixed
    `REPO_CACHE_DIR` inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
