"""Where JAX keeps its persistent compilation cache.

Entry points that compile a full model (`launch.serve`, `launch.train`,
`benchmarks/serve_throughput.py`, `chip_smoke.py`) call
`enable_compile_cache()` once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, git-ignored, inside the checkout: a cache directory whose path
# changes from run to run (a temp name, a pid, the time) never hits
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory.

    With JAX_COMPILATION_CACHE_DIR set, JAX already reads that directory
    (the variable is the default of its `jax_compilation_cache_dir` option)
    and nothing else is set here. Otherwise the cache goes to
    `<checkout>/.jax_cache`.
    """
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
