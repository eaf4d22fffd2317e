"""JAX's persistent compilation cache for the repository's entry points.

The scheduler compiles one search program per shape bucket and the ICU
LSTMs one forward per workload, each taking seconds on a TPU. The entry
points (`repro.launch.serve`, `chip_smoke.py`, the benchmark scripts)
call `enable_compilation_cache()` first, so every process started from
one checkout reuses what an earlier one compiled. Tests never call it.

The directory is the one `JAX_COMPILATION_CACHE_DIR` names when that is
set (JAX reads the variable itself), and otherwise `.jax_cache/` at the
root of the checkout, found from this file's own path rather than the
working directory. The path is part of each entry's key, so it never
varies with the process, the time or a temporary name.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/compile_cache.py -> the checkout root
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def compilation_cache_dir() -> Path:
    """The cache directory the entry points use."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else CHECKOUT_ROOT / ".jax_cache"


def enable_compilation_cache() -> Path:
    """Turn the persistent cache on for this process and return its
    directory. Where the environment names one, JAX already uses it and
    nothing is set here."""
    path = compilation_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
