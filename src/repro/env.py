"""Process-environment setup shared by the entry points.

JAX's persistent compilation cache lets a process reuse the executables an
earlier process compiled. Its location is part of every entry's key, so it
must be a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself, so nothing else is set), else
``<checkout>/.jax_cache``. Entry points (``chip_smoke.py``, the examples,
``benchmarks/run.py``) call :func:`enable_compile_cache`; tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Tuple

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/env.py -> <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir(env: Mapping[str, str] = os.environ
                      ) -> Tuple[Path, bool]:
    """The cache directory and whether the environment chose it."""
    if env.get(ENV_VAR):
        return Path(env[ENV_VAR]), True
    return DEFAULT_DIR, False


def enable_compile_cache(env: Mapping[str, str] = os.environ) -> Path:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`.
    Returns the directory in use."""
    path, from_env = compile_cache_dir(env)
    if not from_env:
        import jax
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
