"""Where the program keeps what it generates between runs.

Everything lands in the checkout's git-ignored ``.cache`` directory
unless the environment places it elsewhere, so no run reads or writes
around its checkout and nothing is derived from a temp name, a pid or
the time:

* compiled executables — JAX's persistent compilation cache
  (``$JAX_COMPILATION_CACHE_DIR``, else ``.cache/jax``), switched on by
  :func:`use_compile_cache` from each entry point (`chip_smoke.py`,
  ``benchmarks/run.py``, ``launch/serve_cpd.py``);
* measured plans — the autotuner's plan store
  (``$REPRO_PLAN_CACHE``, else ``.cache/plans.json``, `core.autotune`).
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = CHECKOUT / ".cache"
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    With ``$JAX_COMPILATION_CACHE_DIR`` set JAX reads the variable itself
    and nothing is set here; otherwise the cache is the fixed in-checkout
    path ``.cache/jax``.
    """
    env = os.environ.get(COMPILE_CACHE_ENV)
    if env:
        return env
    import jax
    path = str(CACHE_DIR / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
