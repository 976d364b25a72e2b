"""JAX persistent compilation cache, placed from outside or at one fixed path.

The fleet chunk program at M=10008 takes tens of seconds to compile; across
bench runs, smoke runs and test sessions the program is byte-identical, so
the XLA compilation cache turns every run after the first into a disk read.
`enable_persistent_cache()` picks the directory by one rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other.  JAX
  reads the variable itself; this module only creates the directory and
  sets no path in code.
* unset: ``.jax_cache/`` at the root of the checkout (gitignored).  The
  path is fixed because it is part of the cache's key — a temporary or
  per-process directory would never hit.

`chip_smoke.py`, ``benchmarks/run.py`` and the examples call it at start-up.
The test suite calls it only when ``JAX_COMPILATION_CACHE_DIR`` is set
(tests/conftest.py), so a plain test run writes nothing into the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Idempotent — safe to call from several entry points.
    """
    import jax

    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    Path(cache_dir).mkdir(parents=True, exist_ok=True)
    # cache everything, including sub-second compiles: the many small jit
    # programs of a study add up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
