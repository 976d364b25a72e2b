"""Test-suite bootstrap.

Two jobs:

1. Opt-in persistent XLA compilation cache (`JAX_COMPILATION_CACHE_DIR=...`):
   the suite jit-compiles hundreds of small programs plus a handful of
   expensive fleet-scale ones; on a warm cache a full run saves minutes
   of single-core compile time.  Unset, no cache is kept, so a test run
   writes nothing into the checkout.

2. When the real `hypothesis` package is unavailable (minimal containers
   where nothing can be pip-installed), install a tiny deterministic
   stand-in so the suite still collects and the property tests still run —
   each `@given` test executes a fixed number of seeded pseudo-random
   examples instead of hypothesis's managed search.  The stub covers
   exactly the strategy surface this repo uses (`integers`, `floats`,
   `lists`, `sampled_from`); with hypothesis installed (see
   pyproject.toml) it is never touched.
"""

from __future__ import annotations

import functools
import inspect
import os
import random
import sys
import types
import zlib

from repro.utils.cache import ENV_VAR, enable_persistent_cache

if os.environ.get(ENV_VAR):
    enable_persistent_cache()

try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:

    class _Strategy:
        def __init__(self, sample):
            self.sample = sample  # sample(rng) -> value

    def integers(min_value, max_value):
        return _Strategy(lambda r: r.randint(min_value, max_value))

    def floats(min_value, max_value):
        return _Strategy(lambda r: r.uniform(min_value, max_value))

    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda r: r.choice(elements))

    def lists(elements, min_size=0, max_size=10, unique=False):
        def sample(r):
            n = r.randint(min_size, max_size)
            out, seen, tries = [], set(), 0
            while len(out) < n and tries < 10_000:
                tries += 1
                v = elements.sample(r)
                if unique:
                    if v in seen:
                        continue
                    seen.add(v)
                out.append(v)
            return out
        return _Strategy(sample)

    # Cap examples: the stub has no shrinking/database, so keep the fallback
    # suite fast; the declared max_examples applies under real hypothesis.
    _STUB_CAP = 20

    def given(*strategies, **kw_strategies):
        def deco(fn):
            n = min(getattr(fn, "_stub_max_examples", 10), _STUB_CAP)

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                # crc32, not hash(): str hashing is salted per process and
                # would break run-to-run reproducibility of the examples.
                base = zlib.crc32(fn.__qualname__.encode())
                for i in range(n):
                    rng = random.Random((base ^ (i * 0x9E3779B9))
                                        & 0xFFFFFFFF)
                    drawn = [s.sample(rng) for s in strategies]
                    kw = {k: s.sample(rng) for k, s in kw_strategies.items()}
                    fn(*args, *drawn, **kwargs, **kw)

            # Hide the wrapped signature from pytest, which would otherwise
            # resolve the strategy-filled parameters as fixtures.
            del wrapper.__wrapped__
            wrapper.__signature__ = inspect.Signature()
            return wrapper
        return deco

    def settings(max_examples=None, deadline=None, **_ignored):
        def deco(fn):
            if max_examples is not None:
                fn._stub_max_examples = max_examples
            return fn
        return deco

    st_mod = types.ModuleType("hypothesis.strategies")
    st_mod.integers = integers
    st_mod.floats = floats
    st_mod.lists = lists
    st_mod.sampled_from = sampled_from

    hyp_mod = types.ModuleType("hypothesis")
    hyp_mod.given = given
    hyp_mod.settings = settings
    hyp_mod.strategies = st_mod
    hyp_mod.__stub__ = True

    sys.modules["hypothesis"] = hyp_mod
    sys.modules["hypothesis.strategies"] = st_mod
