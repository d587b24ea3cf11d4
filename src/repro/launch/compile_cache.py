"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory itself
and nothing is set here. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout (listed in ``.gitignore``): one fixed path, never built
from a temp name, a pid or the time, so a later run of the same checkout
finds what an earlier one compiled.

Entry points call :func:`enable` before their first compile (``chip_smoke.py``,
``python -m repro.launch.simulate``, ``benchmarks/run.py``); library code and
tests never do.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the cache on and return its directory.

    Without the environment variable the package must be imported from a
    checkout (``PYTHONPATH=src``); an installed copy has no checkout to
    hold the cache, so it raises rather than write into site-packages."""
    path = os.environ.get(ENV)
    if not path:
        if not os.path.isdir(os.path.join(CHECKOUT, "src", "repro")):
            raise RuntimeError(
                f"repro is not imported from a checkout ({CHECKOUT} has no "
                f"src/repro): set {ENV} to place the compile cache")
        import jax
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
