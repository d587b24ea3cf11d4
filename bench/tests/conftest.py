"""CPU tests of the benchmark harness (``python -m pytest bench/tests``).

They never touch a chip: JAX is held to the CPU, the harness's look for a
TPU is replaced where a test drives a run, and the cells are the tiny copies
of ``tiny.py``.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    import jax
    from bench.tests import tiny
    # one persistent cache for the session: each point re-traces, as on the
    # chip, and loads what an earlier point compiled
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


def run_cell(root, cell, seed=4_000_000_123, seconds=0.0, trace=0):
    """One run of ``bench/run.py``'s main on the CPU; returns its result."""
    import time
    from bench import run
    from bench.tests import tiny
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace)], root=root,
                    devices_fn=tiny.cpu_devices, t_start=time.monotonic())
