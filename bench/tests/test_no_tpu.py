"""Off the chip the benchmark fails and prints no result line."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "t0t1_fig2.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    return p.returncode != 0 and not any(
        line.lstrip().startswith("{") for line in p.stdout.splitlines())


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert _no_result(p), p.stdout
    assert "not 'tpu'" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert _no_result(p), p.stdout
    assert "no repro package" in p.stderr
