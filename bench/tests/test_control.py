"""The control (the reference with one bit of clock resolution dropped)
reads above the limits in every cell, at the tiny sizes."""
import pytest

from bench import harness, run
from bench.tests.conftest import ROOT
import json
import os

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell):
    from bench.control import control_readings
    c = harness.load_cell(tiny_root, cell)
    got = control_readings(c, 31)
    assert any(v > run.LIMITS[k] for k, v in got.items()), got
