"""The point loop at a tiny size gives what the plain reference gives, and
the plain reference gives what the program's own sequential oracle gives."""
import json
import os

import numpy as np
import pytest

from bench import harness, reference
from bench.tests.conftest import ROOT, run_cell


def test_tiny_t0t1_sweep_is_correct(tiny_root):
    out = run_cell(tiny_root, "t0t1_fig2.sweep")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 2   # one point of two bandwidths
    assert list(out)[-1] == "checks"
    assert {k: v["value"] for k, v in out["checks"].items()} == {
        "world_elems_diff": 0, "counters_diff": 0, "trace_rows_diff": 0}
    assert set(out["metrics"]) == {"events_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"


def test_point_counters_equal_the_program_oracle(tiny_root):
    """The point loop's counters against ``repro.core.run_sequential``."""
    import jax
    from repro.core import run_sequential
    from repro.core import monitoring as mon
    cell = harness.load_cell(tiny_root, "t0t1_fig2.sweep")
    rec = harness.Recorder()
    try:
        pr = harness.run_point(
            harness.plan_point(cell.config, cell.traffic, 5, 1),
            cell.config, cell.traffic, rec, jax.devices("cpu")[:1])
    finally:
        rec.close()
    for params, got in zip(pr.point.runs, pr.results):
        _w, oc, trace = run_sequential(
            *harness.build_scenario(cell.config, params))
        oc = np.asarray(oc)
        assert got["counters"] == {
            k: int(oc[getattr(mon, "C_" + k.upper())])
            for k in got["counters"]}
        assert got["counters"]["events"] == len(trace) > 0
    assert pr.record["events"] == sum(r["counters"]["events"]
                                      for r in pr.results)
    assert pr.record["compile_load_s"] > 0 and pr.record["trace_lower_s"] > 0


def _variant(**change):
    """The study's configuration at 6 flows, with components changed."""
    with open(os.path.join(ROOT, "bench", "configs", "t0t1_fig2.json")) as f:
        cfg = json.load(f)
    cfg["params"]["n_flows"] = 6
    for key, value in change.items():
        name, field = key.split("__")
        if name == "dims":
            cfg["dims"][field] = value
        elif name == "params":
            cfg["params"][field] = value
        else:
            next(c for c in cfg["components"] if c["name"] == name)[
                field] = value
    return cfg


VARIANTS = {
    "study": {},
    # T1's disk overflows: DATA_WRITE schedules MIGRATE to tape
    "small_disk": dict(t1__disk=100.0),
    # one slow CPU and a short queue: jobs queue, and some are dropped
    "queue": dict(t1__n_cpu=1, t1__cpu_power=1.0, dims__queue_cap=2,
                  params__interval=1),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("wan_bw", [8.0, 0.125])
def test_reference_equals_the_program_oracle(variant, wan_bw):
    """A second witness: the plain reference and ``run_sequential`` (the
    program's own sequential oracle over its handler table) agree bit for
    bit, on paths the study does not take too."""
    import jax
    from repro.core import run_sequential
    from repro.core import monitoring as mon
    cfg = _variant(**VARIANTS[variant])
    params = dict(cfg["params"], wan_bw=wan_bw)
    w, oc, trace = run_sequential(*harness.build_scenario(cfg, params))
    oc = np.asarray(oc)
    got = dict(world={k: v[None] for k, v in
                      jax.device_get(w)._asdict().items()},
               counters={k: int(oc[getattr(mon, "C_" + k.upper())])
                         for k in reference.COUNTERS},
               trace=trace)
    want = reference.run(cfg, params)
    assert harness.diff(got, want, traced=True) == {
        "world_elems_diff": 0, "counters_diff": 0, "trace_rows_diff": 0}
    if variant == "small_disk":
        assert want[1]["migrations"] > 0
    if variant == "queue":
        assert want[1]["drop_queue"] > 0


def test_reference_imports_nothing_of_the_program():
    import ast
    tree = ast.parse(open(reference.__file__).read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom) and n.module]
    assert not [n for n in names if n.split(".")[0] == "repro"], names
