"""The harness finds every cell, configuration, traffic mix and metric by
name from files alone, and BENCHMARK.json keeps to its contract."""
import json
import os
import re

import pytest

from bench import harness
from bench.tests.conftest import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]] + CELLS + METRICS)
    assert all(NAME.match(n) for n in names)
    assert len(set(METRICS)) == len(METRICS) and len(set(CELLS)) == len(CELLS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_from_files(cell):
    """The cell's files resolve, and the program's builder and the
    reference build the same initial world from the configuration."""
    import jax
    from bench import reference
    c = harness.load_cell(ROOT, cell)
    assert c.config["name"] == [w for w in BENCH["workloads"]
                                if w["name"] == cell][0]["config"]
    for index in (0, 1):
        point = harness.plan_point(c.config, c.traffic, 2**31 + 5, index)
        for params in point.runs:
            world = harness.build_scenario(c.config, params)[0]
            got = {k: v[None] for k, v in
                   jax.device_get(world)._asdict().items()}
            want = reference.build(c.config, params)[0]
            assert set(want) == set(got)
            assert harness.world_diff(got, want) == 0
    assert c.end_to_end and c.per_layer


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reader_by_name(metric):
    read = harness.load_reader(ROOT, metric)
    assert callable(read)


def test_plan_is_fixed_by_seed_and_index():
    c = harness.load_cell(ROOT, "t0t1_fig2.sweep")
    a = harness.plan_point(c.config, c.traffic, 77, 3)
    b = harness.plan_point(c.config, c.traffic, 77, 3)
    assert a == b
    # every point runs the same four bandwidths, in a seeded order
    bws = sorted(r["wan_bw"] for r in a.runs)
    assert bws == sorted(c.config["sweep"]["wan_bw"])


def test_union_of_nested_spans():
    assert harness.union_s([(0, 10), (2, 3), (9, 12), (20, 21)]) == 13
    assert harness.union_s([]) == 0
