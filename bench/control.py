"""The control of the correctness check: the reference with one bit of
simulated-clock resolution dropped, put in the program's place.

    python bench/control.py --workload <cell> --seeds 101,102,103

For each seed it takes the first window point of the cell at the cell's own
size and compares, run by run, the control's output
(``reference.run(..., quantum=2)``) with the exact reference by the same
numbers a run compares. Every number the control reads above its limit shows
the check can fail; a seed on which the control reads no number above its
limit would mean the check cannot see the fault. Prints one JSON line per
seed and a last line with the smallest reading per number (the upper
readings the limits are set below). Needs a TPU, like bench/run.py.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402


def control_readings(cell, seed: int) -> dict:
    """The numbers the check reads when the control stands in for the
    program, over the runs of point 1 of a run seeded ``seed``."""
    from bench import harness, reference
    traced = int(cell.traffic["trace_cap"]) > 0
    totals = dict.fromkeys(harness.CHECKS if traced else harness.CHECKS[:2],
                           0)
    point = harness.plan_point(cell.config, cell.traffic, seed, 1)
    for params in point.runs:
        cw, cc, ct = reference.run(cell.config, params, quantum=2)
        got = dict(world={k: v[None] for k, v in cw.items()}, counters=cc,
                   trace=ct)
        want = reference.run(cell.config, params)
        for k, v in harness.diff(got, want, traced).items():
            totals[k] += v
    return totals


def main(argv=None, root=run.ROOT, devices_fn=run.device_info):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    run.setup_paths()
    from bench import harness
    cell = harness.load_cell(root, args.workload)
    devices_fn(cell.chips)
    run.enable_cache()
    readings = []
    for s in (int(x) for x in args.seeds.split(",")):
        got = control_readings(cell, s)
        readings.append(got)
        print(json.dumps({"workload": cell.name, "seed": s,
                          "control": got}), flush=True)
    upper = {k: min(r[k] for r in readings) for k in readings[0]}
    fails = all(any(r[k] > run.LIMITS[k] for k in r) for r in readings)
    print(json.dumps({"workload": cell.name, "upper": upper,
                      "control_fails_every_seed": fails}), flush=True)
    return readings


if __name__ == "__main__":
    main()
