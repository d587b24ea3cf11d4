"""A tiny copy of the benchmark's cells for CPU tests: the same
configurations, traffic mixes and metric readers at sizes a CPU runs in
seconds."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

CONFIGS = {
    "t0t1_fig2": dict(params={"wan_bw": 2.0, "n_flows": 6, "interval": 15,
                              "flow_mb": 40.0},
                      build={"n_agents": 1, "lookahead": 2, "t_end": 4000,
                             "pool_cap": 256, "work_per_mb": 2.0},
                      sweep={"wan_bw": [8.0, 0.5]}),
}
TRAFFIC: dict = {}


def make_root(dest: str) -> str:
    """Write ``dest/BENCHMARK.json`` and ``dest/bench/{configs,traffic,
    metrics}``: the real files with tiny sizes swapped in."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(dest, "bench", sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(dest, "bench", "metrics"),
                    dirs_exist_ok=True)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        cfg.update(CONFIGS[c["name"]])
        with open(os.path.join(dest, c["file"]), "w") as f:
            json.dump(cfg, f)
    for w in bench["workloads"]:
        name = w["traffic"]
        with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
            tr = json.load(f)
        tr.update(TRAFFIC.get(name, {}))
        with open(os.path.join(dest, "bench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(tr, f)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


def cpu_devices(chips: int):
    """The look for the chip, skipped: the CPU device stands in."""
    import jax
    d = jax.devices("cpu")[:chips]
    return d, {"platform": d[0].platform, "kind": d[0].device_kind,
               "count": len(d)}
