"""Run one cell's window with the program's SpanLog attached and print the
program's own per-layer metrics beside the benchmark's.

    python bench/probe.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on the chip, as ``run.py`` runs: the same
set-up, points and window. With ``--trace 0`` the profiler stays off, so
``events_per_s`` against a plain ``run.py --trace 0`` run is the cost of
the program's span log. With ``--trace 1`` the window also runs under the
profiler, and the line carries every per-layer metric of ``BENCHMARK.json``
and of ``METRICS`` below, ``stage_busy_s``, and the idle gaps labelled with
the program's spans. No check against the reference: ``run.py`` makes it.
Prints one JSON line.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from bench import run as brun  # noqa: E402

# the readers of the program's spans, counters and stage scopes, by unit
METRICS = {"engine_build_s": "s/point", "init_state_s": "s/point",
           "program_traces": "traces/point",
           "driver_trace_lower_s": "s/point",
           "select_device_us": "us/window", "execute_device_us": "us/window",
           "route_device_us": "us/window"}


def recorder():
    """The benchmark's Recorder with the program's SpanLog attached: each
    point's record gains ``program`` (``bench.program.program_record``)."""
    from bench import harness, program
    from repro.core import monitoring as mon

    class ProgramRecorder(harness.Recorder):
        def __init__(self):
            super().__init__()
            self.log = mon.SpanLog()
            self.log.__enter__()

        def close(self):
            self.log.__exit__(None, None, None)
            super().close()

        def mark(self):
            return super().mark() + self.log.mark()

        def since(self, mark) -> dict:
            return dict(super().since(mark[:3]),
                        program=program.program_record(self.log, mark[3:]))

    return ProgramRecorder()


def measure(cell, devices, seed: int, seconds: float, traced: bool,
            t_start: float):
    """``run.measure`` with the SpanLog attached and, traced, the program's
    reduction of the profiler trace."""
    from bench import harness, program, trace as btrace
    rec = recorder()

    def plan(i):
        return harness.plan_point(cell.config, cell.traffic, seed, i)

    harness.run_point(plan(0), cell.config, cell.traffic, rec, devices)
    setup_s = time.monotonic() - t_start
    mark = rec.mark()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        results, window_s = harness.run_window(plan, cell.config,
                                               cell.traffic, rec, devices,
                                               seconds, trace_dir)
        summary = None
        if traced:
            window_t0 = [a for n, a, _ in rec.spans if n == "window"][-1]
            walls = rec.jax_wall_spans(mark)
            summary = btrace.summarize(trace_dir, walls, window_t0)
            ours = program.summarize(trace_dir, walls, window_t0)
            if summary is not None and ours is not None:
                summary["stage_busy_s"] = ours["stage_busy_s"]
                summary["program_idle_gaps"] = ours["idle_gaps"]
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    record = dict(points=[r.record for r in results], window_s=window_s,
                  setup_s=setup_s, peak_bytes=brun.peak_bytes(devices),
                  trace=summary)
    rec.close()
    return record


def main(argv=None, root=brun.ROOT, devices_fn=brun.device_info,
         t_start=T_START):
    args = brun.parse(argv)
    brun.setup_paths()
    from bench import harness
    cell = harness.load_cell(root, args.workload)
    devices, info = devices_fn(cell.chips)
    brun.enable_cache()
    record = measure(cell, devices, args.seed, args.seconds, bool(args.trace),
                     t_start)
    names = {m["name"]: m["unit"] for m in cell.per_layer} if args.trace \
        else {"events_per_s": "events/s"}
    names.update(METRICS)
    metrics = {}
    for name, unit in names.items():
        value = harness.load_reader(root, name)(record)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    pts = record["points"]
    self_s = {}
    for p in pts:
        for name, s in p["program"]["self_s"].items():
            self_s[name] = self_s.get(name, 0.0) + s / len(pts)
    out = {"metrics": metrics, "device": dict(info),
           "points": len(pts), "window_s": record["window_s"],
           "setup_s": record["setup_s"], "program_self_s": self_s}
    if record["trace"] is not None:
        tr = record["trace"]
        out["trace"] = {k: tr.get(k) for k in
                        ("busy_s", "window_s", "stage_busy_s", "device_ops",
                         "idle_gaps", "program_idle_gaps")}
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
