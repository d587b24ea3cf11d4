"""Run one benchmark cell once on the chip and print one JSON result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Set-up is imports, the compile cache placement,
and one warm-up point; then whole points run back to back until the first
completion at or after ``--seconds``. With ``--trace 0`` the result carries
the cell's end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics and a breakdown. After
the window, every run of the window's points is compared with the plain
sequential reference (``bench/reference.py``); each compared number is
printed beside its limit as the last lines on standard error and under
``checks``, the last key of the result line.

Exits non-zero with no result line when JAX finds no TPU, fewer chips than
the cell asks for, a device kind that ``bench/peaks.json`` does not list, or
no ``src/repro`` beside ``bench/``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# every compared number is exact: the limit of each is 0
LIMITS = {"world_elems_diff": 0, "counters_diff": 0, "trace_rows_diff": 0}


def fail(msg: str, code: int = 2):
    print(f"bench/run.py: {msg}", file=sys.stderr)
    sys.exit(code)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_paths():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}: run from a checkout")
    for p in (src, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    # the program takes the cache directory it is given: a fixed path in
    # the checkout, whatever the environment held
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR


def device_info(chips: int):
    """The devices as JAX reports them; exits unless they are TPUs enough,
    of a kind whose peaks ``bench/peaks.json`` lists."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        fail(f"JAX platform is {info['platform']!r}, not 'tpu': the "
             "benchmark measures nothing off the chip", 3)
    if len(devs) < chips:
        fail(f"the cell asks for {chips} chips, JAX has {len(devs)}", 3)
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        known = json.load(f)["devices"]
    if info["kind"] not in known:
        fail(f"device kind {info['kind']!r} has no peaks in "
             f"bench/peaks.json (have {sorted(known)})", 3)
    return devs[:chips], info


def enable_cache():
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    # every program of a point, however quick to compile, is cached, so the
    # window loads and never compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes(devices):
    """Peak device memory of the fullest chip: the allocator's peak of
    buffers in use plus its peak reserve for compiled programs' temporaries
    (the TPU backend keeps those apart; a program's scratch is most of what
    a cell holds)."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(st["peak_bytes_in_use"]
                         + st.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def measure(cell, devices, seed: int, seconds: float, traced_run: bool,
            t_start: float):
    """Set-up (warm-up point) and window; returns the run record, the
    window's point results and the compile-cache misses in the window."""
    from bench import harness, trace as btrace
    rec = harness.Recorder()

    def plan(i):
        return harness.plan_point(cell.config, cell.traffic, seed, i)

    harness.run_point(plan(0), cell.config, cell.traffic, rec, devices)
    setup_s = time.monotonic() - t_start
    mark = rec.mark()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced_run else None
    try:
        results, window_s = harness.run_window(plan, cell.config,
                                               cell.traffic, rec, devices,
                                               seconds, trace_dir)
        summary = None
        if traced_run:
            window_t0 = [a for n, a, _ in rec.spans if n == "window"][-1]
            summary = btrace.summarize(trace_dir, rec.jax_wall_spans(mark),
                                       window_t0)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    record = dict(points=[r.record for r in results], window_s=window_s,
                  setup_s=setup_s, peak_bytes=peak_bytes(devices),
                  trace=summary)
    misses = rec.misses - mark[2]
    rec.close()
    return record, results, misses


def main(argv=None, root=ROOT, devices_fn=device_info, t_start=T_START):
    """One run. ``root`` holds ``BENCHMARK.json`` and ``bench/``;
    ``devices_fn(chips) -> (devices, info)`` is the look for the chip."""
    args = parse(argv)
    setup_paths()
    from bench import harness
    cell = harness.load_cell(root, args.workload)
    devices, info = devices_fn(cell.chips)
    enable_cache()
    record, results, misses = measure(cell, devices, args.seed, args.seconds,
                                      bool(args.trace), t_start)

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = harness.load_reader(root, m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    info = dict(info, memory_peak_bytes=record["peak_bytes"])
    if record["trace"] is not None:
        info.update(busy_s=record["trace"]["busy_s"],
                    window_s=record["trace"]["window_s"])

    # the check: every run, read back as it finished, against the reference
    traced = int(cell.traffic["trace_cap"]) > 0
    items = harness.fetch(results)
    del results
    t0 = time.monotonic()
    got, bad = harness.compare(cell.config, items, traced)
    ref_s = time.monotonic() - t0
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in got.items()}
    correct = bad == 0 and all(v["value"] <= v["limit"]
                               for v in checks.values())

    print(f"runs={len(items)} window_s={record['window_s']} "
          f"setup_s={record['setup_s']} compiles_in_window={misses} "
          f"checked={len(items)} reference_s={ref_s}", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    out = {"correct": correct, "attempted": len(items),
           "failed": bad, "metrics": metrics,
           "device": info}
    if record["trace"] is not None:
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
