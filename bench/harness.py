"""The benchmark's machinery: cells by name, points, spans, the check.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the deployment (its components as the
  program's ``ScenarioBuilder`` declares them, parameters, build settings, an
  optional sweep axis), read by :func:`build_scenario` for the program and by
  ``reference.build`` for the plain reference;
* ``bench/traffic/<traffic>.json``: the mix, read by :func:`plan_point`, the
  one generator (driver, sweep axis, trace size);
* ``bench/metrics/<metric>.py``: a reader ``read(record) -> float | None``.

A *point* is what a user's script does per parameter point, once per run of
the point: the scenario built through ``ScenarioBuilder``, then
``fleet.Orchestrator.run``, ``jax.block_until_ready`` and the counters read
back to the host.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import time

import numpy as np


# ------------------------------------------------------------------ cells
@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple
    per_layer: tuple


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its configuration
    and traffic files and the benchmark's metrics."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=tuple(bench["end_to_end"]),
                per_layer=tuple(bench["per_layer"]))


def load_reader(root: str, metric: str):
    """``read(record)`` of ``bench/metrics/<metric>.py``, loaded by path."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------- points
@dataclasses.dataclass(frozen=True)
class Point:
    """Point ``index`` of a run: the parameters of each of its runs."""
    index: int
    runs: tuple


def plan_point(config: dict, traffic: dict, seed: int, index: int) -> Point:
    """The runs of point ``index`` of a run seeded ``seed``; point 0 is the
    warm-up. With an ``axis``, one run per value of the configuration's
    sweep, in an order the seed and index permute; else one run. The same
    seed and index always give the same runs."""
    base = dict(config["params"])
    axis = traffic.get("axis")
    if not axis:
        return Point(index, (base,))
    (key, values), = config[axis].items()
    rng = np.random.default_rng([seed % 2**62, index])
    order = rng.permutation(len(values))
    return Point(index, tuple(dict(base, **{key: values[i]}) for i in order))


def build_scenario(config: dict, params: dict):
    """The program's ``(world, own, init_events, spec)`` of the configuration
    at ``params``, declared through ``ScenarioBuilder`` component by
    component (``$p``, ``@c`` and ``#K`` resolve as in ``reference.resolve``;
    a payload given as an object is packed by its kind's layout)."""
    from repro.core import ScenarioBuilder
    from repro.core import components
    from bench.reference import resolve

    def kind_ids(v):
        if isinstance(v, str) and v[:1] == "#":
            return getattr(components, v[1:]).id
        return [kind_ids(x) for x in v] if isinstance(v, list) else v

    b = ScenarioBuilder(**config["dims"])
    lps = {}
    for comp in config["components"]:
        args = {k: resolve(kind_ids(v), params, lps)
                for k, v in comp.items()
                if k not in ("name", "add", "kind", "payload")}
        if "kind" in comp:
            kind = getattr(components, comp["kind"].lstrip("#"))
            args["kind"] = kind
            args["payload"] = kind.pack(**{
                k: resolve(kind_ids(v), params, lps)
                for k, v in comp["payload"].items()})
        lps[comp["name"]] = getattr(b, "add_" + comp["add"])(**args)
    return b.build(**config["build"])


# ------------------------------------------------------------------ spans
JAX_TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")
JAX_COMPILE = ("/jax/core/compile/backend_compile_duration",)
JAX_MISS = "/jax/compilation_cache/cache_misses"


def union_s(spans) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, -float("inf")
    for a, b in sorted(spans):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


class Recorder:
    """Host spans of the benchmark's own calls plus JAX's compile-time
    events, bucketed per point. JAX's trace events nest (a jitted helper
    traced inside a jitted function), so the time is their union."""

    def __init__(self):
        import jax
        self._jax = jax
        self.spans: list[tuple[str, float, float]] = []
        self.jax_spans: list[tuple[str, float, float]] = []
        self.misses = 0
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self):
        self._jax.monitoring.unregister_event_time_span_listener(self._on_span)
        self._jax.monitoring.unregister_event_listener(self._on_event)

    def _on_span(self, event, start, end, **_):
        if event in JAX_TRACE or event in JAX_COMPILE:
            self.jax_spans.append((event, start, end))

    def _on_event(self, event, **_):
        if event == JAX_MISS:
            self.misses += 1

    @contextlib.contextmanager
    def span(self, name: str):
        with self._jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.time()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.time()))

    def jax_wall_spans(self, mark):
        """JAX's compile-time spans since ``mark``, named for a trace:
        ``jax.trace_lower`` and ``jax.compile_load``."""
        return [("jax.trace_lower" if e in JAX_TRACE else "jax.compile_load",
                 a, b) for e, a, b in self.jax_spans[mark[1]:]]

    def mark(self):
        return len(self.spans), len(self.jax_spans), self.misses

    def since(self, mark) -> dict:
        """Seconds per span kind since ``mark``."""
        s, j, m = mark
        out = {}
        for name, a, b in self.spans[s:]:
            out[name + "_s"] = out.get(name + "_s", 0.0) + (b - a)
        jx = self.jax_spans[j:]
        out["trace_lower_s"] = union_s((a, b) for e, a, b in jx
                                       if e in JAX_TRACE)
        out["compile_load_s"] = union_s((a, b) for e, a, b in jx
                                        if e in JAX_COMPILE)
        out["cache_misses"] = self.misses - m
        return out


# ------------------------------------------------------------ point loop
@dataclasses.dataclass
class PointResult:
    point: Point
    record: dict
    results: list   # per run: what it produced, on the host (host_result)


def run_point(point: Point, config: dict, traffic: dict, rec: Recorder,
              devices) -> PointResult:
    """Build and run every run of a point through ``Orchestrator.run`` and
    read what it produced back to the host, as a user reads counters,
    trace and world; the device keeps nothing of a finished run."""
    import jax
    from repro.core import monitoring as mon
    from repro.fleet import FleetPolicy, Orchestrator

    mark = rec.mark()
    pol = FleetPolicy(driver=traffic["driver"])
    traced = int(traffic["trace_cap"]) > 0
    results = []
    events = windows = fallback = 0
    for params in point.runs:
        with rec.span("build"):
            built = build_scenario(config, params)
        with rec.span("run"):
            res = Orchestrator(pol, trace_cap=int(traffic["trace_cap"])).run(
                built, devices=devices)
            jax.block_until_ready(res.state)
        with rec.span("readback"):
            c = np.asarray(res.state.counters)
            w = np.asarray(res.state.windows)
            results.append(host_result(res.state, traced))
        del res
        events += int(c[..., mon.C_EVENTS].sum())
        fallback += int(c[..., mon.C_BATCH_FALLBACK].sum())
        windows += int(w.max())   # agents run their windows in lockstep
    record = dict(rec.since(mark), index=point.index, events=events,
                  windows=windows, fallback=fallback)
    return PointResult(point, record, results)


def run_window(plan, config, traffic, rec, devices, seconds: float,
               trace_dir: str | None = None):
    """Whole points back to back from index 1; the window closes at the first
    point completion at or after ``seconds``. Returns the results and the
    window's wall seconds."""
    import jax
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    results = []
    try:
        with rec.span("window"):
            t0 = time.perf_counter()
            while True:
                results.append(run_point(plan(len(results) + 1), config,
                                         traffic, rec, devices))
                wall = time.perf_counter() - t0
                if wall >= seconds:
                    break
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return results, wall


# ------------------------------------------------------------------ check
def _bits(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x)
    if x.dtype.kind == "f":
        return x.view(np.dtype(f"i{x.dtype.itemsize}"))
    return x


def world_diff(got: dict, want: dict) -> int:
    """Elements that differ bit for bit, over every table the reference
    keeps and every agent copy of the program's stacked ``(A, ...)`` world;
    a table the program lacks, or holds at another shape, counts whole."""
    n = 0
    for name, w in want.items():
        w = _bits(w)
        g = _bits(got[name]) if name in got else None
        if g is None or g.shape[1:] != w.shape:
            n += w.size
            continue
        n += int((g != w[None]).sum())
    return n


def counter_diff(got: dict, want: dict) -> int:
    """Counters the reference books that differ from the program's."""
    return sum(got.get(name) != v for name, v in want.items())


def trace_diff(got: list, want: list) -> int:
    """Rows that differ between two traces. Event ``seq`` ids are not
    unique (children of different parents can share one), and neither side
    orders rows of equal ``(time, seq)``, so both are put in full-row order
    first; the world comparison sees any effect of their order."""
    got, want = sorted(got), sorted(want)
    return sum(a != b for a, b in zip(got, want)) + abs(len(got) - len(want))


CHECKS = ("world_elems_diff", "counters_diff", "trace_rows_diff")


def host_result(state, traced: bool) -> dict:
    """What a run produced, on the host: the stacked ``(A, ...)`` world by
    table name, the counters the reference books, found among the program's
    by name and summed over agents, and the merged trace of a traced run."""
    import jax
    from bench.reference import COUNTERS
    from repro.core import merged_engine_trace
    from repro.core import monitoring as mon
    c = np.asarray(state.counters).sum(axis=0)
    out = dict(world=jax.device_get(state.world)._asdict(),
               counters={name: int(c[getattr(mon, "C_" + name.upper())])
                         for name in COUNTERS})
    if traced:
        out["trace"] = merged_engine_trace(np.asarray(state.trace),
                                           np.asarray(state.trace_n))
    return out


def fetch(results: list) -> list:
    """Every run of every window point: ``(params, got)`` pairs."""
    return [(params, got) for r in results
            for params, got in zip(r.point.runs, r.results)]


def diff(got: dict, want: tuple, traced: bool) -> dict:
    """The compared numbers for one run: ``got`` as :func:`host_result`
    gives it, ``want`` the reference's ``(world, counters, trace)``."""
    w, c, t = want
    out = dict(world_elems_diff=world_diff(got["world"], w),
               counters_diff=counter_diff(got["counters"], c))
    if traced:
        out["trace_rows_diff"] = trace_diff(got["trace"], t)
    return out


def compare(config: dict, items: list, traced: bool, quantum: int = 1):
    """Run the reference over each fetched run and count what differs.
    Returns the totals per compared number and how many runs differed."""
    from bench import reference
    totals = dict.fromkeys(CHECKS if traced else CHECKS[:2], 0)
    bad = 0
    for params, got in items:
        d = diff(got, reference.run(config, params, quantum=quantum), traced)
        for k, v in d.items():
            totals[k] += v
        bad += any(d.values())
    return totals, bad
