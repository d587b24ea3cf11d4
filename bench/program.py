"""What the program reports about itself, reduced for the benchmark.

The program's host spans and counters (``repro.core.monitoring.SpanLog``)
give each point a ``program`` record: self seconds per span name, the
number of programs the engine traced, and JAX's trace and lowering of the
driver programs (the ``jax.trace_lower`` children of ``engine.run`` spans).
Its device stage scopes (``superstep/<stage>`` in each op's ``op_name``)
give the traced window's device self time per stage, ``stage_busy_s`` (the
names are in the trace viewer's ``tf_op`` argument of each op), and
its ``repro.*`` host spans label the idle gaps between the benchmark's span
and JAX's. The pure functions take tuples, so they are tested on synthetic
events; ``summarize`` reads a ``.xplane.pb`` the way ``trace.summarize``
does.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

from bench import trace as btrace

# the trace viewer's argument of a device op that holds its ``op_name``
OP_NAME_STAT = "tf_op"
STAGE = re.compile(r"superstep/([a-z_]+)")
OTHER = "other"   # ops under no stage scope: the outer loop's control


def program_record(log, mark) -> dict:
    """A point's ``program`` record from a SpanLog since ``mark``."""
    got = log.since(mark)
    return dict(self_s=got["self_s"],
                traces=sum(got["counts"].get("engine.traces", {}).values()),
                driver_trace_lower_s=log.union_s("jax.trace_lower",
                                                 "engine.run", mark))


# ------------------------------------------------------------- readers
# Shared arithmetic of the readers of the program's spans, counters and
# stage scopes (bench/metrics): None where the record has nothing to read,
# a point without a ``program`` record (no SpanLog was attached) or a traced
# run without ``stage_busy_s`` or with none of the stages asked for.
def per_point(record, value):
    """``value(program)`` averaged over the window's points."""
    pts = record["points"]
    if not pts or any("program" not in p for p in pts):
        return None
    return sum(value(p["program"]) for p in pts) / len(pts)


def per_window(record, stages):
    """Device microseconds per window under ``stages``."""
    tr = record.get("trace") or {}
    busy = tr.get("stage_busy_s") or {}
    windows = sum(p["windows"] for p in record["points"])
    if not windows or not any(s in busy for s in stages):
        return None
    return sum(busy.get(s, 0.0) for s in stages) * 1e6 / windows


# -------------------------------------------------------------- stages
def stage_of(op_name: str) -> str:
    """The innermost superstep stage named in an ``op_name``, or ``other``."""
    found = STAGE.findall(op_name or "")
    return found[-1] if found else OTHER


def stage_self_ns(events) -> dict:
    """Device self ns per stage from ``(op_name, start_ns, end_ns)`` op
    events of one device (a ``while`` op's body ops are its children)."""
    out: dict = {}
    labelled = [(stage_of(name), a, b) for name, a, b in events]
    for stage, ns in btrace.self_times(labelled):
        out[stage] = out.get(stage, 0.0) + ns
    return out


def stage_busy_s(device_events: dict, lo: float, hi: float) -> dict:
    """Seconds of device self time per stage in ``[lo, hi]``, averaged over
    devices like ``busy_s``; ``{}`` where no device op was recorded."""
    tot: dict = {}
    for evs in device_events.values():
        inside = [e for e in evs if e[2] > lo and e[1] < hi]
        for stage, ns in stage_self_ns(inside).items():
            tot[stage] = tot.get(stage, 0.0) + ns
    n = len(device_events)
    return {stage: ns / n / 1e9 for stage, ns in tot.items()}


def label(gap, host_spans) -> str:
    """``trace.label`` with the innermost ``repro.*`` span that overlaps the
    gap put between the benchmark's span and JAX's:
    ``bench.run > repro.engine.run > jax.trace_lower``."""
    a, b = gap
    base = btrace.label(gap, [s for s in host_spans
                              if not s[0].startswith("repro.")])

    def key(s):
        return min(b, s[2]) - max(a, s[1]), -(s[2] - s[1])

    ours = [s for s in host_spans
            if s[0].startswith("repro.") and key(s)[0] > 0]
    if not ours:
        return base
    name = max(ours, key=key)[0]
    if base == "outside any host span":
        return name
    parts = base.split(" > ")
    at = 1 if parts[0] in btrace.HOST_LABELS else 0
    return " > ".join(parts[:at] + [name] + parts[at:])


def read_ops(path: str, stat: str = OP_NAME_STAT):
    """``({device: [(op_name, start_ns, end_ns)]}, window)`` from the trace
    viewer's ``*.trace.json.gz`` that the profiler writes beside the
    ``.xplane.pb``: the op events of each device's ops line, named by their
    ``stat`` argument (the raw xplane events carry no ``op_name``; the
    viewer's events do), and the ``(start_ns, end_ns)`` of the
    ``bench.window`` host span on the same clock, or None."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    devices, lines = {}, set()
    for e in events:
        if e.get("ph") != "M":
            continue
        name = e.get("args", {}).get("name", "")
        if e.get("name") == "process_name" and name.startswith(
                btrace.DEVICE_PREFIX):
            devices[str(e["pid"])] = name
        elif e.get("name") == "thread_name" and name == btrace.OPS_LINE:
            lines.add((str(e["pid"]), str(e.get("tid"))))
    ops = {name: [] for name in devices.values()}
    window = None
    for e in events:
        if e.get("ph") != "X":
            continue
        a = float(e["ts"]) * 1e3
        b = a + float(e.get("dur", 0)) * 1e3
        pid = str(e["pid"])
        if pid in devices and (pid, str(e.get("tid"))) in lines:
            ops[devices[pid]].append(
                (str(e.get("args", {}).get(stat, e["name"])), a, b))
        elif e.get("name") == btrace.WINDOW:
            window = (a, b)
    return ops, window


def summarize(trace_dir: str, wall_spans=(), wall_window=None,
              k: int = btrace.TOP):
    """``stage_busy_s`` and the ``k`` longest idle gaps with program-span
    labels, from the trace in ``trace_dir``; None without a trace, a device
    op or the window span. ``wall_spans`` and ``wall_window`` as in
    ``trace.summarize``."""
    path = btrace.find_xspace(trace_dir)
    if path is None:
        return None
    devices, host = btrace.read_xspace(path)
    window = [s for s in host if s[0] == btrace.WINDOW]
    if not window or not any(devices.values()):
        return None
    if wall_window is not None:
        off = window[0][1] - wall_window * 1e9
        host.extend((n, a * 1e9 + off, b * 1e9 + off)
                    for n, a, b in wall_spans)
    lo, hi = window[0][1], window[0][2]
    first = devices[sorted(devices)[0]]
    merged = btrace.clip(btrace.merge((a, b) for _, a, b in first
                                      if b > lo and a < hi), lo, hi)
    longest = sorted(btrace.gaps(merged, lo, hi),
                     key=lambda g: g[0] - g[1])[:k]
    viewer = glob.glob(os.path.join(os.path.dirname(path),
                                    "*.trace.json.gz"))
    busy = {}
    if viewer:
        ops, on_viewer = read_ops(viewer[0])
        if on_viewer is not None:
            busy = stage_busy_s(ops, *on_viewer)
    return dict(stage_busy_s=busy,
                idle_gaps=[[label(g, host), (g[1] - g[0]) / 1e9]
                           for g in longest])
