"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle share,
the device operations that took most time, and the longest idle gaps
labelled by what the host was doing in them.

Busy time is the union of the intervals in which an operation ran on a
device, inside the benchmark's ``bench.window`` host span; idle is the rest
of that span. The pure functions take ``(name, start_ns, end_ns)`` tuples,
so they are tested on synthetic intervals and on a trace recorded on the CPU.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
# the benchmark's own host spans, the outer label of an idle gap
HOST_LABELS = ("bench.build", "bench.run", "bench.readback")
TOP = 10


def merge(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def clip(merged, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in merged if b > lo and a < hi]


def busy_ns(merged) -> float:
    return float(sum(b - a for a, b in merged))


def short(name: str) -> str:
    """An XLA op's name without its signature: ``%fusion.12 = f32[..] ...``
    becomes ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def self_times(events):
    """``(name, self ns)`` per event: its duration less that of the events
    nested in it (a ``while`` op spans the ops of its body)."""
    out, stack = [], []   # stack entries: [end, name, dur, child ns]

    def close(entry):
        out.append((entry[1], entry[2] - entry[3]))

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += b - a
        stack.append([b, name, b - a, 0])
    while stack:
        close(stack.pop())
    return out


def top_ops(events, k: int = TOP) -> list:
    """``[name, seconds]`` of the ``k`` operations with most self time on
    the device, summed by name."""
    tot: dict = {}
    for name, ns in self_times(events):
        name = short(name)
        tot[name] = tot.get(name, 0.0) + ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in best]


def gaps(merged, lo, hi) -> list:
    """Idle ``(start, end)`` intervals of ``[lo, hi]`` between busy ones."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap, host_spans) -> str:
    """What the host was doing in a gap: the benchmark span it overlaps
    most, then JAX's compile-time span (``jax.*``) it overlaps most, or else
    the other host event it overlaps most."""
    a, b = gap

    def overlap(s):
        return min(b, s[2]) - max(a, s[1])

    best = {"bench": None, "jax": None, "other": None}
    for s in host_spans:
        ov = overlap(s)
        if ov <= 0 or s[0] == WINDOW:
            continue
        kind = ("bench" if s[0] in HOST_LABELS else
                "jax" if s[0].startswith("jax.") else "other")
        if best[kind] is None or ov > overlap(best[kind]):
            best[kind] = s
    inner = best["jax"] or best["other"]
    parts = [s[0] for s in (best["bench"], inner) if s is not None]
    return " > ".join(parts) if parts else "outside any host span"


def idle_gaps(merged, lo, hi, host_spans, k: int = TOP) -> list:
    """``[label, seconds]`` of the ``k`` longest idle gaps."""
    longest = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])[:k]
    return [[label(g, host_spans), (g[1] - g[0]) / 1e9] for g in longest]


def reduce(device_events: dict, host_spans: list) -> dict | None:
    """Reduce per-device op events and host spans to the traced run's
    numbers; None where no device op or no window span was recorded."""
    window = [s for s in host_spans if s[0] == WINDOW]
    if not window or not any(device_events.values()):
        return None
    lo, hi = window[0][1], window[0][2]
    busy, ops, merged0 = [], [], None
    for dev in sorted(device_events):
        evs = [e for e in device_events[dev] if e[2] > lo and e[1] < hi]
        merged = clip(merge((a, b) for _, a, b in evs), lo, hi)
        busy.append(busy_ns(merged))
        ops.extend(evs)
        if merged0 is None:
            merged0 = merged
    n = len(busy)
    window_ns = hi - lo
    # ops summed over chips are averaged, like busy time
    device_ops = [[name, s / n] for name, s in top_ops(ops, TOP)]
    return dict(busy_s=sum(busy) / n / 1e9, window_s=window_ns / 1e9,
                devices=n, device_ops=device_ops,
                idle_gaps=idle_gaps(merged0, lo, hi, host_spans))


def read_xspace(path: str, device_prefix: str = DEVICE_PREFIX,
                ops_line: str = OPS_LINE):
    """``(device_events, host_spans)`` from one ``.xplane.pb``: each device
    plane's ops line, and every event of the host planes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == ops_line:
                    evs.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events)
    return devices, host


def find_xspace(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def summarize(trace_dir: str, wall_spans=(), wall_window=None):
    """Reduce the trace in ``trace_dir``. ``wall_spans`` are extra host
    spans ``(name, start, end)`` on the ``time.time()`` clock (JAX's
    compile-time events), put on the trace's clock through ``wall_window``,
    the ``time.time()`` start of the ``bench.window`` span."""
    path = find_xspace(trace_dir)
    if path is None:
        return None
    devices, host = read_xspace(path)
    window = [s for s in host if s[0] == WINDOW]
    if window and wall_window is not None:
        off = window[0][1] - wall_window * 1e9
        host.extend((n, a * 1e9 + off, b * 1e9 + off)
                    for n, a, b in wall_spans)
    return reduce(devices, host)
