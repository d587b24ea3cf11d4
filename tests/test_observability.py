"""Host spans, host counters and superstep scopes (docs/architecture.md,
"Observability"): what a SpanLog records, that nothing is booked without
one, that JAX's compile events land under the program span that caused
them, that the spans share the profiler's clock, that the engine counts its
traces, and that every stage scope reaches the lowered program."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import t0t1_builder
from repro.core import Engine, MetricsStream, TraceStream
from repro.core import monitoring as mon


def test_spans_nest_with_parent_self_time_and_run_id():
    with mon.SpanLog() as log:
        with mon.span("outer", run_id=7, driver="local"):
            with mon.span("inner"):
                mon.count("things", key="a")
            mon.count("things", key="b")
            mon.count("things", key="a")
        with mon.span("after"):
            pass
    outer, inner, after = log.spans
    assert (outer.parent, inner.parent, after.parent) == (None, 0, None)
    assert (outer.run_id, inner.run_id, after.run_id) == (7, 7, None)
    assert outer.attrs == {"driver": "local"}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    got = log.since()
    assert got["counts"] == {"things": {"a": 2, "b": 1}}
    want_outer = ((outer.end_ns - outer.start_ns)
                  - (inner.end_ns - inner.start_ns)) / 1e9
    assert got["self_s"]["outer"] == pytest.approx(want_outer, abs=1e-9)
    assert got["self_s"]["inner"] == pytest.approx(
        (inner.end_ns - inner.start_ns) / 1e9, abs=1e-9)
    mark = log.mark()
    assert log.since(mark) == {"self_s": {}, "counts": {}}


def test_self_time_subtracts_the_union_of_overlapping_children():
    log = mon.SpanLog()
    log.spans = [mon.Span("run", 0, 100, None, None, {}),
                 mon.Span("jax.trace_lower", 10, 40, 0, None, {}),
                 mon.Span("jax.trace_lower", 20, 50, 0, None, {}),
                 mon.Span("init", 60, 70, 0, None, {}),
                 mon.Span("jax.trace_lower", 62, 65, 3, None, {})]
    self_s = log.since()["self_s"]
    assert self_s["run"] == pytest.approx(50e-9)      # 100 - |[10,50]+[60,70]|
    assert self_s["init"] == pytest.approx(7e-9)
    assert self_s["jax.trace_lower"] == pytest.approx(43e-9)  # union
    # the driver's own trace: direct children of "run" only
    assert log.union_s("jax.trace_lower", "run") == pytest.approx(40e-9)


def test_nothing_is_booked_without_a_spanlog():
    log = mon.SpanLog()
    with mon.span("engine.run", driver="local"):
        mon.count("engine.traces", key="run_local")
    assert log.spans == [] and log.counts == [] and mon._log is None
    with log:
        with pytest.raises(RuntimeError):
            mon.SpanLog().__enter__()
    assert mon._log is None


def test_jax_compile_events_are_children_of_the_innermost_span():
    with mon.SpanLog() as log:
        with mon.span("outer"):
            with mon.span("inner"):
                jax.jit(lambda x: x * 3 + 1)(np.arange(5)).block_until_ready()
    jax_spans = [s for s in log.spans if s.name.startswith("jax.")]
    assert {s.name for s in jax_spans} == {"jax.trace_lower",
                                           "jax.compile_load"}
    assert all(s.parent == 1 for s in jax_spans)
    inner = log.spans[1]
    assert all(inner.start_ns <= s.start_ns <= s.end_ns <= inner.end_ns
               for s in jax_spans)
    self_s = log.since()["self_s"]
    assert self_s["inner"] < (inner.end_ns - inner.start_ns) / 1e9
    # after detach JAX's events go nowhere
    jax.jit(lambda x: x * 5)(np.arange(3))
    assert len(log.spans) == 2 + len(jax_spans)


def test_spans_share_the_profiler_clock(tmp_path):
    """Every repro.* span sits in the profiler's host plane at the SpanLog's
    times plus one constant offset."""
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with mon.SpanLog() as log:
        for i in range(5):
            with mon.span("clock", run_id=i):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    traced = sorted((e.start_ns, e.end_ns) for plane in pd.planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name == "repro.clock")
    logged = sorted((s.start_ns, s.end_ns) for s in log.spans)
    assert len(traced) == len(logged) == 5
    offs = [t - s for (ta, tb), (sa, sb) in zip(traced, logged)
            for t, s in ((ta, sa), (tb, sb))]
    assert max(offs) - min(offs) < 100_000   # ns


def _engine(**kw):
    b, bkw = t0t1_builder()
    return b.build(**{**bkw, **kw})


def test_engine_counts_one_trace_per_program():
    # the sequential fold compiles in half the batched dispatch's time, and
    # the count does not depend on the execution path
    w, o, e, s = _engine(n_agents=1, batched_dispatch=False)
    with mon.SpanLog() as log:
        eng = Engine(w, o, e, s, trace_cap=256)
        eng.run_local(max_windows=8)
        assert log.since()["counts"] == {"engine.traces": {"run_local": 1}}
        first = log.mark()
        eng.run_local(max_windows=8)
        assert log.since(first)["counts"] == {}      # cached: no new trace
    names = [sp.name for sp in log.spans if not sp.name.startswith("jax.")]
    assert names == ["engine.build", "engine.run", "engine.init_state",
                     "engine.run", "engine.init_state"]
    # the driver's trace and lowering is booked under its engine.run span
    assert log.union_s("jax.trace_lower", "engine.run") > 0
    assert log.union_s("jax.trace_lower", "engine.run", first) == 0


def test_every_stage_scope_reaches_the_lowered_program():
    """Two agents and both streams: one window program holds every stage.
    A new Engine traces its program anew: one more count."""
    w, o, e, s = _engine(n_agents=2)
    with mon.SpanLog() as log:
        eng = Engine(w, o, e, s, trace_cap=64, trace_stream=TraceStream(),
                     metrics_stream=MetricsStream())
        text = eng._window_fn(16).lower(eng.init_state()).as_text(
            debug_info=True)
    assert log.since()["counts"] == {"engine.traces": {"window": 1}}
    found = set(re.findall(r"superstep/(\w+)", text))
    assert found == set(mon.STAGES)
