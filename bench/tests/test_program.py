"""The readers of the program's own spans, counters and stage scopes, and
the reduction of device op events to time per superstep stage, on
synthetic records and events."""
import pytest

from bench import harness, program
from bench.tests.conftest import ROOT

NEW = ("engine_build_s", "init_state_s", "program_traces",
       "driver_trace_lower_s", "select_device_us", "execute_device_us",
       "route_device_us")


def read(name, record):
    return harness.load_reader(ROOT, name)(record)


def point(windows=100, **program_fields):
    p = dict(events=10, windows=windows, fallback=0, build_s=0.1)
    if program_fields:
        p["program"] = program_fields
    return p


def test_program_readers_average_over_points():
    rec = dict(points=[
        point(self_s={"engine.build": 0.25, "engine.init_state": 0.5},
              traces=4, driver_trace_lower_s=20.0),
        point(self_s={"engine.build": 0.75}, traces=4,
              driver_trace_lower_s=22.0)], trace=None)
    assert read("engine_build_s", rec) == 0.5
    assert read("init_state_s", rec) == 0.25
    assert read("program_traces", rec) == 4.0
    assert read("driver_trace_lower_s", rec) == 21.0


def test_stage_readers_per_window():
    busy = {"gvt": 1e-3, "select": 2e-3, "dispatch": 3e-3, "merge": 1e-3,
            "fallback": 2e-3, "trace": 1e-3, "insert": 4e-3, "sync": 1e-3,
            "other": 9e-3}
    rec = dict(points=[point(60), point(40)],
               trace=dict(busy_s=1.0, window_s=10.0, stage_busy_s=busy))
    assert read("select_device_us", rec) == pytest.approx(30.0)
    assert read("execute_device_us", rec) == pytest.approx(70.0)
    assert read("route_device_us", rec) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_without_their_input(name):
    # an untraced run, a run with no SpanLog attached, a parent program with
    # no stage scopes: None, never 0
    for rec in (dict(points=[point()], trace=None),
                dict(points=[point()], trace=dict(busy_s=1.0, window_s=2.0)),
                dict(points=[point()], trace=dict(stage_busy_s={"other": 1.}))):
        assert read(name, rec) is None


def test_stage_reader_needs_one_of_its_stages():
    rec = dict(points=[point()], trace=dict(stage_busy_s={"gvt": 1e-3}))
    assert read("select_device_us", rec) == pytest.approx(10.0)
    assert read("route_device_us", rec) is None


def test_stage_of_takes_the_innermost_scope():
    name = "jit(run)/while/body/superstep/dispatch/superstep/merge/scatter"
    assert program.stage_of(name) == "merge"
    assert program.stage_of("jit(run)/while/body/add") == program.OTHER
    assert program.stage_of("") == program.OTHER


def viewer_trace(path):
    """A trace viewer file as the profiler writes it beside the xplane: a
    device process with its ops line, op events with a ``tf_op`` argument
    (one without), and the benchmark's window on a host thread."""
    import gzip
    import json
    ev = [{"ph": "M", "pid": 3, "name": "process_name",
           "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
           "args": {"name": "XLA Ops"}},
          {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
           "args": {"name": "XLA Modules"}},
          {"ph": "M", "pid": 701, "name": "process_name",
           "args": {"name": "/host:CPU"}},
          {"ph": "X", "pid": 701, "tid": 9, "ts": 0.5, "dur": 0.2,
           "name": "bench.window"},
          {"ph": "X", "pid": "3", "tid": "2", "ts": "0.5", "dur": "0.1",
           "name": "jit_body(123)"},
          {"ph": "X", "pid": "3", "tid": "3", "ts": "0.5", "dur": "0.1",
           "name": "while.1", "args": {"tf_op": "jit(body)/vmap()/while:"}},
          {"ph": "X", "pid": "3", "tid": "3", "ts": "0.51", "dur": "0.03",
           "name": "fusion.2",
           "args": {"tf_op": "jit(body)/vmap()/while/body/superstep/gvt/"
                             "min:"}},
          {"ph": "X", "pid": "3", "tid": "3", "ts": "0.55", "dur": "0.02",
           "name": "scatter.3",
           "args": {"tf_op": "jit(body)/vmap()/while/body/superstep/"
                             "dispatch/superstep/merge/scatter:"}},
          {"ph": "X", "pid": "3", "tid": "3", "ts": "0.58", "dur": "0.01",
           "name": "copy.4"},
          {"ph": "X", "pid": "3", "tid": "3", "ts": "0.9", "dur": "0.01",
           "name": "fusion.5", "args": {"tf_op": "superstep/route/x:"}},
          {}]
    with gzip.open(path, "wt") as f:
        json.dump({"displayTimeUnit": "ns", "traceEvents": ev}, f)
    return str(path)


def test_op_events_with_their_op_name_reduce_to_stage_self_time(tmp_path):
    ops, window = program.read_ops(viewer_trace(tmp_path / "x.trace.json.gz"))
    assert window == pytest.approx((500.0, 700.0))   # ns
    assert list(ops) == ["/device:TPU:0"]
    assert [name for name, _, _ in ops["/device:TPU:0"]] == [
        "jit(body)/vmap()/while:", "jit(body)/vmap()/while/body/superstep/"
        "gvt/min:", "jit(body)/vmap()/while/body/superstep/dispatch/"
        "superstep/merge/scatter:", "copy.4",   # no tf_op: its own name
        "superstep/route/x:"]
    busy = program.stage_busy_s(ops, *window)
    # the while's 100 ns less its children (30 + 20 + 10) and the unscoped
    # copy's 10 are "other"; the op after the window is left out
    assert busy == pytest.approx({"gvt": 30e-9, "merge": 20e-9,
                                  "other": 50e-9})
    # averaged over devices, like busy_s
    two = dict(ops, **{"/device:TPU:1": []})
    assert program.stage_busy_s(two, *window)["gvt"] == pytest.approx(15e-9)
    assert program.stage_busy_s({}, 0, 1) == {}


def test_idle_gap_label_names_the_innermost_program_span():
    host = [("bench.run", 0, 100), ("repro.orchestrator.run", 5, 95),
            ("repro.engine.run", 10, 90), ("jax.trace_lower", 20, 60),
            ("PjitFunction(run)", 0, 100)]
    assert program.label((30, 50), host) == (
        "bench.run > repro.engine.run > jax.trace_lower")
    assert program.label((70, 80), host) == (
        "bench.run > repro.engine.run > PjitFunction(run)")
    assert program.label((96, 99), host) == "bench.run > PjitFunction(run)"
    assert program.label((200, 300), [("repro.engine.run", 150, 400)]) == (
        "repro.engine.run")


def test_program_record_from_a_spanlog():
    from repro.core import monitoring as mon
    log = mon.SpanLog()
    log.spans = [mon.Span("orchestrator.run", 0, 100, None, 1, {}),
                 mon.Span("engine.run", 10, 90, 0, 1, {}),
                 mon.Span("engine.init_state", 12, 20, 1, 1, {}),
                 mon.Span("jax.trace_lower", 14, 18, 2, 1, {}),
                 mon.Span("jax.trace_lower", 30, 70, 1, 1, {})]
    log.counts = [mon.Count("engine.traces", "run_local"),
                  mon.Count("engine.finalize", "")]
    rec = program.program_record(log, (0, 0))
    assert rec["traces"] == 1
    assert rec["driver_trace_lower_s"] == pytest.approx(40e-9)
    assert rec["self_s"]["engine.run"] == pytest.approx(32e-9)
    assert rec["self_s"]["engine.init_state"] == pytest.approx(4e-9)
