"""Host-streaming observability tests: trace drain + metrics snapshots.

The contract under test (docs/architecture.md, "Streaming trace"): with a
:class:`TraceStream` attached, the engine drains its device-side trace ring
to the host at window boundaries, so a run whose total trace exceeds the
in-device ring still completes with ``C_TRACE_DROP == 0`` and the streamed
trace byte-identical to the sequential heapq oracle — under any drain
cadence, ring size >= the exec width, spill pressure, and adaptive width
changes. :class:`MetricsStream` turns the same window boundary into periodic
JSON-lines fleet snapshots named by the registry counter table.
"""

import io
import json

import numpy as np
import pytest

from conftest import t0t1_builder
from repro.core import Engine, MetricsStream, TraceStream, merged_engine_trace
from repro.core import monitoring as mon
from repro.core.policy import ExecPolicy


def build(n_agents, *, pool_cap=128, exec_cap=None, exec_policy=None):
    b, kw = t0t1_builder()
    kw["pool_cap"] = pool_cap
    if exec_cap is not None:
        kw["exec_cap"] = exec_cap
    if exec_policy is not None:
        kw["exec_policy"] = exec_policy
    return b.build(n_agents=n_agents, **kw)


@pytest.fixture(scope="module")
def oracle(t0t1_oracle):
    _w, _c, trace = t0t1_oracle
    return trace


@pytest.fixture(scope="module")
def buffered_ref(oracle):
    """The in-device big-buffer run the stream must match row-for-row."""
    w, o, e, s = build(4, exec_cap=16)
    st = Engine(w, o, e, s, trace_cap=4096).run_local()
    trace = merged_engine_trace(np.asarray(st.trace), np.asarray(st.trace_n))
    assert trace == oracle  # the PR 2-6 contract this PR extends
    return trace


# --------------------------------------------------------------- streaming
def test_stream_past_cap_zero_drop(oracle, buffered_ref):
    """A 48-row ring, per-agent totals well past it: full trace streamed,
    nothing dropped, merged order == in-device == oracle."""
    ts = TraceStream()
    w, o, e, s = build(4, exec_cap=16)
    eng = Engine(w, o, e, s, trace_cap=48, trace_stream=ts, drain_every=4)
    st = eng.run_local()
    c = np.asarray(st.counters)
    assert int(c[:, mon.C_TRACE_DROP].sum()) == 0
    assert int(np.asarray(st.trace_n).sum()) == len(oracle)
    assert ts.n_streamed == len(oracle)
    assert ts.merged() == buffered_ref == oracle


def test_stream_ring_must_hold_one_window():
    """The zero-drop invariant needs ring >= exec width: the driver refuses
    a ring the drain cannot keep ahead of."""
    w, o, e, s = build(2, exec_cap=64)
    eng = Engine(w, o, e, s, trace_cap=32, trace_stream=TraceStream())
    with pytest.raises(ValueError, match="ring too small"):
        eng.run_local()


def test_stream_requires_trace_cap():
    w, o, e, s = build(2)
    with pytest.raises(ValueError, match="trace_cap"):
        Engine(w, o, e, s, trace_stream=TraceStream())
    with pytest.raises(ValueError, match="drain_every"):
        Engine(w, o, e, s, trace_cap=32, drain_every=0)


def test_stream_adaptive_width_changes(oracle):
    """The drain sizes its forced-drain test with the *current* rung width,
    so ladder moves mid-run keep the invariant."""
    ts = TraceStream()
    w, o, e, s = build(4, exec_policy=ExecPolicy(ladder=(4, 8, 16, 32)))
    eng = Engine(w, o, e, s, trace_cap=40, trace_stream=ts, drain_every=3)
    st = eng.run_adaptive()
    assert int(np.asarray(st.counters)[:, mon.C_TRACE_DROP].sum()) == 0
    assert ts.merged() == oracle


def test_stream_with_pallas_trace_rank(oracle):
    """The Pallas prefix-sum hook (kernels.ops.trace_rank) drives the ring
    append to the same bytes as the default XLA cumsum."""
    from repro.kernels import ops

    ts = TraceStream()
    w, o, e, s = build(4, exec_cap=16)
    eng = Engine(
        w,
        o,
        e,
        s,
        trace_cap=48,
        trace_stream=ts,
        drain_every=4,
        trace_fn=ops.trace_rank,
    )
    st = eng.run_local()
    assert int(np.asarray(st.counters)[:, mon.C_TRACE_DROP].sum()) == 0
    assert ts.merged() == oracle


def test_stream_gap_detection():
    """A lost span is loud: reassembly refuses non-contiguous coverage."""
    ts = TraceStream()
    ts.begin(1)
    ring = np.arange(64 * 4, dtype=np.int32).reshape(64, 4)
    ts.on_drain(0, 0, 8, ring)
    ts.on_drain(0, 16, 8, ring)  # [8, 16) never arrived
    ts.finalize(ring[None, :, :], np.array([24]), np.array([24]))
    with pytest.raises(RuntimeError, match="gap"):
        ts.agent_rows(0)


def test_stream_duplicate_spans_idempotent(oracle):
    """Unordered io_callback delivery may replay a span; keyed segments make
    that a no-op."""
    ts = TraceStream()
    w, o, e, s = build(2, exec_cap=16)
    eng = Engine(w, o, e, s, trace_cap=64, trace_stream=ts, drain_every=5)
    eng.run_local()
    segs = {a: dict(d) for a, d in ts._segments.items()}
    for a, d in segs.items():
        for start, rows in d.items():
            ts.on_drain(a, start, rows.shape[0], _ring_of(rows, start))
    assert ts.merged() == oracle


def _ring_of(rows, start, cap=64):
    """A cap-row ring holding ``rows`` at positions (start + i) % cap."""
    ring = np.zeros((cap, 4), np.int32)
    idx = (start + np.arange(rows.shape[0])) % cap
    ring[idx] = rows
    return ring


def test_stream_checkpoint_resume_past_cap_zero_drop(oracle, buffered_ref, tmp_path):
    """Checkpoint-PR satellite: a streamed run checkpointed PAST trace_cap
    and resumed yields ``merged()`` byte-identical to the big-buffer
    reference with zero ``C_TRACE_DROP`` — the checkpoint carries both the
    device ring (+ ``trace_tail`` cursor) and the host-side drained spans,
    which the resume must reassemble because the pre-checkpoint rows no
    longer exist on the device."""
    from repro.checkpoint import SimCheckpointer

    def make(every=0):
        ts = TraceStream()
        w, o, e, s = build(2, exec_cap=16)
        ck = SimCheckpointer(str(tmp_path), every=every, keep=99)
        eng = Engine(
            w, o, e, s, trace_cap=32, trace_stream=ts, drain_every=4, checkpointer=ck
        )
        return ts, eng

    ts, eng = make(every=6)
    st = eng.run_local()
    assert ts.merged() == buffered_ref == oracle
    # find a saved window whose cumulative trace already exceeded the ring
    chosen = None
    for cand in eng.checkpointer.all_steps():
        ts2, eng2 = make()
        rec = eng2.restore(step=cand)
        if int(np.asarray(rec.state.trace_n).max()) > 32:
            chosen = cand
            break
    assert chosen is not None, "no checkpoint past trace_cap — scenario too small"
    st2 = eng2.run_local(state=rec.state)
    assert int(np.asarray(st2.counters)[:, mon.C_TRACE_DROP].sum()) == 0
    assert ts2.merged() == buffered_ref == oracle


def test_observability_concatenates_across_resume(tmp_path):
    """Fleet satellite: MetricsStream interval records AND TraceStream
    segments concatenate *exactly* across a checkpoint/resume boundary —
    the checkpoint carries the host-side emitted records/drained spans
    (``metrics/`` + ``trace_seg/`` leaves), restore stages them, and the
    resumed run emits only the post-checkpoint intervals, so the two runs'
    observability is indistinguishable record-for-record."""
    from repro.checkpoint import SimCheckpointer

    def make(every=0):
        ts, ms = TraceStream(), MetricsStream(interval=4)
        w, o, e, s = build(3, exec_cap=16)
        ck = SimCheckpointer(str(tmp_path), every=every, keep=99)
        eng = Engine(
            w, o, e, s, trace_cap=32, trace_stream=ts, metrics_stream=ms,
            drain_every=4, checkpointer=ck,
        )
        return ts, ms, eng

    ts, ms, eng = make(every=6)
    eng.run_local()
    ref_lines, ref_trace = list(ms.lines), ts.merged()
    steps = eng.checkpointer.all_steps()
    step = steps[len(steps) // 2]
    # non-vacuous: the chosen boundary splits the interval records
    wins = [r["window"] for r in ref_lines if not r.get("final")]
    assert any(w <= step for w in wins) and any(w > step for w in wins)

    ts2, ms2, eng2 = make()
    rec = eng2.restore(step=step)
    eng2.run_local(state=rec.state)
    assert ms2.lines == ref_lines
    assert ts2.merged() == ref_trace


def test_metrics_resume_does_not_rewrite_out(tmp_path):
    """Restored records seed ``lines`` for exact concatenation but are NOT
    re-written to ``out`` — a resumed process's stdout carries only what it
    emitted itself (the pre-crash lines already left the dead process)."""
    from repro.checkpoint import SimCheckpointer

    w, o, e, s = build(2, exec_cap=16)
    ck = SimCheckpointer(str(tmp_path), every=6, keep=99)
    ms = MetricsStream(interval=4, out=io.StringIO())
    Engine(w, o, e, s, metrics_stream=ms, checkpointer=ck).run_local()
    step = ck.all_steps()[0]
    out2 = io.StringIO()
    ms2 = MetricsStream(interval=4, out=out2)
    eng2 = Engine(
        w, o, e, s, metrics_stream=ms2,
        checkpointer=SimCheckpointer(str(tmp_path)),
    )
    rec = eng2.restore(step=step)
    eng2.run_local(state=rec.state)
    emitted = [json.loads(x) for x in out2.getvalue().strip().splitlines()]
    assert emitted == [r for r in ms2.lines if r["window"] > step]


# ----------------------------------------------------------------- metrics
def test_metrics_stream_json_lines(oracle):
    out = io.StringIO()
    ms = MetricsStream(interval=8, out=out)
    w, o, e, s = build(4, exec_cap=16)
    eng = Engine(w, o, e, s, metrics_stream=ms)
    st = eng.run_local()
    lines = [json.loads(x) for x in out.getvalue().strip().splitlines()]
    assert lines and lines == ms.lines
    names = set(eng.registry.counters)
    for rec in lines:
        assert rec["agents"] == 4
        assert set(rec["counters"]) == names
        if not rec.get("final"):
            assert rec["window"] % 8 == 0
    final = lines[-1]
    assert final["final"] is True
    assert final["counters"]["EVENTS"] == len(oracle)
    assert final["gvt"] == int(np.asarray(st.t_now).max())
    assert ms.latest == final
    # monotone within the run
    gvts = [r["gvt"] for r in lines]
    assert gvts == sorted(gvts)


def test_metrics_stream_validation():
    with pytest.raises(ValueError, match="interval"):
        MetricsStream(interval=0)


def test_snapshot_names_and_totals():
    w, o, e, s = build(2, exec_cap=16)
    eng = Engine(w, o, e, s, trace_cap=256)
    st = eng.run_local()
    snap = mon.snapshot(np.asarray(st.counters), eng.registry)
    assert set(snap) == set(eng.registry.counters)
    assert snap["EVENTS"] == int(np.asarray(st.counters)[:, mon.C_EVENTS].sum())
    # registry-free fallback covers exactly the builtins
    assert set(mon.snapshot(np.asarray(st.counters))) == {
        name for name, _ in mon.BUILTIN_COUNTERS
    }


def test_metrics_stream_book_overlay():
    """Fleet counters are booked host-side (``MetricsStream.book``) and
    merged into every emitted record — the in-graph vector never carries
    them, so a resumed EngineState stays byte-identical."""
    ms = MetricsStream(interval=8)
    ms.book("PREEMPT")
    ms.book("RESUME", 2)
    w, o, e, s = build(2, exec_cap=16)
    st = Engine(w, o, e, s, metrics_stream=ms).run_local()
    for rec in ms.lines:
        assert rec["counters"]["PREEMPT"] == 1
        assert rec["counters"]["RESUME"] == 2
        assert rec["counters"]["RESHARD"] == 0
    c = np.asarray(st.counters)
    assert int(c[:, list(mon.FLEET_COUNTERS)].sum()) == 0
    assert mon.FLEET_COUNTERS == (mon.C_PREEMPT, mon.C_RESUME, mon.C_RESHARD)


def test_counter_docs_follow_registry():
    from repro.core.components import BUILTIN

    reg = BUILTIN.extend()
    idx = reg.counter("MY_METRIC", "something the extension counts")
    assert reg.counters["MY_METRIC"] == idx
    assert reg.counter_docs["MY_METRIC"] == "something the extension counts"
    assert reg.counter_docs["EVENTS"] == dict(mon.BUILTIN_COUNTERS)["EVENTS"]
    # the builtin registry is untouched
    assert "MY_METRIC" not in BUILTIN.counters


def test_gen_counter_docs_up_to_date():
    """The committed docs table matches the declarations (the CI drift gate,
    runnable locally)."""
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "tools" / "gen_counter_docs.py"), "--check"],
        capture_output=True,
        text=True,
    )
    assert out.returncode == 0, out.stdout + out.stderr


# ------------------------------------------------------ hypothesis property
try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised by the no-hypothesis job
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    stream_params = hst.fixed_dictionaries(
        dict(
            drain_every=hst.integers(1, 24),
            trace_cap=hst.sampled_from([40, 48, 64, 96]),
            width=hst.sampled_from([8, 16, 32]),
            adaptive=hst.booleans(),
            metrics_interval=hst.integers(1, 40),
        )
    )

    @settings(max_examples=8, deadline=None)
    @given(p=stream_params)
    def test_streamed_equals_buffered_equals_oracle(p, oracle, buffered_ref):
        """The tentpole property: for any drain cadence, ring size >= width,
        static or adaptive width, the streamed trace is byte-identical to
        the in-device big-buffer trace and to the sequential oracle, with
        C_TRACE_DROP == 0 — spill and ring wrap included (width 8 spills
        heavily; cap 40 vs per-agent totals forces many wraps)."""
        ts = TraceStream()
        ms = MetricsStream(interval=p["metrics_interval"])
        if p["adaptive"]:
            ladder = tuple(sorted({4, p["width"]}))
            w, o, e, s = build(4, exec_policy=ExecPolicy(ladder=ladder))
        else:
            w, o, e, s = build(4, exec_cap=p["width"])
        eng = Engine(
            w,
            o,
            e,
            s,
            trace_cap=p["trace_cap"],
            trace_stream=ts,
            metrics_stream=ms,
            drain_every=p["drain_every"],
        )
        st = eng.run_adaptive() if p["adaptive"] else eng.run_local()
        c = np.asarray(st.counters)
        assert int(c[:, mon.C_TRACE_DROP].sum()) == 0
        assert ts.merged() == buffered_ref == oracle
        assert ms.latest["counters"]["EVENTS"] == len(oracle)
