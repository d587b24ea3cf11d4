"""Monitoring-driven adaptive exec width (core/policy.py + Engine.run_adaptive).

Pins the PR 5 acceptance claim: on a spill-heavy scenario the adaptive ladder
completes in *fewer windows* than the static exec_cap=256 default while the
merged trace and the final world state stay byte-identical to the sequential
oracle — spilling is exact for any width sequence, so the policy trades only
window count. Plus unit coverage for the ladder decision function and the
policy plumbing (spec normalization, single-rung equivalence, jit cache
reuse).
"""

import numpy as np
import pytest

from repro.core import (
    Engine,
    ScenarioBuilder,
    events as ev,
    merged_engine_trace,
    run_sequential,
)
from repro.core import monitoring as mon
from repro.core import policy as pol
from repro.core.engine import _programs, clear_programs
from conftest import t0t1_builder

LADDER = pol.ExecPolicy(ladder=(64, 256, 1024))


def stats(processed=0, spilled=0, rows=0, occupancy=0.0):
    return pol.WindowStats(
        processed=processed, spilled=spilled, rows=rows, occupancy=occupancy
    )


# ------------------------------------------------------------ decision unit


def test_grow_on_spill_pressure():
    assert pol.choose_rung(LADDER, 1, stats(processed=256, spilled=100)) == 2


def test_grow_near_pool_saturation():
    assert pol.choose_rung(LADDER, 0, stats(processed=10, occupancy=0.9)) == 1


def test_shrink_on_sparse_window():
    assert pol.choose_rung(LADDER, 2, stats(processed=5, rows=5)) == 1


def test_hold_on_moderate_load():
    s = stats(processed=200, rows=200)  # too big for the lower rung
    assert pol.choose_rung(LADDER, 1, s) == 1


def test_scatter_volume_blocks_shrink():
    """C_BATCH_ROWS is a utilization signal: heavy scatter, no shrink."""
    s = stats(processed=5, rows=200)
    assert pol.choose_rung(LADDER, 2, s) == 2


def test_clamped_at_ladder_ends():
    assert pol.choose_rung(LADDER, 2, stats(spilled=10_000)) == 2
    assert pol.choose_rung(LADDER, 0, stats()) == 0


def test_ladder_validation():
    with pytest.raises(ValueError, match="ascending"):
        pol.ExecPolicy(ladder=(64, 64))
    with pytest.raises(ValueError, match="non-empty"):
        pol.ExecPolicy(ladder=())
    with pytest.raises(ValueError, match="init_rung"):
        pol.ExecPolicy(ladder=(8,), init_rung=3)
    assert pol.normalize(17).ladder == (17,)
    assert pol.normalize(LADDER) is LADDER


def test_default_ladder_shape():
    lad = pol.default_ladder(4096)
    assert lad[0] == 64 and 256 in lad and lad[-1] == 4096
    assert all(b > a for a, b in zip(lad, lad[1:]))


def test_window_stats_extraction():
    prev = np.zeros((2, mon.N_COUNTERS), np.int32)
    cur = np.zeros((2, mon.N_COUNTERS), np.int32)
    cur[0, mon.C_EVENTS] = 10
    cur[1, mon.C_EVENTS] = 30
    cur[1, mon.C_EXEC_SPILL] = 5
    cur[0, mon.C_POOL_OCC] = 96
    s = pol.window_stats(prev, cur, pool_cap=128)
    assert s.processed == 30 and s.spilled == 5 and s.occupancy == 0.75


# -------------------------------------------------- spec / builder plumbing


def test_spec_exec_policy_accepts_int_and_ladder():
    from repro.core.registry import RegistryError

    b, kw = t0t1_builder()
    *_, spec = b.build(n_agents=1, exec_cap=17, **kw)
    assert spec.exec_policy == 17 and spec.exec_cap == 17
    b, kw = t0t1_builder()
    *_, spec = b.build(n_agents=1, exec_policy=LADDER, **kw)
    assert spec.exec_cap == 64  # init rung width
    b, kw = t0t1_builder()
    with pytest.raises(RegistryError, match="not both"):
        b.build(n_agents=1, exec_cap=4, exec_policy=LADDER, **kw)


# ---------------------------------------------------------------- acceptance


def spill_heavy(width=512, n_ticks=3, lookahead=4, pool_cap=2048, **kw):
    """Every window offers ``width`` same-tick events: static 256 spills."""
    b = ScenarioBuilder(max_cpu=1, queue_cap=2, max_link=1, max_flow=2)
    sinks = [b.add_idle_lp() for _ in range(width)]
    for t in range(n_ticks):
        for lp in sinks:
            b.add_event(time=1 + lookahead * t, kind=ev.K_NOOP, src=lp, dst=lp)
    return b.build(
        n_agents=1,
        lookahead=lookahead,
        t_end=lookahead * (n_ticks + 1) + 2,
        pool_cap=pool_cap,
        emit_cap=64,
        **kw,
    )


def test_adaptive_beats_static_windows_and_stays_oracle_exact():
    """The acceptance criterion: fewer windows than static exec_cap=256,
    byte-identical merged trace + final world vs the sequential oracle."""
    built_s = spill_heavy(exec_cap=256)
    world, own, init_ev, spec_s = built_s
    ow, _oc, otrace = run_sequential(world, own, init_ev, spec_s)
    st_s = Engine(world, own, init_ev, spec_s, trace_cap=4096).run_local()

    ladder = pol.ExecPolicy(ladder=(256, 512))
    world, own, init_ev, spec_a = spill_heavy(exec_policy=ladder)
    eng = Engine(world, own, init_ev, spec_a, trace_cap=4096)
    st_a = eng.run_adaptive()

    w_static = int(np.asarray(st_s.windows)[0])
    w_adapt = int(np.asarray(st_a.windows)[0])
    assert w_adapt < w_static
    assert max(eng.adaptive_rungs) == 1  # the ladder actually grew

    tr_a = merged_engine_trace(np.asarray(st_a.trace), np.asarray(st_a.trace_n))
    tr_s = merged_engine_trace(np.asarray(st_s.trace), np.asarray(st_s.trace_n))
    assert tr_a == tr_s == otrace
    for name, a, b in zip(st_a.world._fields, st_a.world, st_s.world):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    # same events processed, strictly less spill
    ca = np.asarray(st_a.counters)[0]
    cs = np.asarray(st_s.counters)[0]
    assert ca[mon.C_EVENTS] == cs[mon.C_EVENTS]
    assert ca[mon.C_EXEC_SPILL] < cs[mon.C_EXEC_SPILL]


def test_adaptive_shrinks_back_on_sparse_tail():
    """A dense burst followed by a sparse tail: the ladder grows for the
    burst, returns to the bottom rung on the tail — and stays oracle-exact."""
    lookahead = 4
    b = ScenarioBuilder(max_cpu=1, queue_cap=2, max_link=1, max_flow=2)
    sinks = [b.add_idle_lp() for _ in range(64)]
    for lp in sinks:  # dense same-tick burst
        b.add_event(time=1, kind=ev.K_NOOP, src=lp, dst=lp)
    for i in range(8):  # sparse one-event tail
        t = 100 + 4 * lookahead * i
        b.add_event(time=t, kind=ev.K_NOOP, src=sinks[0], dst=sinks[0])
    world, own, init_ev, spec = b.build(
        n_agents=1,
        lookahead=lookahead,
        t_end=100 + 4 * lookahead * 9,
        pool_cap=256,
        emit_cap=16,
        exec_policy=pol.ExecPolicy(ladder=(8, 32, 64)),
    )
    _ow, _oc, otrace = run_sequential(world, own, init_ev, spec)
    eng = Engine(world, own, init_ev, spec, trace_cap=4096)
    st = eng.run_adaptive()
    tr = merged_engine_trace(np.asarray(st.trace), np.asarray(st.trace_n))
    assert tr == otrace
    assert max(eng.adaptive_rungs) > 0  # grew for the burst
    assert eng.adaptive_rungs[-1] == 0  # drained back down


def test_single_rung_adaptive_equals_static_run():
    """A one-rung ladder is the static engine, window for window."""
    built = spill_heavy(width=64, exec_cap=256)
    world, own, init_ev, spec = built
    st_s = Engine(world, own, init_ev, spec, trace_cap=4096).run_local()
    eng = Engine(world, own, init_ev, spec, trace_cap=4096)
    st_a = eng.run_adaptive(policy=256)
    np.testing.assert_array_equal(np.asarray(st_s.windows), np.asarray(st_a.windows))
    np.testing.assert_array_equal(
        np.asarray(st_s.counters), np.asarray(st_a.counters)
    )
    for name, a, b in zip(st_s.world._fields, st_s.world, st_a.world):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_rung_programs_are_jit_cached():
    world, own, init_ev, spec = spill_heavy(
        width=64, exec_policy=pol.ExecPolicy(ladder=(32, 64))
    )
    eng = Engine(world, own, init_ev, spec)
    clear_programs()
    with mon.SpanLog() as log:
        eng.run_adaptive()
        first = log.since()["counts"]
        cached = {k[0] for k in _programs}
        assert cached <= {("window", 32), ("window", 64)} and cached
        # one program per rung used, traced once
        assert first["engine.traces"] == {"window": len(cached)}
        assert first["engine.programs"] == {"miss": len(cached)}
        before = {k: id(v) for k, v in _programs.items()}
        second = log.mark()
        eng.run_adaptive()  # second run: no new entries, no recompiles
        assert {k: id(v) for k, v in _programs.items()} == before
        # the engine kept its rung programs: no lookup, no trace
        assert log.since(second)["counts"] == {}
