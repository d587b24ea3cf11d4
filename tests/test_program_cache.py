"""The process-wide program cache (docs/architecture.md, "Program cache"):
engines of one structure share one jitted driver program, so only the first
run of a structure traces it; a change in any keyed value traces anew; the
streaming programs, which carry one engine's callbacks, are never shared;
the cache is bounded."""
import dataclasses
import gc
import os
import sys
import threading
import weakref

import jax
import numpy as np
import pytest

from conftest import t0t1_builder
from repro.core import (Engine, TraceStream, merged_engine_trace,
                        run_sequential)
from repro.core import monitoring as mon
from repro.core.engine import (_PROGRAM_CACHE_SIZE, _programs, clear_programs,
                               select_events_xla)

# counters only the engine books (windows, routing, dispatch and pool
# diagnostics, gauges): the sequential oracle leaves them at 0
ENGINE_ONLY = {mon.C_WINDOWS, mon.C_LP_LOCAL, mon.C_MSGS_REMOTE,
               mon.C_EXEC_SPILL, mon.C_TRACE_DROP, *mon.BATCH_DIAG_COUNTERS,
               *mon.POOL_DIAG_COUNTERS, *mon.GAUGE_COUNTERS}


def _built(wan_bw=2.0, n_agents=1, max_cpu=4, **kw):
    b, bkw = t0t1_builder(wan_bw=wan_bw)
    b.dims["max_cpu"] = max_cpu
    # the sequential fold compiles in half the batched dispatch's time; the
    # cache does not depend on the execution path
    return b.build(n_agents=n_agents, **{**bkw, "batched_dispatch": False,
                                         **kw})


def _host(st):
    return jax.tree.map(np.asarray, st)


def _assert_same(a, b):
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        assert x.tobytes() == y.tobytes(), jax.tree_util.keystr(path)


def test_engines_of_one_structure_share_one_program():
    """Two sweep points, 2.0 and 0.5 MB/tick: one trace, the second run a
    hit; each result equals the oracle and the same run on an emptied
    cache, byte for byte; the cached program holds neither engine."""
    clear_programs()
    built = {bw: _built(bw) for bw in (2.0, 0.5)}
    with mon.SpanLog() as log:
        engines = {bw: Engine(*b, trace_cap=256) for bw, b in built.items()}
        shared = {bw: _host(e.run_local()) for bw, e in engines.items()}
    assert log.since()["counts"] == {
        "engine.traces": {"run_local": 1},
        "engine.programs": {"miss": 1, "hit": 1}}
    first = weakref.ref(engines[2.0])
    del engines
    gc.collect()
    assert first() is None

    clear_programs()
    fresh = _host(Engine(*built[0.5], trace_cap=256).run_local())
    _assert_same(shared[0.5], fresh)

    traces = {}
    for bw, st in shared.items():
        ow, oc, otrace = run_sequential(*built[bw])
        traces[bw] = merged_engine_trace(st.trace, st.trace_n)
        assert traces[bw] == otrace
        for f, u, v in zip(ow._fields, ow, st.world):
            assert np.asarray(u).tobytes() == v[0].tobytes(), f
        booked = [i for i in range(len(oc)) if i not in ENGINE_ONLY]
        np.testing.assert_array_equal(st.counters[0][booked],
                                      np.asarray(oc)[booked])
    assert traces[2.0] != traces[0.5]


def _mine(time_key, seq, exec_cap):
    return select_events_xla(time_key, seq, exec_cap)


def _reversed_own(built):
    w, o, e, s = built
    return w, jax.tree.map(lambda x: x[::-1], o), e, s


# per structure-key field: (build kwargs, the base engine's kwargs, a change
# of the built scenario, the variant engine's kwargs); each variant must
# miss the base's program and trace its own
VARIANTS = {
    "spec_pool_cap": (dict(n_agents=1), {}, lambda b: b[:3] + (
        dataclasses.replace(b[3], pool_cap=128),), {}),
    "spec_t_end": (dict(n_agents=1), {}, lambda b: b[:3] + (
        dataclasses.replace(b[3], t_end=4000),), {}),
    "trace_cap": (dict(n_agents=1), dict(trace_cap=64), None,
                  dict(trace_cap=0)),
    "own_values": (dict(n_agents=2), {}, _reversed_own, {}),
    "select_fn": (dict(n_agents=1), {}, None, dict(select_fn=_mine)),
}


@pytest.mark.parametrize("field", sorted(VARIANTS))
def test_a_change_in_any_keyed_value_traces_anew(field):
    """The base's program is in the cache; the variant misses it and
    traces a program of its own."""
    build_kw, base_kw, change, var_kw = VARIANTS[field]
    base = _built(**build_kw)
    var = change(base) if change else base
    clear_programs()
    Engine(*base, **base_kw)._window_fn(16)
    with mon.SpanLog() as log:
        eng = Engine(*base, **base_kw)
        eng._window_fn(16)
        eng = Engine(*var, **{**base_kw, **var_kw})
        eng._window_fn(16).trace(eng.init_state())
    assert log.since()["counts"] == {"engine.traces": {"window": 1},
                                     "engine.programs": {"hit": 1,
                                                         "miss": 1}}


def test_wider_tables_share_the_program_and_jit_traces_their_shapes():
    """A table width (``dims``) is a shape of the state, not a keyed value:
    the program is shared and ``jax.jit`` traces it for the new avals."""
    clear_programs()
    with mon.SpanLog() as log:
        for max_cpu in (4, 8):
            eng = Engine(*_built(max_cpu=max_cpu))
            eng._window_fn(16).trace(eng.init_state())
    assert log.since()["counts"] == {"engine.traces": {"window": 2},
                                     "engine.programs": {"miss": 1,
                                                         "hit": 1}}


def test_streaming_engines_keep_their_own_programs():
    """Two engines of one structure, each with a TraceStream: each traces
    its own window program and its stream receives only its own rows."""
    built = {bw: _built(bw, exec_cap=16) for bw in (2.0, 0.5)}
    streams = {bw: TraceStream() for bw in built}
    with mon.SpanLog() as log:
        engines = {bw: Engine(*b, trace_cap=48, trace_stream=streams[bw],
                              drain_every=4) for bw, b in built.items()}
        for eng in engines.values():
            eng.run_local()
    assert log.since()["counts"] == {"engine.traces": {"window": 2}}
    merged = {bw: ts.merged() for bw, ts in streams.items()}
    for bw, b in built.items():
        assert merged[bw] == run_sequential(*b)[2]
    assert merged[2.0] != merged[0.5]


def test_program_cache_is_bounded_and_drops_the_least_recent():
    clear_programs()
    eng = Engine(*_built())
    made = []
    for i in range(_PROGRAM_CACHE_SIZE):
        eng._shared_program(("probe", i),
                            lambda core, i=i: made.append(i) or i)
    assert eng._shared_program(("probe", 0), lambda core: -1) == 0  # a hit
    eng._shared_program(("probe", "new"), lambda core: "new")
    assert len(_programs) == _PROGRAM_CACHE_SIZE
    keys = [k[0] for k in _programs]
    assert ("probe", 1) not in keys                 # the least recent went
    assert keys[-2:] == [("probe", 0), ("probe", "new")]
    assert made == list(range(_PROGRAM_CACHE_SIZE))
    clear_programs()


def test_program_cache_lookups_from_many_threads():
    """More threads than cores look up keys beyond the bound in the
    process-wide cache: every lookup returns its own key's program and the
    cache stays bounded."""
    clear_programs()
    eng = Engine(*_built())
    n_keys = _PROGRAM_CACHE_SIZE + 8
    errors = []

    def work(t):
        try:
            for i in range(400):
                k = (t * 7 + i) % n_keys
                got = eng._shared_program(("probe", k), lambda core, k=k: k)
                if got != k:
                    errors.append((k, got))
        except Exception as e:   # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(2 * (os.cpu_count() or 4))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert errors == []
    assert len(_programs) == _PROGRAM_CACHE_SIZE
    clear_programs()
