"""Declarative component & handler registry (PR 4).

Pins the api_redesign contract:

* the generated tables are *identical* to the PR 3 hand-written surface
  (World/WorldDelta/WorldOwnership layouts, DELTA_SCHEMA, KIND_TABLE, kind
  ids) — literal snapshots, so a registry regression cannot silently reshape
  the engine;
* registry validation rejects malformed models (duplicate kinds/components,
  field collisions, bad row shapes, non-mutable writes, missing whole-row
  fields, unknown tables/handlers);
* registry-generated dispatch matches the sequential oracle and the
  sequential engine path byte-for-byte on the seed scenarios (fixed +
  hypothesis), i.e. the refactor changed zero semantics;
* a component defined entirely outside core (the replica cache in
  repro/scenarios/cache.py) runs batched, conflict-masked, synced, and
  byte-identical to the oracle — the seam the PR exists for;
* trace-buffer overflow is counted (C_TRACE_DROP) and oracle-equivalence
  comparisons fail loudly instead of comparing truncated traces.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import t0t1_builder
from repro.core import Engine, events as ev, merged_engine_trace, run_sequential
from repro.core import handlers as hd
from repro.core import monitoring as mon
from repro.core.components import (
    BUILTIN,
    World,
    WorldOwnership,
    register_builtin_model,
)
from repro.core.registry import (
    FieldSpec,
    PayloadSpec,
    Registry,
    RegistryError,
    ScenarioBuilderBase,
)
from repro.scenarios.cache import (
    CACHE_LOOKUP,
    CACHE_REGISTRY,
    CacheScenarioBuilder,
    build_churn_scenario,
)
from test_batched_dispatch import assert_states_identical, engine_trace, run_pair

# ---------------------------------------------------------------------------
# Generated tables == the PR 3 hand-written surface (literal snapshots)
# ---------------------------------------------------------------------------

PR3_WORLD_FIELDS = (
    "lp_kind",
    "lp_agent",
    "lp_res",
    "lp_state",
    "lp_lvt",
    "lp_ctx",
    "cpu_power",
    "cpu_busy",
    "cpu_mem",
    "jobq",
    "jobq_n",
    "link_bw",
    "link_lat",
    "flow_active",
    "flow_rem",
    "flow_rate",
    "flow_tlast",
    "flow_links",
    "flow_notify",
    "net_gen",
    "sto_cap",
    "sto_used",
    "sto_rate",
    "sto_flag",
    "gen_interval",
    "gen_left",
    "gen_target",
    "gen_kind",
    "gen_payload",
)
PR3_DELTA_FIELDS = (
    "farm_row",
    "cpu_busy",
    "cpu_mem",
    "jobq",
    "jobq_n",
    "net_row",
    "flow_active",
    "flow_rem",
    "flow_rate",
    "flow_tlast",
    "flow_links",
    "flow_notify",
    "net_gen",
    "sto_row",
    "sto_used",
    "sto_flag",
    "gen_row",
    "gen_left",
)
PR3_DELTA_SCHEMA = {
    "cpu_busy": "farm_row",
    "cpu_mem": "farm_row",
    "jobq": "farm_row",
    "jobq_n": "farm_row",
    "flow_active": "net_row",
    "flow_rem": "net_row",
    "flow_rate": "net_row",
    "flow_tlast": "net_row",
    "flow_links": "net_row",
    "flow_notify": "net_row",
    "net_gen": "net_row",
    "sto_used": "sto_row",
    "sto_flag": "sto_row",
    "gen_left": "gen_row",
}
PR3_KIND_TABLE = (0, 2, 2, 1, 1, 3, 3, 4)
PR3_KIND_IDS = dict(
    K_NOOP=0,
    K_FLOW_START=1,
    K_FLOW_END=2,
    K_JOB_SUBMIT=3,
    K_JOB_END=4,
    K_DATA_WRITE=5,
    K_MIGRATE=6,
    K_GEN_TICK=7,
)


def test_generated_structs_match_pr3_handwritten_layout():
    assert World._fields == PR3_WORLD_FIELDS
    assert hd.WorldDelta._fields == PR3_DELTA_FIELDS
    assert WorldOwnership._fields == ("farm_lp", "net_lp", "sto_lp", "gen_lp")
    assert hd.DELTA_SCHEMA == PR3_DELTA_SCHEMA
    assert tuple(ev.KIND_TABLE) == PR3_KIND_TABLE
    assert ev.N_KINDS == 8 and ev.N_TABLES == 5
    for name, kid in PR3_KIND_IDS.items():
        assert getattr(ev, name) == kid


def test_fresh_registry_regenerates_identical_tables():
    """The drift gate's core claim: re-running the declarations on a fresh
    registry reproduces exactly what core exports."""
    fresh = Registry()
    register_builtin_model(fresh)
    assert fresh.kind_table == BUILTIN.kind_table
    assert fresh.delta_schema == BUILTIN.delta_schema
    assert fresh.world_struct()._fields == World._fields
    assert fresh.delta_struct()._fields == hd.WorldDelta._fields
    assert fresh.sync_plan() == BUILTIN.sync_plan()


def test_check_api_drift_gate_passes():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "tools/check_api.py"
    spec = importlib.util.spec_from_file_location("check_api", path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    assert m.check() == []


# ---------------------------------------------------------------------------
# Validation errors
# ---------------------------------------------------------------------------


def _mini_registry():
    r = Registry()
    r.dim("ways", 4)
    r.component(
        "box",
        fields=dict(
            box_cap=FieldSpec((), jnp.float32),
            box_used=FieldSpec((), jnp.float32, mutable=True),
            box_tags=FieldSpec(("ways",), jnp.int32, mutable=True, fill=-1),
        ),
    )
    return r


def test_duplicate_component_rejected():
    r = _mini_registry()
    with pytest.raises(RegistryError, match="duplicate component"):
        r.component("box", fields=dict(x=FieldSpec((), jnp.int32)))


def test_duplicate_kind_rejected():
    r = _mini_registry()
    r.kind("PUT", table="box")
    with pytest.raises(RegistryError, match="duplicate event kind"):
        r.kind("PUT", table="box")


def test_field_collision_across_components_rejected():
    """World is one flat structure-of-arrays: field names are global."""
    r = _mini_registry()
    dup = dict(box_used=FieldSpec((), jnp.float32, mutable=True))
    with pytest.raises(RegistryError, match="collides"):
        r.component("box2", fields=dup)
    with pytest.raises(RegistryError, match="collides"):
        r.component("box3", fields=dict(lp_kind=FieldSpec((), jnp.int32)))


def test_kind_with_unknown_table_fails_at_seal():
    r = _mini_registry()
    r.kind("PUT", table="nonexistent")
    with pytest.raises(RegistryError, match="not a registered component"):
        r.world_struct()


def test_unknown_dim_in_field_shape_rejected():
    r = Registry()
    bad = dict(x=FieldSpec(("no_such_dim",), jnp.int32))
    with pytest.raises(RegistryError, match="unknown dim"):
        r.component("box", fields=bad)


def test_mutable_float_field_with_nonzero_fill_rejected():
    """Nonzero fills survive sync via an int shift encoding; floats would
    lose byte-exactness, so the declaration is rejected up front."""
    r = Registry()
    bad = dict(x=FieldSpec((), jnp.float32, mutable=True, fill=-1.0))
    with pytest.raises(RegistryError, match="fill=0"):
        r.component("box", fields=bad)


def test_handler_registration_validation():
    r = _mini_registry()
    put = r.kind("PUT", table="box")
    with pytest.raises(RegistryError, match="unknown event kind"):
        r.on("GET")

    @r.on(put)
    def h_put(env, world, counters, e):  # pragma: no cover - stub
        return env.empty_delta(world), counters, None

    with pytest.raises(RegistryError, match="already has handler"):
        r.on(put)(h_put)


def test_missing_handler_fails_make_handlers():
    r = _mini_registry()
    r.kind("PUT", table="box")
    with pytest.raises(RegistryError, match="no handler registered"):
        r.make_handlers(lookahead=1)


def test_sealed_registry_rejects_new_declarations_but_extend_works():
    r = _mini_registry()
    r.world_struct()  # seals structure
    with pytest.raises(RegistryError, match="sealed"):
        r.component("late", fields=dict(x=FieldSpec((), jnp.int32)))
    with pytest.raises(RegistryError, match="sealed"):
        r.kind("LATE")
    r2 = r.extend()
    r2.component("late", fields=dict(late_x=FieldSpec((), jnp.int32)))
    assert "late" in r2.components and "late" not in r.components


def test_payload_spec_validation():
    with pytest.raises(RegistryError, match="at most"):
        PayloadSpec(*[f"f{i}" for i in range(9)])
    with pytest.raises(RegistryError, match="duplicate payload field"):
        PayloadSpec("a", ("a", 1.0))
    p = PayloadSpec("size", ("lp", -1))
    np.testing.assert_array_equal(p.pack(size=3.0), [3.0, -1.0])
    with pytest.raises(RegistryError, match="unknown payload field"):
        p.pack(bogus=1.0)
    assert p.index("lp") == 1
    with pytest.raises(RegistryError, match="float32 or int32"):
        PayloadSpec(("x", 0, jnp.float64))


def test_builder_row_validation():
    from repro.core.registry import ScenarioBuilderBase

    class Generic(ScenarioBuilderBase):
        _registry = CACHE_REGISTRY

    b = CacheScenarioBuilder(cache_ways=4)
    with pytest.raises(RegistryError, match="unknown builder dim"):
        Generic(no_such_dim=3)
    with pytest.raises(RegistryError, match="unknown field"):
        b.add_component("cache", bogus=1)
    with pytest.raises(RegistryError, match="exceeds the declared dim"):
        b.add_cache(cache_keys=[1, 2, 3, 4, 5])  # ways=4
    with pytest.raises(RegistryError, match="rank-0"):
        b.add_cache(cache_ptr=[1, 2])  # scalar field, 1-D value
    with pytest.raises(RegistryError, match="unknown component"):
        b.add_component("nope")


def test_make_delta_enforces_the_delta_contract():
    built, _caches = build_churn_scenario(n_caches=2, n_rounds=1)
    world = built[0]
    reg = CACHE_REGISTRY
    full = dict(
        cache_keys=world.cache_keys[0],
        cache_ptr=jnp.int32(0),
        cache_hits=jnp.int32(0),
        cache_miss=jnp.int32(0),
    )
    d = reg.make_delta(world, "cache", 0, **full)
    assert int(d.cache_row) == 0
    # writing an immutable field is an error, not a silent scatter
    with pytest.raises(RegistryError, match="non-mutable"):
        reg.make_delta(world, "cache", 0, cache_hit_lat=jnp.int32(2), **full)
    # the whole-row-write half of the contract: every mutable field
    with pytest.raises(RegistryError, match="whole-row"):
        reg.make_delta(world, "cache", 0, cache_hits=jnp.int32(1))
    with pytest.raises(RegistryError, match="unknown component"):
        reg.make_delta(world, "disk", 0)


def test_counter_declaration_and_validation():
    """Registry.counter: builtin seed + extension appends + validation."""
    r = _mini_registry()
    assert r.n_counters == mon.N_COUNTERS
    assert r.counters["EVENTS"] == mon.C_EVENTS
    idx = r.counter("BOX_PUTS", "puts served")
    assert idx == mon.N_COUNTERS and r.counter_index("BOX_PUTS") == idx
    with pytest.raises(RegistryError, match="duplicate counter"):
        r.counter("BOX_PUTS")
    with pytest.raises(RegistryError, match="duplicate counter"):
        r.counter("EVENTS")  # builtin names are taken
    with pytest.raises(RegistryError, match="identifier"):
        r.counter("not a name")
    with pytest.raises(RegistryError, match="unknown counter"):
        r.counter_index("NOPE")
    # extend() inherits declared counters; sealing closes declaration
    child = r.extend()
    assert child.counter_index("BOX_PUTS") == idx
    r.world_struct()
    with pytest.raises(RegistryError, match="sealed"):
        r.counter("LATE")


def test_cache_declared_counters_flow_through_engine_and_oracle():
    """The outside-core cache counters (no monitoring.py edit) count the
    same events in the engine (batched + sequential) and the oracle."""
    from repro.scenarios.cache import C_CACHE_FILLS, C_CACHE_LOOKUPS

    built, _caches = build_churn_scenario(
        n_caches=4, n_keys=3, n_rounds=5, cache_ways=8
    )
    world, own, init_ev, spec = built
    assert CACHE_REGISTRY.n_counters == mon.N_COUNTERS + 2
    _ow, oc, _otrace = run_sequential(world, own, init_ev, spec)
    st_b, st_s = run_cache_pair(built)
    for st_x in (st_b, st_s):
        c = np.asarray(st_x.counters)[0]
        assert c.shape[0] == CACHE_REGISTRY.n_counters
        assert c[C_CACHE_LOOKUPS] == 4 * 5  # one lookup per round
        assert c[C_CACHE_FILLS] == 4 * 3  # one fill per cold miss
    oc = np.asarray(oc)
    assert oc[C_CACHE_LOOKUPS] == 20 and oc[C_CACHE_FILLS] == 12


# ---------------------------------------------------------------------------
# Payload dtype views: int columns survive the float32 lanes bit-exact
# ---------------------------------------------------------------------------


def test_payload_dtype_views_declaration():
    p = PayloadSpec(("token", 0, jnp.int32), "size", ("lp", -1))
    assert p.dtypes["token"] == jnp.dtype(jnp.int32)
    assert p.dtypes["size"] == jnp.dtype(jnp.float32)
    big = (1 << 31) - 1
    row = p.pack(token=big, size=2.5)
    assert row.dtype == np.float32
    # bit-exact decode from the packed float lanes (host + traced)
    assert int(np.asarray(p.get(jnp.asarray(row), "token"))) == big
    np.testing.assert_allclose(np.asarray(p.get(jnp.asarray(row), "size")), 2.5)
    row_j = p.pack_jax(token=jnp.int32(-123456789), size=1.0)
    assert row_j.shape == (ev.PAYLOAD,)
    assert int(np.asarray(p.get(row_j, "token"))) == -123456789


def test_31bit_int_payload_survives_engine_and_oracle():
    """The PR 5 acceptance test for dtype views: a 31-bit id — whose bit
    pattern is a float32 NaN — rides an event payload through the builder,
    the batched engine, routing, and the heapq oracle without losing a bit.
    (Numerically, float32 would round any int above 2^24.)"""
    reg = BUILTIN.extend()
    reg.component(
        "idsink",
        fields=dict(
            sink_token=FieldSpec((), jnp.int32, mutable=True),
            sink_n=FieldSpec((), jnp.int32, mutable=True),
        ),
    )
    payload = PayloadSpec(("token", 0, jnp.int32), "weight")
    put = reg.kind("TOKEN_PUT", table="idsink", payload=payload)

    @reg.on(put)
    def h_token_put(env, world, counters, e):
        s = world.lp_res[e.dst]
        delta = env.delta(
            world,
            "idsink",
            s,
            sink_token=payload.get(e.payload, "token"),
            sink_n=world.sink_n[s] + 1,
        )
        return delta, counters, hd.no_emits()

    class B(ScenarioBuilderBase):
        _registry = reg

    tokens = [(1 << 31) - 1, 0x7F800001, 16777217, -5]
    b = B()
    sinks = [b.add_component("idsink") for _ in tokens]
    for lp, tok in zip(sinks, tokens):
        b.add_event(
            time=1 + lp,
            kind=put,
            src=lp,
            dst=lp,
            payload=payload.pack(token=tok, weight=1.0),
        )
    world, own, init_ev, spec = b.build(
        n_agents=2, lookahead=1, t_end=50, pool_cap=64
    )
    ow, _oc, otrace = run_sequential(world, own, init_ev, spec)
    st = Engine(world, own, init_ev, spec, trace_cap=64).run_local()
    w = jax.tree.map(lambda x: np.asarray(x[0]), st.world)
    np.testing.assert_array_equal(w.sink_token, tokens)
    np.testing.assert_array_equal(np.asarray(ow.sink_token), tokens)
    np.testing.assert_array_equal(w.sink_n, 1)
    trace = merged_engine_trace(np.asarray(st.trace), np.asarray(st.trace_n))
    assert trace == otrace


# ---------------------------------------------------------------------------
# Registry-generated dispatch == oracle / sequential path on seed scenarios
# ---------------------------------------------------------------------------


def check_registry_dispatch_matches_reference(p):
    """Property body: the generated dispatch table (batched + sequential)
    reproduces the heapq oracle's trace and final world bytes."""
    b, kw = t0t1_builder(
        wan_bw=p["bw"],
        n_flows=p["count"],
        interval=p["interval"],
        lookahead=p["lookahead"],
    )
    kw = {**kw, "exec_cap": p["exec_cap"]}
    world, own, init_ev, spec = b.build(n_agents=p["n_agents"], **kw)
    _ow, _oc, otrace = run_sequential(world, own, init_ev, spec)
    st_b, st_s = run_pair(world, own, init_ev, spec)
    assert engine_trace(st_b) == otrace
    assert_states_identical(st_b, st_s)


def test_registry_dispatch_matches_reference_fixed():
    check_registry_dispatch_matches_reference(
        dict(bw=2.0, count=12, interval=25, lookahead=2, n_agents=1, exec_cap=256)
    )
    check_registry_dispatch_matches_reference(
        dict(bw=0.5, count=8, interval=9, lookahead=1, n_agents=2, exec_cap=7)
    )


# ---------------------------------------------------------------------------
# The cache component: defined entirely outside core
# ---------------------------------------------------------------------------


def test_cache_registry_extends_builtin_without_touching_it():
    assert "cache" in CACHE_REGISTRY.components
    assert "cache" not in BUILTIN.components  # core untouched
    assert CACHE_REGISTRY.n_kinds == BUILTIN.n_kinds + 2
    assert CACHE_REGISTRY.kind_table[: BUILTIN.n_kinds] == BUILTIN.kind_table
    # the generated World grows the cache table after the builtin fields
    wf = CACHE_REGISTRY.world_struct()._fields
    assert wf[: len(World._fields)] == World._fields
    assert "cache_keys" in wf and "cache_keys" not in World._fields


def run_cache_pair(built, trace_cap=4096, max_windows=20000):
    world, own, init_ev, spec = built
    eng_b = Engine(world, own, init_ev, spec, trace_cap=trace_cap)
    st_b = eng_b.run_local(max_windows=max_windows)
    spec_s = dataclasses.replace(spec, batched_dispatch=False)
    eng_s = Engine(world, own, init_ev, spec_s, trace_cap=trace_cap)
    st_s = eng_s.run_local(max_windows=max_windows)
    return st_b, st_s


def test_cache_matches_oracle_and_counts_hits():
    built, caches = build_churn_scenario(
        n_caches=4,
        n_keys=3,
        n_rounds=5,
        cache_ways=8,
    )
    world, own, init_ev, spec = built
    _ow, _oc, otrace = run_sequential(world, own, init_ev, spec)
    st_b, st_s = run_cache_pair(built)
    assert engine_trace(st_b) == otrace
    assert_states_identical(st_b, st_s)
    c = np.asarray(st_b.counters)[0]
    assert c[mon.C_BATCH_FALLBACK] == 0  # distinct rows batch clean
    w = jax.tree.map(lambda x: np.asarray(x[0]), st_b.world)
    # keys cycle 0,1,2,0,1 -> 3 cold misses then 2 hits per cache
    np.testing.assert_array_equal(w.cache_miss[:4], 3)
    np.testing.assert_array_equal(w.cache_hits[:4], 2)


def test_cache_same_row_lookups_serialize_and_stay_exact():
    """Two same-window lookups on one cache row are a genuine RMW collision:
    the rows-keyed conflict mask must route them through the sequential
    fallback, and the result still matches the oracle byte-for-byte."""
    b = CacheScenarioBuilder(cache_ways=4, max_cpu=1)
    sink = b.add_idle_lp()
    cache = b.add_cache(cache_hit_lat=1, cache_miss_lat=4)
    for k in (7, 7, 9):
        payload = CACHE_LOOKUP.pack(key=k, size=1.0)
        b.add_event(time=1, kind=CACHE_LOOKUP, src=sink, dst=cache, payload=payload)
    built = b.build(n_agents=1, lookahead=2, t_end=60, pool_cap=64)
    world, own, init_ev, spec = built
    _ow, _oc, otrace = run_sequential(world, own, init_ev, spec)
    st_b, st_s = run_cache_pair(built)
    c = np.asarray(st_b.counters)[0]
    assert c[mon.C_BATCH_FALLBACK] >= 3
    assert engine_trace(st_b) == otrace
    assert_states_identical(st_b, st_s)
    w = jax.tree.map(lambda x: np.asarray(x[0]), st_b.world)
    # dup-key fills are idempotent: key 7 cached once
    assert int(np.sum(w.cache_keys[0] == 7)) == 1


def test_cache_multi_agent_owner_wins_sync():
    """The generated sync plan covers the extension fields (incl. the -1
    fill shift for cache_keys) — a 2-agent run stays oracle-exact."""
    built, _caches = build_churn_scenario(
        n_caches=5,
        n_keys=2,
        n_rounds=4,
        n_agents=2,
    )
    world, own, init_ev, spec = built
    ow, _oc, otrace = run_sequential(world, own, init_ev, spec)
    st_b, st_s = run_cache_pair(built)
    assert engine_trace(st_b) == otrace
    assert_states_identical(st_b, st_s)
    w = jax.tree.map(lambda x: np.asarray(x[0]), st_b.world)
    np.testing.assert_array_equal(np.asarray(ow.cache_keys), w.cache_keys)


def test_owner_wins_sync_keeps_float_bits():
    """The owner's f32 row arrives bit for bit — -0.0 and denormals (int
    bit patterns) included, which a float sum would turn into +0.0 / 0."""
    from repro.core.registry import registry_of
    (world, own, _ev, _spec), _ = build_churn_scenario(
        n_caches=5, n_keys=2, n_rounds=4, n_agents=2)
    reg = registry_of(world)
    specials = np.array([-0.0, 1.0, 0.0], np.float32).view(np.int32)
    specials = np.concatenate([specials, [1, 37, -3]]).astype(np.int32)
    checked = 0
    for comp in reg._components.values():
        owner = np.asarray(world.lp_agent)[np.asarray(
            getattr(own, comp.own_field))]
        for fname, fs in comp.fields.items():
            x = np.asarray(getattr(world, fname))
            if not fs.mutable or x.dtype != np.float32:
                continue
            bits = np.resize(specials, x.size).reshape(x.shape)
            setting = world._replace(**{fname: jnp.asarray(bits.view(
                np.float32))})
            stacked = jax.tree.map(lambda v: jnp.stack([v, v]), setting)
            out = jax.vmap(lambda w: reg.sync_world(w, own, "a"),
                           axis_name="a")(stacked)
            got = np.asarray(getattr(out, fname)).view(np.int32)
            mine = (owner >= 0) & (owner < 2)
            mine = mine.reshape(mine.shape + (1,) * (x.ndim - 1))
            want = np.where(mine, bits, 0)
            np.testing.assert_array_equal(got[0], want, err_msg=fname)
            np.testing.assert_array_equal(got[1], want, err_msg=fname)
            checked += 1
    assert checked


# ---------------------------------------------------------------------------
# Trace-buffer overflow: counted + loud
# ---------------------------------------------------------------------------


def test_trace_overflow_is_counted_and_fails_loudly():
    b, kw = t0t1_builder()
    world, own, init_ev, spec = b.build(n_agents=1, **kw)
    st = Engine(world, own, init_ev, spec, trace_cap=8).run_local()
    c = np.asarray(st.counters)[0]
    n_lost = int(c[mon.C_EVENTS]) - 8
    assert int(c[mon.C_TRACE_DROP]) == n_lost > 0
    with pytest.raises(RuntimeError, match="trace buffer overflowed"):
        merged_engine_trace(np.asarray(st.trace), np.asarray(st.trace_n))
    # sequential path counts the same drops (non-diagnostic counter)
    spec_s = dataclasses.replace(spec, batched_dispatch=False)
    st_s = Engine(world, own, init_ev, spec_s, trace_cap=8).run_local()
    assert int(np.asarray(st_s.counters)[0, mon.C_TRACE_DROP]) == n_lost


def test_no_trace_drop_when_buffer_covers_the_run(t0t1_oracle):
    _ow, _oc, otrace = t0t1_oracle
    b, kw = t0t1_builder()
    world, own, init_ev, spec = b.build(n_agents=1, **kw)
    st = Engine(world, own, init_ev, spec, trace_cap=4096).run_local()
    assert int(np.asarray(st.counters)[0, mon.C_TRACE_DROP]) == 0
    assert engine_trace(st) == otrace


# ---------------------------------------------------------------------------
# Hypothesis properties
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    seed_params = st.fixed_dictionaries(
        dict(
            bw=st.floats(0.25, 8.0),
            count=st.integers(2, 12),
            interval=st.integers(5, 40),
            lookahead=st.integers(1, 4),
            n_agents=st.sampled_from([1, 2]),
            exec_cap=st.sampled_from([3, 17, 256]),
        )
    )

    @settings(max_examples=6, deadline=None)
    @given(seed_params)
    def test_registry_dispatch_matches_reference_property(p):
        """Registry-generated dispatch == oracle + sequential path on
        randomized seed scenarios (traces, counters, world/pool bytes)."""
        check_registry_dispatch_matches_reference(p)

    cache_params = st.fixed_dictionaries(
        dict(
            n_caches=st.integers(1, 6),
            n_keys=st.integers(1, 6),
            n_rounds=st.integers(1, 6),
            cache_ways=st.sampled_from([2, 4, 8]),
            hit_lat=st.integers(1, 3),
            miss_lat=st.integers(4, 9),
            n_agents=st.sampled_from([1, 2]),
        )
    )

    @settings(max_examples=6, deadline=None)
    @given(cache_params)
    def test_cache_component_matches_oracle_property(p):
        """The outside-core cache component is byte-identical to the heapq
        oracle under batched and sequential dispatch on randomized churn
        scenarios (the PR's acceptance property)."""
        built, _caches = build_churn_scenario(**p)
        world, own, init_ev, spec = built
        _ow, _oc, otrace = run_sequential(world, own, init_ev, spec)
        st_b, st_s = run_cache_pair(built)
        assert engine_trace(st_b) == otrace
        assert_states_identical(st_b, st_s)
