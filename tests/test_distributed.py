"""Distributed-path tests: shard_map engine == oracle (subprocess, 4 devices),
the randomized scale-out equivalence property, elastic re-mesh + checkpoint
continuity, event-pool overflow accounting."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import example, given, settings, strategies as st

from distributed_harness import run_distributed_child
from repro.core import events as ev


@pytest.mark.slow
def test_shard_map_engine_matches_oracle_subprocess():
    """The real collective path (lax.pmin/all_to_all under shard_map over 4
    host devices) executes the exact oracle trace."""
    res = run_distributed_child(r"""
otrace = oracle_trace()
w, o, e, s = t0t1_build(4)
eng = Engine(w, o, e, s, trace_cap=4096)
mesh = Mesh(np.array(jax.devices()), ("agents",))
st = eng.run_distributed(mesh, max_windows=20000)
trace = engine_trace(st)
print(json.dumps({"match": trace == otrace, "n": len(trace)}))
""")
    assert res["match"] and res["n"] > 0


@pytest.mark.slow
def test_shard_map_fused_select_matches_oracle_subprocess():
    """The fused superstep megakernel under the real collective path: fused
    run_distributed (including the non-divisible 3-agents-on-4-devices
    packing) == fused run_local == the stitched distributed engine == the
    heapq oracle, byte-exactly in full state; the fused adaptive-width
    driver executes the oracle trace too."""
    res = run_distributed_child(r"""
otrace = oracle_trace()
checks = {}
mesh = Mesh(np.array(jax.devices()), ("agents",))
for n in (3, 4):
    fused = t0t1_build(n, fused_select=True)
    eng_f = Engine(*fused, trace_cap=4096)
    st_f = eng_f.run_distributed(mesh, max_windows=20000)
    checks[f"fused_dist_trace_is_oracle_n{n}"] = engine_trace(st_f) == otrace
    st_l = eng_f.run_local(max_windows=20000)
    checks[f"fused_dist_local_state_equal_n{n}"] = tree_eq(st_f, st_l)
    st_s = Engine(*t0t1_build(n), trace_cap=4096).run_distributed(
        mesh, max_windows=20000)
    checks[f"fused_matches_stitched_n{n}"] = tree_eq(st_f, st_s)
st_a = Engine(*t0t1_build(6, fused_select=True),
              trace_cap=4096).run_distributed_adaptive(
    mesh, max_windows=20000, policy=ExecPolicy(ladder=(1, 4, 16)))
checks["fused_adaptive_trace_is_oracle"] = engine_trace(st_a) == otrace
print(json.dumps(checks))
""")
    failed = {k: v for k, v in res.items() if v is not True}
    assert not failed, failed


@pytest.mark.parametrize("platform,n_dev,env,refused", [
    ("tpu", 4, None, True),
    ("tpu", 4, "1", False),
    ("tpu", 1, None, False),
    ("cpu", 4, None, False),
])
def test_multichip_tpu_mesh_refused_unless_asked(monkeypatch, platform, n_dev,
                                                 env, refused):
    """A mesh over several TPU chips raises a clear error unless
    REPRO_MULTICHIP_TPU=1; one chip and host devices run as before."""
    from types import SimpleNamespace
    from repro.core.engine import MULTICHIP_TPU_ENV, Engine
    if env is None:
        monkeypatch.delenv(MULTICHIP_TPU_ENV, raising=False)
    else:
        monkeypatch.setenv(MULTICHIP_TPU_ENV, env)
    devices = np.array([SimpleNamespace(platform=platform)] * n_dev,
                       dtype=object)
    mesh = SimpleNamespace(axis_names=("agents",), devices=devices)
    eng = SimpleNamespace(spec=SimpleNamespace(n_agents=8))
    if refused:
        with pytest.raises(RuntimeError, match=MULTICHIP_TPU_ENV):
            Engine._dist_axes(eng, mesh)
    else:
        axes = Engine._dist_axes(eng, mesh)
        assert (axes.n_shards, axes.n_lanes) == (n_dev, 8 // n_dev)


# The pinned acceptance cases: one with cross-shard event migration, one with
# the adaptive per-shard width ladder actually moving rungs (verified: this
# scenario spills at width 1 and climbs through every rung).
_MIGRATE_CASE = dict(n_agents=6, pool_cap=256, n_flows=12, interval=25,
                     second_gen=False, ladder=None, migrate=True,
                     mig_window=20)
_ADAPTIVE_CASE = dict(n_agents=6, pool_cap=256, n_flows=12, interval=5,
                      second_gen=True, ladder=(1, 4, 16), migrate=False,
                      mig_window=20)


@pytest.mark.slow
@settings(max_examples=3, deadline=None)
@example(**_MIGRATE_CASE)
@example(**_ADAPTIVE_CASE)
@given(n_agents=st.sampled_from([3, 5, 6, 7]),
       pool_cap=st.sampled_from([48, 256]),
       n_flows=st.sampled_from([8, 12]),
       interval=st.sampled_from([5, 25]),
       second_gen=st.booleans(),
       ladder=st.sampled_from([None, (1, 4, 16), (2, 8, 32)]),
       migrate=st.booleans(),
       mig_window=st.integers(5, 40))
def test_distributed_scale_out_equivalence_property(n_agents, pool_cap,
                                                    n_flows, interval,
                                                    second_gen, ladder,
                                                    migrate, mig_window):
    """Randomized scale-out specs — agent counts not divisible by the device
    count, mixed generators, small pool caps, adaptive ladders, mid-run
    cross-shard migration — all satisfy distributed == run_local ==
    run_adaptive == oracle on traces, counters, and final world (the static
    and adaptive pairs byte-identical in full state; every driver's merged
    trace byte-identical to the sequential heapq oracle; zero drop counters
    as the exactness precondition)."""
    params = dict(n_agents=n_agents, pool_cap=pool_cap, n_flows=n_flows,
                  interval=interval, second_gen=second_gen,
                  ladder=list(ladder) if ladder else None, migrate=migrate,
                  mig_window=mig_window)
    res = run_distributed_child(f"params = {params!r}\n" + r"""
n = params["n_agents"]
bkw = dict(pool_cap=params["pool_cap"], n_flows=params["n_flows"],
           interval=params["interval"], second_gen=params["second_gen"])
otrace = oracle_trace(**bkw)
w, o, e, s = t0t1_build(n, **bkw)
eng = Engine(w, o, e, s, trace_cap=4096)
mesh = Mesh(np.array(jax.devices()), ("agents",))
checks = {}
state_d = state_l = None
if params["migrate"]:
    # run a few windows distributed, swap the first and last agents'
    # LPs (cross-shard for any n > K), then continue both drivers from
    # the migrated state
    axes = eng._dist_axes(mesh)
    stp = eng._pad_state(eng.init_state(), axes.size)
    step = eng._dist_window_fn(mesh, s.exec_cap)
    for _ in range(params["mig_window"]):
        stp = step(stp)
    mid = eng._slice_state(stp)
    la = np.asarray(mid.world.lp_agent[0])
    hi = n - 1
    new_la = np.where(la == 0, hi, np.where(la == hi, 0, la)).astype(np.int32)
    state_d = eng.apply_placement_distributed(mid, new_la, mesh)
    state_l = eng.apply_placement_local(mid, new_la)
    checks["migrated_states_equal"] = tree_eq(state_d, state_l)
    cnt = np.asarray(state_d.counters)
    checks["migrate_out_in_balanced"] = (
        int(cnt[:, mon.C_MIGRATE_OUT].sum())
        == int(cnt[:, mon.C_MIGRATE_IN].sum()))
st_d = eng.run_distributed(mesh, max_windows=20000, state=state_d)
st_l = eng.run_local(max_windows=20000, state=state_l)
checks["static_full_state_equal"] = tree_eq(st_d, st_l)
checks["static_trace_is_oracle"] = engine_trace(st_d) == otrace
if params["ladder"]:
    p = ExecPolicy(ladder=tuple(params["ladder"]))
    st_a = eng.run_adaptive(max_windows=20000, policy=p, state=state_l)
    rungs_a = eng.adaptive_rungs
    st_da = eng.run_distributed_adaptive(mesh, max_windows=20000, policy=p,
                                         state=state_d)
    rungs_da = eng.adaptive_rungs
    checks["adaptive_full_state_equal"] = tree_eq(st_a, st_da)
    checks["adaptive_rungs_lockstep"] = rungs_a == rungs_da
    checks["adaptive_trace_is_oracle"] = engine_trace(st_da) == otrace
    checks["adaptive_final_world_matches_static"] = tree_eq(
        st_da.world, st_d.world)
    checks["info_adaptive_engaged"] = len(set(rungs_a)) > 1
cnt = np.asarray(st_d.counters)
checks["no_drops"] = (int(cnt[:, mon.C_DROP_POOL].sum()) == 0
                      and int(cnt[:, mon.C_DROP_ROUTE].sum()) == 0)
print(json.dumps(checks))
""")
    failed = {k: v for k, v in res.items()
              if not k.startswith("info_") and v is not True}
    assert not failed, (failed, params)
    if params == {**_ADAPTIVE_CASE,
                  "ladder": list(_ADAPTIVE_CASE["ladder"])}:
        assert res["info_adaptive_engaged"], res


def test_elastic_failure_recovery_continuity(tmp_path):
    """Fleet shrink mid-run: checkpoint -> remesh plan -> restore -> continue
    with the re-sharded stateless pipeline; training proceeds and the global
    batch stream is unchanged."""
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.configs.base import TrainConfig
    from repro.configs.registry import smoke_config
    from repro.data import pipeline as dp
    from repro.ft import elastic
    from repro.models.model import build_model
    from repro.train.loop import make_train_step
    from repro.train.optimizer import init_opt_state

    cfg = dataclasses.replace(smoke_config("smollm-135m"), dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    tc = TrainConfig(learning_rate=1e-3)
    step = jax.jit(make_train_step(model, tc))
    dcfg = dp.DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
    ck = Checkpointer(str(tmp_path))

    # healthy fleet: 4 logical shards
    for i in range(3):
        batches = [dp.batch_for_shard(dcfg, i, s, 4) for s in range(4)]
        glob = {k: jnp.concatenate([b[k] for b in batches])
                for k in batches[0]}
        params, opt, m = step(params, opt, glob)
    ck.save(3, (params, opt), blocking=True)

    # lose half the fleet: remesh, restore, resume with 2 shards
    plan = elastic.plan_remesh(2, model_parallel=1)
    assert elastic.validate_plan(plan, 2)
    n_shards = plan.n_devices
    step_no, (params, opt) = ck.restore((params, opt))
    assert step_no == 3
    for i in range(3, 6):
        batches = [dp.batch_for_shard(dcfg, i, s, n_shards)
                   for s in range(n_shards)]
        glob = {k: jnp.concatenate([b[k] for b in batches])
                for k in batches[0]}
        # identical global stream despite re-sharding
        ref = dp.batch_for_shard(dcfg, i, 0, 1)
        np.testing.assert_array_equal(np.asarray(glob["tokens"]),
                                      np.asarray(ref["tokens"]))
        params, opt, m = step(params, opt, glob)
    assert np.isfinite(float(m["loss"]))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 40), st.integers(1, 40), st.integers(0, 2**31 - 1))
def test_event_pool_insert_overflow_accounting(n_live, n_new, seed):
    """insert() fills free slots deterministically and counts every drop."""
    cap = 32
    rng = np.random.RandomState(seed)
    pool = ev.empty_pool(cap)
    pre = ev.empty_batch(max(n_live, 1))
    pre = pre._replace(
        time=jnp.asarray(rng.randint(0, 100, max(n_live, 1)), jnp.int32),
        valid=jnp.asarray([True] * n_live + [False] * (max(n_live, 1) - n_live)))
    pool, d0 = ev.insert(pool, pre)
    live0 = int(np.asarray(pool.valid).sum())
    assert live0 == min(n_live, cap)
    assert int(d0) == max(0, n_live - cap)

    batch = ev.empty_batch(max(n_new, 1))
    batch = batch._replace(
        time=jnp.asarray(rng.randint(0, 100, max(n_new, 1)), jnp.int32),
        valid=jnp.asarray([True] * n_new + [False] * (max(n_new, 1) - n_new)))
    pool2, dropped = ev.insert(pool, batch)
    live = int(np.asarray(pool2.valid).sum())
    assert live == min(live0 + n_new, cap)
    assert int(dropped) == max(0, live0 + n_new - cap)
    # free slots carry T_INF so min-reductions never need a mask
    t = np.asarray(pool2.time)
    assert np.all(t[~np.asarray(pool2.valid)] == 2**31 - 1)
