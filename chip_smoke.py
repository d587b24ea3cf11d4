"""Chip smoke test: the DES engine's main path on a TPU, in one process.

Run from the root of a checkout:

    python chip_smoke.py               # one chip: phases A-E
    python chip_smoke.py --four-chips  # run_distributed on 4 chips vs run_local

Phases (one chip):

A. failure_farm at the ``wide_component`` shape (256 farms x 64 CPUs,
   pool_cap 4096, 8 agents) through ``fleet.Orchestrator.run`` with the
   stitched XLA front end.
B. The same run with ``fused=True``: the compiled Pallas megakernel front end
   and ring-slot kernel. Counters, world and merged trace must equal phase A.
C. Catalog-default ``t0t1`` and ``cache_churn`` must equal the sequential
   heapq oracle (``repro.core.run_sequential``): trace, world, and every
   counter the oracle books.
D. ``t0t1`` with a trace ring smaller than its trace, drained to the host by
   ``io_callback``: the streamed trace must equal the in-device buffer with
   ``C_TRACE_DROP == 0``.
E. Each compiled kernel hook (sort/select/group/ring/trace/route ranks, the
   megakernel, the waterfill) against its reference in ``kernels/ref.py``.

Exits non-zero, with no result line, when JAX finds no TPU or the script is
run outside a checkout. Exceptions are never caught. The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

FARM = dict(n_farms=256, n_cpu=64, pool_cap=4096)
FARM_TRACE_CAP = 1 << 13
FARM_AGENTS = 8
FOUR_CHIP_AGENTS = 16
ORACLE_TRACE_CAP = 1 << 14
# the elastic-fleet CI smoke's t0t1 settings: ~400 trace rows through a
# 32-row ring (exec_cap 32, so one window's writes fit the ring)
STREAM = dict(n_flows=48, interval=10, t_end=40_000, exec_cap=32)
STREAM_RING = 32


def _fail(msg: str) -> None:
    print(f"chip_smoke.py: {msg}", file=sys.stderr)
    sys.exit(1)


def _checkout_src() -> str:
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        _fail(f"no repro package under {src}: run it from a checkout")
    return src


def _device():
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        _fail(f"JAX platform is {info['platform']!r}, not 'tpu': this smoke "
              "test measures nothing off the chip and does not fall back")
    return devs, info


def _n_entries(path: str) -> int:
    """Executables in the compile cache directory (0 when absent)."""
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _world_diff(a, b) -> list:
    """Where two stacked (A, ...) worlds differ: field, differing element
    count, agent rows, and the first differing index with both values."""
    import numpy as np
    out = []
    for name, x, y in zip(a._fields, a, b):
        x, y = np.asarray(x), np.asarray(y)
        bad = np.argwhere(x != y)
        if len(bad):
            i = tuple(int(v) for v in bad[0])
            out.append(f"{name}{x.shape}:{len(bad)} agents="
                       f"{sorted({int(r[0]) for r in bad})} first={i} "
                       f"{x[i]!r} vs {y[i]!r}")
    return out


def _same_result(a, b) -> dict:
    """Byte equality of two final states: counters, world, merged trace."""
    import numpy as np
    diff = _world_diff(a.world, b.world)
    if diff:
        print("world differs: " + "; ".join(diff), flush=True)
    return {"counters": bool(np.array_equal(np.asarray(a.counters),
                                            np.asarray(b.counters))),
            "world": not diff,
            "trace": _summary(a)[1] == _summary(b)[1]}


def _orchestrated(name, overrides, devices, **orch_kw):
    """One ``Orchestrator.run`` of a catalog entry; returns (result, built,
    wall seconds to block_until_ready)."""
    import jax
    from repro.fleet import FleetPolicy, Orchestrator
    from repro.scenarios import catalog
    built, _ = catalog.resolve(name, overrides)
    t0 = time.perf_counter()
    res = Orchestrator(FleetPolicy(), **orch_kw).run(built, devices=devices)
    jax.block_until_ready(res.state)
    return res, built, time.perf_counter() - t0


def _summary(state):
    import numpy as np
    from repro.core import merged_engine_trace
    from repro.core import monitoring as mon
    c = np.asarray(state.counters).sum(axis=0)
    trace = merged_engine_trace(np.asarray(state.trace),
                                np.asarray(state.trace_n))
    return c, trace, int(c[mon.C_EVENTS]), int(np.asarray(state.windows)[0])


def _temp_bytes(built, trace_cap) -> int:
    """memory_analysis() temporaries of the whole-run local program."""
    import jax
    from repro.core import Engine
    eng = Engine(*built, trace_cap=trace_cap)
    st = eng.init_state()
    compiled = jax.jit(
        lambda s: eng.run_local(10_000, jit=False, state=s)).lower(st).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def phase_farm(tag, fused, devices):
    """Phases A/B: failure_farm at the wide_component shape, cold then warm."""
    overrides = dict(FARM, n_agents=FARM_AGENTS, fused=fused)
    res, built, cold = _orchestrated("failure_farm", overrides, devices,
                                     trace_cap=FARM_TRACE_CAP)
    res2, _, warm = _orchestrated("failure_farm", overrides, devices,
                                  trace_cap=FARM_TRACE_CAP)
    same = _same_result(res.state, res2.state)
    if not all(same.values()):
        raise AssertionError(f"phase {tag}: two identical runs differ: {same}")
    c, trace, events, windows = _summary(res.state)
    temp = _temp_bytes(built, FARM_TRACE_CAP)
    print(f"phase {tag}: failure_farm fused={fused} agents={FARM_AGENTS} "
          f"driver={res.driver} events={events} windows={windows} "
          f"trace_rows={len(trace)} cold_wall_s={cold} warm_wall_s={warm} "
          f"temp_bytes={temp}", flush=True)
    return res.state


def phase_oracle(devices):
    """Phase C: catalog defaults == the sequential heapq oracle."""
    import jax
    import numpy as np
    from repro.core import run_sequential
    from repro.core import monitoring as mon
    # counters only the windowed engine keeps (the oracle leaves them 0)
    engine_only = {mon.C_MSGS_REMOTE, mon.C_WINDOWS, mon.C_LP_LOCAL,
                   mon.C_EXEC_SPILL, mon.C_TRACE_DROP, mon.C_MIGRATE_OUT,
                   mon.C_MIGRATE_IN, *mon.BATCH_DIAG_COUNTERS,
                   *mon.POOL_DIAG_COUNTERS, *mon.GAUGE_COUNTERS,
                   *mon.FLEET_COUNTERS}
    for name in ("t0t1", "cache_churn"):
        res, built, wall = _orchestrated(name, {}, devices,
                                         trace_cap=ORACLE_TRACE_CAP)
        c, trace, events, windows = _summary(res.state)
        ow, oc, otrace = run_sequential(*built)
        oc = np.asarray(oc)
        world = jax.tree.map(lambda x: np.asarray(x)[0], res.state.world)
        bad_world = [f for f, a, b in zip(ow._fields, ow, world)
                     if not np.array_equal(np.asarray(a), b)]
        bad_ctr = [i for i in range(oc.shape[0])
                   if i not in engine_only and int(oc[i]) != int(c[i])]
        print(f"phase C: {name} events={events} windows={windows} "
              f"trace_equal={trace == otrace} world_fields_differing="
              f"{bad_world} counters_differing={bad_ctr} wall_s={wall}",
              flush=True)
        if trace != otrace or bad_world or bad_ctr:
            raise AssertionError(f"phase C: {name} differs from the oracle")


def phase_stream(devices):
    """Phase D: streamed trace through a small ring == in-device buffer."""
    import numpy as np
    from repro.core import monitoring as mon
    from repro.core.monitoring import TraceStream
    ts = TraceStream()
    res, _, wall = _orchestrated("t0t1", STREAM, devices, trace_stream=ts,
                                 trace_cap=STREAM_RING, drain_every=8)
    ref, _, _ = _orchestrated("t0t1", STREAM, devices,
                              trace_cap=ORACLE_TRACE_CAP)
    _, want, events, windows = _summary(ref.state)
    got = ts.merged()
    drop = int(np.asarray(res.state.counters)[:, mon.C_TRACE_DROP].sum())
    peak = int(np.asarray(res.state.trace_n).max())
    print(f"phase D: t0t1 streamed_rows={len(got)} ring={STREAM_RING} "
          f"trace_n_max={peak} trace_drop={drop} equal={got == want} "
          f"windows={windows} wall_s={wall}", flush=True)
    if peak <= STREAM_RING:
        raise AssertionError("phase D: the trace never exceeded the ring")
    if drop or got != want:
        raise AssertionError("phase D: streamed trace != in-device buffer")


def phase_kernels(devices):
    """Phase E: every compiled kernel hook == its kernels/ref.py oracle at
    the wide_component widths (pool_cap 4096, exec_cap 256)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import events as ev
    from repro.core.network import incidence, maxmin_rates
    from repro.kernels import ops, ref
    from repro.scenarios.failures import FAIL_REGISTRY
    cap, m = FARM["pool_cap"], 256
    n_emit, n_kinds = m * ev.MAX_EMIT, FAIL_REGISTRY.n_kinds
    rng = np.random.RandomState(0)
    safe = rng.rand(cap) < 0.6
    # int32 fields ride the payload as f32 bit patterns (small ints are
    # denormals): the megakernel must carry them bit for bit
    pay = rng.randint(0, 1 << 12, (cap, ev.PAYLOAD)).astype(np.int32)
    pool = dict(
        time_key=np.where(safe, rng.randint(0, 50, cap), 2**31 - 1),
        seq=rng.randint(0, 1 << 20, cap), safe=safe,
        time=rng.randint(0, 50, cap), kind=rng.randint(0, n_kinds, cap),
        src=rng.randint(0, 16, cap), dst=rng.randint(0, 16, cap),
        ctx=rng.randint(0, 100, cap), payload=pay.view(np.float32),
        valid=rng.rand(cap) < 0.8, table_id=rng.randint(0, 4, cap),
        res=rng.randint(0, 512, cap), free_tail=np.int32(cap - 7))
    pool = {k: jax.device_put(v if v.dtype.kind in "bf" else
                              np.asarray(v, np.int32), devices[0])
            for k, v in pool.items()}
    tk, sq = pool["time_key"], pool["seq"]
    win_kind = jnp.asarray(rng.randint(0, n_kinds, m), jnp.int32)
    win_act = jnp.asarray(rng.rand(m) < 0.7)
    want = jnp.asarray(rng.rand(n_emit) < 0.5)
    ring = jnp.asarray(rng.permutation(cap), jnp.int32)
    dst_agent = jnp.asarray(np.where(rng.rand(n_emit) < 0.8,
                                     rng.randint(0, 16, n_emit), 16), jnp.int32)

    def keys(perm):
        p = np.asarray(perm)
        return np.asarray(tk)[p].tolist(), np.asarray(sq)[p].tolist()

    def same(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    kw = dict(n_kinds=n_kinds, n_res=512, n_tables=4)
    fs = ops.fused_select(*pool.values(), m, **kw)
    fr = ref.fused_select_ref(*pool.values(), m, **kw)
    es = np.asarray(fr.exec_safe)
    checks = {
        "sort_events": keys(ops.sort_events(tk, sq))
        == keys(ref.sort_events_ref(tk, sq)),
        "select_events": keys(ops.select_events(tk, sq, m))
        == keys(ref.select_events_ref(tk, sq, m)),
        "group_by_kind": same(ops.group_by_kind(win_kind, win_act, n_kinds),
                              ref.group_by_kind_ref(win_kind, win_act,
                                                    n_kinds)),
        "ring_slots": same(ops.ring_slots(ring, jnp.int32(cap - 100), want),
                           ref.ring_slots_ref(ring, jnp.int32(cap - 100),
                                              want)),
        "trace_rank": same(ops.trace_rank(win_act),
                           ref.trace_rank_ref(win_act)),
        "route_rank": same(ops.route_rank(dst_agent),
                           ref.route_rank_ref(dst_agent)),
        "fused_select": same(fs._replace(rel_pos=fs.rel_pos[es]),
                             fr._replace(rel_pos=fr.rel_pos[es])),
    }
    # the waterfill is not bound on an engine path; it agrees with the
    # f32 reference up to summation order
    routes = rng.randint(-1, 8, (64, 3)).astype(np.int32)
    routes[:, 0] = rng.randint(0, 8, 64)
    inc = incidence(jnp.asarray(routes), 8)
    bw = jnp.asarray((rng.rand(8) * 10 + 0.1).astype(np.float32))
    act = jnp.asarray(rng.rand(64) > 0.3)
    got = np.asarray(ops.maxmin_rates(inc, bw, act))
    with jax.default_matmul_precision("highest"):
        ref_rates = np.asarray(maxmin_rates(inc, bw, act))
    checks["maxmin_rates"] = bool(np.allclose(got, ref_rates, rtol=1e-5,
                                              atol=1e-5))
    print(f"phase E: kernels vs kernels/ref.py pool_cap={cap} exec_cap={m} "
          f"equal={checks} maxmin_max_abs_diff="
          f"{float(np.max(np.abs(got - ref_rates)))}", flush=True)
    if not all(checks.values()):
        raise AssertionError(f"phase E: kernel != reference: {checks}")


def four_chips(devs):
    """run_distributed over a 1-D mesh of 4 TPU chips == run_local on one."""
    if len(devs) < 4 or any(d.platform != "tpu" for d in devs[:4]):
        _fail(f"--four-chips needs 4 TPU devices, found {devs}")
    from repro.core.engine import MULTICHIP_TPU_ENV
    # the engine refuses a multi-chip TPU mesh unless told: this run is the
    # comparison that would lift the refusal
    os.environ[MULTICHIP_TPU_ENV] = "1"
    overrides = dict(FARM, n_agents=FOUR_CHIP_AGENTS)
    dist, _, dwall = _orchestrated("failure_farm", overrides, devs[:4],
                                   trace_cap=FARM_TRACE_CAP)
    again, _, _ = _orchestrated("failure_farm", overrides, devs[:4],
                                trace_cap=FARM_TRACE_CAP)
    print(f"four chips: distributed run twice, equal="
          f"{_same_result(dist.state, again.state)}", flush=True)
    local, _, lwall = _orchestrated("failure_farm", overrides, devs[:1],
                                    trace_cap=FARM_TRACE_CAP)
    if dist.driver != "distributed" or dist.devices != 4:
        raise AssertionError(f"expected the distributed driver on 4 devices, "
                             f"got {dist.driver} on {dist.devices}")
    _, _, events, windows = _summary(dist.state)
    same = _same_result(dist.state, local.state)
    print(f"four chips: failure_farm agents={FOUR_CHIP_AGENTS} "
          f"events={events} windows={windows} distributed_wall_s={dwall} "
          f"local_wall_s={lwall} equal={same}", flush=True)
    if not all(same.values()):
        raise AssertionError(f"run_distributed != run_local: {same}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only run_distributed on 4 chips vs run_local")
    args = ap.parse_args()
    sys.path.insert(0, _checkout_src())
    devs, info = _device()
    from repro.launch import compile_cache
    cache = compile_cache.enable()
    before = _n_entries(cache)
    print(f"compile cache: {cache} entries_before={before}", flush=True)
    if args.four_chips:
        four_chips(devs)
    else:
        one = devs[:1]
        a = phase_farm("A", False, one)
        b = phase_farm("B", True, one)
        same = _same_result(a, b)
        print(f"phase B: fused vs stitched equal={same}", flush=True)
        if not all(same.values()):
            raise AssertionError("phase B: fused result != stitched result")
        phase_oracle(one)
        phase_stream(one)
        phase_kernels(one)
    print(f"compile cache: {cache} entries_after="
          f"{_n_entries(cache)}", flush=True)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
