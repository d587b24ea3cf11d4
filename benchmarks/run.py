"""Benchmark harness — one benchmark per paper claim/figure.

  fig2_t0t1        — Fig 2: wall time + event count vs WAN bandwidth (the
                     interrupt-storm superlinearity)
  agent_scaling    — §1/§4: distribute the simulation to lift the one-machine
                     bottleneck (events/s vs agent count)
  sync_overhead    — §4.3: collective-GVT windows vs per-event sync; messages
                     per processed event stays ~O(1)
  scheduler        — §4.1: paper placement vs random/round-robin (load balance
                     + cross-agent message ratio)
  contexts         — fig 9: multiplexing independent runs on one fleet
  exec_compaction  — engine step 4: compact-then-scan (exec_cap) vs full-pool
                     scan, events/s on sparse pools at growing pool_cap
  batched_dispatch — engine step 4: grouped vectorized dispatch vs the PR 1
                     sequential fold on dense same-kind windows (dispatch cost
                     isolated: NOOP handlers, distinct-dst events)
  wide_component   — engine step 4: per-row delta scatter vs the PR 2
                     whole-table merge on wide component tables (64-CPU farms;
                     merge cost isolated: conflict-free JOB_SUBMIT windows)
  insert_churn     — PR 5 pool lifecycle: free-list ring insert/release vs the
                     retained insert_ref O(pool_cap) scan (gated subsystem
                     ratio + informational end-to-end engine ratio)
  fused_superstep  — PR 10 fused window front-end: the one-jit fused select +
                     gather + conflict + group + release-rank program vs the
                     same stages dispatched separately (gated); asserts
                     fused engine == stitched engine == heapq oracle before
                     timing
  adaptive_exec    — PR 5 monitoring-driven exec width: ladder policy vs the
                     static exec_cap=256 default on spill-heavy windows
                     (fewer windows, same events, oracle-exact)
  cache_churn      — PR 4 registry seam: the replica-cache component defined
                     entirely outside core (repro/scenarios/cache.py) running
                     through the registry-generated batched dispatch
                     (gated since PR 5)
  shard_scaling    — PR 6 distributed scale-out: events/s at 64 packed agents
                     on 4 forced host devices vs 1 (shard_map x vmap driver;
                     subprocesses, trajectory entry — no gate on shared-CPU
                     "devices")
  ensemble_throughput — PR 8 vmap-over-seeds ensembles: one fused 128-replica
                     run_ensemble launch vs a sequential run_local loop
                     (replicas/s; gated in the distributed CI job since
                     PR 9 — "requires": "distributed" in baseline.json)
  fleet_resume     — PR 9 elastic orchestration: orchestrated preempt+resume
                     wall vs uninterrupted (resume_overhead ratio; trajectory
                     entry — no gate)
  kernels          — µs/call for each Pallas kernel's XLA reference path
  workload_sim     — DESIGN.md §2: DES-predicted step time vs analytic roofline

Output: ``name,us_per_call,derived`` CSV rows on stdout. ``--json PATH``
additionally writes the rows as machine-readable JSON (derived ``k=v`` pairs
parsed into a dict) — CI uploads this as the BENCH_PR2.json artifact and gates
on the batched_dispatch and wide_component speedups
(benchmarks/check_regression.py; see docs/benchmarks.md).
``--quick`` runs only the fast subset (CI smoke): exec_compaction,
batched_dispatch and wide_component at pool_cap=4096, scheduler, kernels,
workload_sim.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Engine, ScenarioBuilder, events as ev
from repro.core import monitoring as mon
from repro.core import scheduler as sched
from repro.core.workload import CellModel, simulate_training

ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us: float, derived: str = ""):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")


def t0t1(wan_bw, n_flows=48, interval=8, n_agents=1, lookahead=2,
         flow_mb=100.0, pool_cap=1024, exec_cap=None, fused_select=False):
    b = ScenarioBuilder(max_cpu=4, queue_cap=32, max_link=4, max_flow=64)
    t0 = b.add_regional_center(n_cpu=2, cpu_power=10.0, disk=20000.0,
                               tape=200000.0, tape_rate=5.0)
    t1 = b.add_regional_center(n_cpu=2, cpu_power=8.0, disk=20000.0,
                               tape=200000.0, tape_rate=5.0)
    wan = b.add_net_region(link_bws=[wan_bw, wan_bw], link_lats=[5, 5])
    b.add_generator(target_lp=wan, kind=ev.K_FLOW_START,
                    payload=[flow_mb, 0, -1, -1, t1["farm"], ev.K_JOB_SUBMIT,
                             t1["storage"], ev.K_DATA_WRITE],
                    interval=interval, count=n_flows)
    kw = {} if exec_cap is None else dict(exec_cap=exec_cap)
    return b.build(n_agents=n_agents, lookahead=lookahead, t_end=200_000,
                   pool_cap=pool_cap, work_per_mb=2.0,
                   fused_select=fused_select, **kw)


def run_engine(built, max_windows=100_000):
    world, own, init_ev, spec = built
    eng = Engine(world, own, init_ev, spec)
    st = eng.run_local(max_windows=max_windows)
    jax.block_until_ready(st.counters)
    return eng, st


def bench_fig2_t0t1():
    """Paper Fig 2: fixed workload, decreasing WAN bandwidth.

    The paper's curve is SEQUENTIAL wall time exploding with the interrupt
    storm; we time the heapq oracle (the sequential simulator) alongside the
    vectorized engine, whose window count stays nearly flat — the distribution
    argument in one row.
    """
    from repro.core import run_sequential
    for bw in (16.0, 4.0, 1.0, 0.25):
        built = t0t1(bw)
        t0 = time.perf_counter()
        _, oc, otrace = run_sequential(*built)
        t_seq = time.perf_counter() - t0
        eng, _ = run_engine(built)                     # compile
        t0 = time.perf_counter()
        _, st = run_engine(built)
        dt = time.perf_counter() - t0
        c = np.asarray(st.counters).sum(axis=0)
        emit(f"fig2_t0t1_bw{bw}", dt * 1e6,
             f"events={int(c[mon.C_EVENTS])};stale={int(c[mon.C_STALE])};"
             f"interrupts={int(c[mon.C_INTERRUPTS])};"
             f"windows={int(np.asarray(st.windows)[0])};"
             f"sequential_ms={t_seq * 1e3:.0f}")


def bench_fig2b_congestion():
    """Fig 2's mechanism on the offered-load axis: at fixed bandwidth, shrink
    the inter-arrival interval — overlap (and thus interrupt/stale events, the
    paper's cost driver) grows superlinearly while the per-flow workload is
    constant. The sequential oracle's wall time follows the event count; the
    conservative-window engine absorbs it in near-constant windows."""
    from repro.core import run_sequential
    for interval in (32, 16, 8, 4):
        built = t0t1(1.0, n_flows=48, interval=interval)
        t0 = time.perf_counter()
        _, oc, otrace = run_sequential(*built)
        t_seq = time.perf_counter() - t0
        c = np.asarray(oc)
        emit(f"fig2b_congestion_iv{interval}", t_seq * 1e6,
             f"events={len(otrace)};stale={int(c[mon.C_STALE])};"
             f"interrupts={int(c[mon.C_INTERRUPTS])};"
             f"dropped_flows={int(c[mon.C_DROP_FLOW])}")


def bench_agent_scaling():
    """Same model, 1..8 agents. On one CPU core vmap lanes run serially, so the
    honest scaling metric is the per-agent load division: the max events any
    single agent processes (== wall time on real parallel hardware)."""
    for a in (1, 2, 4, 8):
        built = t0t1(1.0, n_agents=a)
        run_engine(built)
        t0 = time.perf_counter()
        _, st = run_engine(built)
        dt = time.perf_counter() - t0
        c = np.asarray(st.counters)
        total = int(c[:, mon.C_EVENTS].sum())
        hottest = int(c[:, mon.C_EVENTS].max())
        emit(f"agent_scaling_a{a}", dt * 1e6,
             f"events={total};max_per_agent={hottest};"
             f"parallel_efficiency={total / max(a * hottest, 1):.2f}")


def bench_sync_overhead():
    """Windows (collective syncs) per processed event vs lookahead size —
    the paper's 'minimum number of messages' claim, collectivized."""
    for la in (1, 2, 4, 8):
        built = t0t1(1.0, n_agents=4, lookahead=la)
        run_engine(built)
        t0 = time.perf_counter()
        _, st = run_engine(built)
        dt = time.perf_counter() - t0
        c = np.asarray(st.counters).sum(axis=0)
        windows = int(np.asarray(st.windows)[0])
        events = int(c[mon.C_EVENTS])
        emit(f"sync_overhead_la{la}", dt * 1e6,
             f"windows={windows};events={events};"
             f"syncs_per_event={windows / max(events, 1):.3f}")


def bench_scheduler():
    """Placement quality: paper scheduler vs random vs round-robin."""
    rng = np.random.RandomState(0)
    a, n_lp = 8, 64
    perf = jnp.asarray(rng.rand(a).astype(np.float32) * 10)
    lp_ctx = jnp.asarray(rng.randint(0, 4, n_lp), jnp.int32)

    t0 = time.perf_counter()
    paper = np.asarray(sched.plan_placement(perf, lp_ctx, a))
    dt = time.perf_counter() - t0
    rr = np.arange(n_lp) % a
    rand = rng.randint(0, a, n_lp)

    def stats(placement):
        load = np.bincount(placement, minlength=a)
        # cross-agent message proxy: LP pairs of one ctx on different agents
        cross = 0
        tot = 0
        ctx = np.asarray(lp_ctx)
        for c in range(4):
            ids = np.where(ctx == c)[0]
            for i in ids:
                for j in ids:
                    if i < j:
                        tot += 1
                        cross += placement[i] != placement[j]
        return load.max() / max(load.mean(), 1e-9), cross / max(tot, 1)

    for name, pl in (("paper", paper), ("roundrobin", rr), ("random", rand)):
        imb, cross = stats(pl)
        emit(f"scheduler_{name}", dt * 1e6 if name == "paper" else 0.0,
             f"imbalance={imb:.2f};cross_ratio={cross:.2f}")


def bench_contexts():
    """Two runs multiplexed on one fleet vs run serially."""
    def one_ctx(ctx_count):
        b = ScenarioBuilder(max_cpu=4, max_flow=32)
        for c in range(ctx_count):
            t1 = b.add_regional_center(n_cpu=2, cpu_power=8.0, disk=2000.0,
                                       tape=20000.0, tape_rate=5.0, ctx=c)
            wan = b.add_net_region(link_bws=[1.0], link_lats=[5], ctx=c)
            b.add_generator(target_lp=wan, kind=ev.K_FLOW_START,
                            payload=[40.0, 0, -1, -1, t1["farm"],
                                     ev.K_JOB_SUBMIT, t1["storage"],
                                     ev.K_DATA_WRITE],
                            interval=20, count=12, ctx=c)
        return b.build(n_agents=4, n_ctx=ctx_count, lookahead=2, t_end=20_000,
                       pool_cap=512, work_per_mb=2.0)

    built = one_ctx(1)
    run_engine(built)
    t0 = time.perf_counter()
    run_engine(built)
    t_single = time.perf_counter() - t0

    built = one_ctx(2)
    run_engine(built)
    t0 = time.perf_counter()
    _, st = run_engine(built)
    t_multi = time.perf_counter() - t0
    emit("contexts_multiplex", t_multi * 1e6,
         f"two_runs_vs_serial={t_multi / max(2 * t_single, 1e-9):.2f}x")


def bench_exec_compaction(pool_caps=(1024, 4096, 16384)):
    """Compacted windowed execution vs the seed's full-pool scan.

    Sparse-pool worst case for the seed engine: events spaced wider than the
    lookahead, so every conservative window has ~1 safe event but the seed
    fold still pays O(pool_cap) sequential scan iterations. exec_cap=pool_cap
    reproduces the seed behavior exactly (the compaction is then the identity
    permutation prefix), so the comparison isolates the scan length.
    """
    def build(pool_cap, exec_cap):
        b = ScenarioBuilder(max_cpu=2, queue_cap=8, max_link=2, max_flow=8)
        farm = b.add_farm([5.0])
        n_ev = min(pool_cap // 4, 512)
        for i in range(n_ev):
            b.add_event(time=1 + 8 * i, kind=ev.K_NOOP, src=farm, dst=farm)
        built = b.build(n_agents=1, lookahead=4, t_end=8 * n_ev + 16,
                        pool_cap=pool_cap, emit_cap=64, exec_cap=exec_cap)
        return built, n_ev

    for pool_cap in pool_caps:
        rates = {}
        for label, exec_cap in (("compact", 256), ("fullscan", pool_cap)):
            built, n_ev = build(pool_cap, exec_cap)
            run_engine(built)                         # compile
            t0 = time.perf_counter()
            _, st = run_engine(built)
            dt = time.perf_counter() - t0
            n = int(np.asarray(st.counters)[0, mon.C_EVENTS])
            assert n == n_ev, (n, n_ev)
            rates[label] = n / dt
        emit(f"exec_compaction_p{pool_cap}", 1e6 / rates["compact"],
             f"events_s_compact={rates['compact']:.0f};"
             f"events_s_fullscan={rates['fullscan']:.0f};"
             f"speedup={rates['compact'] / rates['fullscan']:.1f}x")


def bench_batched_dispatch(pool_caps=(4096,), width=1024, lookahead=4):
    """Grouped vectorized dispatch vs the PR 1 sequential compacted fold.

    Dense same-kind worst case for the sequential fold: every conservative
    window holds ``width`` same-tick NOOP events to distinct LPs, so the PR 1
    path pays ``width`` sequential scan iterations while the batched path runs
    one vmapped dispatch (conflict-free by construction) — the benchmark
    isolates dispatch cost because the NOOP handler itself does no work.
    """
    def build(pool_cap, batched):
        b = ScenarioBuilder(max_cpu=1, queue_cap=2, max_link=1, max_flow=2)
        sinks = [b.add_idle_lp() for _ in range(width)]
        n_tick = max(pool_cap // width, 1)
        for t in range(n_tick):
            for lp in sinks:
                b.add_event(time=1 + lookahead * t, kind=ev.K_NOOP,
                            src=lp, dst=lp)
        built = b.build(n_agents=1, lookahead=lookahead,
                        t_end=lookahead * (n_tick + 1) + 2,
                        pool_cap=pool_cap, emit_cap=64, exec_cap=width,
                        batched_dispatch=batched)
        return built, n_tick * width

    for pool_cap in pool_caps:
        rates = {}
        for label, batched in (("batched", True), ("sequential", False)):
            (world, own, init_ev, spec), n_ev = build(pool_cap, batched)
            eng = Engine(world, own, init_ev, spec)
            jax.block_until_ready(eng.run_local().counters)   # compile
            t0 = time.perf_counter()
            st = eng.run_local()                              # cached jit
            jax.block_until_ready(st.counters)
            dt = time.perf_counter() - t0
            n = int(np.asarray(st.counters)[0, mon.C_EVENTS])
            assert n == n_ev, (n, n_ev)
            rates[label] = n / dt
        emit(f"batched_dispatch_p{pool_cap}", 1e6 / rates["batched"],
             f"events_s_batched={rates['batched']:.0f};"
             f"events_s_sequential={rates['sequential']:.0f};"
             f"speedup={rates['batched'] / rates['sequential']:.2f}x")


def bench_wide_component(pool_caps=(4096,), width=256, n_cpu=64, lookahead=4):
    """Per-row delta scatter vs the PR 2 whole-table merge on wide tables.

    ``width`` farms of ``n_cpu`` CPUs each (cpu tables are (width, n_cpu) —
    ≥64 columns), one JOB_SUBMIT per farm per window (conflict-free by
    construction), alternating with the JOB_END completion windows. Both
    configurations run the identical grouped vectorized dispatch; only the
    merge differs — the delta path scatters ``width`` declared rows
    (O(lanes x row)), the dense path materializes ``width`` full-table copies
    and picks changed elements (O(lanes x tables), the PR 2 strategy). The
    events/s ratio therefore isolates the merge cost, which is what the
    regression gate pins (machine-normalized: both sides measured in this
    process on this host).
    """
    def build(pool_cap, merge_mode):
        b = ScenarioBuilder(max_cpu=n_cpu, queue_cap=8, max_link=1, max_flow=2)
        farms = [b.add_farm([1.0] * n_cpu) for _ in range(width)]
        n_tick = max(pool_cap // (2 * width), 1)
        # submits at 1 + 8t start a 3-tick job on a free CPU; with
        # lookahead=4 the JOB_END lands at 5 + 8t — its own window, so
        # submit and completion windows alternate and never conflict
        for t in range(n_tick):
            for lp in farms:
                b.add_event(time=1 + 2 * lookahead * t, kind=ev.K_JOB_SUBMIT,
                            src=lp, dst=lp, payload=[3.0, 1.0, -1, -1, 0])
        built = b.build(n_agents=1, lookahead=lookahead,
                        t_end=2 * lookahead * (n_tick + 1) + 2,
                        pool_cap=pool_cap, emit_cap=width + 8, exec_cap=width,
                        merge_mode=merge_mode)
        return built, 2 * n_tick * width

    for pool_cap in pool_caps:
        rates = {}
        for merge_mode in ("delta", "dense"):
            (world, own, init_ev, spec), n_ev = build(pool_cap, merge_mode)
            eng = Engine(world, own, init_ev, spec)
            jax.block_until_ready(eng.run_local().counters)   # compile
            t0 = time.perf_counter()
            st = eng.run_local()                              # cached jit
            jax.block_until_ready(st.counters)
            dt = time.perf_counter() - t0
            c = np.asarray(st.counters)[0]
            n = int(c[mon.C_EVENTS])
            assert n == n_ev, (n, n_ev)
            assert int(c[mon.C_BATCH_FALLBACK]) == 0, "scenario must be clean"
            rates[merge_mode] = n / dt
        emit(f"wide_component_p{pool_cap}", 1e6 / rates["delta"],
             f"events_s_delta={rates['delta']:.0f};"
             f"events_s_dense={rates['dense']:.0f};"
             f"width={width};n_cpu={n_cpu};"
             f"speedup={rates['delta'] / rates['dense']:.2f}x")


def bench_insert_churn(pool_caps=(4096,), burst=256, iters=64, width=256,
                       n_ticks=8, lookahead=4):
    """Pool-lifecycle churn: the free-list ring vs the retained insert_ref scan.

    The gated metric isolates the subsystem the ring replaced: a jitted loop
    of the per-window lifecycle cycle — release the previous burst's slots,
    insert a dense ``burst``-row emit batch — over a half-resident pool at
    ``pool_cap``. The ring path does O(burst) work per cycle; the scan path
    pays the O(pool_cap) free-rank cumsum + rank->slot scatter (insert) and
    the pool-wide mask (release) every cycle, exactly as the PR 1-4 engine
    did. events/s ratio, machine-normalized (both sides in one process).

    The same row also reports the *end-to-end* engine ratio on an emit-heavy
    dense generator scenario (``engine_speedup``, informational): there the
    common per-window costs — the (time, seq) selection sort above all —
    dilute the lifecycle win, which is exactly why the gate pins the
    subsystem, not the whole window.
    """
    for pool_cap in pool_caps:
        resident = pool_cap // 2
        pool0 = ev.empty_pool(pool_cap)
        rows = [dict(time=100_000 + i, seq=i, kind=0, src=0, dst=0)
                for i in range(resident)]
        pool0, _ = ev.insert(pool0, ev.batch_from_rows(rows))
        batch = ev.batch_from_rows(
            [dict(time=50_000 + i, seq=4096 + i, kind=0, src=0, dst=0)
             for i in range(burst)])
        ones = jnp.ones((burst,), bool)

        @jax.jit
        def churn_ring(pool):
            def body(_, pool):
                slots = pool.free_ring[
                    (pool.free_head + jnp.arange(burst, dtype=jnp.int32))
                    % pool_cap]
                pool, _ = ev.insert(pool, batch)
                return ev.release(pool, slots, ones)
            return jax.lax.fori_loop(0, iters, body, pool)

        @jax.jit
        def churn_ref(pool):
            def body(_, pool):
                before = pool.valid
                pool, _ = ev.insert_ref(pool, batch)
                return ev.pop_mask_ref(pool, pool.valid & ~before)
            return jax.lax.fori_loop(0, iters, body, pool)

        rates = {}
        for label, fn in (("ring", churn_ring), ("ref", churn_ref)):
            out = fn(pool0)
            jax.block_until_ready(out.valid)              # compile
            assert int(np.asarray(out.free_count)) == pool_cap - resident
            t0 = time.perf_counter()
            out = fn(pool0)
            jax.block_until_ready(out.valid)
            rates[label] = iters * burst / (time.perf_counter() - t0)

        # end-to-end engine context: width generators, each window inserting
        # ~2*width emits (activity + next tick) — emit-heavy dense windows
        def build_engine(insert_mode):
            b = ScenarioBuilder(max_cpu=1, queue_cap=2, max_link=1, max_flow=2)
            for _ in range(width):
                lp = b.add_idle_lp()
                b.add_generator(target_lp=lp, kind=ev.K_NOOP, payload=[],
                                interval=lookahead, count=n_ticks)
            return b.build(n_agents=1, lookahead=lookahead,
                           t_end=lookahead * (n_ticks + 3) + 2,
                           pool_cap=pool_cap, emit_cap=2 * width + 8,
                           exec_cap=2 * width, insert_mode=insert_mode)

        erates = {}
        for mode in ("ring", "ref"):
            world, own, init_ev, spec = build_engine(mode)
            eng = Engine(world, own, init_ev, spec)
            jax.block_until_ready(eng.run_local().counters)   # compile
            t0 = time.perf_counter()
            st = eng.run_local()
            jax.block_until_ready(st.counters)
            dt = time.perf_counter() - t0
            n = int(np.asarray(st.counters)[0, mon.C_EVENTS])
            assert n == 2 * width * n_ticks, (n, 2 * width * n_ticks)
            erates[mode] = n / dt

        emit(f"insert_churn_p{pool_cap}", 1e6 / rates["ring"],
             f"events_s_ring={rates['ring']:.0f};"
             f"events_s_ref={rates['ref']:.0f};"
             f"burst={burst};resident={resident};"
             f"speedup={rates['ring'] / rates['ref']:.2f}x;"
             f"engine_events_s_ring={erates['ring']:.0f};"
             f"engine_events_s_ref={erates['ref']:.0f};"
             f"engine_speedup={erates['ring'] / erates['ref']:.2f}x")


def bench_fused_superstep(pool_cap=4096, exec_cap=256, iters=500):
    """PR 10 fused window front-end: the superstep megakernel seam.

    The gated metric is the fused window *tail* — everything the megakernel
    fuses downstream of the (time, seq) sort the two paths share: exec mask,
    slot gathers, conflict mask, same-kind grouping, release ranks — run as
    the megakernel's own algorithm (pairwise duplicate count instead of the
    sort-based ``sync.conflict_mask``) in ONE program, vs the stitched
    composition dispatched one stage at a time with every intermediate
    index/rank array materialized between dispatches, exactly the per-hook
    shape the engine's non-fused path composes from. Dense windows over a
    full pool at ``pool_cap``; windows/s ratio, machine-normalized (both
    sides in one process; insert_churn idiom). The shared pool-wide sort is
    *excluded* from both sides — it is identical work, and including it
    would only dilute the seam the gate pins. Byte-identity of the two
    tails (and of the ref oracle ``fused_select_ref``) is asserted in-bench.

    Before timing anything the row asserts end-to-end byte-identity: the
    fused engine (``spec.fused_select=True``, the interpret-Pallas path off
    TPU) runs the identical trace/counters/world as the stitched engine and
    the sequential heapq oracle on a dense scenario. ``engine_speedup`` is
    the end-to-end fused-engine ratio (informational — off TPU the
    interpreted megakernel *loses*; the gate pins the fusion seam itself).
    """
    from repro.core import merged_engine_trace, run_sequential, sync
    from repro.core.engine import group_by_kind_xla, select_events_xla
    from repro.kernels import ref as kref

    # --- byte-identity proof: fused engine == stitched engine == oracle ---
    built_f = t0t1(2.0, n_flows=32, pool_cap=1024, fused_select=True)
    built_s = t0t1(2.0, n_flows=32, pool_cap=1024)
    _, _, otrace = run_sequential(*built_s)
    states, erates = {}, {}
    for label, built in (("fused", built_f), ("stitched", built_s)):
        eng = Engine(*built, trace_cap=8192)
        jax.block_until_ready(eng.run_local().counters)       # compile
        t0 = time.perf_counter()
        st = eng.run_local()
        jax.block_until_ready(st.counters)
        dt = time.perf_counter() - t0
        states[label] = st
        erates[label] = int(np.asarray(st.counters)[:, mon.C_EVENTS].sum()) / dt
        trace = merged_engine_trace(np.asarray(st.trace),
                                    np.asarray(st.trace_n))
        assert trace == otrace, f"{label} engine trace != heapq oracle"
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        states["fused"], states["stitched"])), \
        "fused engine state != stitched engine state"

    # --- the fusion seam, subsystem-isolated on a dense full pool ---
    cap, m = pool_cap, exec_cap
    n_kinds, n_tables, n_res = ev.N_KINDS, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 10)
    safe = jax.random.bernoulli(ks[0], 0.9, (cap,))
    tk = jnp.where(safe, jax.random.randint(ks[1], (cap,), 0, 1000),
                   jnp.int32(2**31 - 1))
    sq = jax.random.randint(ks[2], (cap,), 0, 2**20)
    tm = jax.random.randint(ks[3], (cap,), 0, 1000)
    kind = jax.random.randint(ks[4], (cap,), 0, n_kinds)
    src = jax.random.randint(ks[5], (cap,), 0, 16)
    dst = jax.random.randint(ks[6], (cap,), 0, 16)
    ctx = jax.random.randint(ks[7], (cap,), 0, 100)
    pay = jax.random.normal(ks[8], (cap, ev.PAYLOAD))
    tbl = jax.random.randint(ks[9], (cap,), 0, n_tables)
    res = jax.random.randint(ks[9], (cap,), 0, n_res)
    valid = jnp.ones((cap,), bool)
    tail = jnp.int32(cap - 7)                      # ring cursor wraps
    kw = dict(n_kinds=n_kinds, n_res=n_res, n_tables=n_tables)

    # the shared sort-select — identical work on both sides, computed once
    # and excluded from the timed seam
    exec_idx = jax.jit(lambda tk, sq: select_events_xla(tk, sq, m))(tk, sq)
    jax.block_until_ready(exec_idx)

    @jax.jit
    def fused_tail(idx, tail):
        # the megakernel's own window tail as one program: exec mask, the
        # slot gathers, the pairwise-count conflict mask (no sort), group,
        # release ranks — nothing materialized between stages
        es = sync.exec_selection_ring(safe, idx)
        tb, rs = tbl[idx], res[idx]
        rkey = tb * jnp.int32(n_res) + rs
        comp = es & (tb > 0)
        cnt = jnp.sum((rkey[:, None] == rkey[None, :]) & comp[None, :],
                      axis=1)
        clean = es & ~(comp & (cnt >= 2))
        g = (tm[idx], kind[idx], src[idx], dst[idx], ctx[idx], pay[idx],
             valid[idx])
        order, _rank, _counts = group_by_kind_xla(g[1], clean,
                                                  n_kinds=n_kinds)
        w = es.astype(jnp.int32)
        return es, clean, order, (tail + jnp.cumsum(w) - w) % cap, g

    # the stitched composition: one dispatch per hook, intermediates
    # materialized between them (the non-fused engine's per-window shape)
    s_safe = jax.jit(sync.exec_selection_ring)
    s_gather = jax.jit(lambda idx, *cols: tuple(c[idx] for c in cols))
    s_clean = jax.jit(lambda es, tb, rs: es & ~sync.conflict_mask(
        es, tb, rs, n_res=n_res, n_tables=n_tables))
    s_group = jax.jit(
        lambda kind_w, clean: group_by_kind_xla(kind_w, clean,
                                                n_kinds=n_kinds)[0])

    @jax.jit
    def s_rel(es, tail):
        w = es.astype(jnp.int32)
        return (tail + jnp.cumsum(w) - w) % cap

    def staged_tail(idx, tail):
        es = s_safe(safe, idx)
        tb, rs = s_gather(idx, tbl, res)
        clean = s_clean(es, tb, rs)
        g = s_gather(idx, tm, kind, src, dst, ctx, pay, valid)
        order = s_group(g[1], clean)
        return es, clean, order, s_rel(es, tail), g

    rates = {}
    for label, fn in (("fused", fused_tail), ("staged", staged_tail)):
        jax.block_until_ready(fn(exec_idx, tail))  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(exec_idx, tail)
        jax.block_until_ready(out)
        rates[label] = iters / (time.perf_counter() - t0)

    # the two tails are byte-identical, and both match the ref oracle
    got, want = fused_tail(exec_idx, tail), staged_tail(exec_idx, tail)
    for a, b in zip(got[:4], want[:4]):
        assert (np.asarray(a) == np.asarray(b)).all()
    for a, b in zip(got[4], want[4]):
        assert (np.asarray(a) == np.asarray(b)).all()
    fs_ref = kref.fused_select_ref(tk, sq, safe, tm, kind, src, dst, ctx,
                                   pay, valid, tbl, res, tail, m, **kw)
    assert (np.asarray(fs_ref.exec_idx) == np.asarray(exec_idx)).all()
    assert (np.asarray(fs_ref.clean) == np.asarray(got[1])).all()
    assert (np.asarray(fs_ref.order) == np.asarray(got[2])).all()

    emit(f"fused_superstep_p{pool_cap}", 1e6 / rates["fused"],
         f"windows_s_fused={rates['fused']:.0f};"
         f"windows_s_staged={rates['staged']:.0f};"
         f"exec_cap={m};"
         f"speedup={rates['fused'] / rates['staged']:.2f}x;"
         f"engine_events_s_fused={erates['fused']:.0f};"
         f"engine_events_s_stitched={erates['stitched']:.0f};"
         f"engine_speedup={erates['fused'] / erates['stitched']:.2f}x")


def bench_adaptive_exec(width=1024, n_ticks=4, lookahead=4, pool_cap=4096):
    """Monitoring-driven exec width vs the static exec_cap=256 default.

    Spill-heavy scenario: every conservative window offers ``width`` same-tick
    events, so the static default executes 256 and spills the rest — paying
    four windows (four GVT collectives) per tick. The adaptive ladder grows to
    the window size after one spilled window and finishes in ~width/ladder_top
    fewer windows, byte-identical to the oracle (spill semantics are exact for
    any width sequence — tests/test_policy.py pins the trace equality).
    Reported: window counts, windows saved, and wall rates (informational —
    the adaptive driver syncs monitoring to the host every window, which the
    vmap driver avoids, so on CPU the window saving is the honest headline).
    """
    from repro.core.policy import ExecPolicy

    def build(**kw):
        b = ScenarioBuilder(max_cpu=1, queue_cap=2, max_link=1, max_flow=2)
        sinks = [b.add_idle_lp() for _ in range(width)]
        for t in range(n_ticks):
            for lp in sinks:
                b.add_event(time=1 + lookahead * t, kind=ev.K_NOOP,
                            src=lp, dst=lp)
        return b.build(n_agents=1, lookahead=lookahead,
                       t_end=lookahead * (n_ticks + 1) + 2,
                       pool_cap=pool_cap, emit_cap=64, **kw)

    world, own, init_ev, spec = build(exec_cap=256)
    eng_s = Engine(world, own, init_ev, spec)
    jax.block_until_ready(eng_s.run_local().counters)     # compile
    t0 = time.perf_counter()
    st_s = eng_s.run_local()
    jax.block_until_ready(st_s.counters)
    dt_s = time.perf_counter() - t0

    ladder = ExecPolicy(ladder=(256, 512, min(width, pool_cap)))
    world, own, init_ev, spec = build(exec_policy=ladder)
    eng_a = Engine(world, own, init_ev, spec)
    eng_a.run_adaptive()                                   # compile rungs
    t0 = time.perf_counter()
    st_a = eng_a.run_adaptive()
    dt_a = time.perf_counter() - t0

    n = int(np.asarray(st_s.counters)[0, mon.C_EVENTS])
    assert n == int(np.asarray(st_a.counters)[0, mon.C_EVENTS]) == width * n_ticks
    w_s = int(np.asarray(st_s.windows)[0])
    w_a = int(np.asarray(st_a.windows)[0])
    assert w_a < w_s, (w_a, w_s)
    emit("adaptive_exec", dt_a * 1e6,
         f"windows_static={w_s};windows_adaptive={w_a};"
         f"windows_saved={w_s - w_a};"
         f"events_s_static={n / dt_s:.0f};events_s_adaptive={n / dt_a:.0f};"
         f"spill_static={int(np.asarray(st_s.counters)[0, mon.C_EXEC_SPILL])};"
         f"spill_adaptive={int(np.asarray(st_a.counters)[0, mon.C_EXEC_SPILL])}")


def bench_cache_churn(pool_caps=(4096,), width=256, n_keys=4, lookahead=4):
    """The outside-core replica-cache component under batched dispatch.

    ``width`` cache LPs, one lookup per cache per round (distinct rows —
    conflict-free batch), keys cycling mod ``n_keys`` so the run mixes cold
    misses (which emit CACHE_FILLs into their own window) with warm hits.
    Registry-generated handlers must keep batched-dispatch throughput: the
    events/s ratio vs the sequential fold is recorded as a trajectory (no
    regression gate yet — see benchmarks/baseline.json "trajectory").
    """
    import dataclasses

    from repro.scenarios.cache import build_churn_scenario

    for pool_cap in pool_caps:
        n_rounds = max(pool_cap // (2 * width), 2)
        built, _caches = build_churn_scenario(
            n_caches=width, n_keys=n_keys, n_rounds=n_rounds,
            cache_ways=n_keys, miss_lat=lookahead, lookahead=lookahead,
            pool_cap=pool_cap, emit_cap=2 * width + 8, exec_cap=width)
        world, own, init_ev, spec = built
        rates = {}
        for label, batched in (("batched", True), ("sequential", False)):
            spec_b = dataclasses.replace(spec, batched_dispatch=batched)
            eng = Engine(world, own, init_ev, spec_b)
            jax.block_until_ready(eng.run_local().counters)   # compile
            t0 = time.perf_counter()
            st = eng.run_local()                              # cached jit
            jax.block_until_ready(st.counters)
            dt = time.perf_counter() - t0
            c = np.asarray(st.counters)[0]
            n = int(c[mon.C_EVENTS])
            assert int(c[mon.C_BATCH_FALLBACK]) == 0, "scenario must be clean"
            rates[label] = n / dt
        w = jax.tree.map(lambda x: np.asarray(x[0]), st.world)
        hits, miss = int(w.cache_hits.sum()), int(w.cache_miss.sum())
        emit(f"cache_churn_p{pool_cap}", 1e6 / rates["batched"],
             f"events_s_batched={rates['batched']:.0f};"
             f"events_s_sequential={rates['sequential']:.0f};"
             f"width={width};hits={hits};misses={miss};"
             f"speedup={rates['batched'] / rates['sequential']:.2f}x")


def bench_trace_stream(n_flows=32, n_agents=2, ring=64, drain_every=8,
                       exec_cap=32):
    """PR 7 host-streaming trace drain: events/s with the device-side ring +
    io_callback drain vs (a) tracing off and (b) a big in-device buffer.

    Same scenario three ways, one process, one host — the gated ``speedup``
    is the stream/off throughput ratio (<= 1; it prices the whole streaming
    path: the host-stepped window driver replacing the fused while_loop, the
    per-window drain callback, and the host-side span reassembly).
    ``stream_vs_buffer`` prices the drain against in-device tracing under
    the same driver economics. Correctness rides along: the streamed trace
    must reassemble byte-identical to the in-device buffer's merge with
    C_TRACE_DROP == 0 — the ring (``ring`` rows, far below the run's total)
    wraps many times over.
    """
    from repro.core import TraceStream, merged_engine_trace

    built = t0t1(4.0, n_flows=n_flows, interval=4, n_agents=n_agents,
                 exec_cap=exec_cap)

    def timed(trace_cap, stream=None):
        world, own, init_ev, spec = built
        kw = dict(trace_cap=trace_cap)
        if stream is not None:
            kw.update(trace_stream=stream, drain_every=drain_every)
        eng = Engine(world, own, init_ev, spec, **kw)
        jax.block_until_ready(eng.run_local().counters)   # compile
        t0 = time.perf_counter()
        st = eng.run_local()
        jax.block_until_ready(st.counters)
        return st, time.perf_counter() - t0

    st_off, dt_off = timed(0)
    st_buf, dt_buf = timed(1 << 16)
    ts = TraceStream()
    st_str, dt_str = timed(ring, stream=ts)

    c = np.asarray(st_str.counters)
    n = int(c[:, mon.C_EVENTS].sum())
    assert n == int(np.asarray(st_off.counters)[:, mon.C_EVENTS].sum())
    drop = int(c[:, mon.C_TRACE_DROP].sum())
    assert drop == 0, f"streaming dropped {drop} trace rows"
    assert int(np.asarray(st_str.trace_n).max()) > ring, "ring never wrapped"
    want = merged_engine_trace(np.asarray(st_buf.trace),
                               np.asarray(st_buf.trace_n))
    assert ts.merged() == want, "streamed trace != in-device buffer"

    emit("trace_stream", dt_str * 1e6,
         f"events={n};streamed={ts.n_streamed};ring={ring};"
         f"windows={int(np.asarray(st_str.windows)[0])};trace_drop={drop};"
         f"events_s_off={n / dt_off:.0f};events_s_buffer={n / dt_buf:.0f};"
         f"events_s_stream={n / dt_str:.0f};"
         f"stream_vs_buffer={dt_buf / dt_str:.2f};"
         f"speedup={dt_off / dt_str:.2f}")


def bench_ensemble_throughput(replicas=128, seq_sample=8):
    """PR 8 vmap-over-seeds ensembles: replicas/s for one fused
    ``run_ensemble`` launch vs a sequential ``run_local`` loop over
    individually seeded states (``seq_sample`` runs extrapolated to a rate).

    The scenario is the failure-injection farm — its ``fp_rng`` LCG is what
    the default seed jump decorrelates, so the replicas genuinely diverge
    (different window counts) rather than re-running one trajectory R times.
    Correctness rides along: a sampled replica's full state slice must be
    byte-identical to its individual seeded run (the while_loop batching
    freezes finished replicas, it never lets them keep stepping). Recorded
    as a baseline.json *trajectory* entry — no gate; the speedup on
    shared-CPU "devices" prices launch amortization, not real parallel
    silicon.
    """
    from repro.core.engine import seed_rng_fields
    from repro.scenarios.failures import build_failure_scenario

    built, _info = build_failure_scenario(n_farms=2, pool_cap=128)
    eng = Engine(*built)
    seeds = np.arange(replicas, dtype=np.int32)
    jax.block_until_ready(eng.run_ensemble(seeds).counters)      # compile
    t0 = time.perf_counter()
    out = eng.run_ensemble(seeds)
    jax.block_until_ready(out.counters)
    dt_ens = time.perf_counter() - t0

    solo = Engine(*built)
    seed_one = jax.jit(seed_rng_fields)
    init = solo.init_state()
    jax.block_until_ready(
        solo.run_local(state=seed_one(init, np.int32(0))).counters)  # compile
    t0 = time.perf_counter()
    for s in range(seq_sample):
        st = solo.run_local(state=seed_one(init, np.int32(s)))
        jax.block_until_ready(st.counters)
    dt_seq = time.perf_counter() - t0

    r = replicas - 1
    one = solo.run_local(state=seed_one(init, np.int32(r)))
    same = jax.tree.all(jax.tree.map(
        lambda x, y: bool((np.asarray(x)[r] == np.asarray(y)).all()),
        out, one))
    assert bool(same), "ensemble replica != individual seeded run"

    rate_ens = replicas / dt_ens
    rate_seq = seq_sample / dt_seq
    n_events = int(np.asarray(out.counters)[:, :, mon.C_EVENTS].sum())
    n_windows = len({int(w) for w in np.asarray(out.windows)[:, 0]})
    emit("ensemble_throughput", dt_ens * 1e6,
         f"replicas={replicas};events={n_events};"
         f"distinct_window_counts={n_windows};"
         f"replicas_s_ensemble={rate_ens:.1f};replicas_s_seq={rate_seq:.1f};"
         f"speedup={rate_ens / rate_seq:.2f}")


def bench_shard_scaling(n_agents=64, n_ticks=32, lookahead=2):
    """Distributed scale-out: events/s at 64 packed agents, 4 host devices vs
    1 (the shard_map x vmap driver; K = 16 vs 64 lanes per shard).

    Each agent owns one idle LP with one NOOP per tick, so every conservative
    window executes one event per agent — embarrassingly agent-parallel,
    isolating the driver overheads (staged all_to_all + tuple-axis GVT
    collective vs pure vmap lanes). Subprocesses, because the host device
    count is fixed at jax import. Recorded as a baseline.json *trajectory*
    entry, no gate: forced host devices share this container's CPU, so the
    wall-clock ratio is hardware truth only on a real multi-device fleet.
    """
    import os
    import subprocess
    import sys

    if jax.default_backend() != "cpu":
        # the children would contend with this process for the accelerator
        # (one process per chip); forced host devices exist only on CPU
        print(f"# shard_scaling skipped: backend {jax.default_backend()} — "
              "its forced-host-device children need the CPU backend")
        return

    child = r"""
import os, sys
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + sys.argv[1])
import json, time
import numpy as np
import jax
from jax.sharding import Mesh
from repro.core import Engine, ScenarioBuilder, events as ev
from repro.core import monitoring as mon

n_agents, n_ticks, lookahead = (int(a) for a in sys.argv[2:5])
b = ScenarioBuilder(max_cpu=1, queue_cap=2, max_link=1, max_flow=2)
lps = [b.add_idle_lp() for _ in range(n_agents)]
for t in range(n_ticks):
    for lp in lps:
        b.add_event(time=1 + lookahead * t, kind=ev.K_NOOP, src=lp, dst=lp)
built = b.build(n_agents=n_agents, lookahead=lookahead,
                t_end=lookahead * (n_ticks + 1) + 2, pool_cap=n_ticks + 2,
                emit_cap=8)
eng = Engine(*built)
mesh = Mesh(np.array(jax.devices()), ("agents",))
jax.block_until_ready(eng.run_distributed(mesh).counters)   # compile
t0 = time.perf_counter()
st = eng.run_distributed(mesh)
jax.block_until_ready(st.counters)
dt = time.perf_counter() - t0
c = np.asarray(st.counters)
print(json.dumps({"events": int(c[:, mon.C_EVENTS].sum()), "s": dt,
                  "windows": int(np.asarray(st.windows)[0])}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    res = {}
    for nd in (1, 4):
        out = subprocess.run(
            [sys.executable, "-c", child, str(nd), str(n_agents),
             str(n_ticks), str(lookahead)],
            capture_output=True, text=True, env=env, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        res[nd] = json.loads(out.stdout.strip().splitlines()[-1])
    assert res[1]["events"] == res[4]["events"] == n_agents * n_ticks
    eps = {nd: r["events"] / r["s"] for nd, r in res.items()}
    emit("shard_scaling", res[4]["s"] * 1e6,
         f"agents={n_agents};devices=4;events={res[4]['events']};"
         f"windows={res[4]['windows']};events_s_d4={eps[4]:.0f};"
         f"events_s_d1={eps[1]:.0f};speedup={eps[4] / eps[1]:.2f}")


def bench_fleet_resume(preempt_window=16, every=8):
    """PR 9 elastic fleet orchestration: the price of surviving a preemption.

    Same checkpointed scenario twice through the Orchestrator on one host:
    uninterrupted, and preempted mid-run (injected shard-loss probe at
    window ``preempt_window``) with automatic resume from the latest
    committed checkpoint. ``resume_overhead`` is the wall ratio
    preempted/uninterrupted — it prices the second attempt's engine
    rebuild + re-jit + checkpoint restore + replayed windows. Trajectory
    entry, no gate: the overhead is dominated by recompilation, which real
    fleets amortize across much longer runs. Byte-equality of the two final
    states is asserted inside (the orchestrator's core promise)."""
    import tempfile

    from repro.fleet import FleetPolicy, Orchestrator

    built = t0t1(2.0, n_flows=32, interval=8, pool_cap=512, exec_cap=64)

    def orchestrated(preempt, tmp):
        pol = FleetPolicy(checkpoint_dir=tmp, checkpoint_every=every)
        orch = Orchestrator(pol, preempt=preempt)
        t0 = time.perf_counter()
        res = orch.run(built, devices=jax.devices()[:1])
        jax.block_until_ready(res.state.counters)
        return res, time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as tmp:       # compile warmup
        orchestrated(None, tmp)
    with tempfile.TemporaryDirectory() as tmp:
        res_u, dt_u = orchestrated(None, tmp)
    with tempfile.TemporaryDirectory() as tmp:
        res_p, dt_p = orchestrated(
            lambda w, a: 1 if a == 0 and w >= preempt_window else None, tmp)
    same = jax.tree.all(jax.tree.map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()),
        res_p.state, res_u.state))
    assert bool(same), "preempted+resumed state != uninterrupted"
    assert res_p.counts["PREEMPT"] == 1 and res_p.counts["RESUME"] == 1
    n = int(np.asarray(res_u.state.counters)[:, mon.C_EVENTS].sum())
    emit("fleet_resume", dt_p * 1e6,
         f"events={n};windows={int(np.asarray(res_u.state.windows)[0])};"
         f"preempt_window={preempt_window};checkpoint_every={every};"
         f"attempts={res_p.attempts};"
         f"s_uninterrupted={dt_u:.3f};s_preempted={dt_p:.3f};"
         f"resume_overhead={dt_p / dt_u:.2f}")


def bench_kernels():
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (8, 512, 64))
    k = jax.random.normal(ks[1], (4, 512, 64))
    v = jax.random.normal(ks[2], (4, 512, 64))

    from repro.kernels.ref import attention_ref
    fa_ref = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    fa_ref(q, k, v)
    t0 = time.perf_counter()
    for _ in range(10):
        jax.block_until_ready(fa_ref(q, k, v))
    emit("kernel_flash_attention_xla_ref", (time.perf_counter() - t0) / 10 * 1e6,
         "shape=8x512x64")

    from repro.models.linear_rnn import gla_chunked
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (1, 512, 8, 64)) * 0.3))
    qq = jax.random.normal(ks[4], (1, 512, 8, 64))
    gf = jax.jit(lambda q, k, v, w: gla_chunked(q, k, v, w, mode="k")[0])
    gf(qq, qq, qq, w)
    t0 = time.perf_counter()
    for _ in range(10):
        jax.block_until_ready(gf(qq, qq, qq, w))
    emit("kernel_gla_chunked_xla_ref", (time.perf_counter() - t0) / 10 * 1e6,
         "shape=1x512x8x64")

    tk = jax.random.randint(ks[0], (1024,), 0, 1000)
    sq = jax.random.randint(ks[1], (1024,), 0, 2**20)
    from repro.core.engine import lexsort_time_seq
    sf = jax.jit(lexsort_time_seq)
    sf(tk, sq)
    t0 = time.perf_counter()
    for _ in range(50):
        jax.block_until_ready(sf(tk, sq))
    emit("kernel_event_sort_xla_ref", (time.perf_counter() - t0) / 50 * 1e6,
         "n=1024")

    from repro.core.network import incidence, maxmin_rates
    routes = jax.random.randint(ks[2], (64, 3), -1, 8)
    inc = incidence(routes, 8)
    bw = jnp.abs(jax.random.normal(ks[3], (8,))) * 5 + 0.5
    act = jax.random.bernoulli(ks[4], 0.7, (64,))
    mf = jax.jit(maxmin_rates)
    mf(inc, bw, act)
    t0 = time.perf_counter()
    for _ in range(50):
        jax.block_until_ready(mf(inc, bw, act))
    emit("kernel_waterfill_xla_ref", (time.perf_counter() - t0) / 50 * 1e6,
         "F=64,L=8")


def bench_workload_sim():
    """DES-simulated multi-pod step time vs analytic roofline estimate."""
    cell = CellModel(n_pods=2, t_compute_s=0.05, dcn_bytes_per_pod=2e9,
                     n_steps=6)
    t0 = time.perf_counter()
    out = simulate_training(cell)
    dt = time.perf_counter() - t0
    emit("workload_sim_2pod", dt * 1e6,
         f"sim={out['simulated_step_s']:.4f}s;analytic={out['analytic_step_s']:.4f}s;"
         f"events={out['events']}")
    # straggler: pod 0 at 1.5x compute — simulated step stretches accordingly
    cell_s = CellModel(n_pods=2, t_compute_s=0.05, dcn_bytes_per_pod=2e9,
                       n_steps=6, slow_pod_factor=1.5)
    out_s = simulate_training(cell_s)
    emit("workload_sim_straggler", 0.0,
         f"sim={out_s['simulated_step_s']:.4f}s;"
         f"slowdown={out_s['simulated_step_s'] / max(out['simulated_step_s'], 1e-12):.2f}x")


def _parse_derived(derived: str):
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v.rstrip("x"))
        except ValueError:
            out[k] = v
    return out


def write_json(path: str) -> None:
    """Machine-readable results (the CI benchmark artifact + regression gate)."""
    rec = {
        "meta": {"backend": jax.default_backend(), "jax": jax.__version__},
        "rows": [{"name": n, "us_per_call": us, "derived": _parse_derived(d)}
                 for n, us, d in ROWS],
    }
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, sort_keys=True)
    print(f"# wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fast CI-smoke subset only")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write results as machine-readable JSON "
                         "(uploaded from CI as the benchmark artifact and "
                         "checked by benchmarks/check_regression.py)")
    ap.add_argument("--shard-scaling", action="store_true",
                    help="also run the multi-device shard_scaling benchmark "
                         "(subprocesses with forced host device counts; run "
                         "by the dedicated distributed CI job)")
    ap.add_argument("--ensemble", action="store_true",
                    help="also run the ensemble_throughput benchmark "
                         "(128-replica vmap-over-seeds launch vs a "
                         "sequential loop; run by the distributed CI job)")
    ap.add_argument("--fleet", action="store_true",
                    help="also run the fleet_resume benchmark (orchestrated "
                         "preempt+resume wall vs uninterrupted; run by the "
                         "distributed CI job)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    dev = jax.devices()
    print(f"# device: platform={dev[0].platform} kind={dev[0].device_kind} "
          f"count={len(dev)}")
    print("name,us_per_call,derived")
    if args.quick:
        bench_exec_compaction(pool_caps=(4096,))
        bench_batched_dispatch(pool_caps=(4096,))
        bench_wide_component(pool_caps=(4096,))
        bench_insert_churn(pool_caps=(4096,))
        bench_fused_superstep()
        bench_adaptive_exec()
        bench_cache_churn(pool_caps=(4096,))
        bench_trace_stream()
        bench_scheduler()
        bench_kernels()
        bench_workload_sim()
    else:
        bench_fig2_t0t1()
        bench_fig2b_congestion()
        bench_agent_scaling()
        bench_sync_overhead()
        bench_scheduler()
        bench_contexts()
        bench_exec_compaction()
        bench_batched_dispatch()
        bench_wide_component()
        bench_insert_churn()
        bench_fused_superstep()
        bench_adaptive_exec()
        bench_cache_churn()
        bench_trace_stream()
        bench_shard_scaling()
        bench_ensemble_throughput()
        bench_fleet_resume()
        bench_kernels()
        bench_workload_sim()
    if args.shard_scaling and args.quick:
        bench_shard_scaling()
    if args.ensemble and args.quick:
        bench_ensemble_throughput()
    if args.fleet and args.quick:
        bench_fleet_resume()
    if args.json:
        write_json(args.json)


if __name__ == "__main__":
    main()
