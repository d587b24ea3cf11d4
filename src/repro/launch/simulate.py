"""Simulation launcher — the paper's own workflow, as a CLI.

Modes:
  t0t1       reproduce the paper's §3.1 CERN study (bandwidth sweep)
  workload   simulate a training cell from a dry-run roofline JSON
  distributed run the T0/T1 scenario under the shard_map x vmap scale-out
             driver (needs >1 device:
             XLA_FLAGS=--xla_force_host_platform_device_count=8);
             --agents-per-device packs multiple agent rows per shard,
             --migrate demos cross-shard event migration, --adaptive-exec
             runs the lockstep per-shard width ladder
  ensemble   Monte Carlo vmap-over-seeds sweep of the failure scenario:
             hundreds of replicas per launch (Engine.run_ensemble), with
             per-replica counters reduced into a MetricsStream summary
  run        resolve a named catalog scenario (repro.scenarios.catalog) and
             dispatch it through the elastic fleet orchestrator
             (repro.fleet.Orchestrator): ``simulate run t0t1 --set wan_bw=0.5``;
             ``simulate run --list`` prints the catalog. The orchestrator
             knobs (--max-retries/--min-devices/--preempt-at-window ...)
             make it the elastic-execution entry point: a preempted run
             auto-resumes from the latest checkpoint on the survivors.

The t0t1 and distributed modes take durable checkpoint/resume knobs:
``--checkpoint-dir D --checkpoint-every W`` saves the full EngineState at
every W-th GVT-aligned window boundary; ``--resume`` restores the latest
checkpoint and continues — for distributed, onto whatever device count the
resumed process has (the checkpoint is device-layout-free). A multi-point
t0t1 sweep keys per-point subdirectories (``DIR/bw_<bw>``) so every sweep
point checkpoints and resumes independently.
``--kill-after-window W`` SIGKILLs the process right after the first
committed checkpoint at window >= W — the CI crash harness.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

import numpy as np


def _stream_args(p):
    """The host-streaming observability knobs (t0t1 + distributed modes)."""
    p.add_argument("--stream-trace", type=int, default=None, metavar="CAP",
                   help="stream the full event trace to the host through a "
                        "CAP-row device-side ring drained at window "
                        "boundaries (keeps C_TRACE_DROP == 0 for runs of any "
                        "length; CAP must be >= the exec width)")
    p.add_argument("--metrics-interval", type=int, default=None, metavar="N",
                   help="emit a fleet metrics snapshot as one JSON line on "
                        "stdout every N windows (registry-declared counter "
                        "names; a final snapshot is always emitted)")
    p.add_argument("--drain-every", type=int, default=16, metavar="N",
                   help="trace-ring drain cadence in windows (forced drains "
                        "still fire whenever the next window could overrun "
                        "the ring; default 16)")


def _build_streams(args):
    """(engine kwargs, TraceStream | None, MetricsStream | None) from the
    CLI knobs — empty kwargs when streaming is off."""
    kw = {}
    ts = ms = None
    if args.stream_trace is not None:
        from repro.core.monitoring import TraceStream
        ts = TraceStream()
        kw.update(trace_cap=args.stream_trace, trace_stream=ts,
                  drain_every=args.drain_every)
    if args.metrics_interval is not None:
        from repro.core.monitoring import MetricsStream
        ms = MetricsStream(interval=args.metrics_interval, out=sys.stdout)
        kw.update(metrics_stream=ms, drain_every=args.drain_every)
    return kw, ts, ms


def _checkpoint_args(p):
    """The durable checkpoint/resume knobs (t0t1 + distributed modes)."""
    p.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                   help="directory for durable EngineState checkpoints "
                        "(atomic step_* subdirs; enables the other "
                        "checkpoint knobs)")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="W",
                   help="save a checkpoint every W windows (GVT-aligned "
                        "boundaries; 0 disables periodic saves)")
    p.add_argument("--checkpoint-keep", type=int, default=3, metavar="N",
                   help="retain the newest N checkpoints (default 3)")
    p.add_argument("--resume", action="store_true",
                   help="restore the latest checkpoint from "
                        "--checkpoint-dir and continue the run from it "
                        "(byte-identical to never having stopped)")
    p.add_argument("--kill-after-window", type=int, default=None, metavar="W",
                   help="SIGKILL this process right after the first "
                        "committed checkpoint at window >= W (crash-harness "
                        "knob; needs --checkpoint-every)")


def _build_checkpointer(args, directory=None):
    """A SimCheckpointer from the CLI knobs, or None when checkpointing is
    off — with the cross-knob validation in one place. ``directory``
    overrides ``args.checkpoint_dir`` (the per-sweep-point subdir case)."""
    if args.checkpoint_dir is None:
        if (args.checkpoint_every or args.resume
                or args.kill_after_window is not None):
            raise SystemExit("--checkpoint-every/--resume/--kill-after-window "
                             "need --checkpoint-dir DIR")
        return None
    if args.kill_after_window is not None and not args.checkpoint_every:
        raise SystemExit("--kill-after-window needs --checkpoint-every W "
                         "(the kill fires after a committed checkpoint)")
    from repro.checkpoint import SimCheckpointer
    return SimCheckpointer(directory or args.checkpoint_dir,
                           every=args.checkpoint_every,
                           keep=args.checkpoint_keep,
                           kill_after=args.kill_after_window)


def _exec_policy_args(args, pool_cap):
    """(exec_cap | exec_policy) build kwargs from the CLI knobs.

    ``pool_cap`` must be the value the builder is given — the default ladder
    tops out at the pool, so the two may not drift apart.
    """
    if not getattr(args, "adaptive_exec", False):
        return dict(exec_cap=args.exec_cap)
    if args.exec_cap is not None:
        raise SystemExit(
            "--exec-cap and --adaptive-exec conflict: pass either a static "
            "width or a ladder (--exec-ladder), not both")
    from repro.core.policy import ExecPolicy, default_ladder
    ladder = (tuple(args.exec_ladder) if args.exec_ladder
              else default_ladder(pool_cap))
    return dict(exec_policy=ExecPolicy(ladder=ladder))


def run_t0t1(args):
    from repro.core import Engine, ScenarioBuilder
    from repro.core import monitoring as mon
    from repro.core.components import DATA_WRITE, FLOW_START, JOB_SUBMIT

    # A multi-point sweep keys one checkpoint subdir per bandwidth so every
    # point saves/resumes independently (a single point uses DIR itself).
    sweep_dirs = {bw: args.checkpoint_dir for bw in args.bandwidths}
    if args.checkpoint_dir is not None and len(args.bandwidths) > 1:
        sweep_dirs = {bw: os.path.join(args.checkpoint_dir, f"bw_{bw:g}")
                      for bw in args.bandwidths}
    for bw in args.bandwidths:
        ck = _build_checkpointer(args, directory=sweep_dirs[bw])
        b = ScenarioBuilder(max_cpu=4, queue_cap=16, max_link=4, max_flow=32)
        t0 = b.add_regional_center(n_cpu=2, cpu_power=10.0, disk=2000.0,
                                   tape=20000.0, tape_rate=5.0)
        t1 = b.add_regional_center(n_cpu=2, cpu_power=8.0, disk=2000.0,
                                   tape=20000.0, tape_rate=5.0)
        wan = b.add_net_region(link_bws=[bw, bw], link_lats=[5, 5])
        b.add_generator(target_lp=wan, kind=FLOW_START,
                        payload=FLOW_START.pack(
                            size=40.0, l0=0, notify_lp=t1["farm"],
                            notify_kind=JOB_SUBMIT.id,
                            notify2_lp=t1["storage"],
                            notify2_kind=DATA_WRITE.id),
                        interval=15, count=args.flows)
        pool_cap = 1024
        world, own, init_ev, spec = b.build(
            n_agents=args.agents, lookahead=2, t_end=100_000,
            pool_cap=pool_cap, work_per_mb=2.0,
            batched_dispatch=args.batched_dispatch,
            merge_mode=args.merge_mode, insert_mode=args.insert_mode,
            fused_select=args.fused_select,
            **_exec_policy_args(args, pool_cap))
        stream_kw, ts, _ms = _build_streams(args)
        eng = Engine(world, own, init_ev, spec, checkpointer=ck, **stream_kw)
        state, rung = None, None
        if args.resume:
            rec = eng.restore()
            state, rung = rec.state, rec.rung
            print(f"[resume] window {rec.step} from {sweep_dirs[bw]}")
        if args.adaptive_exec:
            st = eng.run_adaptive(max_windows=200_000, state=state, rung=rung)
        else:
            st = eng.run_local(max_windows=200_000, state=state)
        c = np.asarray(st.counters).sum(axis=0)
        extra = ""
        if ts is not None:
            extra = (f" streamed={ts.n_streamed}"
                     f" trace_drop={int(c[mon.C_TRACE_DROP])}")
        print(f"[t0t1] bw={bw:7.3f} MB/tick  events={int(c[mon.C_EVENTS]):6d} "
              f"stale={int(c[mon.C_STALE]):5d} "
              f"interrupts={int(c[mon.C_INTERRUPTS]):5d} "
              f"MB={int(c[mon.C_MB_TRANSFERRED])} "
              f"windows={int(np.asarray(st.windows)[0])}" + extra)


def run_workload(args):
    from repro.core.workload import cell_from_roofline, simulate_training
    paths = sorted(glob.glob(os.path.join(args.results, "*.json")))
    if args.cell:
        paths = [p for p in paths if args.cell in p]
    for p in paths[: args.limit]:
        with open(p) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        cell = cell_from_roofline(rec["roofline"], n_pods=2, n_steps=4)
        out = simulate_training(cell)
        print(f"[workload] {rec['arch']} x {rec['shape']} x {rec['mesh']}: "
              f"sim={out['simulated_step_s']:.4f}s "
              f"analytic={out['analytic_step_s']:.4f}s "
              f"events={out['events']}")


def run_distributed(args):
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax
    from repro.core import Engine, ScenarioBuilder
    from repro.core import monitoring as mon
    from repro.core.components import DATA_WRITE, FLOW_START, JOB_SUBMIT
    from repro.launch.mesh import make_sim_mesh

    devs = jax.devices()
    n_dev = min(len(devs), 8)
    if n_dev < 2:
        # the forced host-device count only applies to the CPU backend: a
        # one-chip host cannot give the mesh this mode exists to exercise
        raise SystemExit(f"distributed needs more than one device; the "
                         f"{devs[0].platform} backend has {len(devs)}")
    n = n_dev * args.agents_per_device
    b = ScenarioBuilder(max_cpu=4, queue_cap=16, max_link=4, max_flow=32)
    t0 = b.add_regional_center(n_cpu=2, cpu_power=10.0, disk=2000.0,
                               tape=20000.0, tape_rate=5.0)
    t1 = b.add_regional_center(n_cpu=2, cpu_power=8.0, disk=2000.0,
                               tape=20000.0, tape_rate=5.0)
    wan = b.add_net_region(link_bws=[0.5, 0.5], link_lats=[5, 5])
    b.add_generator(target_lp=wan, kind=FLOW_START,
                    payload=FLOW_START.pack(
                        size=40.0, l0=0, notify_lp=t1["farm"],
                        notify_kind=JOB_SUBMIT.id, notify2_lp=t1["storage"],
                        notify2_kind=DATA_WRITE.id),
                    interval=15, count=args.flows)
    pool_cap = 512
    world, own, init_ev, spec = b.build(n_agents=n, lookahead=2,
                                        t_end=100_000, pool_cap=pool_cap,
                                        work_per_mb=2.0,
                                        batched_dispatch=args.batched_dispatch,
                                        merge_mode=args.merge_mode,
                                        insert_mode=args.insert_mode,
                                        fused_select=args.fused_select,
                                        **_exec_policy_args(args, pool_cap))
    if args.stream_check and args.stream_trace is None:
        raise SystemExit("--stream-check needs --stream-trace CAP")
    ck = _build_checkpointer(args)
    if args.resume and args.migrate:
        raise SystemExit("--resume and --migrate conflict: the checkpoint "
                         "already contains the (possibly migrated) state")
    stream_kw, ts, _ms = _build_streams(args)
    eng = Engine(world, own, init_ev, spec, checkpointer=ck, **stream_kw)
    mesh = make_sim_mesh(n_dev)
    state = None
    if args.migrate and n > 1:
        # cross-shard migration demo: move the agent holding the seeded
        # events (the generator LP's owner) to the opposite end of the fleet
        # so its pool ships through the all_to_all path, then continue from
        # the migrated state
        st0 = eng.init_state()
        la = np.asarray(st0.world.lp_agent[0])
        src = int(np.asarray(st0.pool.valid).sum(axis=1).argmax())
        dst = 0 if src != 0 else n - 1
        new_la = np.where(la == src, dst,
                          np.where(la == dst, src, la)).astype(np.int32)
        state = eng.apply_placement_distributed(st0, new_la, mesh)
    run_state, run_rung = state, None
    if args.resume:
        rec = eng.restore()
        run_state, run_rung = rec.state, rec.rung
        print(f"[resume] window {rec.step} from {args.checkpoint_dir} "
              f"onto {n_dev} devices")
    if args.adaptive_exec:
        st = eng.run_distributed_adaptive(mesh, max_windows=200_000,
                                          state=run_state, rung=run_rung)
    else:
        st = eng.run_distributed(mesh, max_windows=200_000, state=run_state)
    c = np.asarray(st.counters).sum(axis=0)
    extra = ""
    if args.migrate:
        extra = (f" migrate_out={int(c[mon.C_MIGRATE_OUT])}"
                 f" migrate_in={int(c[mon.C_MIGRATE_IN])}")
    if args.adaptive_exec:
        extra += f" rungs={sorted(set(eng.adaptive_rungs))}"
    if ts is not None:
        extra += (f" streamed={ts.n_streamed}"
                  f" trace_drop={int(c[mon.C_TRACE_DROP])}")
    print(f"[distributed] agents={n} devices={n_dev} "
          f"platform={devs[0].platform} "
          f"events={int(c[mon.C_EVENTS])} "
          f"windows={int(np.asarray(st.windows)[0])} "
          f"remote_msgs={int(c[mon.C_MSGS_REMOTE])}" + extra)
    if args.stream_check:
        # end-to-end streaming gate (CI): the streamed trace must (1) have
        # dropped nothing, (2) actually exceed the in-device ring (the run
        # would fit in the buffer otherwise and the check would be vacuous),
        # and (3) be byte-identical to an un-streamed reference run with a
        # buffer big enough to hold everything — which PR 6 pinned to the
        # sequential oracle, closing the chain stream == buffer == oracle.
        # Under --resume the reference still replays the FULL run from
        # scratch (state is the initial state, not the restored one), so the
        # equality proves the killed-and-resumed streamed trace is exactly
        # the never-interrupted trace.
        from repro.core import merged_engine_trace
        drop = int(c[mon.C_TRACE_DROP])
        if drop:
            raise SystemExit(f"stream-check FAILED: C_TRACE_DROP={drop}")
        tn = np.asarray(st.trace_n)
        if int(tn.max()) <= args.stream_trace:
            raise SystemExit(
                f"stream-check vacuous: per-agent trace_n max {int(tn.max())}"
                f" never exceeded the ring cap {args.stream_trace} — lower "
                f"--stream-trace or raise the event count")
        ref_eng = Engine(world, own, init_ev, spec, trace_cap=1 << 16)
        if args.adaptive_exec:
            ref = ref_eng.run_distributed_adaptive(mesh, max_windows=200_000,
                                                   state=state)
        else:
            ref = ref_eng.run_distributed(mesh, max_windows=200_000,
                                          state=state)
        want = merged_engine_trace(np.asarray(ref.trace),
                                   np.asarray(ref.trace_n))
        got = ts.merged()
        if got != want:
            raise SystemExit(
                f"stream-check FAILED: streamed trace ({len(got)} rows) != "
                f"in-device reference ({len(want)} rows)")
        print(f"[stream-check] OK: {len(got)} rows streamed through a "
              f"{args.stream_trace}-row ring == reference, trace_drop=0")


def run_ensemble(args):
    from repro.core import Engine
    from repro.core.monitoring import MetricsStream
    from repro.scenarios.failures import build_failure_scenario

    built, _info = build_failure_scenario(n_farms=args.farms,
                                          pool_cap=args.pool_cap)
    ms = MetricsStream(interval=1_000_000, out=sys.stdout)
    eng = Engine(*built, metrics_stream=ms)
    seeds = np.arange(args.seed0, args.seed0 + args.replicas, dtype=np.int32)
    eng.run_ensemble(seeds)
    ev_stats = ms.latest["per_replica"]["EVENTS"]
    fail_stats = ms.latest["per_replica"]["CPU_FAILS"]
    print(f"[ensemble] replicas={args.replicas} farms={args.farms} "
          f"windows={ms.latest['windows']} "
          f"events/replica min={ev_stats['min']} mean={ev_stats['mean']:.1f} "
          f"max={ev_stats['max']} "
          f"fails/replica min={fail_stats['min']} max={fail_stats['max']}")


def run_catalog(args):
    from repro.scenarios import catalog

    if args.list:
        for name in catalog.names():
            sd = catalog.get(name)
            print(f"{name:15s} [{sd.driver}] {sd.doc}")
            defaults = " ".join(f"{k}={v}" for k, v in sd.params)
            if defaults:
                print(f"{'':15s} params: {defaults}")
        return
    if args.name is None:
        raise SystemExit("simulate run: pass a scenario name (or --list)")
    overrides = {}
    for item in args.set:
        key, sep, value = item.partition("=")
        if not sep or not key:
            raise SystemExit(f"--set expects K=V, got {item!r}")
        overrides[key] = value
    try:
        sd = catalog.get(args.name)
        built, params = sd.resolve(overrides)
    except catalog.CatalogError as e:
        raise SystemExit(str(e)) from None

    if args.devices is not None and args.devices > 1:
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
    import jax
    from repro.fleet import FleetPolicy, Orchestrator

    devices = None
    if args.devices is not None:
        have = jax.devices()
        if args.devices > len(have):
            raise SystemExit(f"--devices {args.devices} > the {len(have)} "
                             f"available on the {have[0].platform} backend "
                             f"(on CPU, set XLA_FLAGS="
                             f"--xla_force_host_platform_device_count=N)")
        devices = have[: args.devices]

    preempt = None
    if args.preempt_at_window is not None:
        if args.preempt_survivors is None:
            raise SystemExit("--preempt-at-window needs --preempt-survivors K")
        if args.checkpoint_dir is None:
            raise SystemExit("--preempt-at-window needs --checkpoint-dir DIR "
                             "(the resume path requires checkpoints)")

        def preempt(window, attempt, *, _w=args.preempt_at_window,
                    _k=args.preempt_survivors):
            # one injected shard loss: the first attempt dies once it
            # reaches window _w, leaving _k survivors; later attempts run out
            return _k if attempt == 0 and window >= _w else None

    if args.stream_check and args.stream_trace is None:
        raise SystemExit("--stream-check needs --stream-trace CAP")
    _stream_kw, ts, ms = _build_streams(args)
    pol = FleetPolicy(
        driver=sd.driver if sd.driver != "auto" else args.driver,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        checkpoint_keep=args.checkpoint_keep,
        kill_after=args.kill_after_window,
        max_windows=args.max_windows,
        max_retries=args.max_retries,
        backoff=args.backoff,
        min_devices=args.min_devices)
    orch = Orchestrator(pol, trace_stream=ts, metrics_stream=ms,
                        preempt=preempt,
                        trace_cap=args.stream_trace or 0,
                        drain_every=args.drain_every)
    seeds = None
    if sd.driver == "ensemble":
        seeds = np.arange(params["seed0"],
                          params["seed0"] + params["replicas"],
                          dtype=np.int32)
    res = orch.run(built, devices=devices, seeds=seeds)

    from repro.core import monitoring as mon
    st = res.state
    cn = np.asarray(st.counters)  # (A, N) — or (R, A, N) for ensembles
    c = cn.sum(axis=tuple(range(cn.ndim - 1)))
    print(f"[run] {args.name} driver={res.driver} devices={res.devices} "
          f"platform={jax.devices()[0].platform} attempts={res.attempts} "
          f"events={int(c[mon.C_EVENTS])} "
          f"windows={int(np.asarray(st.windows).reshape(-1)[0])} "
          f"preempt={res.counts['PREEMPT']} resume={res.counts['RESUME']} "
          f"reshard={res.counts['RESHARD']}")
    if args.stream_check:
        # the elastic streaming gate: the (possibly preempted-and-resumed)
        # streamed trace must have dropped nothing, actually exceeded the
        # in-device ring, and be byte-identical to an un-streamed big-buffer
        # reference run that was never interrupted — the zero-drop oracle
        # equality the orchestrator promises.
        from repro.core import Engine, merged_engine_trace
        drop = int(c[mon.C_TRACE_DROP])
        if drop:
            raise SystemExit(f"stream-check FAILED: C_TRACE_DROP={drop}")
        tn = np.asarray(st.trace_n)
        if int(tn.max()) <= args.stream_trace:
            raise SystemExit(
                f"stream-check vacuous: per-agent trace_n max {int(tn.max())}"
                f" never exceeded the ring cap {args.stream_trace}")
        ref_eng = Engine(*built, trace_cap=1 << 16)
        if res.driver == "local":
            ref = ref_eng.run_local(pol.max_windows)
        elif res.driver == "adaptive":
            ref = ref_eng.run_adaptive(pol.max_windows)
        else:
            from jax.sharding import Mesh
            mesh = Mesh(np.array(jax.devices()[: res.devices]), ("agents",))
            if res.driver == "distributed_adaptive":
                ref = ref_eng.run_distributed_adaptive(mesh, pol.max_windows)
            else:
                ref = ref_eng.run_distributed(mesh, pol.max_windows)
        want = merged_engine_trace(np.asarray(ref.trace),
                                   np.asarray(ref.trace_n))
        got = ts.merged()
        if got != want:
            raise SystemExit(
                f"stream-check FAILED: streamed trace ({len(got)} rows) != "
                f"uninterrupted reference ({len(want)} rows)")
        print(f"[stream-check] OK: {len(got)} rows streamed through a "
              f"{args.stream_trace}-row ring across {res.attempts} "
              f"attempt(s) == uninterrupted reference, trace_drop=0")


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    p1 = sub.add_parser("t0t1")
    p1.add_argument("--bandwidths", type=float, nargs="+",
                    default=[8.0, 2.0, 0.5, 0.125])
    p1.add_argument("--flows", type=int, default=24)
    p1.add_argument("--agents", type=int, default=1)
    p1.add_argument("--exec-cap", type=int, default=None,
                    help="per-window compacted execution cap "
                         "(default min(pool_cap, 256))")
    p1.add_argument("--batched-dispatch", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="grouped vectorized handler dispatch (engine step 4); "
                         "--no-batched-dispatch restores the sequential fold")
    p1.add_argument("--merge-mode", choices=("delta", "dense"),
                    default="delta",
                    help="batched-merge strategy: per-row delta scatters "
                         "(default) or the PR 2 whole-table reference merge")
    p1.add_argument("--insert-mode", choices=("ring", "ref"), default="ring",
                    help="event-pool lifecycle: free-list ring (default) or "
                         "the retained O(pool_cap) insert_ref scan")
    p1.add_argument("--fused-select", action="store_true",
                    help="run the window selection front-end (sort + safe "
                         "prefix + gather + conflict + rank + ring slots) as "
                         "one fused Pallas superstep megakernel instead of "
                         "the XLA-stitched stages (compiled on TPU, "
                         "interpreted elsewhere)")
    p1.add_argument("--adaptive-exec", action="store_true",
                    help="monitoring-driven exec width (core/policy.py "
                         "ladder; Engine.run_adaptive) instead of a static "
                         "exec_cap")
    p1.add_argument("--exec-ladder", type=int, nargs="+", default=None,
                    help="explicit width ladder for --adaptive-exec "
                         "(default: policy.default_ladder(pool_cap))")
    _stream_args(p1)
    _checkpoint_args(p1)
    p2 = sub.add_parser("workload")
    p2.add_argument("--results", default="results/dryrun")
    p2.add_argument("--cell", default="")
    p2.add_argument("--limit", type=int, default=5)
    p3 = sub.add_parser("distributed")
    p3.add_argument("--agents-per-device", type=int, default=2,
                    help="agent rows vmapped inside each shard (total agents "
                         "= devices x this; the engine pads internally, so "
                         "uneven packings also work via the API)")
    p3.add_argument("--migrate", action="store_true",
                    help="demo cross-shard event migration: swap the first "
                         "and last agents' LP placements through the "
                         "all_to_all freight path before running, and report "
                         "MIGRATE_OUT/MIGRATE_IN")
    p3.add_argument("--adaptive-exec", action="store_true",
                    help="lockstep monitoring-driven per-shard exec width "
                         "(Engine.run_distributed_adaptive) instead of a "
                         "static exec_cap")
    p3.add_argument("--exec-ladder", type=int, nargs="+", default=None,
                    help="explicit width ladder for --adaptive-exec "
                         "(default: policy.default_ladder(pool_cap))")
    p3.add_argument("--exec-cap", type=int, default=None,
                    help="per-window compacted execution cap "
                         "(default min(pool_cap, 256))")
    p3.add_argument("--batched-dispatch", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="grouped vectorized handler dispatch (engine step 4); "
                         "--no-batched-dispatch restores the sequential fold")
    p3.add_argument("--merge-mode", choices=("delta", "dense"),
                    default="delta",
                    help="batched-merge strategy: per-row delta scatters "
                         "(default) or the PR 2 whole-table reference merge")
    p3.add_argument("--insert-mode", choices=("ring", "ref"), default="ring",
                    help="event-pool lifecycle: free-list ring (default) or "
                         "the retained O(pool_cap) insert_ref scan")
    p3.add_argument("--fused-select", action="store_true",
                    help="run the window selection front-end as one fused "
                         "Pallas superstep megakernel instead of the "
                         "XLA-stitched stages (compiled on TPU, interpreted "
                         "elsewhere)")
    p3.add_argument("--flows", type=int, default=24,
                    help="generator flow count (drives total event volume — "
                         "raise it to push runs past any in-device trace cap)")
    _stream_args(p3)
    p3.add_argument("--stream-check", action="store_true",
                    help="end-to-end streaming gate (CI): after the streamed "
                         "run, assert C_TRACE_DROP == 0, that the trace "
                         "actually exceeded the ring cap, and that the "
                         "streamed trace is byte-identical to an un-streamed "
                         "big-buffer reference run; exit nonzero on any "
                         "mismatch")
    _checkpoint_args(p3)
    p4 = sub.add_parser("ensemble")
    p4.add_argument("--replicas", type=int, default=128,
                    help="Monte Carlo replicas per launch (one fused "
                         "vmap-over-seeds program; default 128)")
    p4.add_argument("--farms", type=int, default=4,
                    help="failure-scenario farm count (scenario size knob)")
    p4.add_argument("--pool-cap", type=int, default=256)
    p4.add_argument("--seed0", type=int, default=0,
                    help="first replica seed (replica r runs seed0 + r)")
    p5 = sub.add_parser("run")
    p5.add_argument("name", nargs="?", default=None,
                    help="catalog scenario name (see --list)")
    p5.add_argument("--list", action="store_true",
                    help="print the scenario catalog (names, drivers, "
                         "declared parameters) and exit")
    p5.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="override a declared scenario parameter (repeat "
                         "for several; values are coerced to the default's "
                         "type — undeclared keys are a loud error)")
    p5.add_argument("--devices", type=int, default=None, metavar="N",
                    help="start the fleet on the first N jax devices "
                         "(default: all; >1 needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    p5.add_argument("--driver",
                    choices=("auto", "local", "adaptive", "distributed",
                             "distributed_adaptive"), default="auto",
                    help="engine driver (auto picks distributed/adaptive "
                         "from the device count and the spec's exec policy; "
                         "ensemble catalog entries force their own driver)")
    p5.add_argument("--max-windows", type=int, default=10_000, metavar="W",
                    help="per-attempt window budget (default 10000)")
    p5.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="durable checkpoint directory (enables the elastic "
                         "resume path; existing committed checkpoints are "
                         "auto-resumed — the restart-after-SIGKILL contract)")
    p5.add_argument("--checkpoint-every", type=int, default=8, metavar="W",
                    help="save every W windows (default 8; 0 disables)")
    p5.add_argument("--checkpoint-keep", type=int, default=3, metavar="N",
                    help="retain the newest N checkpoints (default 3)")
    p5.add_argument("--kill-after-window", type=int, default=None,
                    metavar="W",
                    help="SIGKILL the process right after the first "
                         "committed checkpoint at window >= W (the crash "
                         "lane; rerun the same command to auto-resume)")
    p5.add_argument("--max-retries", type=int, default=3, metavar="N",
                    help="preemption retry cap before FleetError (default 3)")
    p5.add_argument("--min-devices", type=int, default=1, metavar="N",
                    help="degraded-mode device floor: fewer survivors "
                         "hard-fail instead of resuming (default 1)")
    p5.add_argument("--backoff", type=float, default=0.0, metavar="S",
                    help="base retry backoff seconds (exponential, capped; "
                         "default 0 = immediate)")
    p5.add_argument("--preempt-at-window", type=int, default=None,
                    metavar="W",
                    help="inject one shard-loss preemption once the first "
                         "attempt reaches window W (the in-process elastic "
                         "smoke; needs --preempt-survivors and "
                         "--checkpoint-dir)")
    p5.add_argument("--preempt-survivors", type=int, default=None,
                    metavar="K",
                    help="surviving device count after the injected "
                         "preemption (the fleet shrinks to the first K)")
    _stream_args(p5)
    p5.add_argument("--stream-check", action="store_true",
                    help="elastic streaming gate (CI): after the run, "
                         "assert C_TRACE_DROP == 0, that the trace exceeded "
                         "the ring cap, and that the streamed trace is "
                         "byte-identical to an uninterrupted big-buffer "
                         "reference run; exit nonzero on any mismatch")
    args = ap.parse_args()
    dict(t0t1=run_t0t1, workload=run_workload, distributed=run_distributed,
         ensemble=run_ensemble, run=run_catalog)[args.mode](args)


if __name__ == "__main__":
    # the CLI entry keeps its compiles across runs; in-process callers of
    # main() (the tests) leave JAX's cache settings alone
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
