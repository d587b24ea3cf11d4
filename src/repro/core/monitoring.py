"""In-graph monitoring — the LISA adaptation (paper §4.1).

The paper couples the simulation with the LISA monitoring system so the scheduler can
read "the load of the physical workstation ... the load of the network ... and also
the load of the agents (number of logical processes already executing, what components
are already duplicated locally)". Here the same signals are JAX arrays carried through
the superstep: a per-agent counter vector plus derived *performance values*.

Counters are per-agent and local (never auto-synced); ``gather_counters`` exposes the
fleet view to the scheduler and to ``ft.straggler``.

The host-streaming observability layer also lives here (paper §4.1's LISA
coupling, MONARC's dedicated monitoring layer): :class:`TraceStream` is the
host sink of the engine's device-side trace-ring drain
(``jax.experimental.io_callback`` at window boundaries — see
docs/architecture.md, "Streaming trace"), and :class:`MetricsStream` turns the
per-window counter vectors into periodic JSON-lines snapshots named by the
registry's declared counter table.

Host spans and counters (docs/architecture.md, "Observability") say where a
run's host time goes: :func:`span` opens a ``jax.profiler.TraceAnnotation``
named ``repro.<name>`` at each layer boundary (so the span sits in any
profiler trace beside the device planes), and :func:`count` books host
counts such as how many programs the engine traced. Both are recorded in
memory only while a :class:`SpanLog` is attached; otherwise they book
nothing. They are host-only: no span is opened inside traced code, and
nothing here touches the in-graph counter vector.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Counter indices.
C_EVENTS = 0          # events processed
C_MSGS_REMOTE = 1     # events routed to another agent
C_STALE = 2           # stale (interrupted) flow-completion events — paper's Fig-2 driver
C_INTERRUPTS = 3      # bandwidth-share recomputations
C_JOBS_SUBMITTED = 4
C_JOBS_DONE = 5
C_FLOWS_STARTED = 6
C_FLOWS_DONE = 7
C_MB_TRANSFERRED = 8  # rounded to int MB
C_DROP_POOL = 9       # event-pool overflow
C_DROP_ROUTE = 10     # routing-buffer overflow
C_DROP_FLOW = 11      # flow-table overflow
C_DROP_QUEUE = 12     # job-queue overflow
C_WINDOWS = 13        # conservative windows executed (sync rounds)
C_MIGRATIONS = 14     # disk -> tape migrations
C_WRITES = 15         # storage writes
C_MB_WRITTEN = 16
C_LP_LOCAL = 17       # events destined to locally-owned LPs (scheduler locality signal)
C_EXEC_SPILL = 18     # safe events deferred past exec_cap to the next window
C_BATCH_EXEC = 19     # events executed through the grouped vectorized dispatch
C_BATCH_FALLBACK = 20  # conflicted events executed via the sequential fallback
C_BATCH_ROWS = 21     # component-table rows scattered by the batched merge
C_TRACE_DROP = 22     # trace records lost to the fixed-cap trace buffer; any
                      # nonzero value makes trace-based oracle comparisons
                      # invalid, so oracle.merged_engine_trace refuses to
                      # return a truncated trace (fails loudly instead)
C_RING_WRAP = 23      # free-ring cursor wraps (head on insert, tail on release)
C_POOL_OCC = 24       # GAUGE: live pool slots at window end (occupancy)
C_POOL_FREE = 25      # GAUGE: free pool slots at window end (insert headroom)
C_MIGRATE_OUT = 26    # pending events shipped to another agent by placement
                      # migration (post route-cap; route overflow is
                      # C_DROP_ROUTE as everywhere)
C_MIGRATE_IN = 27     # migrated events received from another agent (counted
                      # pre-insert, so sum(out) == sum(in) globally; receiving
                      # pool overflow lands in C_DROP_POOL, never silent)
C_PREEMPT = 28        # FLEET: shard-loss preemptions observed by the
                      # orchestrator (host-side, never bumped in-graph)
C_RESUME = 29         # FLEET: automatic checkpoint resumes completed
C_RESHARD = 30        # FLEET: resumes that repacked onto a different
                      # device count (the unpadded-checkpoint reshard path)
N_COUNTERS = 31

DROP_COUNTERS = (C_DROP_POOL, C_DROP_ROUTE, C_DROP_FLOW, C_DROP_QUEUE)

# Fleet-orchestration counters: booked host-side by repro.fleet.Orchestrator
# (MetricsStream.book) and surfaced in its emitted records — NEVER bumped
# in-graph, so they are zero in any single engine run's counter state. That
# is deliberate: a preempted-and-resumed run's EngineState stays byte-
# identical to the uninterrupted run's, preemption bookkeeping included.
FLEET_COUNTERS = (C_PREEMPT, C_RESUME, C_RESHARD)

# Gauges: overwritten (not accumulated) every window — the pool-lifecycle
# occupancy signals the adaptive exec policy (core/policy.py) reads alongside
# the C_EXEC_SPILL / C_BATCH_ROWS rates.
GAUGE_COUNTERS = (C_POOL_OCC, C_POOL_FREE)

# Pool-lifecycle diagnostics: the only counters allowed to differ between the
# ring insert path and the retained insert_ref scan path of one scenario
# (the ref path never touches the ring cursors, so it never wraps them).
POOL_DIAG_COUNTERS = (C_RING_WRAP,)

# The engine-infrastructure counters every Registry starts with, in index
# order (Registry.__init__ seeds its counter table from this tuple, so the
# C_* constants above are the indices the registry assigns). Extensions
# declare additional counters with ``Registry.counter(name)`` — see
# docs/scenario_api.md — and size the engine's counter vector through
# ``Registry.n_counters``.
BUILTIN_COUNTERS = (
    ("EVENTS", "events processed (all execution paths)"),
    ("MSGS_REMOTE", "emits routed to another agent"),
    ("STALE", "stale (interrupted) flow-completion events — the paper's "
              "Fig-2 cost driver"),
    ("INTERRUPTS", "bandwidth-share recomputations (max-min refair)"),
    ("JOBS_SUBMITTED", "jobs accepted by a compute farm"),
    ("JOBS_DONE", "jobs completed"),
    ("FLOWS_STARTED", "WAN transfers started"),
    ("FLOWS_DONE", "WAN transfers completed"),
    ("MB_TRANSFERRED", "completed-flow megabytes (rounded to int)"),
    ("DROP_POOL", "event-pool overflow (including oversubscribed init "
                  "seeds)"),
    ("DROP_ROUTE", "routing-buffer overflow"),
    ("DROP_FLOW", "flow-table overflow (flow start refused)"),
    ("DROP_QUEUE", "job-queue overflow (job refused)"),
    ("WINDOWS", "conservative windows executed (collective sync rounds)"),
    ("MIGRATIONS", "disk -> tape migrations"),
    ("WRITES", "storage writes"),
    ("MB_WRITTEN", "written megabytes (rounded to int)"),
    ("LP_LOCAL", "emits destined to locally-owned LPs (scheduler locality "
                 "signal)"),
    ("EXEC_SPILL", "safe events deferred past exec_cap to the next window"),
    ("BATCH_EXEC", "events executed through the grouped vectorized dispatch"),
    ("BATCH_FALLBACK", "conflicted events executed via the sequential "
                       "fallback"),
    ("BATCH_ROWS", "component-table rows scattered by the batched merge — "
                   "the per-window scatter-volume signal for the adaptive "
                   "exec width"),
    ("TRACE_DROP", "trace records lost to the fixed-cap trace buffer, or "
                   "overwritten un-drained ring rows under streaming; "
                   "oracle.merged_engine_trace and TraceStream refuse a "
                   "truncated trace, so oracle-equivalence checks fail "
                   "loudly instead of passing on a prefix"),
    ("RING_WRAP", "free-ring cursor wraps (head on insert, tail on release) "
                  "— pool-recycling pressure"),
    ("POOL_OCC", "live pool slots at window end — the saturation signal the "
                 "adaptive exec policy grows on"),
    ("POOL_FREE", "free pool slots at window end (insert headroom)"),
    ("MIGRATE_OUT", "events shipped to another agent by a placement change "
                    "(donor side, post route-cap)"),
    ("MIGRATE_IN", "migrated events received (counted pre-insert, so "
                   "sum(OUT) == sum(IN) globally even when the receiving "
                   "pool overflows — the excess then lands in DROP_POOL on "
                   "the receiver)"),
    ("PREEMPT", "shard-loss preemptions the fleet orchestrator detected "
                "(injected probe or a process death discovered at restart)"),
    ("RESUME", "automatic checkpoint resumes the orchestrator completed "
               "after a preemption"),
    ("RESHARD", "resumes that repacked the unpadded checkpoint onto a "
                "different device count than it was saved from"),
)
assert len(BUILTIN_COUNTERS) == N_COUNTERS

# Dispatch-path diagnostics: the only counters allowed to differ between the
# batched and the sequential execution of the same scenario (everything else
# is byte-identical by the batched-dispatch equivalence contract).
# C_BATCH_ROWS measures the per-window scatter volume of the delta merge —
# the load signal the adaptive-exec_cap ROADMAP item keys on (a window that
# scatters few rows relative to exec_cap has headroom to grow the window).
BATCH_DIAG_COUNTERS = (C_BATCH_EXEC, C_BATCH_FALLBACK, C_BATCH_ROWS)


def zero_counters(n: int | None = None) -> jax.Array:
    """A zero counter vector. ``n`` sizes it for extended registries
    (``Registry.n_counters``); the default is the builtin width."""
    return jnp.zeros((N_COUNTERS if n is None else n,), jnp.int32)


def bump(counters: jax.Array, idx: int, amount=1) -> jax.Array:
    return counters.at[idx].add(jnp.asarray(amount, jnp.int32))


def gauge(counters: jax.Array, idx: int, value) -> jax.Array:
    """Overwrite a gauge counter (per-window level, not an accumulation)."""
    return counters.at[idx].set(jnp.asarray(value, jnp.int32))


def gather_counters(counters: jax.Array,
                    axis: str | tuple[str, ...] | None) -> jax.Array:
    """(A, N_COUNTERS) fleet view (identity reshape when single-agent).

    ``axis`` may be a (shard, lane) tuple for the shard_map x vmap driver
    (engine.ShardAxes agent packing): ``all_gather`` rejects mixed-axis
    tuples, so the gather is staged innermost-first — lanes, then shards —
    which flattens to the shard-major global agent order (== the global
    agent id ``lax.axis_index((shard, lane))`` yields)."""
    if axis is None:
        return counters[None]
    if isinstance(axis, (tuple, list)):
        out = counters
        for name in reversed(axis):
            out = jax.lax.all_gather(out, name)
        return out.reshape((-1,) + counters.shape)
    return jax.lax.all_gather(counters, axis)


def performance_value(counters: jax.Array, n_owned_lps: jax.Array,
                      pool_occupancy: jax.Array) -> jax.Array:
    """Scalar performance value an agent publishes (paper §4.1). Higher == worse.

    Folds the paper's three signal groups: workstation load (events processed per
    window ~ CPU load; pool occupancy ~ memory), network load (remote message ratio),
    and agent load (#LPs hosted).
    """
    c = counters.astype(jnp.float32)
    windows = jnp.maximum(c[C_WINDOWS], 1.0)
    events_per_window = c[C_EVENTS] / windows
    remote_ratio = c[C_MSGS_REMOTE] / jnp.maximum(c[C_EVENTS], 1.0)
    return (events_per_window
            + 4.0 * remote_ratio
            + 0.5 * n_owned_lps.astype(jnp.float32)
            + 2.0 * pool_occupancy.astype(jnp.float32))


# ------------------------------------------------------- host-streaming layer
def snapshot(counters, registry=None) -> dict:
    """Named view of a counter vector: ``{counter name: int total}``.

    ``counters`` is an (n,) vector or an (A, n) stacked fleet (summed over
    agents — gauges included, so a gauge reads as the fleet-total level).
    ``registry`` supplies the name table for extended models; the default is
    the builtin table.
    """
    names = (registry.counters if registry is not None
             else {name: i for i, (name, _doc) in enumerate(BUILTIN_COUNTERS)})
    c = np.asarray(counters)
    if c.ndim == 2:
        c = c.sum(axis=0)
    return {name: int(c[i]) for name, i in names.items()}


class TraceStream:
    """Host sink for the engine's device-side trace-ring drain.

    The engine appends processed-event rows ``(time, seq, kind, dst)`` to a
    per-agent ring of ``trace_cap`` rows and, at window boundaries, ships the
    un-drained span ``[tail, trace_n)`` through an unordered
    ``jax.experimental.io_callback`` tagged with the global agent id and the
    span start. Tagged spans are order-independent and idempotent, so callback
    arrival order (and duplicate delivery) cannot corrupt the stream: segments
    key on ``(agent, start)`` and reassembly verifies contiguous coverage of
    ``[0, trace_n)`` per agent. ``merged()`` reproduces
    ``oracle.merged_engine_trace`` — global (time, seq) order over all agents,
    shard-major under the distributed driver (the global agent id *is* the
    shard-major state row) — byte-identical to the sequential heapq oracle
    whenever ``C_TRACE_DROP == 0``.
    """

    def __init__(self):
        self._segments: dict[int, dict[int, np.ndarray]] = {}
        self._trace_n: np.ndarray | None = None
        self._resume: dict[int, dict[int, np.ndarray]] | None = None

    def begin(self, n_agents: int) -> None:
        """Reset for a run of ``n_agents`` (the engine calls this).

        If :meth:`load_state` staged checkpointed spans, they seed the
        segment map instead of an empty one — a resumed run's ring only
        re-drains ``[trace_tail, ...)``, so the pre-checkpoint prefix must
        come from the checkpoint for coverage of ``[0, trace_n)`` to close."""
        self.n_agents = n_agents
        self._segments = self._resume if self._resume is not None else {}
        self._resume = None
        self._trace_n = None

    # --------------------------------------------------- checkpoint support
    def state_dict(self) -> dict[str, np.ndarray]:
        """Drained spans as flat serializable arrays (``"<agent>/<start>"``
        keys) — what :class:`repro.checkpoint.SimCheckpointer` persists
        alongside the EngineState (call after ``jax.effects_barrier()``)."""
        return {f"{a}/{start}": seg
                for a, spans in self._segments.items()
                for start, seg in spans.items()}

    def load_state(self, segments: dict[str, np.ndarray]) -> None:
        """Stage checkpointed spans for the next ``begin()`` (restore path)."""
        staged: dict[int, dict[int, np.ndarray]] = {}
        for key, seg in segments.items():
            a, start = key.split("/")
            staged.setdefault(int(a), {})[int(start)] = np.asarray(seg)
        self._resume = staged

    def on_drain(self, agent, start, count, ring) -> None:
        """The io_callback target: one drained span of one agent's ring.

        ``ring`` is the raw (cap, 4) ring; rows are unrolled from positions
        ``(start + i) % cap``. A ``count`` of 0 (nothing pending, or a pad
        agent under the distributed driver) is a no-op.
        """
        agent = np.asarray(agent)
        if agent.ndim:  # batched delivery: unroll per lane
            for i in range(agent.shape[0]):
                self.on_drain(agent[i], np.asarray(start)[i],
                              np.asarray(count)[i], np.asarray(ring)[i])
            return
        n = int(count)
        if n <= 0:
            return
        ring = np.asarray(ring)
        idx = (int(start) + np.arange(n)) % ring.shape[0]
        self._segments.setdefault(int(agent), {})[int(start)] = ring[idx].copy()

    def finalize(self, trace, trace_n, trace_tail) -> None:
        """Flush the never-drained tail spans out of a finished EngineState
        and record the per-agent row counts (the engine calls this after
        ``jax.effects_barrier()``)."""
        trace = np.asarray(trace)
        self._trace_n = np.asarray(trace_n).copy()
        tail = np.asarray(trace_tail)
        for a in range(trace.shape[0]):
            n = int(self._trace_n[a]) - int(tail[a])
            if n > 0:
                idx = (int(tail[a]) + np.arange(n)) % trace.shape[1]
                self._segments.setdefault(a, {})[int(tail[a])] = (
                    trace[a, idx].copy())

    @property
    def n_streamed(self) -> int:
        """Total rows streamed (requires ``finalize``)."""
        if self._trace_n is None:
            raise RuntimeError("TraceStream not finalized — run the engine "
                               "with the stream attached first")
        return int(self._trace_n.sum())

    def agent_rows(self, agent: int) -> np.ndarray:
        """Agent's full (trace_n, 4) trace, reassembled from drained spans.

        Raises if the spans do not contiguously cover ``[0, trace_n)`` — a
        lost callback or an overwritten (dropped) span; ``C_TRACE_DROP``
        counts the latter.
        """
        if self._trace_n is None:
            raise RuntimeError("TraceStream not finalized — run the engine "
                               "with the stream attached first")
        n = int(self._trace_n[agent])
        segs = self._segments.get(agent, {})
        out, pos = [], 0
        for start in sorted(segs):
            seg = segs[start]
            if start != pos:
                raise RuntimeError(
                    f"trace stream gap for agent {agent}: have rows "
                    f"[0, {pos}), next span starts at {start}")
            out.append(seg)
            pos += seg.shape[0]
        if pos != n:
            raise RuntimeError(
                f"trace stream incomplete for agent {agent}: streamed {pos} "
                f"of {n} rows")
        if not out:
            return np.zeros((0, 4), np.int32)
        return np.concatenate(out, axis=0)

    def merged(self) -> list:
        """Global (time, seq)-ordered trace — ``merged_engine_trace``'s exact
        shape: a list of ``(time, seq, kind, dst)`` int tuples."""
        rows = []
        assert self._trace_n is not None
        for a in range(self._trace_n.shape[0]):
            rows.extend(tuple(int(x) for x in r) for r in self.agent_rows(a))
        rows.sort(key=lambda r: (r[0], r[1]))
        return rows


class MetricsStream:
    """Periodic fleet metrics snapshots fed by the registry counter table.

    The engine ships every agent's ``(window, gvt, counters)`` through the
    same window-boundary io_callback path as the trace drain; once all agents
    of a window whose index is a multiple of ``interval`` have reported, one
    JSON line lands on ``out`` (and in ``self.lines``):

        {"window": W, "gvt": T, "agents": A, "counters": {name: total}}

    Counter names and order come from the registry declaration (extension
    counters included); ``Registry.counter_docs`` gives the docstring of
    each name for richer consumers. A final snapshot
    (``"final": true``) is emitted when the run finishes, whatever the
    cadence.
    """

    def __init__(self, interval: int = 32, out=None):
        if interval <= 0:
            raise ValueError(f"interval must be positive, got {interval}")
        self.interval = int(interval)
        self.out = out
        self.lines: list[dict] = []
        self.latest: dict | None = None
        self._booked: dict[str, int] = {}
        self._resume: list[dict] | None = None

    def begin(self, n_agents: int, registry=None) -> None:
        """Reset for a run (the engine calls this with its registry).

        If :meth:`load_state` staged checkpointed records, they seed
        ``self.lines`` instead of an empty list (without re-writing them to
        ``out``) — a resumed run only emits records for post-checkpoint
        windows, so the pre-checkpoint prefix must come from the checkpoint
        for the record sequence to concatenate exactly onto an uninterrupted
        run's. ``_booked`` fleet counters deliberately survive the reset:
        they are host-side orchestration bookkeeping that spans engine runs.
        """
        self.n_agents = n_agents
        self._names = (registry.counters if registry is not None else {
            name: i for i, (name, _doc) in enumerate(BUILTIN_COUNTERS)})
        self._pending: dict[int, dict[int, tuple]] = {}
        self.lines = list(self._resume) if self._resume is not None else []
        self._resume = None
        self.latest = self.lines[-1] if self.lines else None

    # --------------------------------------------------- checkpoint support
    def state_dict(self) -> dict[str, np.ndarray]:
        """Emitted interval records as one serializable array (what
        :class:`repro.checkpoint.SimCheckpointer` persists alongside the
        EngineState; call after ``jax.effects_barrier()``). Mid-run there is
        no final record yet, so the checkpoint holds exactly the interval
        prefix a resumed run must not re-emit."""
        payload = json.dumps(self.lines).encode("utf-8")
        return {"lines": np.frombuffer(payload, dtype=np.uint8).copy()}

    def load_state(self, arrays: dict[str, np.ndarray]) -> None:
        """Stage checkpointed records for the next ``begin()`` (restore)."""
        payload = bytes(np.asarray(arrays["lines"]).tobytes())
        self._resume = json.loads(payload.decode("utf-8"))

    # ------------------------------------------------ fleet-counter overlay
    def book(self, name: str, amount: int = 1) -> None:
        """Accumulate a host-side counter into every later emitted record.

        The fleet orchestrator's preemption bookkeeping (``C_PREEMPT`` /
        ``C_RESUME`` / ``C_RESHARD``) cannot live in the in-graph counter
        vector — a resumed EngineState must stay byte-identical to the
        uninterrupted run's — so it lands here and is added to the named
        column of each record at emit time."""
        self._booked[name] = self._booked.get(name, 0) + int(amount)

    def on_window(self, agent, window, gvt, counters) -> None:
        """The io_callback target: one agent's end-of-window counter vector."""
        agent = np.asarray(agent)
        if agent.ndim:
            for i in range(agent.shape[0]):
                self.on_window(agent[i], np.asarray(window)[i],
                               np.asarray(gvt)[i], np.asarray(counters)[i])
            return
        a, w = int(agent), int(window)
        if a >= self.n_agents or w % self.interval:
            return
        got = self._pending.setdefault(w, {})
        got[a] = (int(gvt), np.asarray(counters).copy())
        if len(got) == self.n_agents:
            self._emit(w, self._pending.pop(w))

    def _emit(self, window: int, got: dict, final: bool = False) -> None:
        total = np.sum([c for _gvt, c in got.values()], axis=0)
        rec = {
            "window": window,
            "gvt": max(g for g, _c in got.values()),
            "agents": self.n_agents,
            "counters": {name: int(total[i])
                         for name, i in self._names.items()},
        }
        for name, v in self._booked.items():
            if name in rec["counters"]:
                rec["counters"][name] += v
        if final:
            rec["final"] = True
        self.latest = rec
        self.lines.append(rec)
        if self.out is not None:
            self.out.write(json.dumps(rec) + "\n")
            self.out.flush()

    def finalize(self, counters, windows, t_now) -> None:
        """Emit the end-of-run snapshot from the finished EngineState."""
        counters = np.asarray(counters)
        windows = np.asarray(windows)
        t_now = np.asarray(t_now)
        got = {a: (int(t_now[a]), counters[a])
               for a in range(min(self.n_agents, counters.shape[0]))}
        self._emit(int(windows[0]), got, final=True)

    # ------------------------------------------------------ ensemble support
    def ensemble(self, seeds, counters, windows, t_now) -> dict:
        """Reduce an ``Engine.run_ensemble`` result into the stream.

        ``counters`` is the (R, A, N) stacked counter table of R replicas;
        each replica's per-agent vectors sum to its fleet totals, stored as
        ``self.replica_counters`` (R, N) with ``self.replica_seeds`` — the
        per-replica books stay individually recoverable via
        :meth:`replica`. One summary JSON line (min/mean/max over replicas
        per counter, plus the ensemble-wide totals) lands on ``out`` /
        ``self.lines`` in the usual snapshot shape."""
        seeds = np.asarray(seeds)
        counters = np.asarray(counters)
        windows = np.asarray(windows)
        t_now = np.asarray(t_now)
        self.replica_seeds = seeds.copy()
        self.replica_counters = counters.sum(axis=1)  # (R, N): sum over agents
        total = self.replica_counters.sum(axis=0)
        rec = {
            "ensemble": int(seeds.shape[0]),
            "agents": self.n_agents,
            "windows": [int(windows.min()), int(windows.max())],
            "gvt": [int(t_now.min()), int(t_now.max())],
            "counters": {name: int(total[i])
                         for name, i in self._names.items()},
            "per_replica": {
                name: {"min": int(self.replica_counters[:, i].min()),
                       "mean": float(self.replica_counters[:, i].mean()),
                       "max": int(self.replica_counters[:, i].max())}
                for name, i in self._names.items()},
        }
        self.latest = rec
        self.lines.append(rec)
        if self.out is not None:
            self.out.write(json.dumps(rec) + "\n")
            self.out.flush()
        return rec

    def replica(self, r: int) -> dict:
        """One replica's fleet-total counters by name (post-``ensemble``)."""
        return {name: int(self.replica_counters[r, i])
                for name, i in self._names.items()}


# ---------------------------------------------------- device stage scopes
# The superstep's stages, each a ``jax.named_scope("superstep/<stage>")``
# (engine._superstep and the calls it makes): the names land in every HLO
# op's ``op_name`` metadata and so in the profiler's device op events. They
# add metadata only; no op changes.
STAGES = ("drain", "gvt", "select", "dispatch", "merge", "fallback", "trace",
          "release", "route", "insert", "sync", "gauges")


def stage(name: str):
    """The named scope of superstep stage ``name`` (one of ``STAGES``)."""
    return jax.named_scope("superstep/" + name)


# ---------------------------------------------------- host spans and counters
SPAN_PREFIX = "repro."

# JAX's compile-time events (time.time() spans, the clock of SpanLog) and the
# child span each becomes under the innermost open program span
JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace_lower",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.trace_lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile_load",
}


class Span(NamedTuple):
    """One host span on the ``time.time_ns()`` clock. ``parent`` is the
    index in ``SpanLog.spans`` of the span that encloses it; ``run_id`` the
    id of the ``Orchestrator.run`` call it belongs to (None outside one)."""
    name: str
    start_ns: int
    end_ns: int | None      # None while the span is open
    parent: int | None
    run_id: int | None
    attrs: dict


class Count(NamedTuple):
    """One host count of ``name`` under ``key``."""
    name: str
    key: str


_log: "SpanLog | None" = None   # the attached sink, if any
_run_ids = itertools.count(1)


def next_run_id() -> int:
    """A fresh run id (process-wide, ascending from 1)."""
    return next(_run_ids)


@contextlib.contextmanager
def span(name: str, run_id: int | None = None, **attrs):
    """Host span ``repro.<name>`` around a block (or, as a decorator, a
    call). Always a profiler ``TraceAnnotation`` (a ``StepTraceAnnotation``
    when ``step_num`` is given), which costs next to nothing while no
    profiler runs; recorded in memory only while a :class:`SpanLog` is
    attached. ``run_id`` marks this span and every span under it as one
    run's."""
    tags = attrs if run_id is None else dict(attrs, run_id=run_id)
    annotate = (jax.profiler.StepTraceAnnotation if "step_num" in attrs
                else jax.profiler.TraceAnnotation)
    with annotate(SPAN_PREFIX + name, **tags):
        log = _log
        if log is None:
            yield
            return
        i = log._open(name, run_id, attrs)
        try:
            yield
        finally:
            log._close(i)


def count(name: str, *, key: str = "") -> None:
    """Book one of host counter ``name`` under ``key`` into the attached
    :class:`SpanLog`; nothing without one."""
    log = _log
    if log is not None:
        log.counts.append(Count(name, key))


def _merge(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _measure(merged) -> int:
    return sum(b - a for a, b in merged)


def _overlap(xs, ys) -> int:
    """Length of the intersection of two merged interval lists."""
    total = i = j = 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(hi - lo, 0)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


class SpanLog:
    """In-memory sink of the program's host spans and counts.

    Attach it as a context manager; everything is kept until it is read.
    While attached it also listens to JAX's compile-time events: each
    becomes a child span (``jax.trace_lower`` or ``jax.compile_load``) of the
    innermost open program span, so a trace or a cache load is charged to
    the layer that caused it. Only one SpanLog is attached at a time.
    """

    def __init__(self):
        self.spans: list[Span] = []    # in the order they opened
        self.counts: list[Count] = []
        self._stack: list[int] = []    # indices of the open spans

    def __enter__(self) -> "SpanLog":
        global _log
        if _log is not None:
            raise RuntimeError("a SpanLog is already attached")
        jax.monitoring.register_event_time_span_listener(self._on_jax)
        _log = self
        return self

    def __exit__(self, *exc) -> None:
        global _log
        _log = None
        jax.monitoring.unregister_event_time_span_listener(self._on_jax)

    def _open(self, name: str, run_id, attrs) -> int:
        parent = self._stack[-1] if self._stack else None
        if run_id is None and parent is not None:
            run_id = self.spans[parent].run_id
        self.spans.append(Span(name, time.time_ns(), None, parent, run_id,
                               attrs))
        self._stack.append(len(self.spans) - 1)
        return len(self.spans) - 1

    def _close(self, i: int) -> None:
        self._stack.remove(i)
        self.spans[i] = self.spans[i]._replace(end_ns=time.time_ns())

    def _on_jax(self, event, start, end, **kw) -> None:
        name = JAX_SPANS.get(event)
        if name is None:
            return
        parent = self._stack[-1] if self._stack else None
        run_id = self.spans[parent].run_id if parent is not None else None
        self.spans.append(Span(name, int(start * 1e9), int(end * 1e9), parent,
                               run_id, kw))

    # ------------------------------------------------------------- reading
    def mark(self) -> tuple[int, int]:
        """A point to read :meth:`since` and :meth:`union_s` from."""
        return len(self.spans), len(self.counts)

    def _closed(self, mark) -> dict[int, Span]:
        return {i: s for i, s in enumerate(self.spans)
                if i >= mark[0] and s.end_ns is not None}

    def since(self, mark=(0, 0)) -> dict:
        """What was booked since ``mark``: ``self_s``, seconds per span name
        less the union of each span's children, and ``counts``, each
        counter's total per key."""
        spans = self._closed(mark)
        own: dict[str, list] = {}
        kids: dict[str, list] = {}
        for s in spans.values():
            own.setdefault(s.name, []).append((s.start_ns, s.end_ns))
        for s in spans.values():
            if s.parent in spans:
                kids.setdefault(spans[s.parent].name, []).append(
                    (s.start_ns, s.end_ns))
        self_s = {}
        for name, iv in own.items():
            mine = _merge(iv)
            self_s[name] = (_measure(mine)
                            - _overlap(mine, _merge(kids.get(name, ())))) / 1e9
        counts: dict[str, dict[str, int]] = {}
        for c in self.counts[mark[1]:]:
            per = counts.setdefault(c.name, {})
            per[c.key] = per.get(c.key, 0) + 1
        return dict(self_s=self_s, counts=counts)

    def union_s(self, name: str, parent: str, mark=(0, 0)) -> float:
        """Seconds covered by the spans ``name`` whose parent is a span
        ``parent`` (JAX's trace and lowering of a driver program:
        ``union_s("jax.trace_lower", "engine.run")``), since ``mark``."""
        spans = self._closed(mark)
        return _measure(_merge(
            (s.start_ns, s.end_ns) for s in spans.values()
            if s.name == name and s.parent is not None
            and self.spans[s.parent].name == parent)) / 1e9
