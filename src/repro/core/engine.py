"""The simulation engine (paper fig 4): conservative-window superstep + run loop.

Per window (one "simulation step" in the paper's event-scheduler terms):

  1. GVT: per-context local min pending timestamp -> collective min (sync.py, C2).
  2. Safe mask: events strictly below the per-context horizon may execute.
  3. Order + compact: stable (time, seq) sort with unsafe slots keyed T_INF — on
     TPU the ``event_select`` Pallas kernel, on CPU the XLA lexsort reference
     (identical prefixes) — keeping only the first ``spec.exec_cap`` gather
     indices (the earliest safe slots).
  4. Execute (grouped vectorized dispatch, the default): the ``exec_cap``
     gathered slots are partitioned by ``kind`` (``group_by_kind``) and checked
     for write conflicts (``sync.conflict_mask``: two events declaring the same
     component row — the exact ``(KIND_TABLE[kind], lp_res[dst])`` row of the
     handlers' delta contract). Conflict-free slots — by construction touching
     pairwise-disjoint world state — execute in ONE vmapped handler call that
     returns per-row ``WorldDelta``s, merged with one O(lanes x row) segment
     scatter per mutable field (``handlers.apply_handler_batch``;
     ``spec.merge_mode="dense"`` selects the PR 2 whole-table reference merge
     instead, kept for equivalence tests and benchmarks). The few
     conflicted slots fall back to a sequential fold compacted to just those
     slots (a while_loop that runs zero iterations on clean windows). Each
     slot's emits land in a per-slot row of an (exec_cap, MAX_EMIT) matrix, so
     flattening it row-major reproduces the sequential fold's emit-append order
     byte-for-byte (``events.compact_batch``), and the trace is written in
     (time, seq) window order independently of execution order — the batched
     path is byte-identical to the sequential fold (and hence to the oracle) in
     traces, counters, and world state. ``spec.batched_dispatch=False``
     restores the PR 1 sequential lax.scan over all exec_cap slots. Safe
     events beyond ``exec_cap`` *spill* either way: they stay in the pool and
     execute in a later window (counted by C_EXEC_SPILL). Spilling preserves
     exactness — the horizon/GVT math is untouched, spilled events remain
     below the horizon, and emits of later windows carry timestamps >=
     horizon > any spilled timestamp, so the per-agent execution order (and
     hence the oracle-merged trace) is unchanged; only the window count grows.
     Caveat: a compacted window frees at most exec_cap pool slots before
     insert, so a near-saturated pool has less headroom for the window's
     emits than a full-pool scan would leave — as everywhere in this engine,
     any resulting overflow is counted (C_DROP_POOL), never silent, and results
     are exact iff the drop counters stay zero. Size pool_cap with that
     headroom (or raise exec_cap) for emit-heavy dense scenarios.
  5. Route: emits are bucketed by destination agent (``lp_agent``) and exchanged with
     one ``all_to_all`` (the Jini remote-event adaptation); overflow is counted.
  6. Insert: received events enter pool free slots. The pool's free-list ring
     (events.py, PR 5) makes this an O(n_insert) ring pop and the
     post-execution reclaim an O(exec_cap) ``events.release`` scatter —
     ``spec.insert_mode="ref"`` restores the PR 1-4 O(pool_cap) rank-scan
     insert + pool-wide pop mask, byte-identical in everything but slot
     layout and the C_RING_WRAP diagnostic.
  7. Sync world: owner-wins all-reduce of replicated component state (C4),
     then the pool occupancy/headroom gauges (C_POOL_OCC / C_POOL_FREE).

The per-window execution width is ``spec.exec_policy``: a static int (the
historical ``exec_cap``) under ``run_local`` / ``run_distributed``, or a
``policy.ExecPolicy`` ladder driven by the per-window monitoring vector under
``run_adaptive`` — one jitted window program per rung, cached, so adaptation
never recompiles (docs/architecture.md, "Pool lifecycle").

The same per-agent program runs under ``jax.vmap(axis_name='agents')`` (LocalComm:
tests, benchmarks, single host) and under ``shard_map`` over a device mesh
(CollectiveComm: production) — collectives are axis-name-polymorphic, so the two
drivers are semantically identical by construction. The distributed driver
composes both: ``run_distributed`` packs ``K = ceil(n_agents / n_devices)``
agents per device (``shard_map`` over the mesh axis x ``vmap`` over an
in-shard lane axis — a :class:`ShardAxes` pair), so agent count is decoupled
from device count (thousands of LPs on a 4-8 device mesh). Collectives then
reduce over the (shard, lane) *tuple* — one fleet-global GVT/psum — and the
routing all_to_all runs in two stages (shards, then lanes) whose flattened
receive order equals the flat single-axis exchange's, keeping the distributed
results byte-identical to ``run_local`` down to pool slot layouts.
``run_distributed_adaptive`` is the per-shard analog of ``run_adaptive``:
per-shard monitoring -> per-shard rung decision -> max-reduce so every shard
stays in lockstep on one jit-cached window program.
"""
from __future__ import annotations

import collections
import functools
import hashlib
import os
import threading
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import events as ev
from repro.core import monitoring as mon
from repro.core import policy as pol
from repro.core import sync
from repro.core.components import ScenarioSpec, World, WorldOwnership
from repro.core.handlers import (Ev, apply_handler, apply_handler_batch,
                                 apply_handler_batch_dense)
from repro.core.registry import registry_of
# the fused front-end's result container only — kernels.event_select imports
# nothing from repro.core, so this cannot cycle
from repro.kernels.event_select import FusedSelect

AXIS = "agents"
# run_distributed over several TPU chips is refused unless this is set: its
# equality with run_local across chips is not yet shown on the chip
MULTICHIP_TPU_ENV = "REPRO_MULTICHIP_TPU"

_shard_map = functools.partial(jax.shard_map, check_vma=False)

# The process-wide cache of jitted driver programs (docs/architecture.md,
# "Program cache"): engines of one structure share one program, so only the
# first run of a structure traces, lowers and loads it. Bounded: the least
# recently used program goes first. Small, because each engine also keeps
# the programs it used, and XLA:CPU maps each loaded executable of a
# whole-run program in 1,600-4,300 memory regions: a long-lived process (a
# test worker) that also kept 8 or 32 such programs reached Linux's default
# limit of 65,530 maps, and its next compile aborted.
_PROGRAM_CACHE_SIZE = 2
_programs: "collections.OrderedDict[tuple, Callable]" = (
    collections.OrderedDict())
_programs_lock = threading.Lock()


def clear_programs() -> None:
    """Empty the process-wide program cache: the next run of every
    structure traces its driver programs anew."""
    with _programs_lock:
        _programs.clear()


def _hook_key(fn):
    """A hook as the structure key holds it: a ``functools.partial`` by its
    func, args and keywords (the default ``group_fn`` is a new partial on
    every Engine), anything else, and a partial of unhashable arguments, by
    identity."""
    if isinstance(fn, functools.partial):
        key = (_hook_key(fn.func), fn.args, tuple(sorted(fn.keywords.items())))
        try:
            hash(key)
        except TypeError:
            return fn
        return key
    return fn


class ShardAxes(NamedTuple):
    """The shard_map x vmap agent packing of ``run_distributed``.

    ``shard`` names the 1-D mesh axis (``n_shards`` devices); ``lane`` names
    the vmap axis inside each shard (``n_lanes`` agents packed per device).
    The stacked state is laid out shard-major, so the global agent id is
    ``lax.axis_index((shard, lane)) == shard_idx * n_lanes + lane_idx`` —
    exactly the row index of the agent in the (A, ...) state. Collectives
    that accept axis-name tuples (pmin/psum/axis_index) reduce over both
    axes directly; all_gather/all_to_all do not, and are staged per axis
    (monitoring.gather_counters, engine._route_and_insert)."""

    shard: str
    lane: str
    n_shards: int
    n_lanes: int

    @property
    def names(self) -> tuple[str, str]:
        return (self.shard, self.lane)

    @property
    def size(self) -> int:
        return self.n_shards * self.n_lanes


def axis_names(axis: "str | ShardAxes | None"):
    """The collective axis-name argument for an engine axis spec."""
    return axis.names if isinstance(axis, ShardAxes) else axis


def lexsort_time_seq(time_key: jax.Array, seq: jax.Array) -> jax.Array:
    """Stable (time, seq) sort permutation — the XLA reference for event_select."""
    perm = jnp.argsort(seq, stable=True)
    perm2 = jnp.argsort(time_key[perm], stable=True)
    return perm[perm2]


def select_events_xla(time_key: jax.Array, seq: jax.Array,
                      exec_cap: int) -> jax.Array:
    """Compacted gather indices (sort + safe-prefix) — XLA default select_fn."""
    return lexsort_time_seq(time_key, seq)[:exec_cap]


def route_rank_xla(dst_agent: jax.Array) -> jax.Array:
    """Stable within-bucket routing ranks — the XLA default route_fn.

    ``rank[i]`` counts earlier rows with the same destination bucket, so the
    emit-routing pack scatters row i to flat slot ``dst * route_cap + rank``:
    sort by bucket, rank within group, scatter back to input order. The
    Pallas predecessor-count kernel (kernels.ops.route_rank) is the hookable
    alternative; kernels.ref.route_rank_ref mirrors this exactly.
    """
    sperm = jnp.argsort(dst_agent, stable=True)
    skey = dst_agent[sperm]
    group_start = jnp.searchsorted(skey, skey, side="left")
    rank_sorted = jnp.arange(skey.shape[0], dtype=jnp.int32) - group_start
    return jnp.zeros_like(rank_sorted).at[sperm].set(rank_sorted)


def group_by_kind_xla(kind: jax.Array, active: jax.Array,
                      n_kinds: int = ev.N_KINDS):
    """Same-kind grouping — the XLA reference for kernels.ops.group_by_kind.

    Returns ``(order, rank, counts)``: ``order`` is the stable permutation
    putting active rows first, grouped by ascending kind and original position
    within a kind (inactive rows trail in original order); ``rank`` is aligned
    with ``order`` and gives each grouped row's index within its segment;
    ``counts`` is the (n_kinds,) active-row population per kind.
    """
    key = jnp.where(active, jnp.clip(kind, 0, n_kinds - 1), n_kinds)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    ks = key[order]
    start = jnp.searchsorted(ks, ks, side="left").astype(jnp.int32)
    rank = jnp.arange(ks.shape[0], dtype=jnp.int32) - start
    counts = jnp.zeros((n_kinds,), jnp.int32).at[key].add(1, mode="drop")
    return order, rank, counts


def fused_select_xla(time_key, seq, safe, time, kind, src, dst, ctx, payload,
                     valid, table_id, res, free_tail, exec_cap, *,
                     n_kinds: int, n_res: int, n_tables: int) -> FusedSelect:
    """XLA-stitched twin of the fused window front-end.

    The exact composition the non-fused superstep runs — select
    (``select_events_xla``), exec mask (``sync.exec_selection_ring``), field
    gathers, conflict mask (``sync.conflict_mask``), group
    (``group_by_kind_xla``), and the free-ring release ranks of
    ``events.release`` — packaged behind the same signature as the Pallas
    megakernel (``kernels.ops.fused_select``), so the two are drop-in
    interchangeable ``fused_fn`` hooks and every output must match
    byte-for-byte. Retained as the reference path for tests and the
    ``fused_superstep`` benchmark.
    """
    cap = time_key.shape[0]
    m = max(min(exec_cap, cap), 1)
    exec_idx = select_events_xla(time_key, seq, m)
    exec_safe = sync.exec_selection_ring(safe, exec_idx)
    dirty = sync.conflict_mask(exec_safe, table_id[exec_idx], res[exec_idx],
                               n_res=n_res, n_tables=n_tables)
    clean = exec_safe & ~dirty
    kind_w = kind[exec_idx]
    order, _rank, _counts = group_by_kind_xla(kind_w, clean, n_kinds=n_kinds)
    w = exec_safe.astype(jnp.int32)
    rel = (jnp.asarray(free_tail, jnp.int32) + jnp.cumsum(w) - w) % jnp.int32(
        cap)
    return FusedSelect(
        exec_idx=exec_idx, exec_safe=exec_safe, time=time[exec_idx],
        seq=seq[exec_idx], kind=kind_w, src=src[exec_idx],
        dst=dst[exec_idx], ctx=ctx[exec_idx], payload=payload[exec_idx],
        valid=valid[exec_idx], clean=clean, order=order, rel_pos=rel)


class EngineState(NamedTuple):
    world: World
    pool: ev.EventPool
    counters: jax.Array   # i32 (N_COUNTERS,)
    t_now: jax.Array      # i32 scalar — agent LVT (== last horizon)
    done: jax.Array       # bool scalar (globally uniform)
    windows: jax.Array    # i32 scalar
    trace: jax.Array      # i32 (trace_cap, 4): processed (time, seq, kind, dst)
    trace_n: jax.Array    # i32 scalar — total rows ever written
    trace_tail: jax.Array  # i32 scalar — rows already drained to host
    #                       (streaming mode: the buffer is a ring holding
    #                       positions [trace_tail, trace_n) at index % cap;
    #                       bounded mode keeps it 0)


class Engine:
    """Binds a built scenario to the superstep program."""

    @mon.span("engine.build")
    def __init__(self, world: World, own: WorldOwnership,
                 init_events: ev.EventBatch, spec: ScenarioSpec,
                 trace_cap: int = 0,
                 select_fn: Callable[[jax.Array, jax.Array, int], jax.Array]
                 | None = None,
                 group_fn: Callable[[jax.Array, jax.Array], tuple]
                 | None = None,
                 route_fn: Callable[[jax.Array], jax.Array] | None = None,
                 trace_fn: Callable[[jax.Array], jax.Array] | None = None,
                 fused_fn: Callable[..., FusedSelect] | None = None,
                 slot_fn: Callable[[jax.Array, jax.Array, jax.Array],
                                   jax.Array] | None = None,
                 trace_stream: "mon.TraceStream | None" = None,
                 metrics_stream: "mon.MetricsStream | None" = None,
                 drain_every: int = 16,
                 checkpointer=None,
                 window_hook: Callable[[int, EngineState], None]
                 | None = None):
        self.world = world
        self.own = own
        self.init_events = init_events
        self.spec = spec
        self.trace_cap = trace_cap
        # host-streaming observability (docs/architecture.md, "Streaming
        # trace"): with a TraceStream attached, trace_cap sizes a device-side
        # *ring* drained to the host through an unordered io_callback at
        # window boundaries (every `drain_every` windows, plus forced drains
        # whenever the next window could overrun the ring), so runs of any
        # length keep C_TRACE_DROP == 0 and the streamed trace byte-identical
        # to the sequential oracle. A MetricsStream ships every window's
        # counter vector the same way (periodic JSON-lines snapshots). Either
        # stream switches run_local/run_distributed to a host-stepped window
        # loop — io_callback is unsupported inside a vmapped while_loop — the
        # same driver shape run_adaptive always uses.
        self.trace_stream = trace_stream
        self.metrics_stream = metrics_stream
        # durable checkpoint/resume (docs/architecture.md, "Checkpoint /
        # resume"): a repro.checkpoint.SimCheckpointer saves the full
        # unpadded EngineState (pool ring + cursors, world tables incl. LCG
        # fields, counters, trace ring + trace_tail) every
        # `checkpointer.every` windows. The window boundary is the GVT sync
        # point, so the snapshot is globally consistent by construction; a
        # restored state re-enters any of the four drivers via their
        # ``state=`` (and ``rung=``) arguments — on a different device
        # count, since the distributed drivers re-pad for whatever mesh
        # they get. Like streaming, an attached checkpointer switches the
        # static drivers to the host-stepped window loop.
        self.checkpointer = checkpointer
        # host observation point for the fleet orchestrator
        # (repro.fleet.Orchestrator): called as ``window_hook(window, state)``
        # after every host-stepped window, *after* any due checkpoint save —
        # so an exception raised here (e.g. an injected shard-loss probe)
        # always leaves the latest due checkpoint committed. Only the
        # host-stepped drivers fire it (run_adaptive and, with a stream or
        # checkpointer attached, run_local/run_distributed); the fused
        # while_loop drivers have no host window boundary to hook.
        self.window_hook = window_hook
        self.drain_every = int(drain_every)
        if self.drain_every < 1:
            raise ValueError(f"drain_every must be >= 1, got {drain_every}")
        if trace_stream is not None and trace_cap <= 0:
            raise ValueError(
                "a TraceStream needs a device-side ring: pass trace_cap > 0")
        # the registry that generated this world's model: the source of the
        # dispatch table, the kind->table map, and the sync/delta schemas —
        # extended models (BUILTIN.extend()) plug in with zero engine edits
        self.registry = registry_of(world)
        # select_fn(time_key, seq, exec_cap) -> (exec_cap,) distinct pool-slot
        # indices: the prefix of the stable (time, seq) sort. Hook point for the
        # Pallas kernel (kernels.ops.select_events); default is the XLA lexsort.
        self.select_fn = select_fn or select_events_xla
        # group_fn(kind, active) -> (order, rank, counts): same-kind grouping
        # for the batched dispatch. Hook point for the Pallas segment-rank
        # kernel (kernels.ops.group_by_kind); default is the XLA argsort.
        self.group_fn = group_fn or functools.partial(
            group_by_kind_xla, n_kinds=self.registry.n_kinds)
        # route_fn(dst_agent) -> stable within-bucket ranks: the emit-routing
        # pack for the all_to_all exchange (and the migration re-home). Hook
        # point for the Pallas predecessor-count kernel
        # (kernels.ops.route_rank); default is the XLA sort-based rank.
        self.route_fn = route_fn or route_rank_xla
        # trace_fn(mask) -> exclusive prefix ranks: the trace-append position
        # math (events.trace_append). Hook point for the Pallas prefix-sum
        # kernel (kernels.ops.trace_rank); default is the XLA cumsum inside
        # trace_append (None passes through).
        self.trace_fn = trace_fn
        if spec.merge_mode not in ("delta", "dense"):
            raise ValueError(
                f"spec.merge_mode must be 'delta' or 'dense', got "
                f"{spec.merge_mode!r}")
        if spec.insert_mode not in ("ring", "ref"):
            raise ValueError(
                f"spec.insert_mode must be 'ring' or 'ref', got "
                f"{spec.insert_mode!r}")
        self.table = self.registry.make_handlers(spec.lookahead,
                                                 spec.work_per_mb)
        # widest resource table: bound for the conflict-detection key space
        self._n_res = self.registry.max_rows(world)
        # fused front-end (spec.fused_select, default off): ONE call replaces
        # the select_fn/gather/conflict_mask/group_fn stitch — and the free
        # ring's insert math rides the same lane (slot_fn -> events.insert).
        # fused_fn(time_key, seq, safe, time, kind, src, dst, ctx, payload,
        # valid, table_id, res, free_tail, exec_cap) -> FusedSelect. Default
        # binding is the Pallas superstep megakernel (kernels.ops.fused_select
        # — compiled on TPU, interpreted elsewhere); fused_select_xla above is
        # the stitched twin, drop-in for tests and benchmarks. Only consulted
        # when the spec flag is on.
        if not isinstance(spec.fused_select, bool):
            raise ValueError(
                f"spec.fused_select must be a bool, got {spec.fused_select!r}")
        self.fused_fn = fused_fn
        self.slot_fn = slot_fn
        if spec.fused_select and self.fused_fn is None:
            from repro.kernels import ops as _ops
            self.fused_fn = functools.partial(
                _ops.fused_select, n_kinds=self.registry.n_kinds,
                n_res=self._n_res, n_tables=self.registry.n_tables)
            if self.slot_fn is None:
                self.slot_fn = _ops.ring_slots
        # the driver programs this engine has used (_program): its own
        # streaming programs, and the shared ones it took from the
        # process-wide cache, so its later runs look up nothing there
        self._jit_cache: dict = {}

    # ------------------------------------------------------- program cache
    _HOOKS = ("select_fn", "group_fn", "route_fn", "trace_fn", "fused_fn",
              "slot_fn")
    # the attributes a traced driver body reads: a structure-only copy of
    # the engine holds these and nothing of a run
    _STRUCTURE = ("spec", "registry", "own", "trace_cap", "table",
                  "_n_res") + _HOOKS
    # the methods a traced driver body calls through ``self``
    _TRACED = ("_run_fn", "_superstep", "_execute_scan", "_execute_batched",
               "_insert", "_route_and_insert", "_apply_placement")

    @functools.cached_property
    def _structure_key(self) -> tuple:
        """What makes two engines' driver programs trace identically: every
        value the traced body reads from ``self``.

        - ``registry``, by identity (handler table, kind table, sync lists);
        - ``spec``, a frozen dataclass (the handler table's lookahead and
          work per MB come from it);
        - the values of ``own``, as a digest of its leaves' bytes: they are
          closed over as constants in ``sync_world``;
        - ``_n_res``, the widest table's row count;
        - ``trace_cap``, compared ``> 0`` in ``_execute_*`` (keyed by value:
          the trace buffer's shape follows it anyway);
        - the hooks ``select_fn``, ``group_fn``, ``route_fn``, ``trace_fn``,
          ``fused_fn`` and ``slot_fn`` (:func:`_hook_key`);
        - the traced methods as this engine's class holds them, so a
          subclass or a patched method traces a program of its own.

        The state's shapes need no entry: ``jax.jit`` keys on avals itself.
        """
        digest = hashlib.sha1()
        for leaf in jax.tree.leaves(self.own):
            a = np.asarray(leaf)
            digest.update(f"{a.dtype}{a.shape}".encode())
            digest.update(a.tobytes())
        cls = type(self)
        return (self.registry, self.spec, digest.hexdigest(), self._n_res,
                self.trace_cap,
                tuple(_hook_key(getattr(self, h)) for h in self._HOOKS),
                tuple(getattr(cls, m) for m in self._TRACED))

    def _program(self, key: tuple, make: Callable[["Engine"], Callable],
                 shared: bool = True) -> Callable:
        """The jitted driver program ``key`` of this engine's structure.

        ``make(core)`` builds the program over ``core``. The engine keeps
        every program it used. The first time it needs a shared one, it
        looks it up in the process-wide cache, keyed by ``key`` and
        :attr:`_structure_key` (booking ``engine.programs`` ``hit`` or
        ``miss``); a miss builds it over a structure-only copy of this
        engine (the ``_STRUCTURE`` attributes alone), so it holds no run's
        world, initial events, streams or checkpointer. A program that
        bakes this engine's stream callbacks in (``shared=False``) is built
        over the engine itself and never shared: shared, it would deliver
        one run's drains to another run's stream."""
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = self._jit_cache[key] = (self._shared_program(key, make)
                                         if shared else make(self))
        return fn

    def _shared_program(self, key: tuple,
                        make: Callable[["Engine"], Callable]) -> Callable:
        full = (key, self._structure_key)
        with _programs_lock:
            fn = _programs.get(full)
            if fn is not None:
                mon.count("engine.programs", key="hit")
                _programs.move_to_end(full)
                return fn
            mon.count("engine.programs", key="miss")
            core = object.__new__(type(self))
            for name in self._STRUCTURE:
                setattr(core, name, getattr(self, name))
            fn = _programs[full] = make(core)
            while len(_programs) > _PROGRAM_CACHE_SIZE:
                _programs.popitem(last=False)
            return fn

    # ------------------------------------------------------------------ init
    @mon.span("engine.init_state")
    def init_state(self) -> EngineState:
        """Stacked (A, ...) initial state; initial events homed to owner agents."""
        A = self.spec.n_agents
        cap = self.spec.pool_cap
        pools = []
        drops = []
        lp_agent = self.world.lp_agent
        # the seed insert also seeds the free ring: an empty pool's ring is
        # the identity permutation, so the ring fast path assigns the same
        # ascending slots as the reference scan here
        ins = ev.insert if self.spec.insert_mode == "ring" else ev.insert_ref
        for a in range(A):
            mine = self.init_events.valid & (lp_agent[self.init_events.dst] == a)
            batch = self.init_events._replace(valid=mine)
            pool, dropped = ins(ev.empty_pool(cap), batch)
            pools.append(pool)
            drops.append(dropped)
        pool = jax.tree.map(lambda *xs: jnp.stack(xs), *pools)
        rep = lambda x: jnp.broadcast_to(x, (A,) + x.shape)
        world = jax.tree.map(rep, self.world)
        tc = max(self.trace_cap, 1)
        # oversubscribed seeds (init events beyond pool_cap) are visible, not
        # silent: the per-agent insert drop count lands in C_DROP_POOL.
        # Counter width comes from the registry: declared extension counters
        # ride in the same per-agent vector as the builtins.
        counters = jnp.zeros((A, self.registry.n_counters), jnp.int32).at[
            :, mon.C_DROP_POOL].set(jnp.stack(drops))
        return EngineState(
            world=world,
            pool=pool,
            counters=counters,
            t_now=jnp.zeros((A,), jnp.int32),
            done=jnp.zeros((A,), bool),
            windows=jnp.zeros((A,), jnp.int32),
            trace=jnp.zeros((A, tc, 4), jnp.int32),
            trace_n=jnp.zeros((A,), jnp.int32),
            trace_tail=jnp.zeros((A,), jnp.int32),
        )

    # ------------------------------------------------------------- superstep
    def _superstep(self, st: EngineState, axis: "str | ShardAxes | None",
                   exec_cap: int | None = None,
                   stream: bool = False) -> EngineState:
        """One conservative window. ``exec_cap`` overrides the spec's static
        width — the adaptive driver (``run_adaptive``) traces one program per
        ladder rung through this hook. ``axis`` is the vmap axis name, a
        :class:`ShardAxes` pair under the shard_map x vmap driver, or None
        for a single agent. ``stream`` (static) bakes the host-streaming
        hooks into the program: the window-boundary trace-ring drain and the
        metrics snapshot io_callbacks — only the host-stepped window drivers
        may set it (io_callback cannot live inside a vmapped while_loop)."""
        spec = self.spec
        world, pool, counters = st.world, st.pool, st.counters
        xcap = max(min(exec_cap if exec_cap is not None else spec.exec_cap,
                       spec.pool_cap), 1)
        stream_trace = stream and self.trace_stream is not None
        stream_metrics = stream and self.metrics_stream is not None
        if stream_trace or stream_metrics:
            # the global agent id tags every callback payload: under vmap it
            # is the lane, under shard_map x vmap the shard-major state row —
            # so host-side reassembly is driver-independent (and pad agents,
            # whose spans are always empty, are simply ignored)
            me = (jax.lax.axis_index(axis_names(axis)) if axis is not None
                  else jnp.int32(0))
        if stream_trace:
            # window-boundary drain (before this window's writes): ship the
            # un-drained span [trace_tail, trace_n) when the cadence hits or
            # when this window's worst case (xcap rows) could overrun the
            # ring. The callback fires every window — a vmapped cond would
            # run both branches anyway — but a masked count of 0 makes the
            # non-drain windows host-side no-ops; the span tag (me, start)
            # keeps delivery order-independent and duplicates idempotent.
            # Post-drain invariant: trace_n - trace_tail + xcap <= trace_cap,
            # so the ring never overwrites an un-drained row (C_TRACE_DROP
            # stays 0) as long as the ring holds one window (checked by the
            # streaming drivers).
            with mon.stage("drain"):
                tcap = st.trace.shape[0]
                pending = st.trace_n - st.trace_tail
                do = ((pending + jnp.int32(xcap) > tcap)
                      | (st.windows % jnp.int32(self.drain_every) == 0))
                io_callback(self._on_trace_drain, None, me, st.trace_tail,
                            jnp.where(do, pending, 0), st.trace,
                            ordered=False)
                st = st._replace(trace_tail=jnp.where(do, st.trace_n,
                                                      st.trace_tail))

        # 1-2. GVT + safe mask (C2)
        with mon.stage("gvt"):
            lmin = sync.local_min_per_ctx(pool, spec.n_ctx)
            gvt = sync.global_min(lmin, axis_names(axis))
            horizon = sync.horizons(gvt, spec.lookahead, spec.t_end)
            done = sync.all_done(gvt, spec.t_end)
            safe = sync.safe_mask(pool, horizon)

        # 3. order (time, seq) + compact: unsafe slots sort to the back, and only
        # the first exec_cap gather indices (the earliest safe slots) are kept
        with mon.stage("select"):
            time_key = jnp.where(safe, pool.time, ev.T_INF)
            if spec.fused_select:
                # fused front-end: select + gather + conflict + group +
                # release ranks in ONE fused_fn call (the Pallas megakernel by
                # default). The conflict key columns are precomputed
                # pool-wide — two cheap registry gathers; clip-then-gather
                # commutes with the gather the stitched path does per window,
                # so the bytes match exactly.
                tbl_pool = jnp.asarray(self.registry.kind_table, jnp.int32)[
                    jnp.clip(pool.kind, 0, self.registry.n_kinds - 1)]
                res_pool = world.lp_res[jnp.clip(pool.dst, 0,
                                                 spec.n_lp - 1)]
                fs = self.fused_fn(time_key, pool.seq, safe, pool.time,
                                   pool.kind, pool.src, pool.dst, pool.ctx,
                                   pool.payload, pool.valid, tbl_pool,
                                   res_pool, pool.free_tail, xcap)
                exec_idx, exec_safe = fs.exec_idx, fs.exec_safe
                cand = ev.EventBatch(time=fs.time, seq=fs.seq, kind=fs.kind,
                                     src=fs.src, dst=fs.dst, ctx=fs.ctx,
                                     payload=fs.payload, valid=fs.valid)
                pre = (fs.clean, fs.order)
                rel_pos = fs.rel_pos
            else:
                exec_idx = self.select_fn(time_key, pool.seq, xcap)
                exec_safe = sync.exec_selection_ring(safe, exec_idx)
                cand = ev.gather(pool, exec_idx)
                pre = None
                rel_pos = None

        # 4. execute the window: grouped vectorized dispatch (default) or the
        # sequential fold — byte-identical results either way; safe events
        # beyond exec_cap spill to the next window
        execute = (self._execute_batched if spec.batched_dispatch
                   else self._execute_scan)
        world, counters, emits, trace, trace_n = execute(
            world, counters, cand, exec_safe, st.trace, st.trace_n,
            ring=stream_trace, pre=pre)
        if stream_trace:
            with mon.stage("trace"):
                # ring overwrite accounting: rows written this window on top
                # of un-drained ones (structurally 0 under the drain
                # invariant above; exact when a caller bypasses the
                # ring-size check)
                pb = st.trace_n - st.trace_tail
                pa = trace_n - st.trace_tail
                tcap = st.trace.shape[0]
                counters = mon.bump(
                    counters, mon.C_TRACE_DROP,
                    jnp.maximum(pa - tcap, 0) - jnp.maximum(pb - tcap, 0))

        with mon.stage("release"):
            n_processed = jnp.sum(exec_safe.astype(jnp.int32))
            n_spill = jnp.sum(safe.astype(jnp.int32)) - n_processed
            counters = mon.bump(counters, mon.C_EVENTS, n_processed)
            counters = mon.bump(counters, mon.C_EXEC_SPILL, n_spill)
            counters = mon.bump(counters, mon.C_WINDOWS, 1)
            # slot reclaim: ring mode pushes the executed slots onto the free
            # ring's tail (O(exec_cap)); ref mode keeps the pool-wide pop mask
            if spec.insert_mode == "ring":
                counters = mon.bump(
                    counters, mon.C_RING_WRAP,
                    pool.free_tail + n_processed >= jnp.int32(spec.pool_cap))
                pool = ev.release(pool, exec_idx, exec_safe, pos=rel_pos)
            else:
                slot_mask, _ = sync.exec_selection(safe, exec_idx)
                pool = ev.pop_mask_ref(pool, slot_mask)

            # processed LPs drop back to WAITING at window end (thread
            # states -> data)
            world = world._replace(
                lp_state=jnp.where(world.lp_state == 2, 3, world.lp_state))

        # 5-6. route + insert
        pool, counters = self._route_and_insert(world, pool, counters, emits, axis)

        # 7. replicated-state sync (C4) — field lists generated by the registry
        with mon.stage("sync"):
            world = self.registry.sync_world(world, self.own, axis_names(axis))

        # pool-lifecycle gauges: the occupancy/headroom signals the adaptive
        # exec policy reads (O(1) off the ring's free count in either mode)
        with mon.stage("gauges"):
            counters = mon.gauge(counters, mon.C_POOL_OCC, ev.occupancy(pool))
            counters = mon.gauge(counters, mon.C_POOL_FREE, pool.free_count)

        if stream_metrics:
            # end-of-window metrics snapshot: every agent ships its counter
            # vector; the host sink assembles a fleet view per window and
            # emits JSON lines on the configured cadence
            with mon.stage("drain"):
                io_callback(self._on_metrics, None, me, st.windows + 1,
                            jnp.max(horizon), counters, ordered=False)

        return EngineState(world=world, pool=pool, counters=counters,
                           t_now=jnp.max(horizon), done=done,
                           windows=st.windows + 1, trace=trace,
                           trace_n=trace_n, trace_tail=st.trace_tail)

    # ------------------------------------------------- step 4: sequential fold
    def _execute_scan(self, world, counters, cand: ev.EventBatch,
                      exec_safe: jax.Array, trace, trace_n, ring: bool = False,
                      pre=None):
        """PR 1 path: lax.scan over the gathered slots in (time, seq) order.

        ``pre`` (the fused front-end's precomputed conflict/group pair) is
        accepted for signature parity with ``_execute_batched`` and ignored —
        the sequential fold needs neither."""
        del pre
        ecap = self.spec.emit_cap
        emit0 = ev.empty_batch(ecap)
        trace0, trace_n0 = trace, trace_n

        def body(carry, x):
            world, counters, emits, emit_n, trace, trace_n = carry
            row, is_safe = x
            e = Ev(time=row.time, seq=row.seq, kind=row.kind,
                   src=row.src, dst=row.dst, ctx=row.ctx,
                   payload=row.payload)

            def run(w, c):
                w2, c2, out = apply_handler(self.table, w, c, e)
                w2 = w2._replace(
                    lp_lvt=w2.lp_lvt.at[e.dst].max(e.time),
                    lp_state=w2.lp_state.at[e.dst].set(2),  # RUNNING
                )
                return w2, c2, out

            def skip(w, c):
                return w, c, ev.empty_batch(ev.MAX_EMIT)

            world, counters, out = jax.lax.cond(is_safe, run, skip, world, counters)

            # append emits to the window emit buffer (overflow counted)
            val = out.valid
            offs = jnp.cumsum(val.astype(jnp.int32)) - 1
            pos = emit_n + offs
            ok = val & (pos < ecap)
            widx = jnp.where(ok, pos, ecap)  # ecap == OOB -> dropped
            emits = ev.EventBatch(
                time=emits.time.at[widx].set(out.time, mode="drop"),
                seq=emits.seq.at[widx].set(out.seq, mode="drop"),
                kind=emits.kind.at[widx].set(out.kind, mode="drop"),
                src=emits.src.at[widx].set(out.src, mode="drop"),
                dst=emits.dst.at[widx].set(out.dst, mode="drop"),
                ctx=emits.ctx.at[widx].set(out.ctx, mode="drop"),
                payload=emits.payload.at[widx].set(out.payload, mode="drop"),
                valid=emits.valid.at[widx].set(ok, mode="drop"),
            )
            emit_n = emit_n + jnp.sum(val.astype(jnp.int32))
            counters = mon.bump(counters, mon.C_DROP_POOL,
                                jnp.sum((val & ~ok).astype(jnp.int32)))

            # trace (bounded buffer, or ring under the streaming drain).
            # Bounded overflow is counted (C_TRACE_DROP), never silent —
            # merged_engine_trace refuses to return a truncated trace; ring
            # overwrites are accounted at the window boundary (_superstep).
            with mon.stage("trace"):
                tcap = trace.shape[0]
                trow = jnp.stack([e.time, e.seq, e.kind, e.dst])
                if ring:
                    tidx = jnp.where(is_safe, trace_n % tcap, tcap)
                    trace = trace.at[tidx].set(trow, mode="drop")
                else:
                    tidx = jnp.where(is_safe & (trace_n < tcap), trace_n,
                                     tcap)
                    trace = trace.at[tidx].set(trow, mode="drop")
                    if self.trace_cap > 0:
                        counters = mon.bump(
                            counters, mon.C_TRACE_DROP,
                            jnp.where(is_safe & (trace_n >= tcap), 1, 0))
                trace_n = trace_n + jnp.where(is_safe, 1, 0)
            return (world, counters, emits, emit_n, trace, trace_n), None

        carry0 = (world, counters, emit0, jnp.int32(0), trace0, trace_n0)
        with mon.stage("dispatch"):
            (world, counters, emits, _, trace, trace_n), _ = jax.lax.scan(
                body, carry0, (cand, exec_safe))
        return world, counters, emits, trace, trace_n

    # -------------------------------------------- step 4: vectorized dispatch
    def _execute_batched(self, world, counters, cand: ev.EventBatch,
                         exec_safe: jax.Array, trace, trace_n,
                         ring: bool = False, pre=None):
        """Grouped vectorized dispatch (see module docstring).

        Conflict-free slots run in one vmapped handler call per window; slots
        whose declared component rows collide fall back to a sequential fold
        compacted to just those slots. Emits land in a per-slot
        (exec_cap, MAX_EMIT) matrix and the trace is written in (time, seq)
        window order, so the results are byte-identical to ``_execute_scan``.
        """
        spec = self.spec
        xcap = cand.time.shape[0]

        with mon.stage("dispatch"):
            if pre is None:
                # conflict detection on the delta contract's declared rows:
                # two safe slots collide iff they address the same
                # (component table, lp_res row)
                table_id = jnp.asarray(self.registry.kind_table, jnp.int32)[
                    jnp.clip(cand.kind, 0, self.registry.n_kinds - 1)]
                res = world.lp_res[jnp.clip(cand.dst, 0, spec.n_lp - 1)]
                dirty = sync.conflict_mask(exec_safe, table_id, res,
                                           n_res=self._n_res,
                                           n_tables=self.registry.n_tables)
                clean = exec_safe & ~dirty

                # batched phase: group the clean rows by kind, dispatch once.
                # The grouped order keeps same-kind lanes contiguous (coherent
                # segments on wide-vector backends); the merge itself is
                # order-independent under the disjoint-write contract, and a
                # vmapped switch traces every handler per lane either way —
                # on CPU the permutation costs a few percent of the window
                # and buys layout, not fewer handler evals.
                order, _rank, _counts = self.group_fn(cand.kind, clean)
            else:
                # fused front-end (spec.fused_select): the megakernel already
                # computed the conflict mask and grouping in-VMEM; dirty is
                # recoverable because clean == exec_safe & ~dirty with
                # dirty ⊆ exec_safe
                clean, order = pre
                dirty = exec_safe & ~clean
            rows_g = jax.tree.map(lambda x: x[order], cand)
            clean_g = clean[order]
            batch_fn = (apply_handler_batch if spec.merge_mode == "delta"
                        else apply_handler_batch_dense)
            world, cdelta, emits_g = batch_fn(self.table, world, rows_g,
                                              clean_g)
            counters = counters + cdelta
            counters = mon.bump(counters, mon.C_BATCH_EXEC,
                                jnp.sum(clean.astype(jnp.int32)))

            # per-slot emit matrix in window order (grouped lanes scattered
            # back)
            emit_mat = jax.tree.map(
                lambda x: jnp.zeros_like(x).at[order].set(x), emits_g)

        with mon.stage("fallback"):
            # conflict fallback: sequential fold compacted to the dirty slots
            # (zero while_loop iterations on a conflict-free window)
            n_dirty = jnp.sum(dirty.astype(jnp.int32))
            counters = mon.bump(counters, mon.C_BATCH_FALLBACK, n_dirty)
            pos = jnp.arange(xcap, dtype=jnp.int32)
            dpos = jnp.sort(jnp.where(dirty, pos, xcap))

            def cond(carry):
                return carry[0] < n_dirty

            def body(carry):
                k, world, counters, emit_mat = carry
                p = dpos[jnp.minimum(k, xcap - 1)]
                row = jax.tree.map(lambda x: x[jnp.minimum(p, xcap - 1)], cand)
                e = Ev(time=row.time, seq=row.seq, kind=row.kind,
                       src=row.src, dst=row.dst, ctx=row.ctx,
                       payload=row.payload)
                active = k < n_dirty

                def run(w, c):
                    w2, c2, out = apply_handler(self.table, w, c, e)
                    w2 = w2._replace(
                        lp_lvt=w2.lp_lvt.at[e.dst].max(e.time),
                        lp_state=w2.lp_state.at[e.dst].set(2),  # RUNNING
                    )
                    return w2, c2, out

                def skip(w, c):
                    return w, c, ev.empty_batch(ev.MAX_EMIT)

                world, counters, out = jax.lax.cond(active, run, skip,
                                                    world, counters)
                emit_mat = ev.EventBatch(
                    time=emit_mat.time.at[p].set(out.time, mode="drop"),
                    seq=emit_mat.seq.at[p].set(out.seq, mode="drop"),
                    kind=emit_mat.kind.at[p].set(out.kind, mode="drop"),
                    src=emit_mat.src.at[p].set(out.src, mode="drop"),
                    dst=emit_mat.dst.at[p].set(out.dst, mode="drop"),
                    ctx=emit_mat.ctx.at[p].set(out.ctx, mode="drop"),
                    payload=emit_mat.payload.at[p].set(out.payload,
                                                       mode="drop"),
                    valid=emit_mat.valid.at[p].set(out.valid & active,
                                                   mode="drop"),
                )
                return k + 1, world, counters, emit_mat

            _, world, counters, emit_mat = jax.lax.while_loop(
                cond, body, (jnp.int32(0), world, counters, emit_mat))

        with mon.stage("trace"):
            # trace in (time, seq) window order — independent of execution
            # order. events.trace_append holds the position math (ring writes
            # wrap under the streaming drain; bounded overflow is counted,
            # never silent).
            rows4 = jnp.stack([cand.time, cand.seq, cand.kind, cand.dst],
                              axis=1)
            trace, trace_n, clipped = ev.trace_append(
                trace, trace_n, rows4, exec_safe, ring=ring,
                rank_fn=self.trace_fn)
            if not ring and self.trace_cap > 0:
                counters = mon.bump(counters, mon.C_TRACE_DROP, clipped)

            # segmented emit merge: flatten the per-slot matrix row-major
            # (== the sequential append order) and compact into the window
            # emit buffer
            flat = jax.tree.map(
                lambda x: x.reshape((xcap * ev.MAX_EMIT,) + x.shape[2:]),
                emit_mat)
            emits, _n_emit, dropped = ev.compact_batch(flat, spec.emit_cap)
            counters = mon.bump(counters, mon.C_DROP_POOL, dropped)
        return world, counters, emits, trace, trace_n

    # ---------------------------------------------------------------- routing
    def _insert(self, pool: ev.EventPool, counters, batch: ev.EventBatch):
        """Pool insert via the spec's lifecycle path (+ wrap accounting).

        ``slot_fn`` (wired by the fused front-end, or explicitly) swaps the
        ring's XLA slot math for the Pallas prefix-sum + ring-gather kernel —
        identical destination slots by the kernel-vs-ref sweeps."""
        if self.spec.insert_mode == "ring":
            pool2, dropped = ev.insert(pool, batch, slot_fn=self.slot_fn)
            n_take = pool.free_count - pool2.free_count
            counters = mon.bump(
                counters, mon.C_RING_WRAP,
                pool.free_head + n_take >= jnp.int32(self.spec.pool_cap))
            return pool2, counters, dropped
        pool2, dropped = ev.insert_ref(pool, batch)
        return pool2, counters, dropped

    def _route_and_insert(self, world: World, pool: ev.EventPool, counters,
                          emits: ev.EventBatch, axis: "str | ShardAxes | None",
                          migrate: bool = False):
        """Route a batch by destination agent, exchange, insert (steps 5-6).

        ``migrate=True`` is the placement-migration flavor: it additionally
        books shipped rows into C_MIGRATE_OUT (donor side, post route-cap —
        route overflow stays C_DROP_ROUTE as everywhere) and received rows
        into C_MIGRATE_IN (pre-insert), so ``sum(C_MIGRATE_OUT) ==
        sum(C_MIGRATE_IN)`` holds globally and exactly; receiving-pool
        overflow lands in C_DROP_POOL, never silent.
        """
        spec = self.spec
        A = axis.size if isinstance(axis, ShardAxes) else spec.n_agents
        if axis is None or A == 1:
            with mon.stage("insert"):
                pool, counters, dropped = self._insert(pool, counters, emits)
                counters = mon.bump(counters, mon.C_DROP_POOL, dropped)
                counters = mon.bump(counters, mon.C_LP_LOCAL,
                                    jnp.sum(emits.valid.astype(jnp.int32)))
            return pool, counters

        with mon.stage("route"):
            me = jax.lax.axis_index(axis_names(axis))
            rcap = spec.route_cap
            dst_agent = jnp.where(emits.valid, world.lp_agent[emits.dst], A)

            # stable bucket ranks (route_fn hook; default XLA sort-based rank)
            rank = self.route_fn(dst_agent)

            ok = emits.valid & (rank < rcap)
            counters = mon.bump(counters, mon.C_DROP_ROUTE,
                                jnp.sum((emits.valid & ~ok).astype(jnp.int32)))
            counters = mon.bump(
                counters, mon.C_MSGS_REMOTE,
                jnp.sum((ok & (dst_agent != me)).astype(jnp.int32)))
            counters = mon.bump(
                counters, mon.C_LP_LOCAL,
                jnp.sum((ok & (dst_agent == me)).astype(jnp.int32)))
            if migrate:
                counters = mon.bump(
                    counters, mon.C_MIGRATE_OUT,
                    jnp.sum((ok & (dst_agent != me)).astype(jnp.int32)))

            # OOB -> drop
            flat = jnp.where(ok, dst_agent * rcap + rank, A * rcap)

            def scatter(col, fill):
                buf = jnp.full((A * rcap,) + col.shape[1:], fill, col.dtype)
                return buf.at[flat].set(col, mode="drop").reshape(
                    (A, rcap) + col.shape[1:])

            if isinstance(axis, ShardAxes):
                # all_to_all takes a single axis name, so the (shard x lane)
                # exchange is staged: reshape the (A, rcap, ...) buffer to the
                # shard-major (D, K, rcap, ...) packing, exchange shard blocks
                # across the mesh, then lane blocks inside each shard. The
                # flattened receive order is ascending global source agent —
                # exactly the flat single-axis exchange's — so pool slot
                # layouts (and hence traces/counters) stay byte-identical to
                # run_local.
                d, k = axis.n_shards, axis.n_lanes

                def a2a(col):
                    x = col.reshape((d, k) + col.shape[1:])
                    x = jax.lax.all_to_all(x, axis.shard, split_axis=0,
                                           concat_axis=0)
                    x = jax.lax.all_to_all(x, axis.lane, split_axis=1,
                                           concat_axis=1)
                    return x.reshape(col.shape)
            else:
                a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                        split_axis=0, concat_axis=0)

            # the payload's int32 fields ride as f32 bit patterns, and small
            # ints are f32 denormals, which a cross-chip TPU all_to_all
            # flushes to zero: exchange the raw bits as int32 and view them
            # back
            payload_bits = jax.lax.bitcast_convert_type(emits.payload,
                                                        jnp.int32)
            rx = ev.EventBatch(
                time=a2a(scatter(emits.time, ev.T_INF)).reshape(A * rcap),
                seq=a2a(scatter(emits.seq, 0)).reshape(A * rcap),
                kind=a2a(scatter(emits.kind, 0)).reshape(A * rcap),
                src=a2a(scatter(emits.src, 0)).reshape(A * rcap),
                dst=a2a(scatter(emits.dst, 0)).reshape(A * rcap),
                ctx=a2a(scatter(emits.ctx, 0)).reshape(A * rcap),
                payload=jax.lax.bitcast_convert_type(
                    a2a(scatter(payload_bits, 0)), jnp.float32).reshape(
                        A * rcap, ev.PAYLOAD),
                valid=a2a(scatter(emits.valid, False)).reshape(A * rcap),
            )
            if migrate:
                # received rows counted before insert: out/in balance is exact,
                # and any overflow below is a C_DROP_POOL, not a silent loss
                counters = mon.bump(counters, mon.C_MIGRATE_IN,
                                    jnp.sum(rx.valid.astype(jnp.int32)))
        with mon.stage("insert"):
            pool, counters, dropped = self._insert(pool, counters, rx)
            counters = mon.bump(counters, mon.C_DROP_POOL, dropped)
        return pool, counters

    # -------------------------------------------------- host-streaming layer
    @property
    def _streaming(self) -> bool:
        return self.trace_stream is not None or self.metrics_stream is not None

    # ------------------------------------------------------ checkpoint layer
    @property
    def _checkpointing(self) -> bool:
        ck = self.checkpointer
        return ck is not None and getattr(ck, "every", 0) > 0

    def _checkpoint_window(self, st: EngineState, rung: int | None = None,
                           padded: bool = False) -> None:
        """Window-boundary checkpoint hook (host-stepped drivers).

        Saves the *unpadded* state when the cadence is due: a checkpoint is
        device-layout-free, so restore re-pads for whatever mesh the resumed
        driver gets. ``rung`` is the adaptive rung already chosen for the
        next window (the adaptive loops call this after ``choose_rung``), so
        a resumed trajectory continues exactly."""
        ck = self.checkpointer
        if ck is None:
            return
        w = int(np.asarray(st.windows).reshape(-1)[0])
        if not ck.due(w):
            return
        with mon.span("engine.checkpoint", window=w):
            ck.save_sim(w, self._slice_state(st) if padded else st,
                        engine=self, rung=rung)

    def restore(self, step: int | None = None):
        """Load a checkpoint written by this engine's checkpointer.

        Returns a ``SimCheckpoint(step, state, rung)``: pass ``state=`` (and
        for the adaptive drivers ``rung=``) to any driver to resume. Also
        reloads the checkpoint's drained trace spans into the attached
        :class:`TraceStream` (so a resumed streamed run reassembles the full
        ``[0, trace_n)`` trace with zero drops) and its emitted metrics
        records into the attached :class:`MetricsStream` (so the interval
        record sequence concatenates exactly across the boundary)."""
        if self.checkpointer is None:
            raise ValueError("no checkpointer attached to this engine")
        with mon.span("engine.restore"):
            return self.checkpointer.restore_sim(self, step=step)

    def _on_trace_drain(self, agent, start, count, ring):
        """io_callback target (host thread): forward a drained span."""
        ts = self.trace_stream
        if ts is not None:
            ts.on_drain(agent, start, count, ring)

    def _on_metrics(self, agent, window, gvt, counters):
        """io_callback target (host thread): forward a window snapshot."""
        ms = self.metrics_stream
        if ms is not None:
            ms.on_window(agent, window, gvt, counters)

    def _begin_streams(self, widths) -> None:
        """Arm the attached streams for a run using exec widths ``widths``.

        The zero-drop invariant needs the ring to hold at least one window's
        worst case, so the widest rung bounds the minimum ``trace_cap``."""
        if self.trace_stream is not None:
            need = max(max(min(int(w), self.spec.pool_cap), 1)
                       for w in widths)
            if self.trace_cap < need:
                raise ValueError(
                    f"streaming trace ring too small: trace_cap="
                    f"{self.trace_cap} must hold one window's writes (max "
                    f"exec width {need}) or the drain cannot keep "
                    f"C_TRACE_DROP == 0")
            self.trace_stream.begin(self.spec.n_agents)
        if self.metrics_stream is not None:
            self.metrics_stream.begin(self.spec.n_agents, self.registry)

    def _finalize_streams(self, st: EngineState) -> EngineState:
        """Drain outstanding callbacks and flush the in-state tail spans.

        ``st`` must be the unpadded (A, ...) final state. effects_barrier
        makes every in-flight io_callback land before reassembly."""
        if not self._streaming:
            return st
        with mon.span("engine.finalize"):
            getattr(jax, "effects_barrier", lambda: None)()
            if self.trace_stream is not None:
                self.trace_stream.finalize(np.asarray(st.trace),
                                           np.asarray(st.trace_n),
                                           np.asarray(st.trace_tail))
            if self.metrics_stream is not None:
                self.metrics_stream.finalize(np.asarray(st.counters),
                                             np.asarray(st.windows),
                                             np.asarray(st.t_now))
        return st

    def _run_hosted(self, max_windows: int,
                    state: EngineState | None = None,
                    mesh: Mesh | None = None) -> EngineState:
        """Host-stepped static-width run with the host hooks live.

        ``run_local``/``run_distributed`` land here when a stream or a
        checkpointer is attached: the whole-run while_loop can carry neither
        io_callbacks under vmap nor a mid-run host save, so the driver steps
        the jit-cached window program from the host (the run_adaptive shape).
        Stream drains fire inside each window program at its boundary; the
        checkpoint hook runs between window programs — the GVT-aligned
        boundary where the state is globally consistent."""
        width = self.spec.exec_cap
        self._begin_streams([width])
        if mesh is None:
            st = self.init_state() if state is None else state
            fn = self._window_fn(width)
        else:
            axes = self._dist_axes(mesh)
            st = self._pad_state(self.init_state() if state is None else state,
                                 axes.size)
            fn = self._dist_window_fn(mesh, width)
        w0 = self._first_window(st)
        for i in range(max_windows):
            if bool(np.asarray(st.done).all()):
                break
            with mon.span("engine.window", step_num=w0 + i):
                st = fn(st)
                self._checkpoint_window(st, padded=mesh is not None)
                self._fire_window_hook(st)
        if mesh is not None:
            st = self._slice_state(st)
        return self._finalize_streams(st)

    @staticmethod
    def _first_window(st: EngineState) -> int:
        """The window count a host-stepped run starts from (its
        ``engine.window`` spans are numbered from there)."""
        return int(np.asarray(st.windows).reshape(-1)[0])

    def _fire_window_hook(self, st: EngineState) -> None:
        """Invoke the orchestrator's host observation point, if any.

        Runs after ``_checkpoint_window`` so a hook that aborts the run
        (raising e.g. ``repro.fleet.PreemptionError``) never outruns the
        latest due checkpoint."""
        if self.window_hook is not None:
            self.window_hook(int(np.asarray(st.windows).reshape(-1)[0]), st)

    # ------------------------------------------------------------------- run
    def _run_fn(self, axis: "str | ShardAxes | None", max_windows: int,
                program: str = "run_local"):
        def cond(st: EngineState):
            return (~st.done) & (st.windows < max_windows)

        def body(st: EngineState):
            return self._superstep(st, axis)

        def run(st: EngineState):
            # a jitted function's body runs only while JAX traces it, so
            # this counts traces of the driver program, never its runs
            mon.count("engine.traces", key=program)
            return jax.lax.while_loop(cond, body, st)

        return run

    def run_local(self, max_windows: int = 10_000, jit: bool = True,
                  state: EngineState | None = None) -> EngineState:
        """Single-device multi-agent execution (vmap over the agents axis).

        ``state`` resumes from a prior EngineState (e.g. after a placement
        migration) instead of ``init_state()``.

        With a trace/metrics stream or a checkpointer attached the run is
        host-stepped (see :meth:`_run_hosted`) — the whole-run while_loop
        cannot carry the drain io_callbacks under a batched predicate, nor
        pause for a mid-run checkpoint save."""
        with mon.span("engine.run", driver="local"):
            if self._streaming or self._checkpointing:
                return self._run_hosted(max_windows, state=state)
            st = self.init_state() if state is None else state
            axis = AXIS if self.spec.n_agents > 1 else None

            def make(core):
                fn = jax.vmap(core._run_fn(axis, max_windows, "run_local"),
                              axis_name=AXIS)
                return jax.jit(fn) if jit else fn

            return self._program(("run_local", max_windows, jit), make)(st)

    # ------------------------------------------------------- distributed run
    def _dist_axes(self, mesh: Mesh) -> ShardAxes:
        """The shard x lane packing of a mesh: ``K = ceil(A / D)`` agents per
        device, stacked state padded to ``D * K`` rows (shard-major)."""
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"run_distributed needs a 1-D mesh, got axes {mesh.axis_names}")
        shard = mesh.axis_names[0]
        d = int(mesh.devices.size)
        if (d > 1 and mesh.devices.flat[0].platform == "tpu"
                and os.environ.get(MULTICHIP_TPU_ENV) != "1"):
            raise RuntimeError(
                f"run_distributed over {d} TPU chips is not yet shown to equal "
                f"run_local; set {MULTICHIP_TPU_ENV}=1 to run it anyway "
                "(chip_smoke.py --four-chips does, and compares the two)")
        k = -(-self.spec.n_agents // d)
        lane = "lanes" if shard != "lanes" else "lanes2"
        return ShardAxes(shard=shard, lane=lane, n_shards=d, n_lanes=k)

    def _pad_state(self, st: EngineState, a_pad: int) -> EngineState:
        """Pad a stacked (A, ...) state to ``a_pad`` rows with inert agents.

        Pad agents exist so ``A % n_devices != 0`` still packs into a
        rectangular (D, K) layout. They must be *invisible*: an empty pool
        contributes T_INF to GVT, an ``lp_agent`` row copied from agent 0
        owns no LP at a pad index (all ``lp_agent`` values are real-agent
        ids), so owner-wins sync and the routing exchange see only zeros from
        them. Globally-uniform scalars (t_now/done/windows — and the
        replicated world copy) are broadcast from row 0, NOT zeroed: every
        row of the while_loop cond must stay uniform even when resuming from
        a mid-run state, or the shards' collective counts diverge. Counters
        and trace are zeroed (pad rows are sliced off before results are
        returned, and all-zero rows are neutral in the max-reduced adaptive
        stats).
        """
        n = a_pad - st.t_now.shape[0]
        if n == 0:
            return st
        rep0 = lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (n,) + x.shape[1:])])
        zero = lambda x: jnp.concatenate(
            [x, jnp.zeros((n,) + x.shape[1:], x.dtype)])
        epool = ev.empty_pool(self.spec.pool_cap)
        pool = jax.tree.map(
            lambda x, e: jnp.concatenate(
                [x, jnp.broadcast_to(e[None], (n,) + e.shape)]),
            st.pool, epool)
        return EngineState(
            world=jax.tree.map(rep0, st.world),
            pool=pool,
            counters=zero(st.counters),
            t_now=rep0(st.t_now),
            done=rep0(st.done),
            windows=rep0(st.windows),
            trace=zero(st.trace),
            trace_n=zero(st.trace_n),
            trace_tail=zero(st.trace_tail),
        )

    def _slice_state(self, st: EngineState) -> EngineState:
        """Drop pad-agent rows: the real agents' (A, ...) state."""
        A = self.spec.n_agents
        if st.t_now.shape[0] == A:
            return st
        return jax.tree.map(lambda x: x[:A], st)

    def _dist_run_fn(self, mesh: Mesh, axes: ShardAxes, max_windows: int):
        def make(core):
            inner = jax.vmap(core._run_fn(axes, max_windows, "dist_run"),
                             axis_name=axes.lane)
            return jax.jit(_shard_map(inner, mesh=mesh,
                                      in_specs=P(axes.shard),
                                      out_specs=P(axes.shard)))

        return self._program(("run_distributed", mesh, max_windows), make)

    def run_distributed(self, mesh: Mesh, max_windows: int = 10_000,
                        state: EngineState | None = None) -> EngineState:
        """shard_map x vmap execution over a 1-D device mesh.

        ``K = ceil(n_agents / n_devices)`` agents pack per device: shard_map
        partitions the stacked (padded) state's leading axis over the mesh
        and ``vmap`` runs the per-agent program over each shard's K-row
        block, so agent count is decoupled from device count. Collectives
        reduce over the (shard, lane) axis-name tuple (one fleet-global
        GVT/psum) and the routing all_to_all is staged per axis with a
        shard-major receive order — results are byte-identical to
        ``run_local`` (down to pool slot layouts) and hence to the
        sequential oracle. ``state`` resumes from a prior (unpadded)
        EngineState.

        With a trace/metrics stream or a checkpointer attached the run is
        host-stepped (see :meth:`_run_hosted`); per-shard rings drain
        independently and the host merge is shard-major, matching
        ``merged_engine_trace``. Checkpoints save the unpadded state, so a
        resumed run may use a different mesh."""
        with mon.span("engine.run", driver="distributed"):
            if self._streaming or self._checkpointing:
                return self._run_hosted(max_windows, state=state, mesh=mesh)
            axes = self._dist_axes(mesh)
            st = self._pad_state(self.init_state() if state is None else state,
                                 axes.size)
            out = self._dist_run_fn(mesh, axes, max_windows)(st)
            return self._slice_state(out)

    # -------------------------------------------------------------- migration
    def _apply_placement(self, st: EngineState, new_lp_agent: jax.Array,
                         axis: "str | ShardAxes | None") -> EngineState:
        """Move LPs to a new placement (paper §4.1 dynamic decomposition).

        Component state is replicated (C4), so migration only (1) rewrites
        ``lp_agent`` and (2) re-homes pending events whose destination LP
        moved — one extra all_to_all, reusing the routing path with
        ``migrate=True`` so shipped/received rows are booked into
        C_MIGRATE_OUT / C_MIGRATE_IN (globally balanced; receiver overflow
        is C_DROP_POOL). The donor pool is canonicalized by ``ev.pop_mask``'s
        ring rebuild, so slot layout after a migration is a pure function of
        the surviving events — identical across drivers.
        """
        world = st.world._replace(lp_agent=jnp.asarray(new_lp_agent,
                                                       jnp.int32))
        pool, counters = st.pool, st.counters
        if axis is None or self.spec.n_agents == 1:
            return st._replace(world=world)
        me = jax.lax.axis_index(axis_names(axis))
        moving = pool.valid & (world.lp_agent[pool.dst] != me)
        emits = ev.extract(pool, moving)
        pool = ev.pop_mask(pool, moving)
        pool, counters = self._route_and_insert(world, pool, counters, emits,
                                                axis, migrate=True)
        return st._replace(world=world, pool=pool, counters=counters)

    def apply_placement_local(self, st: EngineState,
                              new_lp_agent: jax.Array) -> EngineState:
        """vmap driver for migration (new_lp_agent is fleet-global, (NLP,))."""
        axis = AXIS if self.spec.n_agents > 1 else None

        def make(core):
            def place(s, nla):
                mon.count("engine.traces", key="placement")
                return core._apply_placement(s, nla, axis)

            return jax.jit(jax.vmap(place, in_axes=(0, None), axis_name=AXIS))

        return self._program(("placement",), make)(
            st, jnp.asarray(new_lp_agent, jnp.int32))

    def apply_placement_distributed(self, st: EngineState,
                                    new_lp_agent: jax.Array,
                                    mesh: Mesh) -> EngineState:
        """shard_map x vmap driver for migration (cross-shard event re-home).

        ``st`` is an unpadded (A, ...) state (e.g. a ``run_distributed``
        result mid-run); ``new_lp_agent`` is fleet-global. Returns the
        unpadded migrated state — byte-identical to
        ``apply_placement_local`` on the same state."""
        axes = self._dist_axes(mesh)

        def make(core):
            def place(s, nla):
                mon.count("engine.traces", key="placement")
                return core._apply_placement(s, nla, axes)

            inner = jax.vmap(place, in_axes=(0, None), axis_name=axes.lane)
            return jax.jit(_shard_map(inner, mesh=mesh,
                                      in_specs=(P(axes.shard), P()),
                                      out_specs=P(axes.shard)))

        fn = self._program(("dist_placement", mesh), make)
        return self._slice_state(fn(self._pad_state(st, axes.size),
                                    jnp.asarray(new_lp_agent, jnp.int32)))

    def step_local(self, st: EngineState) -> EngineState:
        """One conservative window (vmap driver) — used by tests and benchmarks."""
        axis = AXIS if self.spec.n_agents > 1 else None

        def make(core):
            def step(s):
                mon.count("engine.traces", key="step_local")
                return core._superstep(s, axis)

            return jax.jit(jax.vmap(step, axis_name=AXIS))

        return self._program(("step_local",), make)(st)

    # ------------------------------------------------------ adaptive driver
    def _window_fn(self, width: int):
        """One jitted window program at a fixed exec width (cached per rung,
        so the adaptive ladder recompiles nothing after first use). With a
        stream attached the program carries this engine's callbacks and
        stays on the engine."""
        stream = self._streaming
        axis = AXIS if self.spec.n_agents > 1 else None

        def make(core):
            def window(s):
                mon.count("engine.traces", key="window")
                return core._superstep(s, axis, exec_cap=width, stream=stream)

            return jax.jit(jax.vmap(window, axis_name=AXIS))

        return self._program(("window_stream" if stream else "window", width),
                             make, shared=not stream)

    def run_adaptive(self, max_windows: int = 10_000,
                     policy: "pol.ExecPolicy | int | None" = None,
                     state: EngineState | None = None,
                     rung: int | None = None) -> EngineState:
        """Monitoring-driven execution (vmap driver): the per-window LISA
        loop of core/policy.py.

        Each window runs the jitted program of the current ladder rung, then
        the host reads the window's monitoring vector (spill rate, scatter
        volume, pool occupancy/headroom gauges) and picks the next rung —
        grow under spill pressure or near pool saturation, shrink on sparse
        windows. Exactness is unconditional: spilling is oracle-exact for any
        width sequence, so traces/world bytes match the static drivers and
        the sequential oracle; only the window count (and per-window cost)
        changes. The rung trajectory lands in ``self.adaptive_rungs``.

        ``policy`` overrides ``spec.exec_policy`` (a bare int means a
        single-rung ladder, i.e. the static behavior); ``state`` resumes
        from a prior EngineState and ``rung`` from a checkpointed rung
        (checkpoints save the rung *after* ``choose_rung``, and the restored
        counters are exactly the save-time ``cur``, so a resumed trajectory
        concatenates byte-identically with the prefix).
        """
        with mon.span("engine.run", driver="adaptive"):
            p = pol.normalize(self.spec.exec_policy if policy is None
                              else policy)
            self._begin_streams(p.ladder)
            st = self.init_state() if state is None else state
            rung = p.init_rung if rung is None else int(rung)
            prev = np.asarray(st.counters)
            rungs: list[int] = []
            w0 = self._first_window(st)
            for i in range(max_windows):
                if bool(np.asarray(st.done).all()):
                    break
                rungs.append(rung)
                with mon.span("engine.window", step_num=w0 + i):
                    st = self._window_fn(p.ladder[rung])(st)
                    cur = np.asarray(st.counters)
                    stats = pol.window_stats(prev, cur, self.spec.pool_cap)
                    rung = pol.choose_rung(p, rung, stats)
                    prev = cur
                    self._checkpoint_window(st, rung=rung)
                    self._fire_window_hook(st)
            self.adaptive_rungs = tuple(rungs)
            return self._finalize_streams(st)

    def _dist_window_fn(self, mesh: Mesh, width: int):
        """One jitted shard_map x vmap window program at a fixed exec width
        (cached per (mesh, rung) — lockstep adaptation recompiles nothing
        after each rung's first use). With a stream attached the program
        carries this engine's callbacks and stays on the engine."""
        stream = self._streaming
        axes = self._dist_axes(mesh)

        def make(core):
            def window(s):
                mon.count("engine.traces", key="dist_window")
                return core._superstep(s, axes, exec_cap=width,
                                       stream=stream)

            inner = jax.vmap(window, axis_name=axes.lane)
            return jax.jit(_shard_map(inner, mesh=mesh,
                                      in_specs=P(axes.shard),
                                      out_specs=P(axes.shard)))

        return self._program(
            ("dist_window_stream" if stream else "dist_window", mesh, width),
            make, shared=not stream)

    def run_distributed_adaptive(self, mesh: Mesh, max_windows: int = 10_000,
                                 policy: "pol.ExecPolicy | int | None" = None,
                                 state: EngineState | None = None,
                                 rung: int | None = None) -> EngineState:
        """Monitoring-driven distributed execution: ``run_adaptive``'s LISA
        loop over the shard_map x vmap driver.

        Each window runs the jit-cached program of the current rung on every
        shard (the collectives inside a window are fleet-wide, so all shards
        must trace the same width). The host then reads per-shard
        :func:`pol.shard_window_stats` off the free ring's O(1) occupancy
        gauges, decides a rung per shard, and max-reduces the decisions
        (:func:`pol.choose_rung_lockstep`) — the hottest shard sets the
        fleet's width. Because every ``choose_rung`` condition is monotone in
        the max-reduced stats, the lockstep rung trajectory is byte-identical
        to ``run_adaptive``'s on the same scenario, and exactness is
        unconditional (spilling is oracle-exact for any width sequence). The
        trajectory lands in ``self.adaptive_rungs``. ``state``/``rung``
        resume from a checkpoint — on any mesh, since checkpoints hold the
        unpadded state and this driver re-pads for the mesh it is given."""
        with mon.span("engine.run", driver="distributed_adaptive"):
            p = pol.normalize(self.spec.exec_policy if policy is None
                              else policy)
            self._begin_streams(p.ladder)
            axes = self._dist_axes(mesh)
            A = self.spec.n_agents
            st = self._pad_state(self.init_state() if state is None else state,
                                 axes.size)
            rung = p.init_rung if rung is None else int(rung)
            prev = np.asarray(st.counters)
            rungs: list[int] = []
            w0 = self._first_window(st)
            for i in range(max_windows):
                if bool(np.asarray(st.done)[:A].all()):
                    break
                rungs.append(rung)
                with mon.span("engine.window", step_num=w0 + i):
                    st = self._dist_window_fn(mesh, p.ladder[rung])(st)
                    cur = np.asarray(st.counters)
                    stats = pol.shard_window_stats(
                        prev, cur, self.spec.pool_cap, axes.n_shards)
                    rung = pol.choose_rung_lockstep(p, rung, stats)
                    prev = cur
                    self._checkpoint_window(st, rung=rung, padded=True)
                    self._fire_window_hook(st)
            self.adaptive_rungs = tuple(rungs)
            return self._finalize_streams(self._slice_state(st))

    # ------------------------------------------------------- ensemble driver
    def run_ensemble(self, seeds, max_windows: int = 10_000,
                     seed_fn=None) -> EngineState:
        """Monte Carlo vmap-over-seeds driver: R replicas, one fused launch.

        Stacks R copies of the initial state, perturbs each with
        ``seed_fn(state, seed)`` (default :func:`seed_rng_fields`, which
        jumps every ``*_rng`` world field — the in-handler LCG states), and
        runs the whole-run while_loop under an outer replica vmap, so
        hundreds of replicas execute as one XLA program. jax's while_loop
        batching rule freezes finished replicas with a per-lane select, so
        each replica's slice of the (R, A, ...) result is byte-identical to
        a ``run_local`` of the same seeded state. With a MetricsStream
        attached, per-replica counter totals are reduced into
        ``metrics_stream`` (``replica_counters`` + a summary JSON line).

        "Millions of users" traffic in the paper's terms is exactly this:
        one launch sweeping seeds, not one hand-built spec per run.
        """
        with mon.span("engine.run", driver="ensemble"):
            if self.trace_stream is not None:
                raise ValueError(
                    "run_ensemble cannot stream traces (io_callback is "
                    "unsupported under the nested replica vmap); use a "
                    "bounded trace_cap for per-replica traces")
            if self._checkpointing:
                raise ValueError(
                    "run_ensemble is one fused program with no window "
                    "boundaries on the host; checkpoint cadence applies to "
                    "the single-run drivers")
            seeds = jnp.asarray(seeds, jnp.int32).reshape(-1)
            sfn = seed_fn or seed_rng_fields
            seed_all = self._program(
                ("ensemble_seed", sfn),
                lambda core: jax.jit(jax.vmap(sfn, in_axes=(None, 0))))
            axis = AXIS if self.spec.n_agents > 1 else None

            def make(core):
                inner = jax.vmap(core._run_fn(axis, max_windows, "ensemble"),
                                 axis_name=AXIS)
                return jax.jit(jax.vmap(inner))

            fn = self._program(("run_ensemble", max_windows), make)
            out = fn(seed_all(self.init_state(), seeds))
            ms = self.metrics_stream
            if ms is not None:
                ms.begin(self.spec.n_agents, self.registry)
                ms.ensemble(np.asarray(seeds), np.asarray(out.counters),
                            np.asarray(out.windows), np.asarray(out.t_now))
            return out


def seed_rng_fields(state: EngineState, seed) -> EngineState:
    """Default ensemble ``seed_fn``: decorrelate one replica's RNG streams.

    Folds the replica seed into every integer world field named ``rng`` or
    ``*_rng`` — the registry convention for in-handler LCG state (e.g. the
    failure LP's ``fp_rng``) — using the same affine jump the scenario
    builders use to space per-row streams. Any int32 is a valid LCG state,
    so the perturbed replica is exact under the sequential oracle with the
    same world. A model with no RNG fields yields identical replicas
    (still useful for throughput measurement)."""
    upd = {}
    for name in state.world._fields:
        if name != "rng" and not name.endswith("_rng"):
            continue
        f = getattr(state.world, name)
        if jnp.issubdtype(f.dtype, jnp.integer):
            upd[name] = f + jnp.asarray(seed, f.dtype) * jnp.asarray(
                7919, f.dtype)
    return state._replace(world=state.world._replace(**upd)) if upd else state
