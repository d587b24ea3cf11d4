"""Event handlers: the behavior of each simulation component (paper §4.2).

Handlers are *registered* on the builtin registry (``components.BUILTIN``)
with the ``@on(kind)`` decorator — :func:`register_builtin_handlers` below is
the builtin batch, and a model defined outside core attaches its own the same
way (see ``repro/scenarios/cache.py``). ``make_handlers`` (now generated by
``Registry.make_handlers``) builds the ``lax.switch`` dispatch table.

Every handler is a *per-row segment-scatter kernel*: it gathers only the
component row owned by its destination LP (``lp_res[e.dst]``), computes the
row-local update, and returns a compact ``WorldDelta`` — a typed ``(table row
index, new row)`` write set — instead of a whole mutated ``World``. Deltas are
applied by :func:`apply_delta` (one ``.at[row].set`` scatter per field), which
serves both the sequential paths (one event at a time) and the engine's
batched dispatch (all lanes' deltas in one segment scatter, see
:func:`apply_handler_batch`). This keeps the vectorized merge O(lanes x row)
instead of O(lanes x pool-wide tables).

Invariants the engine and the conflict mask rely on (the **delta contract**):

1. **Row locality** — the handler for kind ``k`` reads and writes exactly one row
   of one component table: row ``lp_res[e.dst]`` of the table the kind declares
   (``registry.kind_table[k]``, exported as ``events.KIND_TABLE`` for the
   builtin model) — plus immutable topology/capacity columns, which are never
   written, and write-only commutative counters. A handler never touches
   another LP's row.
2. **Whole-row writes** — a handler that writes a table writes *every* mutable
   field of that table's row (unchanged fields carry their old bytes), so a delta
   applies with plain ``.at[row].set`` scatters and needs no per-element masks.
   ``env.delta(world, component, row, **writes)`` enforces this at trace time.
3. **Disjoint-write guarantee** — ``sync.conflict_mask`` keys on exactly the
   ``(KIND_TABLE[kind], lp_res[dst])`` row each handler declares, so the batched
   dispatcher only ever scatters pairwise-distinct rows in one call; combined
   with (1) this makes the batched execution byte-identical to folding the same
   events sequentially in any order.
4. **Lookahead contract** (the conservative-sync invariant, see
   docs/architecture.md): every emitted event carries a delay of at least
   ``lookahead`` ticks; handlers clamp all delays with ``env.delay``. The
   sequential oracle reuses these same kernels, so trace equality is exact.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import events as ev
from repro.core import monitoring as mon
from repro.core import network as net
from repro.core.components import (BUILTIN, FLOW_END, GEN_TICK, JOB_END,
                                   K_NOOP, K_FLOW_START, K_FLOW_END,
                                   K_JOB_SUBMIT, K_JOB_END, K_DATA_WRITE,
                                   K_MIGRATE, K_GEN_TICK, MIGRATE)
from repro.core.registry import NO_ROW, Registry, registry_of


class Ev(NamedTuple):
    """Scalar view of one event."""

    time: jax.Array
    seq: jax.Array
    kind: jax.Array
    src: jax.Array
    dst: jax.Array
    ctx: jax.Array
    payload: jax.Array  # (PAYLOAD,)


# The generated per-row write-set struct and its schema (registry output; the
# PR 3 hand-written WorldDelta/DELTA_SCHEMA are pinned identical to these by
# tests/test_registry.py). DELTA_SCHEMA maps each mutable World field to the
# WorldDelta row-index column that addresses it; it mirrors the owner-wins
# field list in components.sync_world minus lp_state/lp_lvt, which the engine
# applies as segment scatters over the event batch (max / idempotent-set, so
# they commute even across duplicate-dst lanes).
WorldDelta = BUILTIN.delta_struct()
DELTA_SCHEMA: dict[str, str] = BUILTIN.delta_schema
ROW_FIELDS = BUILTIN.row_fields


def empty_delta(world):
    """The identity delta: no rows declared, zero-filled row payloads."""
    return registry_of(world).empty_delta(world)


def apply_delta(world, delta):
    """Scatter a delta's declared rows into the world.

    Polymorphic over the lane axis: with scalar row indices this applies one
    handler's delta (the sequential paths); with ``(lanes,)`` row indices and
    ``(lanes, ...)`` row payloads it applies a whole window's deltas in one
    segment scatter per field. ``NO_ROW`` (and any masked-out lane) is out of
    bounds and dropped. Exact under the disjoint-write guarantee: every
    scattered row index appears at most once, so ``.set`` has a unique winner.
    The schema comes from the delta's own registry, so extended models apply
    with zero edits here.
    """
    return registry_of(delta).apply_delta(world, delta)


def no_emits() -> ev.EventBatch:
    """An empty (MAX_EMIT,) emit batch — the starting point for handlers."""
    return ev.empty_batch(ev.MAX_EMIT)


def set_emit(batch: ev.EventBatch, slot: int, *, valid, time, kind, src, dst,
             ctx, payload, parent_seq) -> ev.EventBatch:
    """Write one emit slot. seq is the functional child id (oracle-identical)."""
    return ev.EventBatch(
        time=batch.time.at[slot].set(jnp.asarray(time, jnp.int32)),
        seq=batch.seq.at[slot].set(ev.child_seq(parent_seq, slot)),
        kind=batch.kind.at[slot].set(jnp.asarray(kind, jnp.int32)),
        src=batch.src.at[slot].set(jnp.asarray(src, jnp.int32)),
        dst=batch.dst.at[slot].set(jnp.asarray(dst, jnp.int32)),
        ctx=batch.ctx.at[slot].set(jnp.asarray(ctx, jnp.int32)),
        payload=batch.payload.at[slot].set(payload),
        valid=batch.valid.at[slot].set(valid),
    )


def pad_payload(vals) -> jax.Array:
    """Pad a short positional payload to the (PAYLOAD,) row handlers emit."""
    out = jnp.zeros((ev.PAYLOAD,), jnp.float32)
    for i, v in enumerate(vals):
        out = out.at[i].set(jnp.asarray(v, jnp.float32))
    return out


def start_queued_job(env, world, f, slot, e: Ev, out: ev.EventBatch,
                     emit_slot: int = 0):
    """Pop the farm FIFO head (if any) onto CPU ``slot`` of farm row ``f``.

    The single source of the queue discipline: the head row is
    ``[work, mem, notify_lp, notify_kind, size, _]``, the popped job marks
    the slot busy, and its completion lands as a JOB_END emit at
    ``emit_slot`` of ``out``. Shared by the builtin ``JOB_END`` handler and
    any extension that frees a CPU slot (e.g. the failure scenario's repair
    kind), so the row layout and the duration math cannot drift apart.
    Returns ``(busy_value, mem_value, jobq', jobq_n', out')`` — the caller
    scatters the slot values into its row copy.
    """
    jq = world.jobq[f]
    qn0 = world.jobq_n[f]
    has_q = qn0 > 0
    head = jq[0]
    shifted = jnp.concatenate(
        [jq[1:], jnp.zeros((1, jq.shape[1]), jnp.float32)])
    new_jq = jnp.where(has_q, shifted, jq)
    new_qn = qn0 + jnp.where(has_q, -1, 0)
    busy_v = jnp.where(has_q, 1, 0)
    mem_v = jnp.where(has_q, head[1], 0.0)
    power = world.cpu_power[f, slot]
    dur = jnp.ceil(head[0] / jnp.maximum(power, 1e-6)).astype(jnp.int32)
    out = set_emit(out, emit_slot, valid=has_q,
                   time=e.time + env.delay(dur),
                   kind=JOB_END.id, src=e.dst, dst=e.dst, ctx=e.ctx,
                   payload=pad_payload([slot, head[0], head[1], head[2],
                                        head[3], head[4]]),
                   parent_seq=e.seq)
    return busy_v, mem_v, new_jq, new_qn, out


def register_builtin_handlers(reg: Registry) -> None:
    """Attach the four builtin components' handlers to ``reg``.

    Each handler is a row kernel ``(env, world, counters, e) -> (delta,
    counters, EventBatch[MAX_EMIT])`` honoring the delta contract in the
    module docstring; ``env`` carries the trace-time constants (lookahead,
    work_per_mb) that ``make_handlers`` used to close over.
    """

    # -- NOOP ----------------------------------------------------------------
    @reg.on(K_NOOP)
    def h_noop(env, world, counters, e: Ev):
        return env.empty_delta(world), counters, no_emits()

    # -- GEN_TICK — activity generator ---------------------------------------
    @reg.on(K_GEN_TICK)
    def h_gen_tick(env, world, counters, e: Ev):
        g = world.lp_res[e.dst]
        left = world.gen_left[g]
        fire = left > 0
        new_left = left + jnp.where(fire, -1, 0)
        out = no_emits()
        # slot 0: the generated activity event
        out = set_emit(out, 0, valid=fire,
                       time=e.time + env.delay(1),
                       kind=world.gen_kind[g], src=e.dst,
                       dst=world.gen_target[g], ctx=e.ctx,
                       payload=world.gen_payload[g], parent_seq=e.seq)
        # slot 1: next tick to self
        out = set_emit(out, 1, valid=fire & (left > 1),
                       time=e.time + env.delay(world.gen_interval[g]),
                       kind=GEN_TICK.id, src=e.dst, dst=e.dst, ctx=e.ctx,
                       payload=jnp.zeros((ev.PAYLOAD,), jnp.float32),
                       parent_seq=e.seq)
        delta = env.delta(world, "gen", g, gen_left=new_left)
        return delta, counters, out

    # -- JOB_SUBMIT — compute farm -------------------------------------------
    # payload (JOB_SUBMIT.payload): [work, mem, notify_lp, notify_kind, size]
    @reg.on(K_JOB_SUBMIT)
    def h_job_submit(env, world, counters, e: Ev):
        f = world.lp_res[e.dst]
        busy = world.cpu_busy[f]       # (MAXCPU,) row gathers
        memr = world.cpu_mem[f]
        jq = world.jobq[f]
        qn0 = world.jobq_n[f]
        power_row = world.cpu_power[f]
        work, mem = e.payload[0], e.payload[1]
        counters = mon.bump(counters, mon.C_JOBS_SUBMITTED)

        free = (busy == 0) & (power_row > 0)
        has_free = jnp.any(free)
        slot = jnp.argmax(free).astype(jnp.int32)

        # start immediately on a free CPU
        power = power_row[slot]
        dur = jnp.ceil(work / jnp.maximum(power, 1e-6)).astype(jnp.int32)
        finish = e.time + env.delay(dur)
        busy = busy.at[slot].add(jnp.where(has_free, 1, 0))
        memr = memr.at[slot].add(jnp.where(has_free, mem, 0.0))

        # or queue (FIFO) when all CPUs are busy
        qcap = jq.shape[0]
        can_q = (~has_free) & (qn0 < qcap)
        qrow = jnp.stack([e.payload[0], e.payload[1], e.payload[2], e.payload[3],
                          e.payload[4], 0.0])
        qi = jnp.where(can_q, qn0, 0)
        jq = jq.at[qi].set(jnp.where(can_q, qrow, jq[qi]))
        new_qn = qn0 + jnp.where(can_q, 1, 0)
        counters = mon.bump(counters, mon.C_DROP_QUEUE,
                            jnp.where((~has_free) & (qn0 >= qcap), 1, 0))

        out = no_emits()
        out = set_emit(out, 0, valid=has_free, time=finish, kind=JOB_END.id,
                       src=e.dst, dst=e.dst, ctx=e.ctx,
                       payload=pad_payload([slot, work, mem, e.payload[2],
                                            e.payload[3], e.payload[4]]),
                       parent_seq=e.seq)
        delta = env.delta(world, "farm", f,
                          cpu_busy=busy, cpu_mem=memr, jobq=jq, jobq_n=new_qn)
        return delta, counters, out

    # -- JOB_END — compute farm ----------------------------------------------
    # payload (JOB_END.payload): [slot, work, mem, notify_lp, notify_kind, size]
    @reg.on(K_JOB_END)
    def h_job_end(env, world, counters, e: Ev):
        f = world.lp_res[e.dst]
        slot = e.payload[0].astype(jnp.int32)
        counters = mon.bump(counters, mon.C_JOBS_DONE)

        # free the slot, then pop the FIFO head onto it (shared queue
        # discipline: start_queued_job emits the popped job's JOB_END)
        busy_v, mem_v, new_jq, new_qn, out = start_queued_job(
            env, world, f, slot, e, no_emits(), 0)
        busy = world.cpu_busy[f].at[slot].set(busy_v)
        memr = world.cpu_mem[f].at[slot].set(mem_v)
        # slot 1: notification (e.g. DATA_WRITE to storage after an analysis job)
        nlp = e.payload[3].astype(jnp.int32)
        nkind = e.payload[4].astype(jnp.int32)
        out = set_emit(out, 1, valid=nlp >= 0, time=e.time + env.delay(1),
                       kind=nkind, src=e.dst, dst=jnp.maximum(nlp, 0), ctx=e.ctx,
                       payload=pad_payload([e.payload[5]]),
                       parent_seq=e.seq)
        delta = env.delta(world, "farm", f,
                          cpu_busy=busy, cpu_mem=memr, jobq=new_jq,
                          jobq_n=new_qn)
        return delta, counters, out

    # -- network helpers ------------------------------------------------------
    def _reshare_and_schedule(env, counters, e: Ev, links_row, bw_row,
                              active_row, rem_row, tlast_row, gen0):
        """Recompute fair shares for one region row, schedule the next completion."""
        inc = net.incidence(links_row, bw_row.shape[0])
        rates = net.maxmin_rates(inc, bw_row, active_row)
        counters = mon.bump(counters, mon.C_INTERRUPTS)
        gen = gen0 + 1
        t_fin = net.completion_times(rem_row, rates, tlast_row, active_row)
        tmin = jnp.min(t_fin)
        any_active = jnp.any(active_row)
        t_next = jnp.maximum(tmin, e.time + env.delay(0))
        return rates, gen, counters, any_active, t_next

    # -- FLOW_START — network region ------------------------------------------
    # payload (FLOW_START.payload):
    #   [size, l0, l1, l2, notify_lp, notify_kind, notify2_lp, notify2_kind]
    @reg.on(K_FLOW_START)
    def h_flow_start(env, world, counters, e: Ev):
        r = world.lp_res[e.dst]
        active = world.flow_active[r]  # (MAXFLOW,) row gathers
        rate = world.flow_rate[r]
        links = world.flow_links[r]
        notif = world.flow_notify[r]
        size = e.payload[0]
        counters = mon.bump(counters, mon.C_FLOWS_STARTED)

        # progress flows to now (the paper's interrupt scheme: shares change now)
        rem, tlast = net.progress_flows(world.flow_rem[r], rate,
                                        world.flow_tlast[r], active, e.time)

        free = ~active
        has_free = jnp.any(free)
        s = jnp.argmax(free).astype(jnp.int32)
        counters = mon.bump(counters, mon.C_DROP_FLOW, jnp.where(has_free, 0, 1))

        route = e.payload[1:4].astype(jnp.int32)  # -1 padded
        nrow = jnp.stack([e.payload[4], e.payload[5],
                          size * env.work_per_mb, size,
                          e.payload[6], e.payload[7]])
        active = active.at[s].set(jnp.where(has_free, True, active[s]))
        rem = rem.at[s].set(jnp.where(has_free, size, rem[s]))
        tlast = tlast.at[s].set(jnp.where(has_free, e.time, tlast[s]))
        links = links.at[s].set(jnp.where(has_free, route, links[s]))
        notif = notif.at[s].set(jnp.where(has_free, nrow, notif[s]))

        rates, gen, counters, any_active, t_next = _reshare_and_schedule(
            env, counters, e, links, world.link_bw[r], active, rem, tlast,
            world.net_gen[r])
        out = no_emits()
        out = set_emit(out, 2, valid=any_active, time=t_next, kind=FLOW_END.id,
                       src=e.dst, dst=e.dst, ctx=e.ctx,
                       payload=pad_payload([gen]), parent_seq=e.seq)
        delta = env.delta(world, "net", r,
                          flow_active=active, flow_rem=rem, flow_rate=rates,
                          flow_tlast=tlast, flow_links=links,
                          flow_notify=notif, net_gen=gen)
        return delta, counters, out

    # -- FLOW_END — network region ---------------------------------------------
    # payload (FLOW_END.payload): [gen]
    @reg.on(K_FLOW_END)
    def h_flow_end(env, world, counters, e: Ev):
        r = world.lp_res[e.dst]
        gen_ok = e.payload[0].astype(jnp.int32) == world.net_gen[r]
        counters = mon.bump(counters, mon.C_STALE, jnp.where(gen_ok, 0, 1))

        def stale(counters):
            return env.empty_delta(world), counters, no_emits()

        def live(counters):
            active = world.flow_active[r]
            rem, tlast = net.progress_flows(world.flow_rem[r], world.flow_rate[r],
                                            world.flow_tlast[r], active, e.time)
            done = active & (rem <= 1e-3)
            # complete up to 2 flows this event; a follow-up FLOW_END drains the rest
            order = jnp.argsort(jnp.where(done, jnp.arange(done.shape[0]), 1 << 20))
            d0, d1 = order[0], order[1]
            c0 = done[d0]
            c1 = done[d1]
            active2 = active.at[d0].set(jnp.where(c0, False, active[d0]))
            active2 = active2.at[d1].set(jnp.where(c1, False, active2[d1]))
            n_done = c0.astype(jnp.int32) + c1.astype(jnp.int32)
            counters2 = mon.bump(counters, mon.C_FLOWS_DONE, n_done)
            notif = world.flow_notify[r]
            mb = (jnp.where(c0, notif[d0, 3], 0.0)
                  + jnp.where(c1, notif[d1, 3], 0.0))
            counters2 = mon.bump(counters2, mon.C_MB_TRANSFERRED,
                                 jnp.round(mb).astype(jnp.int32))

            rates, gen, counters2, any_active, t_next = _reshare_and_schedule(
                env, counters2, e, world.flow_links[r], world.link_bw[r],
                active2, rem, tlast, world.net_gen[r])

            out = no_emits()
            for slot, (di, ci) in enumerate([(d0, c0), (d1, c1)]):
                note = notif[di]
                nlp = note[0].astype(jnp.int32)
                # notification payload: [work, mem(=size), notify2_lp, notify2_kind, size]
                out = set_emit(out, slot, valid=ci & (nlp >= 0),
                               time=e.time + env.delay(1),
                               kind=note[1].astype(jnp.int32), src=e.dst,
                               dst=jnp.maximum(nlp, 0), ctx=e.ctx,
                               payload=pad_payload([note[2], note[3], note[4],
                                                    note[5], note[3]]),
                               parent_seq=e.seq)
            out = set_emit(out, 2, valid=any_active, time=t_next,
                           kind=FLOW_END.id, src=e.dst, dst=e.dst, ctx=e.ctx,
                           payload=pad_payload([gen]), parent_seq=e.seq)
            delta = env.delta(world, "net", r,
                              flow_active=active2, flow_rem=rem,
                              flow_rate=rates, flow_tlast=tlast,
                              flow_links=world.flow_links[r],
                              flow_notify=notif, net_gen=gen)
            return delta, counters2, out

        return jax.lax.cond(gen_ok, live, stale, counters)

    # -- DATA_WRITE — storage --------------------------------------------------
    # payload (DATA_WRITE.payload): [size]
    @reg.on(K_DATA_WRITE)
    def h_data_write(env, world, counters, e: Ev):
        s = world.lp_res[e.dst]
        size = e.payload[0]
        counters = mon.bump(counters, mon.C_WRITES)
        counters = mon.bump(counters, mon.C_MB_WRITTEN,
                            jnp.round(size).astype(jnp.int32))
        used_row = world.sto_used[s]   # (2,) [disk, tape]
        used = used_row[0] + size
        used_row = used_row.at[0].set(used)

        flag0 = world.sto_flag[s]
        over = (used > 0.9 * world.sto_cap[s, 0]) & (flag0 == 0)
        amount = jnp.maximum(used - 0.7 * world.sto_cap[s, 0], 0.0)
        dur = jnp.ceil(amount / jnp.maximum(world.sto_rate[s], 1e-6)).astype(jnp.int32)
        new_flag = jnp.where(over, 1, flag0)
        out = no_emits()
        out = set_emit(out, 0, valid=over, time=e.time + env.delay(dur),
                       kind=MIGRATE.id, src=e.dst, dst=e.dst, ctx=e.ctx,
                       payload=pad_payload([amount]), parent_seq=e.seq)
        delta = env.delta(world, "sto", s, sto_used=used_row, sto_flag=new_flag)
        return delta, counters, out

    # -- MIGRATE — storage (db server -> mass storage, paper §4.2) -------------
    # payload (MIGRATE.payload): [amount]
    @reg.on(K_MIGRATE)
    def h_migrate(env, world, counters, e: Ev):
        s = world.lp_res[e.dst]
        used_row = world.sto_used[s]
        amount = jnp.minimum(e.payload[0], used_row[0])
        used_row = used_row.at[0].add(-amount).at[1].add(amount)
        counters = mon.bump(counters, mon.C_MIGRATIONS)
        delta = env.delta(world, "sto", s, sto_used=used_row,
                          sto_flag=jnp.int32(0))
        return delta, counters, no_emits()


register_builtin_handlers(BUILTIN)


def make_handlers(lookahead: int, work_per_mb: float = 1.0):
    """Build the builtin dispatch table (list indexed by event kind).

    Generated by the registry from the ``@on``-registered row kernels; kept as
    a module function for the historical call sites. The engine and the oracle
    derive the registry from the world they run, so extended models dispatch
    through ``registry.make_handlers`` automatically.
    """
    return BUILTIN.make_handlers(lookahead, work_per_mb)


def dispatch_delta(table, world, counters, e: Ev):
    """Dispatch one event to its kind's row kernel (lax.switch over kind).

    Returns ``(delta, counters, emits)`` without applying the delta — the
    building block shared by the sequential wrapper and the batched dispatcher.
    """
    kind = jnp.clip(e.kind, 0, len(table) - 1)
    return jax.lax.switch(kind, table, world, counters, e)


def apply_handler(table, world, counters, e: Ev):
    """Dispatch one event and apply its delta (the sequential contract).

    Byte-identical to the pre-delta in-place handlers: a row kernel computes its
    new row from the same gathered values the old whole-world handler read, and
    writing the full row stores unchanged elements back with their old bytes.
    Used by the sequential oracle, the engine's scan path, and the conflict
    fallback.
    """
    delta, counters, out = dispatch_delta(table, world, counters, e)
    return apply_delta(world, delta), counters, out


def _dispatch_lanes(table, world, rows: ev.EventBatch):
    """vmap the row kernels over a window's candidate rows (no apply)."""
    n_counters = registry_of(world).n_counters
    def lane(row):
        e = Ev(time=row.time, seq=row.seq, kind=row.kind, src=row.src,
               dst=row.dst, ctx=row.ctx, payload=row.payload)
        return dispatch_delta(table, world, mon.zero_counters(n_counters), e)
    return jax.vmap(lane)(rows)


def _mask_lanes(lanes_delta, active: jax.Array):
    """OOB the row declarations of inactive lanes so their scatters drop."""
    return lanes_delta._replace(**{
        rf: jnp.where(active, getattr(lanes_delta, rf), NO_ROW)
        for rf in registry_of(lanes_delta).row_fields})


def _count_rows(masked) -> jax.Array:
    """Component-table rows this window's batched phase will scatter."""
    counts = [jnp.sum((getattr(masked, rf) != NO_ROW).astype(jnp.int32))
              for rf in registry_of(masked).row_fields]
    return sum(counts[1:], counts[0])


def _finalize_batch(world, rows: ev.EventBatch, active: jax.Array,
                    lanes_counters, lanes_out: ev.EventBatch, n_rows):
    """Shared batched-dispatch tail: counters, per-LP columns, emit masking.

    Counters are write-only int adds, so summing the active lanes' deltas
    equals bumping them one by one in window order. The per-LP LVT/lifecycle
    columns commute even across duplicate-dst lanes (max is commutative; the
    RUNNING mark is an idempotent constant set), so two direct segment
    scatters are exact.
    """
    cdelta = jnp.sum(jnp.where(active[:, None], lanes_counters, 0), axis=0)
    cdelta = cdelta.at[mon.C_BATCH_ROWS].add(n_rows)
    dst = jnp.where(active, rows.dst, world.lp_lvt.shape[0])  # OOB -> drop
    world = world._replace(
        lp_lvt=world.lp_lvt.at[dst].max(rows.time, mode="drop"),
        lp_state=world.lp_state.at[dst].set(2, mode="drop"),  # RUNNING
    )
    out_valid = lanes_out.valid & active[:, None]
    return world, cdelta, lanes_out._replace(valid=out_valid)


def apply_handler_batch(table, world, rows: ev.EventBatch,
                        active: jax.Array):
    """Dispatch a window's candidate rows through one vectorized handler call
    and merge the results with per-row segment scatters (the delta path).

    Batch-safety contract: the caller guarantees ``active`` rows declare
    pairwise-distinct component rows (sync.conflict_mask keys on the exact
    ``(KIND_TABLE[kind], lp_res[dst])`` row of the delta contract), so every
    scattered row is written by at most one lane and ``apply_delta``'s
    ``.at[rows].set`` merge is exact — no arithmetic on state values, hence
    byte-identical to folding the same rows sequentially in any order. Cost is
    O(lanes x row) per mutable field, independent of component-table width or
    count — the point of the delta rewrite.

    Returns ``(world', counter_delta, emits)`` with emits shaped (B, MAX_EMIT)
    per field, lane-aligned with ``rows`` and masked by ``active``. The
    counter delta includes C_BATCH_ROWS (rows scattered this window).
    """
    lanes_delta, lanes_counters, lanes_out = _dispatch_lanes(table, world, rows)
    masked = _mask_lanes(lanes_delta, active)
    n_rows = _count_rows(masked)
    with mon.stage("merge"):
        world = apply_delta(world, masked)
    return _finalize_batch(world, rows, active, lanes_counters, lanes_out,
                           n_rows)


def apply_handler_batch_dense(table, world, rows: ev.EventBatch,
                              active: jax.Array):
    """PR 2 reference merge: per-lane whole tables + element-wise pick.

    Materializes each lane's delta into a full copy of every mutable table and
    merges element-wise ("take the one lane that changed it") — the
    O(lanes x pool-wide tables) strategy the delta path replaces. Kept as the
    ``spec.merge_mode="dense"`` engine option so equivalence tests can pin
    delta == dense == sequential and the wide-component benchmark can measure
    the delta win as a machine-normalized in-process ratio.
    """
    lanes_delta, lanes_counters, lanes_out = _dispatch_lanes(table, world, rows)
    masked = _mask_lanes(lanes_delta, active)
    n_rows = _count_rows(masked)
    n_lanes = rows.time.shape[0]
    mutable = registry_of(masked).mutable_fields

    def lane_tables(d):
        w2 = apply_delta(world, d)
        return {f: getattr(w2, f) for f in mutable}

    def merge(lane_field, base):
        m = active.reshape((n_lanes,) + (1,) * base.ndim)
        changed = m & (lane_field != base[None])
        pick = jnp.argmax(changed, axis=0)
        picked = jnp.take_along_axis(lane_field, pick[None], axis=0)[0]
        return jnp.where(jnp.any(changed, axis=0), picked, base)

    with mon.stage("merge"):
        lanes_mut = jax.vmap(lane_tables)(masked)
        world = world._replace(**{
            f: merge(lanes_mut[f], getattr(world, f)) for f in mutable})
    return _finalize_batch(world, rows, active, lanes_counters, lanes_out,
                           n_rows)
