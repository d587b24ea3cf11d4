"""The plain reference: the model's semantics, one event at a time.

An implementation of the paper's basic components (compute farms with a FIFO
job queue, network regions with max-min bandwidth sharing under the interrupt
scheme, storage with disk-to-tape migration, activity generators) written
from their documented behaviour and nothing of the program: it imports no
module of ``repro`` and takes nothing the program built. Its initial state
comes from the configuration's own numbers (:func:`build`); events are popped
from a heap in exact global ``(time, seq)`` order and applied by one jitted
function per event kind, on whatever device JAX gives (the chip, in a run:
float division there is the chip's, as in the program).

The model's conventions, which both sides follow by specification:

* LPs are numbered in the order the configuration declares components; a
  regional centre declares its farm, then its storage.
* Event kinds and table kinds have the ids of :data:`KINDS` and
  :data:`TABLES`; payloads are 8 float32 values, laid out per kind.
* A child event of slot ``k`` of a parent with sequence number ``s`` gets
  ``(4 * s + k + 1) mod 2**31``; initial events are numbered from 0.
* Every emitted delay is at least the lookahead; an LP that processed an
  event is marked waiting (3) and its clock advances to the event's time.

``quantum`` is the control: with ``quantum=2`` every event time is floored to
a multiple of two ticks as the event enters the heap, which drops one bit of
the simulated clock's resolution. It breaks the exactness the configurations
state, and the comparison must see it.
"""
from __future__ import annotations

import heapq
import itertools

import jax
import jax.numpy as jnp
import numpy as np

KINDS = ("NOOP", "FLOW_START", "FLOW_END", "JOB_SUBMIT", "JOB_END",
         "DATA_WRITE", "MIGRATE", "GEN_TICK")
KIND = {name: i for i, name in enumerate(KINDS)}
TABLES = {"farm": 1, "net": 2, "sto": 3, "gen": 4}
PAYLOAD = 8
MAXHOP = 3
SLOTS = 4            # emit slots per event
SEQ_MOD = 2**31
LP_READY, LP_WAITING = 1, 3
T_INF = 2**31 - 1
EPS = 1e-6           # a rate at or under it moves nothing
BIG = 3.0e38         # "never", as a float
DONE_MB = 1e-3       # a flow with this little left is complete
# payload layouts that a configuration may give by name: (field, default)
LAYOUTS = {"FLOW_START": (("size", 0.0), ("l0", -1), ("l1", -1), ("l2", -1),
                          ("notify_lp", -1), ("notify_kind", 0),
                          ("notify2_lp", -1), ("notify2_kind", 0))}
COUNTERS = ("events", "stale", "interrupts", "jobs_submitted", "jobs_done",
            "flows_started", "flows_done", "mb_transferred", "drop_pool",
            "drop_route", "drop_flow", "drop_queue", "migrations", "writes",
            "mb_written")
_CI = {name: i for i, name in enumerate(COUNTERS)}


# ----------------------------------------------------------------- build
def resolve(value, params, lps):
    """A configuration value: ``$p`` a parameter, ``@c`` or ``@c.part`` the
    LP of a declared component, ``#K`` the id of event kind ``K``."""
    if isinstance(value, list):
        return [resolve(v, params, lps) for v in value]
    if isinstance(value, str) and value[:1] == "$":
        return params[value[1:]]
    if isinstance(value, str) and value[:1] == "@":
        name, _, part = value[1:].partition(".")
        return lps[name][part] if part else lps[name]
    if isinstance(value, str) and value[:1] == "#":
        return KIND[value[1:]]
    return value


def build(config: dict, params: dict):
    """``(world, events, consts)`` of the configuration at ``params``:
    the tables as numpy arrays named as the model names them, the initial
    events as ``(time, seq, kind, src, dst, ctx, payload)``, and the
    run's constants."""
    d = config["dims"]
    rows = {t: [] for t in TABLES}
    lps = []                              # (table, row)
    events = []
    names = {}

    def add(table, **fields):
        rows[table].append(fields)
        lps.append((table, len(rows[table]) - 1))
        return len(lps) - 1

    for comp in config["components"]:
        a = {k: resolve(v, params, names) for k, v in comp.items()
             if k not in ("name", "add")}
        if comp["add"] == "regional_center":
            farm = add("farm", cpu_power=[a["cpu_power"]] * a["n_cpu"])
            sto = add("sto", sto_cap=[a["disk"], a["tape"]],
                      sto_rate=a["tape_rate"])
            names[comp["name"]] = {"farm": farm, "storage": sto}
        elif comp["add"] == "net_region":
            names[comp["name"]] = add("net", link_bw=a["link_bws"],
                                      link_lat=a["link_lats"])
        elif comp["add"] == "generator":
            layout = LAYOUTS[KINDS[a["kind"]]]
            pay = a["payload"]
            payload = [resolve(pay.get(f, dflt), params, names)
                       for f, dflt in layout]
            lp = add("gen", gen_interval=a["interval"], gen_left=a["count"],
                     gen_target=a["target_lp"], gen_kind=a["kind"],
                     gen_payload=payload)
            names[comp["name"]] = lp
            events.append((a.get("start", 0), len(events), KIND["GEN_TICK"],
                           lp, lp, 0, np.zeros(PAYLOAD, np.float32)))
        else:
            raise KeyError(f"no component {comp['add']!r} in the reference")

    f32, i32 = np.float32, np.int32
    shapes = {  # field: (table, trailing shape, dtype, fill)
        "cpu_power": ("farm", (d["max_cpu"],), f32, 0),
        "cpu_busy": ("farm", (d["max_cpu"],), i32, 0),
        "cpu_mem": ("farm", (d["max_cpu"],), f32, 0),
        "jobq": ("farm", (d["queue_cap"], 6), f32, 0),
        "jobq_n": ("farm", (), i32, 0),
        "link_bw": ("net", (d["max_link"],), f32, 0),
        "link_lat": ("net", (d["max_link"],), i32, 0),
        "flow_active": ("net", (d["max_flow"],), np.bool_, False),
        "flow_rem": ("net", (d["max_flow"],), f32, 0),
        "flow_rate": ("net", (d["max_flow"],), f32, 0),
        "flow_tlast": ("net", (d["max_flow"],), i32, 0),
        "flow_links": ("net", (d["max_flow"], MAXHOP), i32, -1),
        "flow_notify": ("net", (d["max_flow"], 6), f32, 0),
        "net_gen": ("net", (), i32, 0),
        "sto_cap": ("sto", (2,), f32, 0),
        "sto_used": ("sto", (2,), f32, 0),
        "sto_rate": ("sto", (), f32, 0),
        "sto_flag": ("sto", (), i32, 0),
        "gen_interval": ("gen", (), i32, 1),
        "gen_left": ("gen", (), i32, 0),
        "gen_target": ("gen", (), i32, 0),
        "gen_kind": ("gen", (), i32, 0),
        "gen_payload": ("gen", (PAYLOAD,), f32, 0),
    }
    n_agents = int(config["build"]["n_agents"])
    world = {
        "lp_kind": np.array([TABLES[t] for t, _ in lps], i32),
        "lp_agent": np.arange(len(lps), dtype=i32) % n_agents,
        "lp_res": np.array([r for _, r in lps], i32),
        "lp_state": np.full(len(lps), LP_READY, i32),
        "lp_lvt": np.zeros(len(lps), i32),
        "lp_ctx": np.zeros(len(lps), i32),
    }
    for field, (table, shape, dtype, fill) in shapes.items():
        arr = np.full((max(len(rows[table]), 1),) + shape, fill, dtype)
        for i, row in enumerate(rows[table]):
            if field in row:
                v = np.asarray(row[field], dtype)
                if v.ndim:
                    arr[i, :v.shape[0]] = v   # a short row fills a prefix
                else:
                    arr[i] = v
        world[field] = arr
    b = config["build"]
    consts = dict(lookahead=int(b["lookahead"]), t_end=int(b["t_end"]),
                  work_per_mb=float(b["work_per_mb"]))
    return world, events, consts


# ------------------------------------------------------------- semantics
class _Out:
    """The emits and counter bumps of one event."""

    def __init__(self, dst, ctx):
        self.dst, self.ctx = dst, ctx
        self.slots = [None] * SLOTS
        self.count = dict.fromkeys(COUNTERS, jnp.int32(0))

    def bump(self, name, n=1):
        self.count[name] = self.count[name] + jnp.asarray(n, jnp.int32)

    def emit(self, slot, valid, time, kind, dst, payload):
        pay = jnp.zeros(PAYLOAD, jnp.float32)
        for i, v in enumerate(payload):
            pay = pay.at[i].set(jnp.asarray(v, jnp.float32))
        self.slots[slot] = (valid, time, kind, self.dst, dst, pay)

    def take(self, other, when):
        """Add ``other``'s bumps and emits where ``when`` holds."""
        for c in COUNTERS:
            self.count[c] = self.count[c] + jnp.where(when, other.count[c], 0)
        for i, s in enumerate(other.slots):
            if s is not None:
                self.slots[i] = (s[0] & when,) + s[1:]

    def arrays(self):
        ints, pays = [], []
        for s in self.slots:
            if s is None:
                s = (False, 0, 0, 0, 0, jnp.zeros(PAYLOAD, jnp.float32))
            ints.append(jnp.stack([jnp.asarray(x, jnp.int32)
                                   for x in (*s[:5], self.ctx)]))
            pays.append(s[5])
        return (jnp.stack([self.count[c] for c in COUNTERS]),
                jnp.stack(ints), jnp.stack(pays))


def _progress(rem, rate, tlast, active, now):
    """Every active flow of a region moved on to ``now`` at its rate."""
    elapsed = jnp.maximum(now - tlast, 0).astype(jnp.float32)
    moved = jnp.maximum(rem - rate * elapsed, 0.0)
    return (jnp.where(active, moved, rem), jnp.where(active, now, tlast))


def _maxmin(links, bw, active):
    """Max-min fair rates by progressive filling: each round, the links
    whose residual capacity per unfrozen flow is lowest fix that share on
    every unfrozen flow crossing them."""
    n_links = bw.shape[0]
    uses = jnp.stack([jnp.any(links == l, axis=1) for l in range(n_links)],
                     axis=1) & active[:, None]                 # (F, L)
    rate = jnp.zeros(active.shape, jnp.float32)
    frozen = ~active
    for _ in range(n_links):
        open_ = active & ~frozen
        n_open = jnp.sum(uses & open_[:, None], axis=0).astype(jnp.float32)
        taken = jnp.sum(jnp.where(uses & frozen[:, None], rate[:, None], 0.0),
                        axis=0)
        share = jnp.maximum(bw - taken, 0.0) / jnp.maximum(n_open, 1.0)
        share = jnp.where(n_open > 0, share, BIG)
        share = jnp.where((bw <= 0) & (n_open > 0), 0.0, share)
        level = jnp.min(share)
        tight = share <= level + EPS
        fix = open_ & jnp.any(uses & tight[None, :], axis=1)
        rate = jnp.where(fix, level, rate)
        frozen = frozen | fix
    return jnp.where(active, rate, 0.0)


def _next_completion(rem, rate, tlast, active, now, la):
    """The tick of the region's next flow completion, at least ``la`` on."""
    ticks = jnp.where(rate > EPS, jnp.ceil(rem / jnp.maximum(rate, EPS)), BIG)
    done_at = tlast.astype(jnp.float32) + jnp.maximum(ticks, 1.0)
    done_at = jnp.where(active, done_at, BIG)
    first = jnp.min(jnp.minimum(done_at, jnp.float32(T_INF)).astype(jnp.int32))
    return jnp.maximum(first, now + la)


def _ceil_div(a, b):
    return jnp.ceil(a / jnp.maximum(b, EPS)).astype(jnp.int32)


def _handlers(la: int, work_per_mb: float):
    """One function per event kind: ``(world, t, dst, p, out) -> world``,
    bumping counters and emitting into ``out``."""
    la = jnp.int32(la)

    def after(t, d):
        return t + jnp.maximum(jnp.asarray(d, jnp.int32), la)

    def noop(w, t, dst, p, out):
        return w

    def gen_tick(w, t, dst, p, out):
        g = w["lp_res"][dst]
        left = w["gen_left"][g]
        fire = left > 0
        w["gen_left"] = w["gen_left"].at[g].set(jnp.where(fire, left - 1,
                                                          left))
        out.emit(0, fire, after(t, 1), w["gen_kind"][g], w["gen_target"][g],
                 w["gen_payload"][g])
        out.emit(1, fire & (left > 1), after(t, w["gen_interval"][g]),
                 KIND["GEN_TICK"], dst, ())
        return w

    def job_submit(w, t, dst, p, out):
        f = w["lp_res"][dst]
        out.bump("jobs_submitted")
        busy, power = w["cpu_busy"][f], w["cpu_power"][f]
        free = (busy == 0) & (power > 0)
        start = jnp.any(free)
        slot = jnp.argmax(free).astype(jnp.int32)
        w["cpu_busy"] = w["cpu_busy"].at[f, slot].set(
            jnp.where(start, 1, busy[slot]))
        w["cpu_mem"] = w["cpu_mem"].at[f, slot].set(
            jnp.where(start, w["cpu_mem"][f, slot] + p[1],
                      w["cpu_mem"][f, slot]))
        out.emit(0, start, after(t, _ceil_div(p[0], power[slot])),
                 KIND["JOB_END"], dst, (slot, p[0], p[1], p[2], p[3], p[4]))
        n = w["jobq_n"][f]
        cap = w["jobq"].shape[1]
        queue = ~start & (n < cap)
        at = jnp.minimum(n, cap - 1)
        job = jnp.stack([p[0], p[1], p[2], p[3], p[4], jnp.float32(0)])
        w["jobq"] = w["jobq"].at[f, at].set(
            jnp.where(queue, job, w["jobq"][f, at]))
        w["jobq_n"] = w["jobq_n"].at[f].set(n + queue.astype(jnp.int32))
        out.bump("drop_queue", ~start & (n >= cap))
        return w

    def job_end(w, t, dst, p, out):
        f = w["lp_res"][dst]
        out.bump("jobs_done")
        slot = p[0].astype(jnp.int32)
        q, n = w["jobq"][f], w["jobq_n"][f]
        nxt = n > 0
        head = q[0]
        rest = jnp.concatenate([q[1:], jnp.zeros((1, 6), jnp.float32)])
        w["jobq"] = w["jobq"].at[f].set(jnp.where(nxt, rest, q))
        w["jobq_n"] = w["jobq_n"].at[f].set(jnp.where(nxt, n - 1, n))
        w["cpu_busy"] = w["cpu_busy"].at[f, slot].set(nxt.astype(jnp.int32))
        w["cpu_mem"] = w["cpu_mem"].at[f, slot].set(
            jnp.where(nxt, head[1], 0.0))
        out.emit(0, nxt, after(t, _ceil_div(head[0],
                                            w["cpu_power"][f, slot])),
                 KIND["JOB_END"], dst, (slot, *head[:5]))
        to = p[3].astype(jnp.int32)
        out.emit(1, to >= 0, after(t, 1), p[4].astype(jnp.int32),
                 jnp.maximum(to, 0), (p[5],))
        return w

    def reshare(w, r, t, rem, tlast, active, out):
        rate = _maxmin(w["flow_links"][r], w["link_bw"][r], active)
        out.bump("interrupts")
        gen = w["net_gen"][r] + 1
        w["flow_rem"] = w["flow_rem"].at[r].set(rem)
        w["flow_tlast"] = w["flow_tlast"].at[r].set(tlast)
        w["flow_active"] = w["flow_active"].at[r].set(active)
        w["flow_rate"] = w["flow_rate"].at[r].set(rate)
        w["net_gen"] = w["net_gen"].at[r].set(gen)
        out.emit(2, jnp.any(active),
                 _next_completion(rem, rate, tlast, active, t, la),
                 KIND["FLOW_END"], out.dst, (gen,))
        return w

    def flow_start(w, t, dst, p, out):
        r = w["lp_res"][dst]
        out.bump("flows_started")
        active = w["flow_active"][r]
        rem, tlast = _progress(w["flow_rem"][r], w["flow_rate"][r],
                               w["flow_tlast"][r], active, t)
        room = jnp.any(~active)
        s = jnp.argmax(~active).astype(jnp.int32)
        out.bump("drop_flow", ~room)
        size = p[0]
        active = active.at[s].set(active[s] | room)
        rem = rem.at[s].set(jnp.where(room, size, rem[s]))
        tlast = tlast.at[s].set(jnp.where(room, t, tlast[s]))
        route = p[1:1 + MAXHOP].astype(jnp.int32)
        w["flow_links"] = w["flow_links"].at[r, s].set(
            jnp.where(room, route, w["flow_links"][r, s]))
        note = jnp.stack([p[4], p[5], size * work_per_mb, size, p[6], p[7]])
        w["flow_notify"] = w["flow_notify"].at[r, s].set(
            jnp.where(room, note, w["flow_notify"][r, s]))
        return reshare(w, r, t, rem, tlast, active, out)

    def flow_end(w, t, dst, p, out):
        r = w["lp_res"][dst]
        # a FLOW_END scheduled before the region's shares last changed is
        # stale and does nothing
        current = p[0].astype(jnp.int32) == w["net_gen"][r]
        out.bump("stale", ~current)
        live = _Out(dst, out.ctx)
        active = w["flow_active"][r]
        rem, tlast = _progress(w["flow_rem"][r], w["flow_rate"][r],
                               w["flow_tlast"][r], active, t)
        done = active & (rem <= DONE_MB)
        # the two lowest-numbered finished flows complete now; the next
        # FLOW_END takes the rest
        first = jnp.argmax(done)
        c0 = done[first]
        second = jnp.argmax(done.at[first].set(False))
        c1 = done.at[first].set(False)[second]
        active = active.at[first].set(active[first] & ~c0)
        active = active.at[second].set(active[second] & ~c1)
        live.bump("flows_done", c0.astype(jnp.int32) + c1.astype(jnp.int32))
        notes = w["flow_notify"][r]
        mb = (jnp.where(c0, notes[first, 3], 0.0)
              + jnp.where(c1, notes[second, 3], 0.0))
        live.bump("mb_transferred", jnp.round(mb).astype(jnp.int32))
        for slot, (i, c) in enumerate(((first, c0), (second, c1))):
            nlp, kind, work, size, n2lp, n2kind = (notes[i, k]
                                                   for k in range(6))
            to = nlp.astype(jnp.int32)
            live.emit(slot, c & (to >= 0), after(t, 1),
                      kind.astype(jnp.int32), jnp.maximum(to, 0),
                      (work, size, n2lp, n2kind, size))
        moved = reshare(dict(w), r, t, rem, tlast, active, live)
        out.take(live, current)
        return {k: jnp.where(current, moved[k], w[k]) for k in w}

    def data_write(w, t, dst, p, out):
        s = w["lp_res"][dst]
        size = p[0]
        out.bump("writes")
        out.bump("mb_written", jnp.round(size).astype(jnp.int32))
        disk = w["sto_used"][s, 0] + size
        w["sto_used"] = w["sto_used"].at[s, 0].set(disk)
        cap = w["sto_cap"][s, 0]
        flag = w["sto_flag"][s]
        # past 90% of the disk, migrate down to 70% at the tape's rate
        migrate = (disk > 0.9 * cap) & (flag == 0)
        amount = jnp.maximum(disk - 0.7 * cap, 0.0)
        w["sto_flag"] = w["sto_flag"].at[s].set(jnp.where(migrate, 1, flag))
        out.emit(0, migrate, after(t, _ceil_div(amount, w["sto_rate"][s])),
                 KIND["MIGRATE"], dst, (amount,))
        return w

    def migrate(w, t, dst, p, out):
        s = w["lp_res"][dst]
        out.bump("migrations")
        used = w["sto_used"][s]
        moved = jnp.minimum(p[0], used[0])
        w["sto_used"] = w["sto_used"].at[s].set(
            jnp.stack([used[0] - moved, used[1] + moved]))
        w["sto_flag"] = w["sto_flag"].at[s].set(0)
        return w

    return dict(NOOP=noop, GEN_TICK=gen_tick, JOB_SUBMIT=job_submit,
                JOB_END=job_end, FLOW_START=flow_start, FLOW_END=flow_end,
                DATA_WRITE=data_write, MIGRATE=migrate)


_STEPS: dict = {}


def _steps(la: int, work_per_mb: float) -> list:
    """The jitted one-event step per kind id, built once per process."""
    key = (la, work_per_mb)
    if key not in _STEPS:
        table = _handlers(la, work_per_mb)

        def make(fn):
            def step(w, t, dst, ctx, p):
                out = _Out(dst, ctx)
                w = fn(dict(w), t, dst, p, out)
                w["lp_lvt"] = w["lp_lvt"].at[dst].max(t)
                w["lp_state"] = w["lp_state"].at[dst].set(LP_WAITING)
                return (w,) + out.arrays()
            return jax.jit(step, donate_argnums=0)

        _STEPS[key] = [make(table[k]) for k in KINDS]
    return _STEPS[key]


def run(config: dict, params: dict, *, quantum: int = 1,
        max_events: int = 10_000_000):
    """The configuration at ``params``, run sequentially to ``t_end``.
    Returns ``(world, counters, trace)``: the tables as numpy arrays, the
    counters by name, and the processed ``(time, seq, kind, dst)`` rows in
    processing order."""
    world, init, c = build(config, params)
    steps = _steps(c["lookahead"], c["work_per_mb"])
    heap: list = []
    rows: dict = {}
    uids = itertools.count()

    def push(time, seq, kind, src, dst, ctx, payload):
        time = int(time)
        if quantum > 1:
            time -= time % quantum
        uid = next(uids)
        rows[uid] = (int(kind), int(dst), int(ctx),
                     np.asarray(payload, np.float32))
        heapq.heappush(heap, (time, int(seq), uid))

    trace: list = []
    for e in init:
        push(*e)
    w = jax.device_put(world)
    counts = np.zeros(len(COUNTERS), np.int64)
    while heap and len(trace) < max_events:
        t, seq, uid = heapq.heappop(heap)
        if t >= c["t_end"]:
            break  # the simulation horizon
        kind, dst, ctx, payload = rows.pop(uid)
        w, cnt, ints, pays = steps[kind](w, np.int32(t), np.int32(dst),
                                         np.int32(ctx), payload)
        trace.append((t, seq, kind, dst))
        cnt, ints, pays = jax.device_get((cnt, ints, pays))
        counts += cnt
        for k in np.flatnonzero(ints[:, 0]):
            child = (seq * SLOTS + int(k) + 1) % SEQ_MOD
            push(ints[k, 1], child, ints[k, 2], ints[k, 3], ints[k, 4],
                 ints[k, 5], pays[k])
    if heap and len(trace) >= max_events:
        raise RuntimeError(f"reference stopped at max_events={max_events}")
    counts[_CI["events"]] = len(trace)
    return (jax.device_get(w),
            {name: int(counts[i]) for i, name in enumerate(COUNTERS)}, trace)
