"""Event-selection Pallas kernels: the DES engine's window front end.

The conservative window's hot loop starts by ordering the event pool by
(timestamp, tie-break seq) with unsafe slots pushed to the back (their key is
T_INF). These kernels run a bitonic sorting network entirely in VMEM over the
(time, seq, index) triple — log^2(N) vectorized compare-exchange stages, no HBM
traffic beyond one read and one write of the pool keys.

Layout: every vector is padded to a power of two of at least one lane row and
held as an ``(N / 128, 128)`` int32 tile, flat position ``row * 128 + lane``.
The compare-exchange partner of flat slot ``p`` is ``p ^ j``: ``j`` lanes away
for ``j < 128`` and ``j / 128`` rows away otherwise, so each stage is two
``pltpu.roll`` rotations along one axis plus selects — no reshapes, no
unaligned slices, which is what Mosaic compiles. Prefix sums are log-step
shift-adds built from the same rotations (lanes first, then row offsets).

``sort_events`` outputs the full permutation (i32 indices), matching
engine.lexsort_time_seq exactly (stable for equal (time, seq) pairs because the
index participates as the final tie-break, and input indices are distinct).
``select_events`` is the compacted variant for the engine's windowed execution:
sort + safe-prefix in one pass — only the first ``exec_cap`` indices leave VMEM,
so the engine can gather exactly the slots it will execute.

Invariants the engine's batched dispatch relies on (docs/architecture.md):

* **Stable (time, seq) prefix** — the ``select_events`` output is byte-identical
  to ``lexsort_time_seq(...)[:exec_cap]``; the engine's trace is written in this
  window order, so any kernel deviation breaks oracle trace equality, not just
  performance.
* **Segment-rank ordering** — ``group_by_kind`` returns active rows first,
  grouped by ascending kind, *stable in original window position within each
  kind*; ``rank`` is each row's index inside its kind segment and ``counts`` the
  per-kind populations. The dispatcher scatters handler emits back through this
  permutation, so stability is what keeps the flattened emit matrix equal to the
  sequential fold's append order. Both kernels must stay interchangeable with
  their XLA references (engine.group_by_kind_xla / select_events_xla) — the
  tests sweep kernel vs reference over random inputs.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32_MAX = jnp.int32(2**31 - 1)
LANES = 128


def _pow2_tile(n: int) -> int:
    """Flat width of a sort tile: the power of two >= n, at least one row."""
    return max(1 << max((n - 1).bit_length(), 0), LANES)


def _row_tile(n: int) -> int:
    """Flat width of a scan tile: n rounded up to whole lane rows."""
    return max(-(-n // LANES), 1) * LANES


def _to_tile(x: jax.Array, n: int, fill) -> jax.Array:
    """(k,) -> (n // 128, 128) int32, padded with ``fill`` beyond k."""
    return jnp.full((n,), fill, jnp.int32).at[: x.shape[0]].set(
        x.astype(jnp.int32)).reshape(n // LANES, LANES)


def _flat_pos(shape) -> jax.Array:
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _roll(x, shift: int, axis: int):
    shift %= x.shape[axis]
    return x if shift == 0 else pltpu.roll(x, shift, axis)


def _lex_less(a, b):
    """Elementwise lexicographic a < b over parallel key lists."""
    less = a[-1] < b[-1]
    for x, y in zip(reversed(a[:-1]), reversed(b[:-1])):
        less = (x < y) | ((x == y) & less)
    return less


def _bitonic(keys, carry=()):
    """Sort (R, 128) tiles ascending by the lexicographic ``keys`` (the last
    key must be distinct per slot); the ``carry`` tiles ride along."""
    keys, carry = list(keys), list(carry)
    shape = keys[0].shape
    n = shape[0] * shape[1]
    pos = _flat_pos(shape)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            axis, d = (1, j) if j < shape[1] else (0, j // shape[1])
            size = shape[axis]
            # the partner p ^ j sits d slots ahead or behind along ``axis``; the
            # rotated position grid says which rotation delivers it
            ahead = _roll(pos, size - d, axis) == (pos ^ j)

            def partner(x, ahead=ahead, axis=axis, d=d, size=size):
                return jnp.where(ahead, _roll(x, size - d, axis),
                                 _roll(x, d, axis))

            pk = [partner(x) for x in keys]
            keep_min = ((pos & j) == 0) == ((pos & k) == 0)
            take = _lex_less(pk, keys) == keep_min
            keys = [jnp.where(take, p, x) for p, x in zip(pk, keys)]
            carry = [jnp.where(take, partner(x), x) for x in carry]
            j //= 2
        k *= 2
    return keys, carry


def _behind(x, s: int, axis: int):
    """y[c] = x[c - s] along ``axis``, zero for c < s."""
    size = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    from_a = _roll(idx, s, axis) == idx - s
    from_b = _roll(idx, size - s, axis) == idx - s
    return jnp.where(from_a, _roll(x, s, axis),
                     jnp.where(from_b, _roll(x, size - s, axis), 0))


def _exclusive_cumsum(w):
    """Row-major exclusive prefix sum of an int32 (R, 128) tile: log-step
    shift-adds along the lanes, then the same over the row totals."""
    rows, lanes = w.shape
    x = w
    s = 1
    while s < lanes:
        x = x + _behind(x, s, 1)
        s *= 2
    tot = jnp.broadcast_to(jnp.sum(w, axis=1, keepdims=True), w.shape)
    off = tot
    s = 1
    while s < rows:
        off = off + _behind(off, s, 0)
        s *= 2
    return x - w + off - tot


def _sort_kernel(time_ref, seq_ref, perm_ref):
    t = time_ref[...]
    (_, _, idx), _ = _bitonic([t, seq_ref[...], _flat_pos(t.shape)])
    # the out block may be a prefix of the sorted permutation (select_events)
    perm_ref[...] = idx[: perm_ref.shape[0]]


def _run_sort(time_key: jax.Array, seq: jax.Array, m: int, *, interpret):
    """Shared pallas_call: sort padded keys, emit the first ``m`` indices."""
    n = _pow2_tile(time_key.shape[0])
    perm = pl.pallas_call(
        _sort_kernel,
        out_shape=jax.ShapeDtypeStruct((_pow2_tile(m) // LANES, LANES),
                                       jnp.int32),
        interpret=interpret,
    )(_to_tile(time_key, n, I32_MAX), _to_tile(seq, n, I32_MAX))
    return perm.reshape(-1)[:m]


def sort_events(time_key: jax.Array, seq: jax.Array, *, interpret=False):
    """(CAP,) i32 keys -> (CAP,) i32 permutation, ascending (time, seq)."""
    return _run_sort(time_key, seq, time_key.shape[0], interpret=interpret)


def select_events(time_key: jax.Array, seq: jax.Array, exec_cap: int, *,
                  interpret=False):
    """Compacted gather indices: first ``exec_cap`` of the (time, seq) sort.

    With unsafe slots keyed T_INF, the returned indices are the ``exec_cap``
    earliest safe pool slots (then, if fewer are safe, unsafe filler the engine
    masks out). One kernel pass; only the prefix is written back.
    """
    return _run_sort(time_key, seq, min(exec_cap, time_key.shape[0]),
                     interpret=interpret)


def _total(x):
    """Sum of a whole tile as a (1, 1) array."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _group_kernel(kind_ref, act_ref, order_ref, rank_ref, counts_ref, *,
                  n_kinds: int):
    """Segment-rank grouping: bitonic sort by (kind, index) + in-VMEM ranks.

    Active rows get key = kind, inactive rows key = n_kinds (grouping them
    after every real kind), zero-padding beyond the caller's cap sorts last
    (its index exceeds every real row's). After the sort the grouped index
    vector IS the permutation; segment ranks fall out of a static loop over
    the n_kinds+1 possible keys (position minus the segment's exclusive
    prefix count), so no dynamic gather is needed on the VPU.
    """
    pos = _flat_pos(kind_ref.shape)
    key = jnp.where(act_ref[...] != 0,
                    jnp.clip(kind_ref[...], 0, n_kinds - 1), n_kinds)
    (key, idx), _ = _bitonic([key, pos])
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    rank = pos
    total = jnp.zeros((1, 1), jnp.int32)
    counts = jnp.zeros((1, LANES), jnp.int32)
    for g in range(n_kinds + 1):
        in_g = key == g
        cnt = _total(in_g.astype(jnp.int32))
        rank = rank - jnp.where(in_g, total, 0)
        if g < n_kinds:
            counts = jnp.where(lane == g, cnt, counts)
        total = total + cnt
    order_ref[...] = idx
    rank_ref[...] = rank
    counts_ref[...] = counts


def _ring_slots_kernel(ring_ref, want_ref, head_ref, out_ref, *, cap: int):
    """Free-ring slot assignment: prefix-sum the insert mask, gather the ring.

    The insert path of the free-ring event pool (``events.insert``): the r-th
    masked batch row takes the slot at ring position ``(head + r) % cap``.
    The insert rank is the log-step exclusive prefix sum; the ring gather is
    a blocked one-hot selection — 128 ring slots down the sublanes against
    128 batch rows across the lanes, masked and summed over the sublanes — so
    no dynamic VMEM gather is needed on the VPU.
    """
    pos = (head_ref[...] + _exclusive_cumsum(want_ref[...])) % jnp.int32(cap)
    ring = ring_ref[...]
    ids = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0)
    for rb in range(out_ref.shape[0]):
        prow = pos[rb:rb + 1]
        acc = jnp.zeros((1, LANES), jnp.int32)
        for rr in range(ring.shape[0]):
            col = ring[rr:rr + 1].reshape(LANES, 1)
            hit = (ids + jnp.int32(rr * LANES)) == prow
            acc = acc + jnp.sum(jnp.where(hit, col, 0), axis=0, keepdims=True)
        out_ref[rb:rb + 1] = acc


def ring_slots(free_ring: jax.Array, head: jax.Array, want: jax.Array, *,
               interpret=False):
    """(cap,) free ring + head cursor + (n,) insert mask -> (n,) slot ids.

    The free-ring variant of the event-pool insert: destination pool slots
    for a window's emit batch, matching ``kernels.ref.ring_slots_ref`` (and
    hence the XLA path inside ``events.insert``) exactly on masked rows —
    unmasked rows carry the garbage the engine drops. One VMEM pass of
    O(n log n + cap * n / lanes) vector work; no pool-wide rank scan.
    """
    cap = free_ring.shape[0]
    nb = want.shape[0]
    nw = _row_tile(nb)
    out = pl.pallas_call(
        functools.partial(_ring_slots_kernel, cap=cap),
        out_shape=jax.ShapeDtypeStruct((nw // LANES, LANES), jnp.int32),
        interpret=interpret,
    )(_to_tile(free_ring, _row_tile(cap), 0), _to_tile(want, nw, 0),
      jnp.broadcast_to(jnp.asarray(head, jnp.int32), (1, LANES)))
    return out.reshape(-1)[:nb]


def _trace_rank_kernel(want_ref, out_ref):
    """Exclusive prefix rank of the processed mask: the r-th masked window
    lane writes absolute trace position ``trace_n + r``. Same log-step
    prefix sum as the ring-slot kernel, without the ring gather — the write
    itself is a plain XLA scatter on the (cap, 4) trace buffer."""
    out_ref[...] = _exclusive_cumsum(want_ref[...])


def trace_rank(mask: jax.Array, *, interpret=False):
    """(n,) processed mask -> (n,) exclusive prefix ranks (int32).

    The trace-ring append's position math (``events.trace_append`` rank_fn
    hook): masked row r's trace slot is ``(trace_n + rank[r]) % trace_cap``.
    Matches ``kernels.ref.trace_rank_ref`` on every row (unmasked rows carry
    the running count like the XLA cumsum — the append masks them out).
    """
    nb = mask.shape[0]
    nw = _row_tile(nb)
    out = pl.pallas_call(
        _trace_rank_kernel,
        out_shape=jax.ShapeDtypeStruct((nw // LANES, LANES), jnp.int32),
        interpret=interpret,
    )(_to_tile(mask, nw, 0))
    return out.reshape(-1)[:nb]


def _route_rank_kernel(dst_ref, rank_ref, *, n: int, chunk: int):
    """Within-bucket routing ranks: chunked predecessor-count, all in VMEM.

    rank[i] counts earlier rows with the same destination bucket — exactly
    the stable bucket rank of the emit-routing pack. The count is a chunked
    (n, chunk) equality compare + masked sum over the row axis (the same
    one-hot trick as the ring-slot gather), so no sort and no dynamic
    gather is needed on the VPU.
    """
    dst = dst_ref[0]                       # (n,)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)[0]
    acc = jnp.zeros((n,), jnp.int32)
    jd0 = jax.lax.broadcasted_iota(jnp.int32, (n, chunk), 1)
    for c in range(0, n, chunk):
        jdx = jd0 + jnp.int32(c)
        seg = dst_ref[0, c:c + chunk]      # (chunk,) static slice
        eq = (dst[:, None] == seg[None, :]) & (jdx < pos[:, None])
        acc = acc + jnp.sum(eq.astype(jnp.int32), axis=1)
    rank_ref[0] = acc


def route_rank(dst_agent: jax.Array, *, interpret=False):
    """(n,) destination buckets -> (n,) stable within-bucket ranks.

    The emit-routing pack of the engine's all_to_all exchange (step 5 and the
    migration re-home): row i's slot in the (n_agents, route_cap) scatter
    buffer is ``dst_agent[i] * route_cap + rank[i]``. Matches
    ``kernels.ref.route_rank_ref`` exactly on every row (invalid rows carry a
    sentinel bucket and rank like any other bucket — the engine masks them).
    """
    nb = dst_agent.shape[0]
    n = 1 << max((nb - 1).bit_length(), 1)
    chunk = min(n, 512)
    # pad rows with per-row distinct sentinels so they never contaminate a
    # real bucket's count (ranks beyond nb are discarded anyway)
    pad_ids = -jnp.arange(1, n - nb + 1, dtype=jnp.int32)
    dpad = jnp.concatenate(
        [dst_agent.astype(jnp.int32), pad_ids])[None] if n > nb else (
        dst_agent.astype(jnp.int32)[None])
    kernel = functools.partial(_route_rank_kernel, n=n, chunk=chunk)
    rank = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((1, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(dpad)
    return rank[0, :nb]


def group_by_kind(kind: jax.Array, active: jax.Array, n_kinds: int, *,
                  interpret=False):
    """Same-kind grouping for the engine's batched dispatch (step 4).

    Returns ``(order, rank, counts)`` matching ref.group_by_kind_ref: active
    rows first, grouped by ascending kind and stable in original position;
    ``rank`` gives each grouped row's index within its kind segment; ``counts``
    is the (n_kinds,) active population per kind.
    """
    if n_kinds > LANES:
        raise ValueError(f"group_by_kind counts at most {LANES} kinds, "
                         f"got n_kinds={n_kinds}")
    cap = kind.shape[0]
    n = _pow2_tile(cap)
    tile = jax.ShapeDtypeStruct((n // LANES, LANES), jnp.int32)
    order, rank, counts = pl.pallas_call(
        functools.partial(_group_kernel, n_kinds=n_kinds),
        out_shape=[tile, tile,
                   jax.ShapeDtypeStruct((1, LANES), jnp.int32)],
        interpret=interpret,
    )(_to_tile(kind, n, 0), _to_tile(active, n, 0))
    return (order.reshape(-1)[:cap], rank.reshape(-1)[:cap],
            counts[0, :n_kinds])


class FusedSelect(NamedTuple):
    """Everything the engine's window front-end needs, from ONE kernel pass.

    All fields are window-aligned: length ``m = min(exec_cap, pool_cap)``
    (``payload`` is ``(m, PAYLOAD)``). ``exec_idx``/``exec_safe`` replace the
    select_fn + ``exec_selection_ring`` pair; the event fields replace the
    ``ev.gather`` slot gather; ``clean``/``order`` replace the conflict mask +
    group_by_kind pair inside the batched dispatch; ``rel_pos`` is the
    free-ring release position each executed slot reclaims into
    (``events.release(..., pos=rel_pos)``)."""

    exec_idx: jax.Array   # (m,) i32 pool slots in (time, seq) window order
    exec_safe: jax.Array  # (m,) bool — selected slot is safe this window
    time: jax.Array       # (m,) i32 gathered event fields ...
    seq: jax.Array
    kind: jax.Array
    src: jax.Array
    dst: jax.Array
    ctx: jax.Array
    payload: jax.Array    # (m, PAYLOAD) f32
    valid: jax.Array      # (m,) bool
    clean: jax.Array      # (m,) bool — safe and conflict-free
    order: jax.Array      # (m,) i32 same-kind grouping permutation
    rel_pos: jax.Array    # (m,) i32 free-ring release position (safe rows)


def _fused_select_kernel(tkey_ref, seq_ref, safe_ref, time_ref, kind_ref,
                         src_ref, dst_ref, ctx_ref, valid_ref, tbl_ref,
                         res_ref, pay_ref, tail_ref,
                         idx_out, safe_out, time_out, seq_out, kind_out,
                         src_out, dst_out, ctx_out, valid_out, pay_out,
                         clean_out, order_out, rel_out, *,
                         m: int, cap: int, n_kinds: int, n_res: int,
                         n_pay: int):
    """The superstep megakernel: select + gather + conflict + group + release.

    One VMEM-resident pass fuses the four front-end stages XLA otherwise
    stitches through HBM:

    1. **Sort-select**: the (time_key, seq, index) bitonic network of
       ``_sort_kernel`` — but every event field (time, kind, src, dst, ctx,
       valid, the conflict key columns, and all PAYLOAD payload lanes) rides
       through the compare-exchange as sort payload, so the window's slot
       *gather* falls out of the sort for free: after the network, lane i of
       every carried tile IS pool slot ``exec_idx[i]``'s field. No dynamic
       VMEM gather, no HBM round-trip for the index array.
    2. **Conflict mask**: duplicate detection on the declared component rows
       (``rkey = table_id * n_res + res``) via a blocked pairwise count —
       ``cnt[j] = sum_i comp[i] & (rkey[i] == rkey[j])`` — matching
       ``sync.conflict_mask`` semantics exactly (rows with table_id == 0
       never conflict).
    3. **Group-by-kind**: the segment bitonic of ``_group_kernel`` over the
       window lanes, keyed (clean ? kind : n_kinds, position).
    4. **Release ranks**: the log-step exclusive prefix sum of the safe
       mask; with the ``free_tail`` ring cursor broadcast across one lane
       row, each executed slot's reclaim position ``(free_tail + rank) %
       cap`` leaves the kernel ready for the O(1) ``events.release`` scatter.
    """
    t = tkey_ref[...]
    carry = [safe_ref[...], time_ref[...], kind_ref[...], src_ref[...],
             dst_ref[...], ctx_ref[...], valid_ref[...], tbl_ref[...],
             res_ref[...]] + [pay_ref[p] for p in range(n_pay)]
    (_, s, idx), carry = _bitonic([t, seq_ref[...], _flat_pos(t.shape)],
                                  carry)

    # window prefix: the first ``wrows`` rows hold the m window lanes (lanes
    # at or beyond m are padding and masked everywhere below)
    wrows = idx_out.shape[0]
    carry = [x[:wrows] for x in carry]
    pos = _flat_pos(idx_out.shape)
    es = (carry[0] != 0) & (pos < m)
    kind_w = carry[2]

    # step 2: conflict mask on the declared (component table, resource row):
    # 128 lanes i down the sublanes against 128 lanes j across, per block
    tb, rs = carry[7], carry[8]
    rkey = tb * jnp.int32(n_res) + rs
    comp = (es & (tb > 0)).astype(jnp.int32)
    row = jax.lax.broadcasted_iota(jnp.int32, idx_out.shape, 0)
    cnt = jnp.zeros(idx_out.shape, jnp.int32)
    for rj in range(wrows):
        krow = rkey[rj:rj + 1]
        c = jnp.zeros((1, LANES), jnp.int32)
        for ri in range(wrows):
            kcol = rkey[ri:ri + 1].reshape(LANES, 1)
            ccol = comp[ri:ri + 1].reshape(LANES, 1)
            c = c + jnp.sum(jnp.where(kcol == krow, ccol, 0), axis=0,
                            keepdims=True)
        cnt = jnp.where(row == rj, c, cnt)
    clean = es & ~((comp != 0) & (cnt >= 2))

    # step 3: same-kind grouping of the clean lanes (stable in window order)
    gkey = jnp.where(clean, jnp.clip(kind_w, 0, n_kinds - 1), n_kinds)
    (_, gidx), _ = _bitonic([gkey, pos])

    # step 4: release ranks off the free_tail ring cursor
    rel = (tail_ref[...] + _exclusive_cumsum(es.astype(jnp.int32))) \
        % jnp.int32(cap)

    idx_out[...] = idx[:wrows]
    safe_out[...] = es.astype(jnp.int32)
    time_out[...] = carry[1]
    seq_out[...] = s[:wrows]
    kind_out[...] = kind_w
    src_out[...] = carry[3]
    dst_out[...] = carry[4]
    ctx_out[...] = carry[5]
    valid_out[...] = carry[6]
    for p in range(n_pay):
        pay_out[p] = carry[9 + p]
    clean_out[...] = clean.astype(jnp.int32)
    order_out[...] = gidx
    rel_out[...] = rel


def fused_select(time_key: jax.Array, seq: jax.Array, safe: jax.Array,
                 time: jax.Array, kind: jax.Array, src: jax.Array,
                 dst: jax.Array, ctx: jax.Array, payload: jax.Array,
                 valid: jax.Array, table_id: jax.Array, res: jax.Array,
                 free_tail: jax.Array, exec_cap: int, *, n_kinds: int,
                 n_res: int, n_tables: int | None = None,
                 interpret=False) -> FusedSelect:
    """The fused window front-end over a (pool_cap,) event pool.

    Byte-compatible with the stitched composition
    (``engine.fused_select_xla`` / ``ref.fused_select_ref``): select the
    ``exec_cap`` earliest safe slots, gather their fields, mask write
    conflicts, group by kind, and rank the free-ring release — one
    ``pallas_call``, intermediates never leaving VMEM. ``table_id``/``res``
    are the pool-wide conflict key columns (the engine precomputes the two
    registry gathers, the kernel has no table access); ``free_tail`` is the
    pool's ring cursor. Lanes where ``exec_safe`` is False carry the sorted
    slot's raw fields, exactly like the XLA gather — the engine masks them
    everywhere.
    """
    del n_tables  # bounds the stitched twins' key space; the pairwise count
    #               needs no sentinel span
    cap = time_key.shape[0]
    m = max(min(exec_cap, cap), 1)
    n = _pow2_tile(cap)
    wshape = (_pow2_tile(m) // LANES, LANES)
    n_pay = payload.shape[1]

    def pad(xv, fill):
        return _to_tile(xv, n, fill)

    args = [pad(time_key, I32_MAX), pad(seq, I32_MAX), pad(safe, 0),
            pad(time, 0), pad(kind, 0), pad(src, 0), pad(dst, 0),
            pad(ctx, 0), pad(valid, 0), pad(table_id, 0), pad(res, 0)]
    payp = jnp.zeros((n_pay, n), payload.dtype).at[:, :cap].set(
        payload.T).reshape(n_pay, n // LANES, LANES)
    tailp = jnp.broadcast_to(jnp.asarray(free_tail, jnp.int32), (1, LANES))

    kernel = functools.partial(_fused_select_kernel, m=m, cap=cap,
                               n_kinds=n_kinds, n_res=n_res, n_pay=n_pay)
    tile = jax.ShapeDtypeStruct(wshape, jnp.int32)
    outs = pl.pallas_call(
        kernel,
        out_shape=[tile] * 9
        + [jax.ShapeDtypeStruct((n_pay,) + wshape, payload.dtype)]
        + [tile] * 3,
        interpret=interpret,
    )(*args, payp, tailp)
    (idxo, safeo, timeo, seqo, kindo, srco, dsto, ctxo, valido, payo,
     cleano, ordero, relo) = outs

    def flat(x):
        return x.reshape(-1)[:m]

    return FusedSelect(
        exec_idx=flat(idxo),
        exec_safe=flat(safeo) != 0,
        time=flat(timeo),
        seq=flat(seqo),
        kind=flat(kindo),
        src=flat(srco),
        dst=flat(dsto),
        ctx=flat(ctxo),
        payload=payo.reshape(n_pay, -1)[:, :m].T,
        valid=flat(valido) != 0,
        clean=flat(cleano) != 0,
        order=flat(ordero),
        rel_pos=flat(relo),
    )
