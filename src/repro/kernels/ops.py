"""jit'd public wrappers for the Pallas kernels.

Dispatch policy: on a TPU backend the kernels are compiled by Mosaic; on any
other backend (the CPU tests) ``interpret=True`` runs the same kernel bodies
for correctness validation against ref.py. Interpret mode says nothing about
the chip: ``tests/test_tpu_compile.py`` compiles the engine's kernels for a
described TPU v5e, and ``chip_smoke.py`` refuses to run off a TPU. The model
zoo calls these through cfg.use_flash / engine select_fn hooks, so the XLA
fallbacks and the kernels are interchangeable implementations of identical
math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bandwidth_share as _bw
from repro.kernels import event_select as _es
from repro.kernels import flash_attention as _fa
from repro.kernels import rwkv6_scan as _gla
from repro.kernels import ssm_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128):
    """q: (BH, Sq, D); k, v: (BKV, Skv, D). GQA via BH % BKV grouping."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(q, k, v, w, u, *, chunk=64):
    """RWKV6 chunked recurrence. (BH, S, d) operands, u: (BH, d)."""
    return _gla.gla_pallas(q, k, v, w, u, mode="k", chunk=chunk,
                           interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(q, k, v, w, *, chunk=64):
    """Mamba2-style SSD chunked recurrence (decay on V channels)."""
    return _ssd.ssd_pallas(q, k, v, w, chunk=chunk, interpret=_interpret())


@jax.jit
def sort_events(time_key, seq):
    """(CAP,) -> permutation ascending by (time, seq). Engine sort hook."""
    return _es.sort_events(time_key, seq, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("exec_cap",))
def select_events(time_key, seq, exec_cap):
    """(CAP,) -> (exec_cap,) compacted gather indices. Engine select_fn hook."""
    return _es.select_events(time_key, seq, exec_cap, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n_kinds",))
def group_by_kind(kind, active, n_kinds):
    """(CAP,) kinds + active mask -> (order, rank, counts). Engine group_fn
    hook for batched same-kind dispatch (segment-rank Pallas kernel).

    ``n_kinds`` is the model's kind count — registry-dependent since PR 4, so
    it must come from the scenario: bind it with
    ``functools.partial(ops.group_by_kind, n_kinds=engine.registry.n_kinds)``
    when wiring the hook.
    """
    return _es.group_by_kind(kind, active, n_kinds, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("exec_cap", "n_kinds", "n_res",
                                             "n_tables"))
def fused_select(time_key, seq, safe, time, kind, src, dst, ctx, payload,
                 valid, table_id, res, free_tail, exec_cap, *, n_kinds,
                 n_res, n_tables=None):
    """The superstep megakernel: the whole window front-end in one call.

    Fuses select + gather + conflict mask + group_by_kind + release ranks
    (kernels.event_select.fused_select). Engine fused_fn hook —
    ``spec.fused_select=True`` binds it as

        functools.partial(ops.fused_select, n_kinds=registry.n_kinds,
                          n_res=registry.max_rows(world),
                          n_tables=registry.n_tables)

    The stitched twins (engine.fused_select_xla, kernels.ref.fused_select_ref)
    are the byte-compatibility references the tests sweep against.
    """
    return _es.fused_select(time_key, seq, safe, time, kind, src, dst, ctx,
                            payload, valid, table_id, res, free_tail,
                            exec_cap, n_kinds=n_kinds, n_res=n_res,
                            n_tables=n_tables, interpret=_interpret())


@jax.jit
def ring_slots(free_ring, head, want):
    """(cap,) free ring + head + (n,) insert mask -> (n,) destination slots.

    The free-ring variant of the event-pool insert (Pallas prefix-sum +
    chunked one-hot ring gather). Hook it into the pool with
    ``events.insert(pool, batch, slot_fn=ops.ring_slots)``; the default XLA
    path inside ``events.insert`` is the reference (kernels.ref.ring_slots_ref
    — tests sweep kernel vs reference).
    """
    return _es.ring_slots(free_ring, head, want, interpret=_interpret())


@jax.jit
def trace_rank(mask):
    """(n,) processed mask -> (n,) exclusive prefix ranks.

    The trace-ring append's position math (streaming-trace drain, PR 5 ring
    idiom): masked window lane r writes trace slot ``(trace_n + rank[r]) %
    trace_cap``. Hook it into the engine with ``Engine(...,
    trace_fn=ops.trace_rank)``; the default XLA cumsum inside
    ``events.trace_append`` is the reference (kernels.ref.trace_rank_ref —
    tests sweep kernel vs reference).
    """
    return _es.trace_rank(mask, interpret=_interpret())


@jax.jit
def route_rank(dst_agent):
    """(n,) destination buckets -> (n,) stable within-bucket ranks.

    The emit-routing pack for the engine's all_to_all exchange (and the
    migration re-home): flat scatter slot = ``dst * route_cap + rank``. Hook
    it into the engine with ``Engine(..., route_fn=ops.route_rank)``; the
    default XLA path (engine.route_rank_xla == kernels.ref.route_rank_ref)
    is the reference the tests sweep against.
    """
    return _es.route_rank(dst_agent, interpret=_interpret())


@jax.jit
def maxmin_rates(inc, bw, active):
    """(F, L), (L,), (F,) -> (F,) max-min fair rates."""
    return _bw.maxmin_rates_pallas(inc, bw, active, interpret=_interpret())
