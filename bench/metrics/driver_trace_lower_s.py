"""Seconds per point of JAX tracing and lowering of the driver programs: the
union of the ``jax.trace_lower`` spans whose parent is a program
``repro.engine.run`` span (``init_state``'s eager ops and the builder's jits
are not under it)."""
from bench.program import per_point


def read(record):
    return per_point(record, lambda p: p["driver_trace_lower_s"])
