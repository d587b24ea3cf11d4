"""Seconds per point of JAX tracing and lowering to MLIR: the union of the
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration`` spans."""


def read(record):
    pts = record["points"]
    return sum(p["trace_lower_s"] for p in pts) / len(pts)
