"""Device microseconds per conservative window under the superstep's
``dispatch``, ``merge``, ``fallback`` and ``trace`` scopes (conflict mask,
grouping, the vmapped handler call, the delta merge, the conflict fallback,
the trace append and emit compaction)."""
from bench.program import per_window

STAGES = ("dispatch", "merge", "fallback", "trace")


def read(record):
    return per_window(record, STAGES)
