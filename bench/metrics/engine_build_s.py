"""Host seconds per point in ``Engine.__init__`` (registry lookup, handler
table, hook binding): the self time of the program's ``repro.engine.build``
span. Only a run with a SpanLog attached has it."""
from bench.program import per_point


def read(record):
    return per_point(record, lambda p: p["self_s"].get("engine.build", 0.0))
