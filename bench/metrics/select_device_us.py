"""Device microseconds per conservative window under the superstep's
``gvt`` and ``select`` scopes (GVT, safe mask, time key, selection, gather),
op self time from the traced window."""
from bench.program import per_window

STAGES = ("gvt", "select")


def read(record):
    return per_window(record, STAGES)
