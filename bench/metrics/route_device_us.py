"""Device microseconds per conservative window under the superstep's
``release``, ``route``, ``insert``, ``sync``, ``gauges`` and ``drain``
scopes (slot release, the routing exchange, pool insert, world sync, the
pool gauges, the streaming drain)."""
from bench.program import per_window

STAGES = ("release", "route", "insert", "sync", "gauges", "drain")


def read(record):
    return per_window(record, STAGES)
