"""Programs the engine traced per point: the program's ``engine.traces``
host counter over every program (booked once per trace of a jitted driver
function, never at run time)."""
from bench.program import per_point


def read(record):
    return per_point(record, lambda p: p["traces"])
