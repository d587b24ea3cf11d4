"""Seconds per point in ``backend_compile_duration`` spans: a compile on a
cache miss, the persistent cache's retrieval and load on a hit (JAX times
both inside that one span)."""


def read(record):
    pts = record["points"]
    return sum(p["compile_load_s"] for p in pts) / len(pts)
