"""Simulated events (C_EVENTS over agents) of all completed points over the
window's wall time."""


def read(record):
    return sum(p["events"] for p in record["points"]) / record["window_s"]
