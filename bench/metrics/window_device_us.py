"""Device busy microseconds of the traced window per conservative window
run."""


def read(record):
    tr = record.get("trace")
    windows = sum(p["windows"] for p in record["points"])
    if tr is None or not windows:
        return None
    return tr["busy_s"] * 1e6 / windows
