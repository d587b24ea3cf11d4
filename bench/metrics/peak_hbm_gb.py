"""Device memory peak after the window, in GB: ``peak_bytes_in_use`` plus
``peak_bytes_reserved`` (the reserve for compiled programs' temporaries)."""


def read(record):
    peak = record.get("peak_bytes")
    return None if peak is None else peak / 1e9
