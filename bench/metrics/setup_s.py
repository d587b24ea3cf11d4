"""Process start to window start: imports, cache placement, warm-up point."""


def read(record):
    return record["setup_s"]
