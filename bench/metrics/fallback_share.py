"""Share of events run through the conflict fallback:
C_BATCH_FALLBACK / C_EVENTS of the window's points, in percent."""


def read(record):
    events = sum(p["events"] for p in record["points"])
    if not events:
        return None
    return 100.0 * sum(p["fallback"] for p in record["points"]) / events
