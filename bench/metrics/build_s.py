"""Host seconds per point declaring and building the scenario through
``ScenarioBuilder`` (the benchmark's own ``build`` span)."""


def read(record):
    pts = record["points"]
    return sum(p["build_s"] for p in pts) / len(pts)
