"""Host seconds per point in ``Engine.init_state`` (the per-agent seed
insert and stacking, eager ops and their compiles): the self time of the
program's ``repro.engine.init_state`` span, JAX's compile events under it
left out."""
from bench.program import per_point


def read(record):
    return per_point(record,
                     lambda p: p["self_s"].get("engine.init_state", 0.0))
