"""API drift gate: the registry must stay the single source of the engine tables.

Usage: PYTHONPATH=src python tools/check_api.py   (exit 1 on drift)

Rebuilds the builtin model on a *fresh* registry (``register_builtin_model`` +
``register_builtin_handlers`` — the same declarations core itself runs) and
fails when anything ``repro.core`` exports diverges from the regenerated
schema: ``DELTA_SCHEMA``, ``KIND_TABLE``, the ``World``/``WorldDelta``/
``WorldOwnership`` field layouts, the owner-wins sync field lists, the kind
ids, or handler coverage. Catches hand-edits that bypass the declarative API
(the pre-PR 4 failure mode: six files to keep in sync by eye). Also checks
that ``repro.core.__all__`` — the supported public surface — resolves.

Wired into the CI lint and docs jobs; mirrored by ``tests/test_registry.py``.
"""

from __future__ import annotations

import sys


def _counter_class():
    """``counter_class`` of the sibling ``gen_counter_docs.py``, the one
    definition of a counter index's class."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).with_name("gen_counter_docs.py")
    spec = importlib.util.spec_from_file_location("gen_counter_docs", path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m.counter_class


def check() -> list[str]:
    import repro.core as core
    from repro.core import __all__ as public
    from repro.core import components, events, handlers
    from repro.core.registry import Registry

    fresh = Registry()
    components.register_builtin_model(fresh)
    handlers.register_builtin_handlers(fresh)

    errors: list[str] = []

    def expect(name: str, got, want):
        if got != want:
            errors.append(
                f"{name} drifted:\n  exported: {got}\n  regenerated: {want}"
            )

    expect("events.KIND_TABLE", tuple(events.KIND_TABLE), fresh.kind_table)
    expect("events.N_KINDS", events.N_KINDS, fresh.n_kinds)
    expect("events.N_TABLES", events.N_TABLES, fresh.n_tables)
    expect("handlers.DELTA_SCHEMA", handlers.DELTA_SCHEMA, fresh.delta_schema)
    expect("handlers.ROW_FIELDS", tuple(handlers.ROW_FIELDS), fresh.row_fields)
    expect("World fields", components.World._fields, fresh.world_struct()._fields)
    expect(
        "WorldDelta fields",
        handlers.WorldDelta._fields,
        fresh.delta_struct()._fields,
    )
    expect(
        "WorldOwnership fields",
        components.WorldOwnership._fields,
        fresh.ownership_struct()._fields,
    )
    expect(
        "sync field lists (owner-wins plan)",
        components.BUILTIN.sync_plan(),
        fresh.sync_plan(),
    )
    # counter indices: the registry's builtin counter table must be exactly
    # the monitoring C_* constants (Registry.__init__ seeds from
    # monitoring.BUILTIN_COUNTERS; a drifted index would silently misattribute
    # every stat an extension declares on top)
    from repro.core import monitoring as mon

    expect(
        "builtin counter table",
        {name: idx for name, idx in fresh.counters.items()},
        {name: getattr(mon, f"C_{name}") for name, _doc in mon.BUILTIN_COUNTERS},
    )
    expect("n_counters (builtin)", fresh.n_counters, mon.N_COUNTERS)

    kind_ids = {k.name: k.id for k in components.BUILTIN.kinds}
    expect("kind ids", {k.name: k.id for k in fresh.kinds}, kind_ids)
    for name, kid in kind_ids.items():
        exported = getattr(events, f"K_{name}")
        if exported != kid:
            errors.append(f"events.K_{name} == {exported}, registry says {kid}")

    # handler coverage: every kind dispatches (raises RegistryError if not)
    try:
        fresh.make_handlers(lookahead=1)
    except Exception as e:  # noqa: BLE001
        errors.append(f"regenerated dispatch table failed: {e}")

    # the declared public surface must resolve
    missing = [n for n in public if not hasattr(core, n)]
    if missing:
        errors.append(f"repro.core.__all__ names missing attributes: {missing}")

    # checkpoint surface: the saved-leaf layout is derived from the
    # registry-generated structs, so every World/EngineState field must
    # appear under its struct-field name (the pre-PR 8 checkpointer used a
    # str(path) fallback that produced '.world'-style keys and silently
    # drifted from the PR 4 registry structs)
    import repro.checkpoint as ckpkg
    from repro.checkpoint import tree_keys
    from repro.core.engine import EngineState

    missing = [n for n in ckpkg.__all__ if not hasattr(ckpkg, n)]
    if missing:
        errors.append(f"repro.checkpoint.__all__ names missing attributes: {missing}")
    scalar_fields = (
        "counters",
        "t_now",
        "done",
        "windows",
        "trace",
        "trace_n",
        "trace_tail",
    )
    want_keys = sorted(
        [f"world/{f}" for f in fresh.world_struct()._fields]
        + [f"pool/{f}" for f in events.EventPool._fields]
        + list(scalar_fields)
    )
    template = EngineState(
        world=fresh.world_struct()(*[0] * len(fresh.world_struct()._fields)),
        pool=events.EventPool(*[0] * len(events.EventPool._fields)),
        **{f: 0 for f in scalar_fields},
    )
    expect("checkpoint leaf keys", sorted(tree_keys(template)), want_keys)

    # fleet surface: the orchestrator's public names must resolve, the fleet
    # counters must be registry-declared with the host-side-only class (an
    # in-graph "counter" class here would mean someone started bumping them
    # inside the window program, breaking resume byte-identity)
    import repro.fleet as fleet

    missing = [n for n in fleet.__all__ if not hasattr(fleet, n)]
    if missing:
        errors.append(f"repro.fleet.__all__ names missing attributes: {missing}")
    counter_class = _counter_class()
    for idx in mon.FLEET_COUNTERS:
        if counter_class(idx) != "fleet":
            errors.append(
                f"counter {idx} in FLEET_COUNTERS but counter_class says "
                f"{counter_class(idx)!r} (must be 'fleet': booked "
                "host-side only)"
            )

    # catalog surface: every entry must build-resolve cleanly and ensemble
    # entries must declare the replicas/seed0 sizing convention
    from repro.scenarios import catalog

    if not catalog.names():
        errors.append("scenario catalog is empty")
    for name in catalog.names():
        sd = catalog.get(name)
        if not callable(sd.build):
            errors.append(f"catalog entry {name!r}: build is not callable")
        if not sd.doc:
            errors.append(f"catalog entry {name!r}: missing doc")
        if sd.driver == "ensemble" and "seed0" not in sd.defaults():
            errors.append(
                f"catalog ensemble entry {name!r}: missing 'seed0' parameter"
            )
    return errors


def main() -> int:
    errors = check()
    for e in errors:
        print(f"FAIL: {e}")
    if errors:
        print(
            f"{len(errors)} API drift error(s); regenerate exports from "
            "the registry (see docs/scenario_api.md)"
        )
        return 1
    print("OK: registry and core exports agree (no schema drift)")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, "src")
    sys.exit(main())
