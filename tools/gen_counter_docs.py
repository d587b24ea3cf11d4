"""Generate the monitoring-counter tables in docs/architecture.md.

The counter declarations are the single source of truth: names, docstrings,
and indices come from the registry (``monitoring.BUILTIN_COUNTERS`` seeds
every ``Registry``; extensions add rows with ``Registry.counter(name, doc)``),
and the class column from the class tuples of ``monitoring`` (``GAUGE_COUNTERS``,
``DROP_COUNTERS``, ...). This script
rewrites everything between the ``counter-table:begin`` / ``counter-table:end``
markers in docs/architecture.md from a fresh registry — the same drift
discipline ``check_api.py`` applies to the schema exports, but for the
documented counter surface: edit the declaration, regenerate the docs.

Usage:
  python tools/gen_counter_docs.py --write   rewrite docs/architecture.md
  python tools/gen_counter_docs.py --check   exit 1 if the docs drifted (CI)
"""

from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

BEGIN = "<!-- counter-table:begin -->"
END = "<!-- counter-table:end -->"

# The class column, in the order the legend below the table explains it.
_CLASS_LABEL = {
    "counter": "",
    "drop": "drop",
    "gauge": "**gauge**",
    "pool-diag": "pool-diag",
    "batch-diag": "batch-diag",
    "fleet": "fleet",
}

_DRIFT_MSG = (
    "FAIL: docs/architecture.md counter tables drifted from the registry "
    "declarations.\nRun: python tools/gen_counter_docs.py --write"
)


def counter_class(idx: int) -> str:
    """The class of a counter index: how a fleet snapshot reads it
    (``gauge`` = per-window level, everything else accumulates) and which
    equivalence contracts exempt it (``pool-diag`` / ``batch-diag``)."""
    from repro.core import monitoring as mon

    for cls, members in (
        ("gauge", mon.GAUGE_COUNTERS),
        ("drop", mon.DROP_COUNTERS),
        ("pool-diag", mon.POOL_DIAG_COUNTERS),
        ("batch-diag", mon.BATCH_DIAG_COUNTERS),
        ("fleet", mon.FLEET_COUNTERS),
    ):
        if idx in members:
            return cls
    return "counter"


def counter_rows(registry) -> list[tuple[str, str, str]]:
    """(display name, class label, doc) rows in declared index order.

    Builtin rows display as their ``monitoring.C_<name>`` constant (the
    symbol engine code bumps); extension rows have no constant in core, so
    they display as the registry name ``Registry.counter`` declared."""
    from repro.core import monitoring as mon

    docs = registry.counter_docs
    rows = []
    for name, idx in sorted(registry.counters.items(), key=lambda kv: kv[1]):
        shown = f"C_{name}" if idx < mon.N_COUNTERS else name
        rows.append((shown, _CLASS_LABEL[counter_class(idx)], docs.get(name, "")))
    return rows


def render_table(rows) -> str:
    out = ["| Counter | Class | Meaning |", "|---|---|---|"]
    for name, cls, doc in rows:
        out.append(f"| `{name}` | {cls} | {doc} |")
    return "\n".join(out)


def render_block() -> str:
    """Everything between the markers: builtin + extension counter tables."""
    from repro.core.components import BUILTIN
    from repro.scenarios.cache import CACHE_REGISTRY
    from repro.scenarios.failures import FAIL_REGISTRY

    n_builtin = len(BUILTIN.counters)
    parts = [
        "_Generated by `tools/gen_counter_docs.py` from the registry counter",
        "declarations — edit the declarations, then `python",
        "tools/gen_counter_docs.py --write` (CI's docs job runs `--check`)._",
        "",
        render_table(counter_rows(BUILTIN)),
    ]
    for title, reg in (
        ("`scenarios/cache.py` extension counters", CACHE_REGISTRY),
        ("`scenarios/failures.py` extension counters", FAIL_REGISTRY),
    ):
        ext = counter_rows(reg)[n_builtin:]
        if not ext:
            continue
        parts += ["", f"### {title}", "", render_table(ext)]
    return "\n".join(parts)


def apply(text: str) -> str:
    try:
        head, rest = text.split(BEGIN, 1)
        _stale, tail = rest.split(END, 1)
    except ValueError:
        msg = f"docs/architecture.md is missing the {BEGIN} / {END} markers"
        raise SystemExit(msg)
    return head + BEGIN + "\n" + render_block() + "\n" + END + tail


def main() -> int:
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument(
        "--write",
        action="store_true",
        help="rewrite the generated block in docs/architecture.md",
    )
    g.add_argument(
        "--check",
        action="store_true",
        help="exit 1 if the generated block is out of date (CI)",
    )
    args = ap.parse_args()

    path = ROOT / "docs" / "architecture.md"
    old = path.read_text(encoding="utf-8")
    new = apply(old)
    if args.write:
        if new != old:
            path.write_text(new, encoding="utf-8")
            print(f"gen_counter_docs: rewrote {path}")
        else:
            print("gen_counter_docs: up to date")
        return 0
    if new != old:
        print(_DRIFT_MSG)
        return 1
    print("OK: docs/architecture.md counter tables match the registry declarations")
    return 0


if __name__ == "__main__":
    sys.exit(main())
