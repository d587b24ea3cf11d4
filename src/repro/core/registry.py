"""Declarative component & handler registry — the scenario-authoring API.

The paper's pitch (§4.2) is a framework that "models very complex distributed
systems while hiding the computational effort from the end-user" through an
extensible component library. This module is that seam for the JAX engine:
instead of hand-editing six core files to add a component type, a model author
*declares* components, event kinds, and handlers, and the registry **generates**
every table the engine consumes:

  ``Registry.component(name, fields={...: FieldSpec(...)})``
      -> a structure-of-arrays table inside the generated ``World`` NamedTuple,
         a ``<name>_row`` column in the generated ``WorldDelta``, a
         ``<name>_lp`` inverse map in the generated ``WorldOwnership``, the
         owner-wins entries of ``sync_world``, and an ``add_<name>`` builder
         method.
  ``Registry.kind(name, table=..., payload=PayloadSpec(...))``
      -> an event-kind id, its row in the generated ``KIND_TABLE`` (what the
         conflict mask keys on), and a named payload view replacing magic
         index lists.
  ``@Registry.on(kind)``
      -> an entry in the generated ``lax.switch`` dispatch table.

The four built-in components (compute farm, network region, storage,
activity generator) are registered in ``components.py`` / ``handlers.py`` via
this same API — the hand-written ``World`` / ``WorldDelta`` NamedTuples of
PR 3 are now the *generated output*, pinned byte-identical by
``tests/test_registry.py`` and the ``tools/check_api.py`` drift gate. A new
component (see ``repro/scenarios/cache.py`` for a complete example) needs zero
edits inside core: ``BUILTIN.extend()`` gives a fresh registry that inherits
the built-ins, and every engine entry point (``Engine``, the oracle,
``sync_world``, ``apply_delta``) discovers the registry from the world/delta
*type* (``type(world)._registry``), so extended models run batched,
conflict-masked, and byte-identical to the sequential oracle automatically.

Handler contract: a registered handler has signature
``fn(env, world, counters, e) -> (delta, counters, EventBatch[MAX_EMIT])``
where ``env`` is a :class:`HandlerEnv` carrying the trace-time constants
(``env.delay`` clamps emit delays to the lookahead — the conservative-sync
invariant) and the validating delta constructor ``env.delta(...)`` which
enforces the delta contract (declared row + *every* mutable field of that
table, see handlers.py).
"""
from __future__ import annotations

import collections
import dataclasses
import importlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import monitoring as _mon

# Payload width: enough scalars for the richest built-in handler (flow start:
# size, route, two notify pairs). ``events.PAYLOAD`` re-exports this.
PAYLOAD = 8

# Sentinel row index meaning "this delta writes no row of that table".
# Out of bounds for every component table, so ``mode="drop"`` scatters skip it.
NO_ROW = jnp.int32(2**31 - 1)

# LP lifecycle states (paper §4.3) — engine infrastructure, not model state.
LPS_CREATED = 0
LPS_READY = 1
LPS_RUNNING = 2
LPS_WAITING = 3
LPS_FINISHED = 4

# The per-LP columns every generated World starts with (engine infrastructure;
# lp_state/lp_lvt are owner-wins synced, the rest are replicated inputs).
LP_FIELDS = ("lp_kind", "lp_agent", "lp_res", "lp_state", "lp_lvt", "lp_ctx")


class RegistryError(ValueError):
    """A scenario/model declaration violated the registry's rules."""


@dataclasses.dataclass(frozen=True)
class FieldSpec:
    """One column of a component table.

    ``shape`` is the *per-row* shape; entries may be ints or strings naming a
    builder dimension (declared with ``Registry.dim``) resolved at build time.
    ``mutable`` fields are the ones handlers may write — they enter the
    generated ``WorldDelta`` / ``DELTA_SCHEMA`` and the owner-wins sync list;
    immutable fields (topology, capacities) are replicated build-time inputs.
    ``fill`` is the initial/absent-row value (e.g. ``-1`` route padding).
    """

    shape: tuple
    dtype: Any
    mutable: bool = False
    fill: Any = 0
    doc: str = ""


class PayloadSpec:
    """Named, typed view of an event kind's payload scalars.

    Replaces magic index lists: ``spec.pack(size=40.0, notify_lp=f)`` builds
    the positional payload row with declared defaults for the rest. Fields are
    given as ``"name"`` (float32, default 0.0), ``("name", default)``
    (float32), or ``("name", default, dtype)`` — the **dtype view** (PR 5).

    The engine's payload storage is a flat float32 row; an ``int32`` field
    would historically round-trip through float32 *numerically* and silently
    lose precision beyond 2^24. Declaring ``("token", 0, jnp.int32)`` instead
    stores the int's raw bits reinterpreted as a float32 bit pattern
    (``lax.bitcast_convert_type`` in-graph, numpy views on the host): no
    arithmetic ever touches the value, and the engine only ever copies,
    gathers, and scatters payload bytes, so any 32-bit int — including the
    31-bit ids the registry tests pin — survives intact. Read typed fields
    back with :meth:`get` (which bitcasts int fields to int32); never read an
    int field positionally as a float.
    """

    def __init__(self, *fields):
        self.names: tuple[str, ...] = ()
        self.defaults: dict[str, Any] = {}
        self.dtypes: dict[str, Any] = {}
        for f in fields:
            if isinstance(f, str):
                name, default, dtype = f, 0.0, jnp.float32
            elif len(f) == 2:
                (name, default), dtype = f, jnp.float32
            else:
                name, default, dtype = f
            if not isinstance(name, str) or not name.isidentifier():
                raise RegistryError(f"payload field name {name!r} must be an "
                                    "identifier")
            if name in self.defaults:
                raise RegistryError(f"duplicate payload field {name!r}")
            dtype = jnp.dtype(dtype)
            if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.int32)):
                raise RegistryError(
                    f"payload field {name!r} dtype must be float32 or int32 "
                    f"(a payload scalar is one 32-bit lane), got {dtype}")
            self.names += (name,)
            self.dtypes[name] = dtype
            self.defaults[name] = (int(default) if dtype == jnp.int32
                                   else float(default))
        if len(self.names) > PAYLOAD:
            raise RegistryError(
                f"payload has {len(self.names)} fields; the engine carries at "
                f"most PAYLOAD={PAYLOAD} scalars per event")

    def index(self, name: str) -> int:
        """Positional index of ``name`` in the payload row."""
        try:
            return self.names.index(name)
        except ValueError:
            raise RegistryError(f"unknown payload field {name!r}; "
                                f"declared: {self.names}") from None

    def _check_known(self, values):
        unknown = set(values) - set(self.names)
        if unknown:
            raise RegistryError(f"unknown payload field(s) {sorted(unknown)}; "
                                f"declared: {self.names}")

    def pack(self, **values) -> "np.ndarray":
        """Positional payload row from named values (declared defaults fill
        the rest). The builder pads it to ``PAYLOAD`` scalars.

        Host-side: returns a float32 numpy row. Int32 fields are encoded as
        raw bit patterns via numpy views — never through a Python float, whose
        float64 round-trip would quiet signaling-NaN bit patterns.
        """
        self._check_known(values)
        row = np.zeros((len(self.names),), np.float32)
        for i, n in enumerate(self.names):
            v = values.get(n, self.defaults[n])
            if self.dtypes[n] == jnp.int32:
                row[i] = np.asarray(int(v), np.int32).view(np.float32)
            else:
                row[i] = v
        return row

    def pack_jax(self, **values) -> jax.Array:
        """In-graph payload packing: a padded (``PAYLOAD``,) float32 row for
        handler emits, bitcasting int32 fields (the traced twin of
        :meth:`pack`)."""
        self._check_known(values)
        row = jnp.zeros((PAYLOAD,), jnp.float32)
        for i, n in enumerate(self.names):
            v = values.get(n, self.defaults[n])
            if self.dtypes[n] == jnp.int32:
                f = jax.lax.bitcast_convert_type(
                    jnp.asarray(v, jnp.int32), jnp.float32)
            else:
                f = jnp.asarray(v, jnp.float32)
            row = row.at[i].set(f)
        return row

    def get(self, payload: jax.Array, name: str) -> jax.Array:
        """Read one named scalar from a (``PAYLOAD``,) payload row — int32
        fields are bit-exact (bitcast, not a float->int conversion)."""
        v = payload[..., self.index(name)]
        if self.dtypes[name] == jnp.int32:
            return jax.lax.bitcast_convert_type(v, jnp.int32)
        return v

    def __repr__(self):
        return f"PayloadSpec({', '.join(self.names)})"


@dataclasses.dataclass(frozen=True)
class ComponentDef:
    """A registered component table (returned by ``Registry.component``)."""

    name: str
    table_id: int                     # conflict-mask table id (0 == no table)
    fields: dict                      # field name -> FieldSpec, decl order
    doc: str = ""

    @property
    def lp_kind(self) -> int:
        """The ``lp_kind`` value of LPs owning a row of this component."""
        return self.table_id

    @property
    def row_field(self) -> str:
        """The WorldDelta column that declares this table's written row."""
        return f"{self.name}_row"

    @property
    def own_field(self) -> str:
        """The WorldOwnership column mapping rows back to owning LPs."""
        return f"{self.name}_lp"

    @property
    def first_field(self) -> str:
        return next(iter(self.fields))

    def mutable_fields(self):
        return tuple(f for f, s in self.fields.items() if s.mutable)


@dataclasses.dataclass(frozen=True)
class EventKindDef:
    """A registered event kind (returned by ``Registry.kind``)."""

    name: str
    id: int
    table: str | None                 # component written by the handler
    payload: PayloadSpec

    def pack(self, **values) -> list:
        """Named payload packing — sugar for ``self.payload.pack``."""
        return self.payload.pack(**values)


class HandlerEnv:
    """Trace-time constants + helpers passed to every registered handler."""

    __slots__ = ("registry", "lookahead", "work_per_mb", "_LA")

    def __init__(self, registry: "Registry", lookahead: int,
                 work_per_mb: float):
        self.registry = registry
        self.lookahead = lookahead
        self.work_per_mb = work_per_mb
        self._LA = jnp.int32(lookahead)

    def delay(self, d) -> jax.Array:
        """Clamp an emit delay to the lookahead (the conservative-sync
        invariant: every emitted event lands >= lookahead ticks out)."""
        return jnp.maximum(jnp.asarray(d, jnp.int32), self._LA)

    def empty_delta(self, world):
        return self.registry.empty_delta(world)

    def delta(self, world, component: str, row, **writes):
        """Validating delta constructor — see ``Registry.make_delta``."""
        return self.registry.make_delta(world, component, row, **writes)


class Registry:
    """Holds component/kind/handler declarations and generates engine tables.

    Structural declarations (``dim``/``component``/``kind``) are sealed the
    first time a generated artifact is requested (``world_struct`` & co.);
    handler registration stays open until ``make_handlers`` validates full
    coverage. ``extend()`` returns an unsealed copy that inherits everything —
    the supported way to add components without touching core.
    """

    def __init__(self):
        self._dims: dict[str, int] = {}
        self._components: dict[str, ComponentDef] = {}
        self._kinds: list[EventKindDef] = []
        self._handlers: dict[int, Callable] = {}
        # counter name -> index. Every registry starts with the engine-
        # infrastructure counters (monitoring.BUILTIN_COUNTERS, whose C_*
        # constants are exactly these indices); extensions append their own
        # with Registry.counter and the engine sizes its per-agent counter
        # vector with Registry.n_counters.
        self._counters: dict[str, int] = {
            name: i for i, (name, _doc) in enumerate(_mon.BUILTIN_COUNTERS)}
        # counter name -> docstring: the documentation half of the counter
        # table. tools/gen_counter_docs.py renders it into
        # docs/architecture.md and monitoring.MetricsStream labels snapshots
        # with it, so declared docs are load-bearing, not decoration.
        self._counter_docs: dict[str, str] = {
            name: doc for name, doc in _mon.BUILTIN_COUNTERS}
        self._sealed = False
        # modules whose import registers handlers onto this registry (lets
        # components.py declare the model without importing handlers.py)
        self.deferred_handler_modules: list[str] = []
        self._cache: dict[str, Any] = {}

    # ------------------------------------------------------------ declaration
    def _check_open(self, what: str):
        if self._sealed:
            raise RegistryError(
                f"registry is sealed (a World/Delta struct was already "
                f"generated); cannot add {what}. Use .extend() to grow a "
                f"sealed registry.")

    def dim(self, name: str, default: int) -> str:
        """Declare a builder dimension (e.g. ``max_cpu``) with its default."""
        self._check_open(f"dim {name!r}")
        if not name.isidentifier():
            raise RegistryError(f"dim name {name!r} must be an identifier")
        if name in self._dims and self._dims[name] != default:
            raise RegistryError(f"dim {name!r} already declared with default "
                                f"{self._dims[name]}")
        self._dims[name] = int(default)
        return name

    @property
    def dims(self) -> dict:
        return dict(self._dims)

    def component(self, name: str, fields: dict, doc: str = "") -> ComponentDef:
        """Register a component table; returns its :class:`ComponentDef`."""
        self._check_open(f"component {name!r}")
        if not name.isidentifier():
            raise RegistryError(f"component name {name!r} must be an "
                                "identifier")
        if name in self._components:
            raise RegistryError(f"duplicate component {name!r}")
        if not fields:
            raise RegistryError(f"component {name!r} declares no fields")
        taken = set(LP_FIELDS)
        for comp in self._components.values():
            taken |= set(comp.fields) | {comp.row_field, comp.own_field}
        for fname, fs in fields.items():
            if not isinstance(fs, FieldSpec):
                raise RegistryError(f"{name}.{fname} must be a FieldSpec, "
                                    f"got {type(fs).__name__}")
            if not fname.isidentifier():
                raise RegistryError(f"field name {fname!r} must be an "
                                    "identifier")
            if fname in taken:
                raise RegistryError(
                    f"field {fname!r} of component {name!r} collides with an "
                    "existing World column (field names are global: World is "
                    "one flat structure-of-arrays)")
            for d in fs.shape:
                if isinstance(d, str):
                    if d not in self._dims:
                        raise RegistryError(
                            f"{name}.{fname} shape names unknown dim {d!r}; "
                            f"declare it with Registry.dim first")
                elif not (isinstance(d, int) and d > 0):
                    raise RegistryError(f"{name}.{fname} shape entry {d!r} "
                                        "must be a positive int or a dim name")
            if (fs.mutable and fs.fill != 0
                    and jnp.issubdtype(jnp.dtype(fs.dtype), jnp.floating)):
                raise RegistryError(
                    f"{name}.{fname}: mutable float fields must use fill=0 — "
                    "nonzero fills survive the owner-wins all-reduce via an "
                    "integer shift encoding, which is not byte-exact for "
                    "floats (see Registry.sync_world)")
            taken.add(fname)
        comp = ComponentDef(name=name, table_id=len(self._components) + 1,
                            fields=dict(fields), doc=doc)
        if comp.row_field in taken or comp.own_field in taken:
            raise RegistryError(f"component {name!r}: generated column "
                                f"{comp.row_field}/{comp.own_field} collides "
                                "with an existing field")
        self._components[name] = comp
        return comp

    @property
    def components(self) -> dict:
        return dict(self._components)

    def kind(self, name: str, table: str | None = None,
             payload: PayloadSpec | None = None) -> EventKindDef:
        """Register an event kind; returns its :class:`EventKindDef`.

        ``table`` names the component whose row the kind's handler writes
        (``None`` == the handler touches no component table, e.g. NOOP) —
        this is the row the conflict mask keys on, so it must match the delta
        the handler returns. Components may be registered after the kinds
        that reference them; resolution happens at seal time.
        """
        self._check_open(f"kind {name!r}")
        if not name.isidentifier():
            raise RegistryError(f"kind name {name!r} must be an identifier")
        if any(k.name == name for k in self._kinds):
            raise RegistryError(f"duplicate event kind {name!r}")
        kd = EventKindDef(name=name, id=len(self._kinds), table=table,
                          payload=payload or PayloadSpec())
        self._kinds.append(kd)
        return kd

    @property
    def kinds(self) -> tuple:
        return tuple(self._kinds)

    def kind_def(self, ref) -> EventKindDef:
        """Look up a kind by def / id / name."""
        if isinstance(ref, EventKindDef):
            return ref
        if isinstance(ref, int):
            if not 0 <= ref < len(self._kinds):
                raise RegistryError(f"unknown kind id {ref}")
            return self._kinds[ref]
        for k in self._kinds:
            if k.name == ref:
                return k
        raise RegistryError(f"unknown event kind {ref!r}")

    def counter(self, name: str, doc: str = "") -> int:
        """Declare a named monitoring counter; returns its index.

        The way outside-core components get named stats without editing
        ``monitoring.py``: the returned index is stable for this registry
        (builtin engine counters occupy ``0..monitoring.N_COUNTERS-1``; each
        declaration appends), and handlers bump it with ``mon.bump(counters,
        idx)`` exactly like a builtin. The engine, the oracle, and the batched
        dispatcher all size their counter vectors with :attr:`n_counters`, so
        declared counters flow through every execution path — including the
        batched-lane summation — with zero core edits.
        """
        self._check_open(f"counter {name!r}")
        if not name.isidentifier():
            raise RegistryError(f"counter name {name!r} must be an identifier")
        if name in self._counters:
            raise RegistryError(f"duplicate counter {name!r} "
                                f"(index {self._counters[name]})")
        idx = len(self._counters)
        self._counters[name] = idx
        self._counter_docs[name] = doc
        return idx

    @property
    def counters(self) -> dict:
        """counter name -> index (builtin engine counters first)."""
        return dict(self._counters)

    @property
    def counter_docs(self) -> dict:
        """counter name -> declared docstring (same keys as :attr:`counters`)."""
        return dict(self._counter_docs)

    @property
    def n_counters(self) -> int:
        """Width of the per-agent counter vector for this registry's models."""
        return len(self._counters)

    def counter_index(self, name: str) -> int:
        try:
            return self._counters[name]
        except KeyError:
            raise RegistryError(
                f"unknown counter {name!r}; declared: "
                f"{sorted(self._counters)}") from None

    def on(self, kind) -> Callable:
        """Decorator registering ``fn(env, world, counters, e)`` as the
        handler of ``kind`` (an :class:`EventKindDef`, id, or name)."""
        kd = self.kind_def(kind)

        def register(fn):
            if kd.id in self._handlers:
                raise RegistryError(
                    f"kind {kd.name!r} already has handler "
                    f"{self._handlers[kd.id].__name__!r}")
            self._handlers[kd.id] = fn
            return fn

        return register

    def extend(self) -> "Registry":
        """An unsealed copy inheriting dims, components, kinds, and handlers
        — the extension point for models defined outside core."""
        self._import_deferred()   # so already-registered handlers are copied
        child = Registry()
        child._dims = dict(self._dims)
        child._components = dict(self._components)
        child._kinds = list(self._kinds)
        child._handlers = dict(self._handlers)
        child._counters = dict(self._counters)
        child._counter_docs = dict(self._counter_docs)
        return child

    # ----------------------------------------------------------------- freeze
    def _seal(self):
        if self._sealed:
            return
        for k in self._kinds:
            if k.table is not None and k.table not in self._components:
                raise RegistryError(
                    f"kind {k.name!r} declares table {k.table!r}, which is "
                    f"not a registered component "
                    f"({sorted(self._components) or 'none registered'})")
        self._sealed = True

    def _import_deferred(self):
        for mod in self.deferred_handler_modules:
            importlib.import_module(mod)

    # ------------------------------------------------------- generated tables
    @property
    def n_kinds(self) -> int:
        return len(self._kinds)

    @property
    def n_tables(self) -> int:
        return len(self._components) + 1   # 0 == "no component table"

    @property
    def kind_table(self) -> tuple:
        """kind id -> component table id written by its handler (0 = none)."""
        self._seal()
        return tuple(
            0 if k.table is None else self._components[k.table].table_id
            for k in self._kinds)

    def _struct(self, key: str, name: str, field_names: tuple, doc: str,
                extra: dict | None = None):
        if key not in self._cache:
            base = collections.namedtuple(name, field_names)
            ns = {"__slots__": (), "__doc__": doc, "_registry": self}
            ns.update(extra or {})
            self._cache[key] = type(name, (base,), ns)
        return self._cache[key]

    def world_struct(self):
        """The generated ``World`` NamedTuple: per-LP columns + one
        structure-of-arrays table per registered component."""
        self._seal()
        names = LP_FIELDS + tuple(
            f for comp in self._components.values() for f in comp.fields)
        doc = ("All mutable simulation state (generated from the registry). "
               "Replicated on every agent; synced per window.")
        return self._struct(
            "world", "World", names, doc,
            {"n_lp": property(lambda s: s.lp_kind.shape[-1])})

    def ownership_struct(self):
        """The generated res -> LP inverse maps (one column per component)."""
        self._seal()
        names = tuple(c.own_field for c in self._components.values())
        return self._struct(
            "own", "WorldOwnership", names,
            "res -> LP inverse maps, built once per scenario (generated).")

    def delta_struct(self):
        """The generated ``WorldDelta``: per component, a declared row index
        (``NO_ROW`` == untouched) followed by its mutable fields' new rows."""
        self._seal()
        names = tuple(
            n for comp in self._components.values()
            for n in (comp.row_field,) + comp.mutable_fields())
        return self._struct(
            "delta", "WorldDelta",
            names, "Typed per-row write set of one handler invocation "
                   "(generated from the registry; see handlers.py for the "
                   "delta contract).")

    @property
    def delta_schema(self) -> dict:
        """mutable World field -> the WorldDelta row column addressing it."""
        self._seal()
        return {f: comp.row_field for comp in self._components.values()
                for f in comp.mutable_fields()}

    @property
    def row_fields(self) -> tuple:
        self._seal()
        return tuple(c.row_field for c in self._components.values())

    @property
    def mutable_fields(self) -> tuple:
        return tuple(self.delta_schema)

    def sync_plan(self) -> dict:
        """World field -> sync rule: ``"lp"`` (per-LP owner-wins),
        a component name (owner-wins with that table's mask), or
        ``"replicated"`` (build-time input, never synced)."""
        self._seal()
        plan = {f: "replicated" for f in LP_FIELDS}
        plan["lp_state"] = plan["lp_lvt"] = "lp"
        for comp in self._components.values():
            for fname, fs in comp.fields.items():
                plan[fname] = comp.name if fs.mutable else "replicated"
        return plan

    def resolve_shape(self, shape: tuple, dims: dict) -> tuple:
        return tuple(dims[d] if isinstance(d, str) else d for d in shape)

    def max_rows(self, world) -> int:
        """Widest component table — bound for the conflict-mask key space."""
        return max((getattr(world, c.first_field).shape[0]
                    for c in self._components.values()), default=1)

    # --------------------------------------------------------------- numerics
    def empty_delta(self, world):
        """The identity delta: no rows declared, zero-filled row payloads."""
        vals = {}
        for comp in self._components.values():
            vals[comp.row_field] = NO_ROW
            for f in comp.mutable_fields():
                vals[f] = jnp.zeros_like(getattr(world, f)[0])
        return self.delta_struct()(**vals)

    def make_delta(self, world, component: str, row, **writes):
        """Build a validated delta: declares ``row`` of ``component`` and
        writes *every* mutable field of that table (the whole-row-write half
        of the delta contract; missing or non-mutable fields raise)."""
        comp = self._components.get(component)
        if comp is None:
            raise RegistryError(f"unknown component {component!r}")
        mutable = set(comp.mutable_fields())
        bad = set(writes) - mutable
        if bad:
            immut = sorted(b for b in bad if b in comp.fields)
            if immut:
                raise RegistryError(
                    f"delta writes non-mutable field(s) {immut} of component "
                    f"{component!r}; declare them FieldSpec(mutable=True) if "
                    "handlers must write them")
            raise RegistryError(
                f"delta writes unknown field(s) {sorted(bad)} for component "
                f"{component!r}; declared mutable fields: {sorted(mutable)}")
        missing = mutable - set(writes)
        if missing:
            raise RegistryError(
                f"delta for component {component!r} must write every mutable "
                f"field of the row (whole-row-write contract); missing: "
                f"{sorted(missing)}")
        writes[comp.row_field] = jnp.asarray(row, jnp.int32)
        return self.empty_delta(world)._replace(**writes)

    def apply_delta(self, world, delta):
        """Scatter a delta's declared rows into the world (polymorphic over a
        leading lane axis — see handlers.apply_delta for the contract)."""
        return world._replace(**{
            f: getattr(world, f).at[getattr(delta, rf)].set(
                getattr(delta, f), mode="drop")
            for f, rf in self.delta_schema.items()})

    def sync_world(self, world, own, axis: str | tuple[str, ...] | None):
        """Owner-wins replication sync generated from the field specs.

        Mutable fields all-reduce ``where(mine, row, 0)`` with their owning
        component's mask (exact: one nonzero contribution per row); int
        fields with a nonzero ``fill`` are shifted so the pad value survives
        the zero-identity sum (e.g. ``-1`` route padding). Replicated fields
        pass through untouched.
        """
        if axis is None:
            return world
        me = jax.lax.axis_index(axis)

        def owner_wins(x, mask):
            m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
            if x.dtype == jnp.bool_:
                y = jax.lax.psum(jnp.where(m, x.astype(jnp.int32), 0), axis)
                return y > 0
            if x.dtype == jnp.float32:
                # sum the raw bits: the owner's value arrives exactly, -0.0
                # and denormals included, whatever the float reduction does
                bits = jax.lax.bitcast_convert_type(x, jnp.int32)
                y = jax.lax.psum(jnp.where(m, bits, 0), axis)
                return jax.lax.bitcast_convert_type(y, jnp.float32)
            return jax.lax.psum(jnp.where(m, x, jnp.zeros((), x.dtype)), axis)

        lp_mine = world.lp_agent == me
        out = {"lp_state": owner_wins(world.lp_state, lp_mine),
               "lp_lvt": owner_wins(world.lp_lvt, lp_mine)}
        for comp in self._components.values():
            res_lp = getattr(own, comp.own_field)
            mask = world.lp_agent[res_lp] == me
            for fname, fs in comp.fields.items():
                if not fs.mutable:
                    continue
                x = getattr(world, fname)
                if fs.fill != 0 and x.dtype != jnp.bool_:
                    fill = jnp.asarray(fs.fill, x.dtype)
                    out[fname] = owner_wins(x - fill, mask) + fill
                else:
                    out[fname] = owner_wins(x, mask)
        return world._replace(**out)

    def make_handlers(self, lookahead: int, work_per_mb: float = 1.0) -> list:
        """The generated dispatch table: one ``(world, counters, e)`` row
        kernel per kind id, in kind order (the ``lax.switch`` index)."""
        self._seal()
        self._import_deferred()
        missing = [k.name for k in self._kinds if k.id not in self._handlers]
        if missing:
            raise RegistryError(f"no handler registered for kind(s) "
                                f"{missing}; attach one with @registry.on")
        env = HandlerEnv(self, lookahead, work_per_mb)

        def bind(fn):
            def kernel(world, counters, e, _fn=fn):
                return _fn(env, world, counters, e)
            kernel.__name__ = fn.__name__
            return kernel

        return [bind(self._handlers[k.id]) for k in self._kinds]


def registry_of(obj) -> Registry:
    """The registry that generated ``obj``'s type (World/WorldDelta/...)."""
    reg = getattr(type(obj), "_registry", None)
    if reg is None:
        raise RegistryError(
            f"{type(obj).__name__} was not generated by a Registry; build "
            "worlds through a registry ScenarioBuilder")
    return reg


# ---------------------------------------------------------------------------
# Scenario spec + builder base (host-side)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """Static (trace-time constant) facts about a built scenario."""

    n_agents: int
    n_ctx: int
    lookahead: int          # ticks; min event-generation delay (conservative window)
    t_end: int              # ticks; horizon after which the run stops
    pool_cap: int           # per-agent event-pool capacity
    emit_cap: int           # per-window emit-buffer capacity
    route_cap: int          # per-(src,dst)-agent routing-buffer capacity
    n_lp: int
    work_per_mb: float = 1.0  # CPU ops per transferred MB (job sizing)
    exec_policy: Any = 256  # per-window execution width: a static int (the
                            # PR 1-4 exec_cap; safe events beyond it spill to
                            # the next window) or a policy.ExecPolicy ladder
                            # driven by monitoring (Engine.run_adaptive)
    batched_dispatch: bool = True  # engine step 4: grouped vectorized dispatch
                                   # (False = PR 1 sequential compacted fold)
    merge_mode: str = "delta"      # batched-dispatch merge strategy:
                                   # "delta" = per-row segment scatters of the
                                   # handlers' declared rows, O(lanes x row);
                                   # "dense" = the PR 2 reference merge over
                                   # whole component tables, O(lanes x tables)
                                   # — kept for equivalence tests + benchmarks
    insert_mode: str = "ring"      # event-pool lifecycle strategy: "ring" =
                                   # free-list ring (O(n_insert) insert +
                                   # O(exec_cap) release); "ref" = the PR 1-4
                                   # O(pool_cap) rank-scan insert + pool-wide
                                   # pop mask — kept for equivalence tests and
                                   # the insert_churn benchmark gate
    fused_select: bool = False     # window front-end: True fuses select +
                                   # gather + conflict + group + release ranks
                                   # into one Pallas megakernel call (engine
                                   # fused_fn hook; compiled on TPU,
                                   # interpreted elsewhere — byte-identical
                                   # either way); False (default) keeps the
                                   # XLA-stitched per-stage path

    @property
    def exec_cap(self) -> int:
        """The static per-window execution width the non-adaptive drivers
        use: the int itself, or an adaptive policy's initial-rung width."""
        p = self.exec_policy
        return p if isinstance(p, int) else p.ladder[p.init_rung]


class ScenarioBuilderBase:
    """Generic registry-driven scenario builder.

    Subclasses bind a registry with the ``_registry`` class attribute
    (``components.ScenarioBuilder`` binds the built-ins and layers the legacy
    ergonomic wrappers on top). For every registered component the builder
    exposes ``add_<component>(**field_values)`` (resolved dynamically, unless
    the subclass defines a bespoke wrapper) plus the generic
    ``add_component(name, **field_values)``; ``build()`` allocates the
    generated ``World`` tables, the ownership inverse maps, the initial event
    batch, and the :class:`ScenarioSpec`.
    """

    _registry: Registry

    def __init__(self, **dims):
        reg = self._registry
        unknown = set(dims) - set(reg.dims)
        if unknown:
            raise RegistryError(f"unknown builder dim(s) {sorted(unknown)}; "
                                f"declared: {sorted(reg.dims)}")
        self.dims = {**reg.dims, **{k: int(v) for k, v in dims.items()}}
        for k, v in self.dims.items():
            setattr(self, k, v)
        self._lps: list[dict] = []       # kind, res, ctx
        self._rows: dict[str, list] = {c: [] for c in reg.components}
        self._events: list[dict] = []
        self._seq = 0

    # --------------------------------------------------------------- generic
    def __getattr__(self, name):
        # add_<component> sugar for components without a bespoke wrapper
        if name.startswith("add_"):
            reg = type(self)._registry
            comp = reg.components.get(name[len("add_"):])
            if comp is not None:
                return lambda **kw: self.add_component(comp.name, **kw)
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    def _new_lp(self, kind: int, res: int, ctx: int) -> int:
        self._lps.append(dict(kind=kind, res=res, ctx=ctx))
        return len(self._lps) - 1

    def add_component(self, name: str, *, ctx: int = 0, **fields) -> int:
        """Add one row of component ``name``; returns the owning LP's id.

        Field values are validated against the component's declared shapes:
        scalars for ``()`` fields, sequences no longer than the declared dim
        for 1-D fields (shorter sequences prefix-fill, the rest keeps the
        declared ``fill``), exact shape for >=2-D fields.
        """
        reg = self._registry
        comp = reg.components.get(name)
        if comp is None:
            raise RegistryError(f"unknown component {name!r}; registered: "
                                f"{sorted(reg.components)}")
        unknown = set(fields) - set(comp.fields)
        if unknown:
            raise RegistryError(
                f"unknown field(s) {sorted(unknown)} for component {name!r}; "
                f"declared: {sorted(comp.fields)}")
        import numpy as np
        for fname, value in fields.items():
            spec = comp.fields[fname]
            shape = reg.resolve_shape(spec.shape, self.dims)
            v = np.asarray(value)
            if v.ndim != len(shape):
                raise RegistryError(
                    f"{name}.{fname} expects a rank-{len(shape)} row "
                    f"{spec.shape}, got shape {v.shape}")
            if len(shape) >= 1 and v.shape[0] > shape[0]:
                raise RegistryError(
                    f"{name}.{fname} row of length {v.shape[0]} exceeds the "
                    f"declared dim {spec.shape[0]!r}={shape[0]}")
            if len(shape) >= 2 and v.shape[1:] != shape[1:]:
                raise RegistryError(
                    f"{name}.{fname} trailing shape {v.shape[1:]} must match "
                    f"declared {shape[1:]}")
        self._rows[name].append(dict(fields))
        return self._new_lp(comp.lp_kind, len(self._rows[name]) - 1, ctx)

    def add_idle_lp(self, ctx: int = 0) -> int:
        """A bare LP with no component row (lp_kind 0): a NOOP event sink.

        Used by dispatch benchmarks/tests that want many distinct destination
        LPs without growing any component table, and as a placement target.
        """
        return self._new_lp(0, 0, ctx)

    def add_event(self, *, time: int, kind, src: int, dst: int, payload=(),
                  ctx: int = 0):
        """Seed one initial event. ``kind`` may be an :class:`EventKindDef`
        or a kind id; ``payload`` a positional list (use ``kind.pack(...)``
        for named packing)."""
        self._events.append(dict(time=time, seq=self._seq,
                                 kind=getattr(kind, "id", kind), src=src,
                                 dst=dst, payload=payload, ctx=ctx))
        self._seq += 1

    # ----------------------------------------------------------------- build
    @_mon.span("builder.build")
    def build(self, *, n_agents: int = 1, n_ctx: int = 1, lookahead: int,
              t_end: int, pool_cap: int = 1024, emit_cap: int | None = None,
              route_cap: int | None = None, exec_cap: int | None = None,
              exec_policy=None, placement=None, work_per_mb: float = 1.0,
              batched_dispatch: bool = True, merge_mode: str = "delta",
              insert_mode: str = "ring", fused_select: bool = False):
        from repro.core import events as ev   # late: events imports registry

        reg = self._registry
        World = reg.world_struct()
        nlp = max(len(self._lps), 1)

        lp_kind = jnp.asarray([l["kind"] for l in self._lps] or [0], jnp.int32)
        lp_res = jnp.asarray([l["res"] for l in self._lps] or [0], jnp.int32)
        lp_ctx = jnp.asarray([l["ctx"] for l in self._lps] or [0], jnp.int32)
        if placement is None:
            lp_agent = jnp.arange(nlp, dtype=jnp.int32) % n_agents
        else:
            lp_agent = jnp.asarray(placement, jnp.int32)

        vals = dict(
            lp_kind=lp_kind,
            lp_agent=lp_agent,
            lp_res=lp_res,
            lp_state=jnp.full((nlp,), LPS_READY, jnp.int32),
            lp_lvt=jnp.zeros((nlp,), jnp.int32),
            lp_ctx=lp_ctx,
        )
        n_rows = {}
        for comp in reg.components.values():
            rows = self._rows[comp.name]
            n = max(len(rows), 1)
            n_rows[comp.name] = n
            for fname, spec in comp.fields.items():
                shape = (n,) + reg.resolve_shape(spec.shape, self.dims)
                arr = jnp.full(shape, spec.fill, spec.dtype)
                for i, row in enumerate(rows):
                    if fname not in row:
                        continue
                    v = jnp.asarray(row[fname], spec.dtype)
                    if v.ndim == 0:
                        arr = arr.at[i].set(v)
                    else:
                        arr = arr.at[i, : v.shape[0]].set(v)
                vals[fname] = arr
        world = World(**vals)

        def inverse_map(comp):
            out = [0] * n_rows[comp.name]
            for lp, l in enumerate(self._lps):
                if l["kind"] == comp.lp_kind:
                    out[l["res"]] = lp
            return jnp.asarray(out, jnp.int32)

        own = reg.ownership_struct()(**{
            comp.own_field: inverse_map(comp)
            for comp in reg.components.values()})

        if exec_policy is not None and exec_cap is not None:
            raise RegistryError(
                "pass either exec_cap (static width) or exec_policy "
                "(adaptive ladder), not both")
        if exec_policy is None:
            exec_policy = max(exec_cap if exec_cap is not None
                              else min(pool_cap, 256), 1)
        spec = ScenarioSpec(
            n_agents=n_agents,
            n_ctx=n_ctx,
            lookahead=lookahead,
            t_end=t_end,
            pool_cap=pool_cap,
            emit_cap=emit_cap or pool_cap,
            route_cap=route_cap or max(pool_cap // max(n_agents, 1), 16),
            exec_policy=exec_policy,
            n_lp=nlp,
            work_per_mb=work_per_mb,
            batched_dispatch=batched_dispatch,
            merge_mode=merge_mode,
            insert_mode=insert_mode,
            fused_select=fused_select,
        )
        init_events = ev.batch_from_rows(self._events)
        return world, own, init_events, spec
