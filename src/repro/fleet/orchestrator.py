"""Elastic preemptible execution: the fleet orchestration host loop.

The paper pitches a simulation *service* that "hides the computational
effort from the end-user" — the run should survive the fabric it executes
on. PRs 5-8 built the mechanisms (GVT-aligned durable checkpoints that are
device-layout-free, host-streamed observability that concatenates exactly
across a resume, a SIGKILL crash harness); :class:`Orchestrator` is the
control loop that composes them:

* **One entry point over all drivers.** ``run(built, devices, policy)``
  dispatches to ``run_local`` / ``run_adaptive`` / ``run_distributed`` /
  ``run_distributed_adaptive`` (``policy.driver="auto"`` picks from the
  device count and the spec's exec policy) — or ``run_ensemble`` for
  catalog ensemble entries.
* **GVT-aligned checkpoints.** A :class:`~repro.checkpoint.SimCheckpointer`
  saves the unpadded EngineState (plus the drained trace spans and emitted
  metrics records) every ``checkpoint_every`` windows.
* **Shard-loss detection.** Two lanes: an injected probe (``preempt=``)
  fired through the engine's per-window host hook — the in-process test
  lane — and process death (SIGKILL), discovered at the next start through
  the ``fleet.json`` sidecar's missing clean flag.
* **Automatic resume on the survivors.** The next attempt restores the
  latest committed checkpoint and re-enters the driver on the surviving
  device set; the unpadded checkpoint re-pads for whatever mesh the
  smaller fleet builds, so a 4-device run resumes on 3 (or 1) with
  traces/counters/world byte-identical to the uninterrupted run — the
  orchestrator changes *where* the run executes, never *what* it computes.
* **Caps and floors.** ``max_retries`` bounds the preemption count,
  exponential ``backoff`` (capped) spaces the attempts, and ``min_devices``
  is the degraded-mode floor below which the run hard-fails
  (:class:`FleetError`) instead of limping.
* **Fleet counters.** ``C_PREEMPT`` / ``C_RESUME`` / ``C_RESHARD`` are
  registry-declared but booked *host-side* (``MetricsStream.book``) — never
  in-graph, so the resumed EngineState stays byte-identical to the
  uninterrupted run's, preemption bookkeeping included.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, NamedTuple

import jax
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint import SimCheckpointer
from repro.core import monitoring as mon
from repro.core import policy as pol_mod
from repro.core.engine import Engine

_SIDECAR = "fleet.json"


class PreemptionError(RuntimeError):
    """A shard-loss signal: the run lost devices mid-flight.

    Raised by the injected probe (or any window hook) to abort the current
    attempt; ``survivors`` is the surviving device count the orchestrator
    shrinks to before resuming."""

    def __init__(self, survivors: int, at_window: int | None = None):
        self.survivors = int(survivors)
        self.at_window = at_window
        super().__init__(
            f"preempted at window {at_window}: "
            f"{self.survivors} surviving device(s)")


class FleetError(RuntimeError):
    """Unrecoverable orchestration failure: the degraded-mode device floor
    was breached, the retry cap was exhausted, or the policy is invalid."""


@dataclasses.dataclass(frozen=True)
class FleetPolicy:
    """Declarative orchestration policy for one elastic run.

    ``driver`` selects the engine driver (``"auto"`` = distributed when more
    than one device is given, the adaptive variant when the spec carries an
    exec ladder; ``"ensemble"`` runs the fused vmap-over-seeds driver, which
    supports neither checkpointing nor elastic resume — one XLA program has
    no window boundaries to save at). ``checkpoint_dir`` enables durable
    GVT-aligned checkpoints every ``checkpoint_every`` windows (the elastic
    loop requires it to resume across preemptions); ``kill_after`` passes
    through to the SIGKILL crash harness. ``max_retries`` caps preemptions
    per run, ``backoff``/``backoff_cap`` space the attempts (seconds;
    attempt k sleeps ``min(backoff * 2**(k-1), backoff_cap)``), and
    ``min_devices`` is the degraded-mode floor: a preemption that leaves
    fewer survivors hard-fails instead of resuming."""

    driver: str = "auto"
    checkpoint_dir: str | None = None
    checkpoint_every: int = 8
    checkpoint_keep: int = 3
    kill_after: int | None = None
    max_windows: int = 10_000
    max_retries: int = 3
    backoff: float = 0.0
    backoff_cap: float = 30.0
    min_devices: int = 1

    _DRIVERS = ("auto", "local", "adaptive", "distributed",
                "distributed_adaptive", "ensemble")

    def __post_init__(self):
        if self.driver not in self._DRIVERS:
            raise FleetError(
                f"unknown driver {self.driver!r}; one of {self._DRIVERS}")
        if self.min_devices < 1:
            raise FleetError(
                f"min_devices must be >= 1, got {self.min_devices}")
        if self.max_retries < 0:
            raise FleetError(
                f"max_retries must be >= 0, got {self.max_retries}")
        if self.checkpoint_every < 0:
            raise FleetError(
                f"checkpoint_every must be >= 0, got {self.checkpoint_every}")


class OrchestratorResult(NamedTuple):
    """The elastic run's outcome.

    ``state`` is the final unpadded EngineState (stacked ``(R, A, ...)``
    for the ensemble driver); ``devices`` the device count the finishing
    attempt ran on; ``attempts`` the total driver attempts (1 = no
    preemption); ``counts`` the host-side fleet-counter books
    (``{"PREEMPT": n, "RESUME": n, "RESHARD": n}``)."""

    state: Any
    driver: str
    devices: int
    attempts: int
    counts: dict


class Orchestrator:
    """The elastic host loop: checkpoint, preempt, shrink, resume, finish.

    Streams (``trace_stream``/``metrics_stream``) and the device-side trace
    ring size (``trace_cap``/``drain_every``) are orchestrator-level because
    they must outlive individual engine attempts: the same stream objects
    attach to every attempt's engine, and the checkpoint/restore path
    carries their host state across the preemption boundary so observability
    concatenates exactly.

    ``preempt`` is the injected shard-loss probe for tests and smokes:
    ``preempt(window, attempt) -> surviving-device-count | None``, called at
    every host-stepped window boundary (after any due checkpoint save).
    Returning an int aborts the attempt with :class:`PreemptionError`.
    """

    def __init__(self, policy: FleetPolicy | None = None, *,
                 trace_stream=None, metrics_stream=None,
                 preempt: Callable[[int, int], int | None] | None = None,
                 trace_cap: int = 0, drain_every: int = 16,
                 sleep: Callable[[float], None] = time.sleep):
        self.policy = FleetPolicy() if policy is None else policy
        self.trace_stream = trace_stream
        self.metrics_stream = metrics_stream
        self._preempt = preempt
        self.trace_cap = trace_cap
        self.drain_every = drain_every
        self._sleep = sleep
        self.counts = {"PREEMPT": 0, "RESUME": 0, "RESHARD": 0}

    # ------------------------------------------------------------- bookkeeping
    def _book(self, name: str, amount: int = 1) -> None:
        """Host-side fleet-counter booking (never the in-graph vector)."""
        self.counts[name] += amount
        if self.metrics_stream is not None:
            self.metrics_stream.book(name, amount)

    def _sidecar_path(self, pol: FleetPolicy) -> str | None:
        if pol.checkpoint_dir is None:
            return None
        return os.path.join(pol.checkpoint_dir, _SIDECAR)

    def _write_sidecar(self, pol: FleetPolicy, n_devices: int,
                       clean: bool) -> None:
        """Record the attempt's device count and books (atomic rename).

        ``clean=False`` at attempt start, flipped to True only on a
        completed run — a missing clean flag at the next start IS the
        process-death preemption signal (the SIGKILL lane)."""
        path = self._sidecar_path(pol)
        if path is None:
            return
        os.makedirs(pol.checkpoint_dir, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"n_devices": n_devices, "clean": clean,
                       "counts": self.counts}, f)
        os.replace(tmp, path)

    def _read_sidecar(self, pol: FleetPolicy) -> dict | None:
        path = self._sidecar_path(pol)
        if path is None or not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)

    # ---------------------------------------------------------------- dispatch
    def _resolve_driver(self, pol: FleetPolicy, spec, n_devices: int) -> str:
        if pol.driver != "auto":
            return pol.driver
        ladder = isinstance(spec.exec_policy, pol_mod.ExecPolicy)
        if n_devices > 1:
            return "distributed_adaptive" if ladder else "distributed"
        return "adaptive" if ladder else "local"

    def _dispatch(self, engine: Engine, driver: str, pol: FleetPolicy,
                  devices: list, state, rung):
        mw = pol.max_windows
        if driver == "local":
            return engine.run_local(mw, state=state)
        if driver == "adaptive":
            return engine.run_adaptive(mw, state=state, rung=rung)
        mesh = Mesh(np.array(devices), ("agents",))
        if driver == "distributed":
            return engine.run_distributed(mesh, mw, state=state)
        if driver == "distributed_adaptive":
            return engine.run_distributed_adaptive(mesh, mw, state=state,
                                                   rung=rung)
        raise FleetError(f"unknown driver {driver!r}")  # pragma: no cover

    def _hook(self, attempt: int):
        """The engine window hook wrapping the injected preemption probe."""
        probe = self._preempt
        if probe is None:
            return None

        def hook(window: int, _state) -> None:
            survivors = probe(window, attempt)
            if survivors is not None:
                raise PreemptionError(survivors, at_window=window)

        return hook

    # --------------------------------------------------------------------- run
    def run(self, built, devices=None,
            policy: FleetPolicy | None = None,
            seeds=None) -> OrchestratorResult:
        """Run a built scenario elastically to completion.

        ``built`` is the ``(world, own, init_events, spec)`` tuple of
        ``ScenarioBuilderBase.build`` (what a catalog entry resolves to);
        ``devices`` the device list to start on (default ``jax.devices()``);
        ``policy`` overrides the constructor's. For the ensemble driver,
        ``seeds`` is the per-replica seed vector.

        Use a fresh ``checkpoint_dir`` per logical run: existing committed
        checkpoints in the directory are treated as *this* run's and
        auto-resumed (that is exactly the restart-after-SIGKILL contract).

        The call is one ``orchestrator.run`` host span with a fresh run id,
        which every program span under it shares.
        """
        pol = self.policy if policy is None else policy
        with mon.span("orchestrator.run", run_id=mon.next_run_id(),
                      driver=pol.driver):
            world, own, init_events, spec = built
            if pol.driver == "ensemble":
                return self._run_ensemble(built, pol, seeds)
            devices = (list(jax.devices()) if devices is None
                       else list(devices))
            ck = None
            if pol.checkpoint_dir is not None and pol.checkpoint_every > 0:
                ck = SimCheckpointer(pol.checkpoint_dir,
                                     every=pol.checkpoint_every,
                                     keep=pol.checkpoint_keep,
                                     kill_after=pol.kill_after)

            # The SIGKILL lane: a sidecar without the clean flag means the
            # prior orchestrated process died mid-run — restore its books and
            # count the death as the preemption it was.
            prev = self._read_sidecar(pol)
            saved_n_dev = None
            if prev is not None and not prev.get("clean", False):
                for name, value in (prev.get("counts") or {}).items():
                    if name in self.counts and value:
                        self._book(name, int(value) - self.counts[name])
                saved_n_dev = prev.get("n_devices")
                self._book("PREEMPT")

            attempt = 0
            while True:
                n_dev = len(devices)
                if n_dev < pol.min_devices:
                    raise FleetError(
                        f"degraded below the device floor: {n_dev} "
                        f"survivor(s) < min_devices={pol.min_devices}")
                driver = self._resolve_driver(pol, spec, n_dev)
                engine = Engine(world, own, init_events, spec,
                                trace_cap=self.trace_cap,
                                trace_stream=self.trace_stream,
                                metrics_stream=self.metrics_stream,
                                drain_every=self.drain_every,
                                checkpointer=ck,
                                window_hook=self._hook(attempt))
                state = rung = None
                if ck is not None and ck.latest_step() is not None:
                    rec = engine.restore()
                    state, rung = rec.state, rec.rung
                    self._book("RESUME")
                    if saved_n_dev is not None and saved_n_dev != n_dev:
                        self._book("RESHARD")
                self._write_sidecar(pol, n_dev, clean=False)
                try:
                    st = self._dispatch(engine, driver, pol, devices, state,
                                        rung)
                except PreemptionError as e:
                    self._book("PREEMPT")
                    attempt += 1
                    if attempt > pol.max_retries:
                        raise FleetError(
                            f"retry cap exhausted: {attempt - 1} retries "
                            f"after {self.counts['PREEMPT']} preemption(s)"
                        ) from e
                    saved_n_dev = n_dev
                    if e.survivors < n_dev:
                        devices = devices[:e.survivors]
                    if pol.backoff > 0:
                        self._sleep(min(pol.backoff * 2 ** (attempt - 1),
                                        pol.backoff_cap))
                    continue
                self._write_sidecar(pol, n_dev, clean=True)
                return OrchestratorResult(state=st, driver=driver,
                                          devices=n_dev,
                                          attempts=attempt + 1,
                                          counts=dict(self.counts))

    def _run_ensemble(self, built, pol: FleetPolicy,
                      seeds) -> OrchestratorResult:
        """The fused vmap-over-seeds driver (no elastic features: one XLA
        program has no window boundaries to checkpoint or probe at — the
        engine itself rejects streaming traces and checkpointing here)."""
        if seeds is None:
            raise FleetError("the ensemble driver needs a seed vector "
                             "(pass seeds=)")
        world, own, init_events, spec = built
        engine = Engine(world, own, init_events, spec,
                        metrics_stream=self.metrics_stream)
        st = engine.run_ensemble(np.asarray(seeds), pol.max_windows)
        return OrchestratorResult(state=st, driver="ensemble", devices=1,
                                  attempts=1, counts=dict(self.counts))
