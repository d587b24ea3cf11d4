"""The check sees a broken timed path: each fault the cell can have is
planted under ``Orchestrator.run`` and the run must come out not correct.

The faults: a run that returns its state unchanged (no window executed);
half of each window's batch of events left out of the vectorized dispatch
(every other active lane); an answer altered where it is produced (one
counter of the result). The exchange between chips cannot be left out of a
one-chip cell."""
import jax.numpy as jnp
import pytest

from bench.tests.conftest import run_cell


def unchanged(orch, built, state):
    from repro.core import Engine
    return Engine(*built, trace_cap=orch.trace_cap).init_state()


def altered(orch, built, state):
    from repro.core import monitoring as mon
    return state._replace(counters=state.counters.at[
        ..., mon.C_JOBS_DONE].add(1))


def half_lanes(monkeypatch):
    """Every other active lane of a window's dispatch left out."""
    from repro.core.engine import Engine
    real = Engine._execute_batched

    def broken(self, world, counters, cand, exec_safe, *a, **kw):
        odd = jnp.cumsum(exec_safe.astype(jnp.int32)) % 2 == 0
        return real(self, world, counters, cand, exec_safe & ~odd, *a, **kw)

    monkeypatch.setattr(Engine, "_execute_batched", broken)


FAULTS = {"unchanged": unchanged, "altered": altered}


@pytest.mark.parametrize("cell,fault", [
    ("t0t1_fig2.sweep", f) for f in ("unchanged", "half_lanes", "altered")])
def test_fault_makes_the_run_not_correct(tiny_root, monkeypatch, cell,
                                         fault):
    from repro.fleet import orchestrator as orch_mod
    if fault == "half_lanes":
        half_lanes(monkeypatch)
    else:
        real = orch_mod.Orchestrator.run
        calls = []

        def broken(self, built, devices=None, policy=None, seeds=None):
            res = real(self, built, devices=devices, policy=policy,
                       seeds=seeds)
            calls.append(1)
            if len(calls) == 1:  # the warm-up point runs as it should
                return res
            return res._replace(state=FAULTS[fault](self, built, res.state))

        monkeypatch.setattr(orch_mod.Orchestrator, "run", broken)
    out = run_cell(tiny_root, cell)
    assert out["correct"] is False
    assert any(v["value"] > v["limit"] for v in out["checks"].values())
