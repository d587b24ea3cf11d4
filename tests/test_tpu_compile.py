"""The engine's Pallas kernels compile for a TPU v5e chip (no chip needed).

The TPU compiler is installed with jaxlib: it compiles for a chip that is
described (``topologies.get_topology_desc``) and not attached, and refuses
what Mosaic would refuse on the chip — which interpret mode, the CPU path of
every other kernel test, cannot see. Each kernel the engine can bind is
compiled with ``interpret=False`` at the wide_component shape
(pool_cap=4096, exec_cap=256).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and under xdist every
worker imports this file. All such compiles live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

POOL_CAP = 4096
EXEC_CAP = 256
N_AGENTS = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _case(name):
    """(kernel call, argument (shape, dtype) list) at the real widths."""
    from repro.core import events as ev
    from repro.kernels import bandwidth_share as bw
    from repro.kernels import event_select as es
    from repro.scenarios.failures import FAIL_REGISTRY

    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    pool, win = (POOL_CAP,), (EXEC_CAP,)
    emits = (EXEC_CAP * ev.MAX_EMIT,)
    n_kinds = FAIL_REGISTRY.n_kinds
    fused_args = ([(pool, i32)] * 2 + [(pool, b)] + [(pool, i32)] * 5
                  + [((POOL_CAP, ev.PAYLOAD), f32), (pool, b), (pool, i32),
                     (pool, i32), ((), i32)])

    def fused(*a):
        return es.fused_select(*a, EXEC_CAP, n_kinds=n_kinds, n_res=512)

    cases = {
        "fused_select": (fused, fused_args),
        # the engine runs the megakernel vmapped over its agents
        "fused_select_vmapped": (
            jax.vmap(fused),
            [((N_AGENTS,) + s, d) for s, d in fused_args]),
        "select_events": (
            lambda t, s: es.select_events(t, s, EXEC_CAP),
            [(pool, i32), (pool, i32)]),
        "sort_events": (es.sort_events, [(pool, i32), (pool, i32)]),
        "group_by_kind": (
            lambda k, a: es.group_by_kind(k, a, n_kinds),
            [(win, i32), (win, b)]),
        "ring_slots": (es.ring_slots, [(pool, i32), ((), i32), (emits, b)]),
        "trace_rank": (es.trace_rank, [(win, b)]),
        "route_rank": (es.route_rank, [(emits, i32)]),
        "maxmin_rates_pallas": (
            bw.maxmin_rates_pallas, [((64, 8), f32), ((8,), f32),
                                     ((64,), b)]),
    }
    return cases[name]


@pytest.mark.parametrize("name", [
    "fused_select", "fused_select_vmapped", "select_events", "sort_events",
    "group_by_kind", "ring_slots", "trace_rank", "route_rank",
    "maxmin_rates_pallas"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # a Mosaic kernel, not an interpreted lowering
    assert "tpu_custom_call" in compiled.as_text()
