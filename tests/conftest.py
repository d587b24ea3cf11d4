"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see 1 device; only the
dry-run (and the subprocess tests that exec it) get placeholder devices."""
import pytest

from repro.core import ScenarioBuilder


def pytest_configure(config):
    # pytest-xdist's loadfile mode sends whole files to workers, by default
    # in descending order of their test count, which sends the few-test,
    # long property files last and makes them the tail of a parallel run;
    # keep the collection order below instead
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    """Files that hold hypothesis property tests run longest: collect them
    first (file order otherwise kept), so a parallel run starts them first."""
    prop = {it.path for it in items
            if getattr(getattr(it, "obj", None), "is_hypothesis_test", False)}
    items.sort(key=lambda it: it.path not in prop)


def t0t1_builder(*, wan_bw=2.0, n_flows=12, interval=25, flow_mb=40.0,
                 lookahead=2):
    """The paper's T0/T1 replication study, small: production at T0 generates
    WAN transfers; arrival triggers analysis jobs at T1; results hit storage."""
    from repro.core.components import DATA_WRITE, FLOW_START, JOB_SUBMIT

    b = ScenarioBuilder(max_cpu=4, queue_cap=8, max_link=4, max_flow=16)
    t0 = b.add_regional_center(n_cpu=2, cpu_power=10.0, disk=500.0, tape=5000.0,
                               tape_rate=5.0)
    t1 = b.add_regional_center(n_cpu=2, cpu_power=8.0, disk=300.0, tape=3000.0,
                               tape_rate=5.0)
    wan = b.add_net_region(link_bws=[wan_bw, wan_bw], link_lats=[5, 5])
    b.add_generator(
        target_lp=wan, kind=FLOW_START,
        payload=FLOW_START.pack(size=flow_mb, l0=0, notify_lp=t1["farm"],
                                notify_kind=JOB_SUBMIT.id,
                                notify2_lp=t1["storage"],
                                notify2_kind=DATA_WRITE.id),
        interval=interval, count=n_flows, start=0)
    return b, dict(lookahead=lookahead, t_end=5000, pool_cap=256,
                   work_per_mb=2.0)


@pytest.fixture(scope="session")
def t0t1_oracle():
    from repro.core import run_sequential
    b, kw = t0t1_builder()
    world, own, init_ev, spec = b.build(n_agents=1, **kw)
    return run_sequential(world, own, init_ev, spec)
