"""The trace reduction: busy union, idle gaps and top ops, on synthetic
intervals and on a profiler trace recorded on the CPU."""
import numpy as np

from bench import trace as bt


def test_busy_union_gaps_and_top_ops():
    ops = [("a", 10, 20), ("b", 15, 30), ("a", 50, 60), ("c", 95, 130)]
    host = [("bench.window", 0, 100), ("bench.run", 0, 40),
            ("bench.build", 40, 100), ("jit_compile", 70, 90)]
    out = bt.reduce({"dev0": ops}, host)
    # busy: [10, 30] + [50, 60] + [95, 100] clipped to the window
    assert out["busy_s"] == 35e-9 and out["window_s"] == 100e-9
    assert out["device_ops"][0] == ["c", 35e-9]
    gaps = out["idle_gaps"]
    assert gaps[0] == ["bench.build > jit_compile", 35e-9]
    assert sorted(g[1] for g in gaps) == sorted([10e-9, 20e-9, 35e-9])


def test_top_ops_count_self_time_under_short_names():
    ops = [("%while.1 = (s32[8]) while(...)", 0, 100),
           ("%fusion.2 = f32[8] fusion(...)", 10, 40),
           ("%fusion.2 = f32[8] fusion(...)", 50, 70),
           ("%copy.3 = f32[8] copy(...)", 80, 90)]
    assert bt.top_ops(ops) == [["%fusion.2", 50e-9], ["%while.1", 40e-9],
                               ["%copy.3", 10e-9]]


def test_gap_label_prefers_jax_compile_spans():
    host = [("bench.run", 0, 100), ("jax.trace_lower", 20, 60),
            ("PjitFunction(run)", 0, 100)]
    assert bt.label((30, 50), host) == "bench.run > jax.trace_lower"
    assert bt.label((70, 90), host) == "bench.run > PjitFunction(run)"


def test_busy_is_averaged_over_devices():
    host = [("bench.window", 0, 100)]
    out = bt.reduce({"d0": [("x", 0, 50)], "d1": [("x", 0, 100)]}, host)
    assert out["busy_s"] == 75e-9 and out["devices"] == 2


def test_nothing_to_read_gives_none():
    assert bt.reduce({}, [("bench.window", 0, 1)]) is None
    assert bt.reduce({"d": [("x", 0, 1)]}, []) is None


def test_reduction_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(20):
            with jax.profiler.TraceAnnotation("bench.run"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = bt.find_xspace(str(tmp_path))
    devices, host = bt.read_xspace(path)
    assert devices == {}  # no TPU plane on the CPU: the readers stay silent
    assert bt.summarize(str(tmp_path)) is None
    # the CPU executions stand in for device ops
    ex = [e for e in host if e[0] == "PjRtCpuExecutable::Execute"]
    assert len(ex) >= 20
    out = bt.reduce({"cpu": ex}, host)
    lo, hi = [(a, b) for n, a, b in host if n == "bench.window"][0]
    # an independent union: a boolean grid at 1 us over the window
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, a, b in ex:
        grid[int((max(a, lo) - lo) // 1000):int((min(b, hi) - lo) // 1000)] = 1
    assert abs(out["busy_s"] - grid.sum() * 1e-6) < 25e-6 * len(ex)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"][0][0] == "PjRtCpuExecutable::Execute"
    assert 0 < len(out["idle_gaps"]) <= bt.TOP
    assert all(isinstance(g[0], str) and g[1] > 0 for g in out["idle_gaps"])
