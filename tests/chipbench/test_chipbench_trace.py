"""``chipbench/trace.py`` on a trace recorded on the CPU in the test."""

import time

import jax
import jax.numpy as jnp
import pytest
from _chipbench_tiny import ROOT  # noqa: F401

from chipbench import trace as T


def test_interval_arithmetic():
    assert T.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert T.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]
    assert T.covered([(0, 1), (0.5, 1.5), (2, 3)]) == pytest.approx(2.5)


def test_reduce_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.submit"):
                y = f(x)
            y.block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    tr = T.read(T.find(str(tmp_path)), host_ops=True)
    assert tr.window_s() >= 0.06
    assert 0 < tr.busy_s() < tr.window_s()
    mods = tr.module_seconds()
    assert any(name.startswith("jit_") for name in mods) and sum(mods.values()) > 0
    gaps = tr.idle_gaps(5)
    assert gaps and gaps[0][1] >= 0.015
    assert gaps[0][0] == "chipbench.sleep"
    assert {s.name for s in tr.spans} == {"chipbench.submit", "chipbench.sleep"}
    # without a device plane, and not asked to, the host's operations never stand in for the device's
    bare = T.read(T.find(str(tmp_path)))
    assert not bare.ops and bare.busy_s() == 0 and not bare.idle_gaps()
