"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on the CPU in the test."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import trace


def test_interval_arithmetic():
    m = trace.merge([(5, 7), (0, 2), (1, 3), (6, 9), (4, 4)])
    assert m == [(0, 3), (5, 9)]
    assert trace.total(m) == 7
    assert trace.clip(m, 1, 6) == [(1, 3), (5, 6)]
    assert trace.gaps(m, 0, 12) == [(3, 5), (9, 12)]
    assert trace.gaps(m, -2, 8) == [(-2, 0), (3, 5)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_summary_of_a_hand_made_profile():
    prof = trace.Profile(
        device_ops={"/device:TPU:0": [("dot", 0, 40), ("add", 30, 50),
                                      ("dot", 70, 90)],
                    "/device:TPU:1": [("dot", 0, 100)]},
        spans=[("bench.window", 0, 100), ("bench.step", 0, 60),
               ("bench.wait", 60, 100)])
    s = trace.summarize(prof)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((70 + 100) / 2 * 1e-9)
    assert s.idle_share == pytest.approx(0.15)
    assert s.device_ops[0] == ["dot", pytest.approx((60 + 100) / 2 * 1e-9)]
    # chip 0 idles 50-70 (middle at 60: inside bench.wait) and 90-100
    assert s.idle_gaps == [["bench.wait", pytest.approx(15e-9)]]


def test_cpu_recorded_trace(tmp_path):
    wait = 0.05
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(wait)
    finally:
        jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no TPU"):
        trace.read_profile(str(tmp_path))
    prof = trace.read_profile(str(tmp_path), host_ops=True)
    assert sum(n == "bench.step" for n, _, _ in prof.spans) == 3
    s = trace.summarize(prof)
    assert s.devices == 1 and 0 < s.busy_s < s.window_s
    assert 0.0 < s.idle_share < 1.0
    assert any("dot" in name for name, _ in s.device_ops)
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10
    names = [name for name, _ in s.idle_gaps]
    assert "bench.wait" in names
    # every wait is idle; its gap runs from the end of a step's op to the
    # start of the next, so most of it is found inside the wait
    assert sum(v for _, v in s.idle_gaps) >= 3 * wait
    waited = dict(s.idle_gaps)["bench.wait"]
    assert waited >= 3 * wait * 0.9
