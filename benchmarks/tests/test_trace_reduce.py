"""The trace reduction on a small trace recorded on a TPU v5e.

``data/small_tpu.xplane.pb`` (17 KB) holds one ``bench.window`` with a
``bench.matmul`` and a ``bench.add`` mark inside it, two launches of
``jit_bench_small_matmul`` and two of ``jit_bench_small_add`` in the
window (a third matmul launch, the warm-up, lies before it).
"""

import os

import pytest

import trace_reduce

from conftest import HERE

PB = os.path.join(HERE, "data", "small_tpu.xplane.pb")


@pytest.fixture(scope="module")
def loaded():
    return trace_reduce.load_trace(PB)


def test_busy_idle_and_window(loaded):
    r = trace_reduce.reduce_trace(loaded)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.013100768)
    assert r["busy_s"] == pytest.approx(4.413e-06)
    assert 0 < r["busy_s"] < r["window_s"]
    idle_pct = 100 * (1 - r["busy_s"] / r["window_s"])
    assert 99.9 < idle_pct < 100


def test_kernel_time_by_program_name(loaded):
    r = trace_reduce.reduce_trace(loaded)
    assert r["program_s"] == pytest.approx(
        {"jit_bench_small_matmul": 1.328e-06,
         "jit_bench_small_add": 3.105e-06})
    assert [name for name, _ in r["device_ops"]] == [
        "jit_bench_small_add", "jit_bench_small_matmul"]
    assert trace_reduce.program_name("jit__burst_cycles(123)") == (
        "jit__burst_cycles")
    # a window that takes in the warm-up launch counts it too
    wide = trace_reduce.reduce_trace(loaded, window=(0, 10**12))
    assert wide["program_s"]["jit_bench_small_matmul"] > 2.6e-06


def test_gaps_by_mark(loaded):
    r = trace_reduce.reduce_trace(loaded)
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"bench.matmul", "bench.add", "unmarked"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # marks of the caller's own, on the trace's clock, take their place
    lo, hi = trace_reduce.window_of(loaded)
    mine = [("bench.first_half", lo, (lo + hi) / 2),
            ("bench.second_half", (lo + hi) / 2, hi),
            ("bench.narrow", lo, lo + 1000)]
    r2 = trace_reduce.reduce_trace(loaded, marks=mine)
    assert set(dict(r2["idle_gaps"])) <= {
        "bench.first_half", "bench.second_half", "bench.narrow"}
    assert len(r2["idle_gaps"]) <= 10


def test_no_device_plane_is_nothing_to_read(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    assert path is not None and trace_reduce.load_trace(path) is None
