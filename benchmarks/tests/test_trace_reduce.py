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


def test_a_span_opened_at_the_mark_lands_on_the_windows_start(loaded):
    lo, hi = trace_reduce.window_of(loaded)
    t_mark = 1234.5                      # the host's clock as it opened
    (name, a, b), (_, c, _) = trace_reduce.onto_trace_clock(
        (lo, hi), t_mark, [("span", t_mark, t_mark + 0.25),
                           ("later", t_mark + 0.00118, t_mark + 1)])
    assert (name, a, b) == ("span", lo, lo + 0.25e9)
    assert c - lo == pytest.approx(1.18e6)     # ns: 1.18 ms into the window


def test_host_clock_and_annotations_agree_through_the_mark(tmp_path):
    """The harness's one-point move, on a real profile: a probe
    annotation opened right after the host's clock is read lands, once
    moved, on its own event of /host:CPU.  The closest of five probes
    agrees to 0.1 ms (a probe can lose its core between the two)."""
    import time

    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    probes = []
    with TraceAnnotation(trace_reduce.WINDOW_MARK):
        t_mark = time.perf_counter()
        for i in range(5):
            time.sleep(0.002)
            t = time.perf_counter()
            with TraceAnnotation(f"bench.probe{i}"):
                probes.append((f"bench.probe{i}", t, t))
    jax.profiler.stop_trace()
    data = ProfileData.from_file(trace_reduce.find_xplane(str(tmp_path)))
    noted = {ev.name: ev.start_ns for plane in data.planes
             if plane.name == "/host:CPU" for line in plane.lines
             for ev in line.events if ev.name.startswith("bench.")}
    window = (noted[trace_reduce.WINDOW_MARK], None)
    moved = trace_reduce.onto_trace_clock(window, t_mark, probes)
    off_ms = [abs(a - noted[name]) / 1e6 for name, a, _ in moved]
    assert len(off_ms) == 5 and min(off_ms) < 0.1, off_ms
