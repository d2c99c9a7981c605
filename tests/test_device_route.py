"""The solver plane has one device route and nothing that hides it.

- one function names the device, and it is the default JAX backend's;
- no module on the solve path asks JAX for the CPU behind the caller's
  back, and none answers a missing or short device set by carrying on;
- asking for more shards than there are devices raises;
- the compile cache is placed by JAX_COMPILATION_CACHE_DIR, else at one
  fixed path inside the checkout;
- a run "for the device" fails when nothing was dispatched to it.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys

import jax
import pytest

from kueue_tpu import compilecache
from kueue_tpu.controller.driver import Driver
from kueue_tpu.ops.burst import BurstSolver
from kueue_tpu.ops.device import solver_device

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SOLVE_PATH = (
    "kueue_tpu/ops/device.py", "kueue_tpu/ops/solver.py",
    "kueue_tpu/ops/burst.py", "kueue_tpu/ops/cycle.py",
    "kueue_tpu/ops/preemption_solver.py",
    "kueue_tpu/ops/preemption_kernel.py", "kueue_tpu/ops/tas_kernel.py",
    "kueue_tpu/ops/fairsharing_kernel.py", "kueue_tpu/ops/fs_scan.py",
    "kueue_tpu/parallel/sharded.py", "kueue_tpu/controller/driver.py",
    "kueue_tpu/scheduler/scheduler.py", "kueue_tpu/scheduler/preemption.py",
    "kueue_tpu/compilecache.py", "kueue_tpu/__init__.py",
)


def _source(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


def test_solver_device_is_the_default_backends():
    assert solver_device() is jax.devices()[0]


@pytest.mark.parametrize("rel", SOLVE_PATH)
def test_solve_path_never_reroutes(rel):
    src = _source(rel)
    for pattern in (r"jax\.devices\(\s*[\"']",      # a named platform
                    r"jax_platforms",                # re-pinning in code
                    r"default_device\("):            # a second route
        assert not re.search(pattern, src), (rel, pattern)
    if rel != "kueue_tpu/__init__.py":   # gated there, checked below
        assert "TF_CPP_MIN_LOG_LEVEL" not in src, rel


def test_logs_are_silenced_only_on_the_cpu_choice():
    """TF_CPP_MIN_LOG_LEVEL=3 also swallows libtpu's start-up errors, so
    importing the package sets it only when the caller chose the CPU."""
    code = ("import os, kueue_tpu; "
            "print(os.environ.get('TF_CPP_MIN_LOG_LEVEL'))")
    for platforms, want in (("cpu", "3"), (None, "None"), ("tpu", "None")):
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "TF_CPP_MIN_LOG_LEVEL")}
        if platforms is not None:
            env["JAX_PLATFORMS"] = platforms
        out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                             env=env, capture_output=True, text=True,
                             timeout=60)
        assert out.stdout.strip() == want, (platforms, out.stderr[-500:])


def _flat(use_device):
    from tests.test_device_cycle import build_driver, drive_cycles
    d, clock, workloads = build_driver(21, use_device)
    return d, lambda: drive_cycles(d, clock, workloads, n_cycles=12)


def _forest(use_device):
    from tests.test_device_cycle import build_driver, drive_cycles
    d, clock, workloads = build_driver(22, use_device, n_cohorts=16,
                                       cqs_per_cohort=4, n_wl=400)
    return d, lambda: drive_cycles(d, clock, workloads, n_cycles=12)


def _preempting(use_device):
    from tests.test_device_cycle import (build_preemption_heavy,
                                         drive_two_phase)
    d, clock, low, high = build_preemption_heavy(23, use_device)
    return d, lambda: drive_two_phase(d, clock, low, high, n_cycles=12)


@pytest.fixture(scope="module")
def compile_events():
    """Every executable JAX builds (or loads) from here on, as chip_smoke
    counts them."""
    events = []

    def on_duration(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return events


@pytest.mark.parametrize("build,kind", [
    (_flat, "flat"), (_forest, "forest"), (_preempting, "preempt")])
def test_warmed_cpu_host_takes_the_jitted_scan(build, kind, compile_events):
    """After warmup on a CPU host every cycle's admit scan is the jitted
    program on the XLA:CPU device (or one of the two shortcuts that
    launch nothing), already built: warm-up measures nothing, picks
    nothing and leaves the cycles nothing to compile."""
    d, drive = build(True)
    solver = d.scheduler.solver
    snapshot = d.cache.snapshot()
    solver.warmup(snapshot, len(snapshot.cluster_queues))
    routes, kinds = [], set()
    dispatch, scan = solver.dispatch, solver._scan

    def record_dispatch(*a, **kw):
        handle = dispatch(*a, **kw)
        routes.append(handle.route)
        return handle

    def record_scan(st, args, order, mfw=None, preempt=None):
        kinds.add("preempt" if preempt is not None
                  else "flat" if mfw is None else "forest")
        return scan(st, args, order, mfw=mfw, preempt=preempt)

    solver.dispatch, solver._scan = record_dispatch, record_scan
    built = len(compile_events)
    log = drive()
    assert len(compile_events) == built, "a warmed cycle built a program"
    assert log == build(False)[1]()
    assert "cpu" in routes and kind in kinds, (routes, kinds)
    assert set(routes) <= {"cpu", "singleton", "no_fit"}, routes
    assert solver.stats["cpu_dispatches"] == routes.count("cpu")
    assert not [k for k in solver.stats
                if k.startswith(("native", "calibration"))]


def test_more_shards_than_devices_raises(monkeypatch):
    n = len(jax.devices()) + 1
    monkeypatch.setenv("KUEUE_TPU_SHARDS", str(n))
    with pytest.raises(ValueError, match="shards requested"):
        Driver(use_device_solver=True)
    with pytest.raises(ValueError, match="shards requested"):
        BurstSolver().set_shards(n)


def _one_cpu_device(code, cwd, **extra):
    """Run ``code`` in a child with ONE CPU device (the suite's eight
    virtual devices go without the persistent cache altogether)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR",
                        "KUEUE_TPU_COMPILE_CACHE")}
    env.update(PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", **extra)
    out = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-800:]
    return out.stdout.strip().splitlines()


def test_cache_dir_from_env_is_left_to_jax(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no cache
    directory in code (JAX read the variable itself) and names that
    one and no other."""
    code = (
        "import jax, os\n"
        "from kueue_tpu import compilecache as c\n"
        "c.DEFAULT_DIR = os.path.join(os.getcwd(), 'must-not-appear')\n"
        "calls = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda k, v: (calls.append(k), real(k, v))\n"
        "print(c.enable())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print('jax_compilation_cache_dir' in calls)\n"
        "print(c.cache_dir())\n")
    cache = tmp_path / "cache"
    assert _one_cpu_device(code, str(tmp_path),
                           JAX_COMPILATION_CACHE_DIR=str(cache)) == [
        str(cache), str(cache), "False", str(cache)]
    assert os.listdir(tmp_path) in ([], ["cache"])


def test_default_cache_dir_is_one_path_in_the_checkout(tmp_path):
    """Unset, the cache resolves to the same in-checkout path from any
    working directory (the path is part of the cache's world: a
    directory that moves never hits)."""
    code = "from kueue_tpu import compilecache as c; print(c.enable())"
    seen = {_one_cpu_device(code, cwd)[-1] for cwd in (ROOT, str(tmp_path))}
    assert seen == {os.path.join(ROOT, ".kueue-tpu", "xla-cache")}


def test_cache_off_by_flag_and_on_a_virtual_cpu_mesh(monkeypatch):
    """XLA:CPU deadlocks running a multi-device executable loaded from
    the persistent cache, so this process (eight virtual devices) gets
    none; the directory is still named."""
    assert len(jax.devices()) > 1
    before = jax.config.jax_compilation_cache_dir
    assert compilecache.enable() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert compilecache.cache_dir() == compilecache.DEFAULT_DIR
    monkeypatch.setenv("KUEUE_TPU_COMPILE_CACHE", "0")
    assert compilecache.cache_dir() is None


def test_require_accel_checks_the_dispatches_not_the_device():
    from kueue_tpu.perf.harness import (require_accel_dispatches,
                                        require_accel_or_die)
    with pytest.raises(SystemExit, match="CPU"):
        require_accel_or_die()          # this process is pinned to CPU
    with pytest.raises(SystemExit, match="0 dispatches reached"):
        require_accel_dispatches({"accel_dispatches": 0,
                                  "cpu_dispatches": 831})
    with pytest.raises(SystemExit, match="ran off it"):
        require_accel_dispatches({"accel_dispatches": 5,
                                  "cpu_dispatches": 1})
    require_accel_dispatches({"accel_dispatches": 5})
    require_accel_dispatches({}, {"burst_accel_dispatches": 2})


def test_supervisor_children_are_pinned_to_the_cpu(monkeypatch):
    from kueue_tpu.dist.supervisor import ProcessSupervisor
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert ProcessSupervisor._child_env(os.environ)["JAX_PLATFORMS"] == "cpu"
    assert ProcessSupervisor._child_env({})["JAX_PLATFORMS"] == "cpu"
