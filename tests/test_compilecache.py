"""Persistent warmup artifacts: the compile-cache sidecar JSON store and
the native-vs-XLA calibration reload that makes a second cold process
skip the measurement pass (reference analog: minimalkueue starts in milliseconds,
test/performance/scheduler/minimalkueue/main.go — restart cost must be
one-time per machine)."""

from __future__ import annotations

import os

import pytest

from kueue_tpu import compilecache
from kueue_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.controller.driver import Driver


def test_sidecar_json_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    obj = {"calibration": [[["xla", "flat", 8, 8], 0.001]]}
    assert compilecache.save_json("t.json", obj)
    assert os.listdir(tmp_path) == ["t.json"]
    assert compilecache.load_json("t.json") == obj
    assert compilecache.load_json("missing.json") is None


def test_warmup_reloads_persisted_calibration(tmp_path, monkeypatch):
    """A second solver with the same (build, shape) fingerprint loads
    the persisted calibration table and skips the measurement pass."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def build():
        d = Driver(clock=lambda: 1000.0, use_device_solver=True)
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        d.apply_cluster_queue(ClusterQueue(
            name="cq", cohort="co",
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=4000)})])]))
        d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
        d.create_workload(Workload(
            name="w", queue_name="lq",
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": 1000})]))
        return d

    d1 = build()
    d1.scheduler.solver.warmup(d1.cache.snapshot(), 8)
    assert d1.scheduler.solver.stats["calibration_loaded"] == 0
    assert d1.scheduler.solver.calibration
    files = [f for f in os.listdir(tmp_path)
             if f.startswith("calibration-")]
    assert files, "warmup must persist the calibration table"

    d2 = build()
    d2.scheduler.solver.warmup(d2.cache.snapshot(), 8)
    assert d2.scheduler.solver.stats["calibration_loaded"] == 1
    assert d2.scheduler.solver.calibration == d1.scheduler.solver.calibration
    # the reloaded table serves a real cycle without re-measuring
    s = d2.schedule_once()
    assert s.admitted == ["default/w"]
