"""jax.profiler tracing surface (SURVEY §5.1): per-cycle step markers
and on-demand traces around real scheduling activity."""

import os

from kueue_tpu import profiling
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


def test_trace_captures_scheduling_cycles(tmp_path):
    d = Driver(use_device_solver=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    d.apply_cluster_queue(ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=8000)})])]))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    for i in range(4):
        d.create_workload(Workload(
            name=f"wl-{i}", queue_name="lq", creation_time=float(i + 1),
            pod_sets=[PodSet(name="m", count=1, requests={"cpu": 1000})]))

    logdir = str(tmp_path / "trace")
    assert not profiling.trace_active()
    profiling.start_trace(logdir)
    try:
        assert profiling.trace_active()
        for _ in range(4):
            d.schedule_once()
    finally:
        profiling.stop_trace()
    assert not profiling.trace_active()
    assert d.admitted_keys()

    # a trace was actually written (plugins/profile/<ts>/*)
    files = [os.path.join(r, f) for r, _, fs in os.walk(logdir) for f in fs]
    assert files, f"no trace output under {logdir}"
    # stop is idempotent / safe when inactive
    profiling.stop_trace()


def test_cycle_step_noop_without_trace():
    with profiling.cycle_step(7):
        pass


def test_named_phases_are_spans_not_a_second_system():
    """``profiling.annotation`` is gone: a named host phase is a span of
    obs/trace.py, which holds the profiler annotation itself (the trace
    side is checked in test_obs.py)."""
    assert not hasattr(profiling, "annotation")
    from kueue_tpu.obs.trace import HOT_PATH_PHASES
    assert "cycle.nominate.scan_dispatch" in HOT_PATH_PHASES
