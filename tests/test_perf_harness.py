"""Perf harness tests (reference test/performance/scheduler runner +
checker) on a scaled-down scenario."""

import pytest

from kueue_tpu.perf import check_rangespec, run_scenario

SMALL_CONFIG = [{
    "className": "cohort", "count": 2,
    "queuesSets": [{
        "className": "cq", "count": 2,
        "nominalQuota": 20, "borrowingLimit": 100,
        "reclaimWithinCohort": "Any",
        "withinClusterQueue": "LowerPriority",
        "workloadsSets": [
            {"count": 30, "creationIntervalMs": 100,
             "workloads": [{"className": "small", "runtimeMs": 200,
                            "priority": 50, "request": 1}]},
            {"count": 10, "creationIntervalMs": 500,
             "workloads": [{"className": "medium", "runtimeMs": 500,
                            "priority": 100, "request": 5}]},
            {"count": 5, "creationIntervalMs": 1200,
             "workloads": [{"className": "large", "runtimeMs": 1000,
                            "priority": 200, "request": 20}]},
        ]}]}]


@pytest.fixture(scope="module")
def stats():
    return run_scenario(SMALL_CONFIG)


def test_scenario_drains_completely(stats):
    assert stats.total_workloads == 2 * 2 * (30 + 10 + 5)
    assert stats.finished == stats.total_workloads
    assert stats.admitted >= stats.total_workloads  # re-admissions possible


def test_priority_classes_admit_faster(stats):
    tta = stats.avg_time_to_admission_ms
    assert set(tta) == {"small", "medium", "large"}
    # higher priority → faster admission (the reference's central
    # observable: large(200) < medium(100) < small(50))
    assert tta["large"] < tta["medium"] < tta["small"]


def test_usage_is_tracked(stats):
    assert "cq" in stats.min_avg_usage_pct
    assert 0.0 < stats.min_avg_usage_pct["cq"] <= 100.0


def test_rangespec_checker(stats):
    ok_spec = {
        "cmd": {"maxWallMs": 10 * 60 * 1000},
        "wlClassesMaxAvgTimeToAdmissionMs": {
            "large": stats.avg_time_to_admission_ms["large"] + 1},
    }
    assert check_rangespec(stats, ok_spec) == []
    bad_spec = {
        "cmd": {"maxWallMs": 0.001},
        "clusterQueueClassesMinUsage": {"cq": 101},
        "wlClassesMaxAvgTimeToAdmissionMs": {"large": -1, "missing": 1},
    }
    failures = check_rangespec(stats, bad_spec)
    assert len(failures) == 4


def test_ab_block_requires_interleaved_control():
    from kueue_tpu.perf.harness import MissingControlArm, ab_block

    treatment = {"arm": "shards_8", "p99_ms": 12.0}
    control = {"arm": "serial", "p99_ms": 15.0, "interleaved": True}
    block = ab_block(treatment, control)
    assert block["treatment"]["arm"] == "shards_8"
    assert block["control"]["interleaved"] is True
    with pytest.raises(MissingControlArm):
        ab_block(treatment, None)
    with pytest.raises(MissingControlArm):
        ab_block(treatment, {})
    with pytest.raises(MissingControlArm):
        # a control measured in a different run/box is not a control
        ab_block(treatment, {"arm": "serial", "p99_ms": 15.0})
    relabeled = ab_block(treatment, control, treatment_label="sharded",
                         control_label="serial_control")
    assert set(relabeled) == {"sharded", "serial_control",
                              "environment_drift"}


def test_ab_block_records_fallback_counters():
    from kueue_tpu.perf.harness import ab_block

    treatment = {"arm": "burst", "p99_ms": 12.0,
                 "solver_stats": {"host_cycles": 0, "scalar_heads": 0},
                 "burst_stats": {"burst_dirty_cycles": 0,
                                 "burst_dispatches": 9}}
    control = {"arm": "host", "p99_ms": 40.0, "interleaved": True,
               "host_cycles": 30}
    block = ab_block(treatment, control)
    drift = block["environment_drift"]
    assert drift["interleaved"] is True
    fc = drift["fallback_counters"]
    assert fc["treatment"]["host_cycles"] == 0
    assert fc["treatment"]["burst_dirty_cycles"] == 0
    # non-fallback counters are not copied
    assert "burst_dispatches" not in fc["treatment"]
    assert fc["control"]["host_cycles"] == 30
