"""Conformance coverage for FlavorFungibility tables
(reference: pkg/scheduler/flavorassigner/flavorassigner.go whenCanBorrow /
whenCanPreempt semantics), end to end through the scheduler on both the
host and device paths, plus fused-burst parity.

Covers the whenCanBorrow x whenCanPreempt matrix, mid-list resume via
`last_tried_flavor_idx`, and multi-resource Fit/Borrow/Preempt rows.
"""

from __future__ import annotations

import pytest

from kueue_tpu.api.types import (
    ClusterQueue,
    FlavorFungibility,
    FlavorFungibilityPolicy,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    WithinClusterQueue,
    Workload,
)
from kueue_tpu.controller.driver import Driver
from tests.conftest import FakeClock
from tests.test_conformance_preemption import admit, cycle, incoming, preempted

K = 1000
GI = 1024

BORROW = FlavorFungibilityPolicy.BORROW
PREEMPT = FlavorFungibilityPolicy.PREEMPT
TRY_NEXT = FlavorFungibilityPolicy.TRY_NEXT_FLAVOR
LOWER = PreemptionPolicy(within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)


def ff(wcb=BORROW, wcp=TRY_NEXT):
    return FlavorFungibility(when_can_borrow=wcb, when_can_preempt=wcp)


def two_flavor_cq(name, f1_cpu, f2_cpu, cohort=None, fungibility=None,
                  preemption=None, resources=None):
    """One resource group with flavors f1, f2.  `resources` optionally
    maps flavor -> {res: nominal} for multi-resource rows; otherwise a
    cpu-only row with the given nominals."""
    if resources is None:
        resources = {"f1": {"cpu": f1_cpu}, "f2": {"cpu": f2_cpu}}
    covered = sorted({r for q in resources.values() for r in q})
    return ClusterQueue(
        name=name, cohort=cohort,
        preemption=preemption or PreemptionPolicy(),
        flavor_fungibility=fungibility or FlavorFungibility(),
        resource_groups=[ResourceGroup(
            covered_resources=covered,
            flavors=[FlavorQuotas(name=f, resources={
                r: ResourceQuota(nominal=n) for r, n in q.items()})
                for f, q in resources.items()])])


def make_driver(use_device, cqs):
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device)
    for f in ("f1", "f2"):
        d.apply_resource_flavor(ResourceFlavor(name=f))
    for c in cqs:
        d.apply_cluster_queue(c)
        d.apply_local_queue(LocalQueue(name=f"lq-{c.name}",
                                       cluster_queue=c.name))
    return d, clock


def lender():
    """Cohort member with unused f1 headroom so the test CQ can borrow."""
    return two_flavor_cq("lender", 4 * K, 0, cohort="co")


def flavor_of(d, key, res="cpu"):
    return d.workload(key).admission.pod_set_assignments[0].flavors[res]


@pytest.fixture(params=[False, True], ids=["host", "device"])
def use_device(request):
    return request.param


# ---------------------------------------------------------------- whenCanBorrow

def test_wcb_borrow_stops_on_first_borrow_fit(use_device):
    """Default Borrow: a borrow-fit on f1 is final even though f2 would
    fit nominally (flavorassigner.go: whenCanBorrow=Borrow)."""
    d, clock = make_driver(use_device, [
        two_flavor_cq("cq", 1 * K, 4 * K, cohort="co",
                      fungibility=ff(wcb=BORROW)),
        lender()])
    incoming(d, "w", "cq", {"cpu": 2 * K})
    stats = cycle(d, clock)
    assert stats.admitted == ["default/w"], stats
    assert flavor_of(d, "default/w") == "f1"


def test_wcb_try_next_prefers_nominal_fit(use_device):
    """TryNextFlavor: skip the borrow-fit on f1, land nominally on f2."""
    d, clock = make_driver(use_device, [
        two_flavor_cq("cq", 1 * K, 4 * K, cohort="co",
                      fungibility=ff(wcb=TRY_NEXT)),
        lender()])
    incoming(d, "w", "cq", {"cpu": 2 * K})
    stats = cycle(d, clock)
    assert stats.admitted == ["default/w"], stats
    assert flavor_of(d, "default/w") == "f2"


def test_wcb_try_next_falls_back_to_best_borrow(use_device):
    """TryNextFlavor with f2 NoFit: the walk keeps the earlier borrow-fit
    as the best mode and admits borrowing on f1."""
    d, clock = make_driver(use_device, [
        two_flavor_cq("cq", 1 * K, 0, cohort="co",
                      fungibility=ff(wcb=TRY_NEXT)),
        lender()])
    incoming(d, "w", "cq", {"cpu": 2 * K})
    stats = cycle(d, clock)
    assert stats.admitted == ["default/w"], stats
    assert flavor_of(d, "default/w") == "f1"


# ---------------------------------------------------------------- whenCanPreempt

def test_wcp_default_skips_preempt_slot(use_device):
    """Default TryNextFlavor: f1 is preempt-capable but f2 fits, so the
    walk moves on and nothing is preempted."""
    d, clock = make_driver(use_device, [
        two_flavor_cq("cq", 2 * K, 2 * K, preemption=LOWER,
                      fungibility=ff(wcp=TRY_NEXT))])
    admit(d, "victim", "cq", {"cpu": ("f1", 2 * K)}, priority=-10)
    incoming(d, "w", "cq", {"cpu": 2 * K}, priority=0)
    stats = cycle(d, clock)
    assert stats.admitted == ["default/w"], stats
    assert not preempted(stats)
    assert flavor_of(d, "default/w") == "f2"


def test_wcp_preempt_stops_and_preempts(use_device):
    """whenCanPreempt=Preempt: the walk stops on the f1 preempt slot and
    evicts the victim instead of spilling to free f2."""
    d, clock = make_driver(use_device, [
        two_flavor_cq("cq", 2 * K, 2 * K, preemption=LOWER,
                      fungibility=ff(wcp=PREEMPT))])
    admit(d, "victim", "cq", {"cpu": ("f1", 2 * K)}, priority=-10)
    incoming(d, "w", "cq", {"cpu": 2 * K}, priority=0)
    stats = cycle(d, clock)
    assert preempted(stats) == {"victim"}
    for _ in range(4):
        if d.workload("default/w").has_quota_reservation:
            break
        cycle(d, clock)
    assert d.workload("default/w").has_quota_reservation
    assert flavor_of(d, "default/w") == "f1"
    assert not d.workload("default/victim").has_quota_reservation


# ------------------------------------------------------------- mid-list resume

def test_mid_list_resume_skips_tried_flavor(use_device):
    """Preempt stop on f1 with no eligible targets (occupant has higher
    priority): the attempt records last_tried_flavor_idx=0, the workload
    requeues, and the next cycle resumes the walk at f2."""
    d, clock = make_driver(use_device, [
        two_flavor_cq("cq", 2 * K, 2 * K, preemption=LOWER,
                      fungibility=ff(wcp=PREEMPT))])
    admit(d, "occupant", "cq", {"cpu": ("f1", 2 * K)}, priority=50)
    incoming(d, "w", "cq", {"cpu": 2 * K}, priority=0)
    s1 = cycle(d, clock)
    assert not s1.admitted and not preempted(s1), s1
    s2 = cycle(d, clock)
    assert s2.admitted == ["default/w"], s2
    assert not preempted(s2)
    assert flavor_of(d, "default/w") == "f2"
    assert d.workload("default/occupant").has_quota_reservation
    if use_device:
        assert d.scheduler.solver.stats["resume_heads"] >= 1, \
            d.scheduler.solver.stats


# -------------------------------------------------------------- multi-resource

def test_multi_resource_fit_picks_flavor_fitting_all(use_device):
    """A flavor must fit every covered resource: f1 fits cpu but not
    memory, so the row lands on f2 for both."""
    d, clock = make_driver(use_device, [
        two_flavor_cq("cq", 0, 0, resources={
            "f1": {"cpu": 4 * K, "memory": 1 * GI},
            "f2": {"cpu": 4 * K, "memory": 4 * GI}})])
    incoming(d, "w", "cq", {"cpu": 1 * K, "memory": 2 * GI})
    stats = cycle(d, clock)
    assert stats.admitted == ["default/w"], stats
    assert flavor_of(d, "default/w", "cpu") == "f2"
    assert flavor_of(d, "default/w", "memory") == "f2"


def test_multi_resource_borrow_matrix(use_device):
    """Borrow on the memory dimension of f1: Borrow stops there,
    TryNextFlavor walks on to the nominal fit on f2."""
    for wcb, want in ((BORROW, "f1"), (TRY_NEXT, "f2")):
        d, clock = make_driver(use_device, [
            ClusterQueue(
                name="cq", cohort="co",
                flavor_fungibility=ff(wcb=wcb),
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu", "memory"],
                    flavors=[
                        FlavorQuotas(name="f1", resources={
                            "cpu": ResourceQuota(nominal=4 * K),
                            "memory": ResourceQuota(nominal=1 * GI)}),
                        FlavorQuotas(name="f2", resources={
                            "cpu": ResourceQuota(nominal=4 * K),
                            "memory": ResourceQuota(nominal=4 * GI)})])]),
            two_flavor_cq("lender2", 0, 0, cohort="co", resources={
                "f1": {"cpu": 0, "memory": 4 * GI},
                "f2": {"cpu": 0, "memory": 0}})])
        incoming(d, "w", "cq", {"cpu": 1 * K, "memory": 2 * GI})
        stats = cycle(d, clock)
        assert stats.admitted == ["default/w"], (wcb, stats)
        assert flavor_of(d, "default/w", "memory") == want, wcb


def test_multi_resource_preempt_stop(use_device):
    """whenCanPreempt=Preempt with a memory-bound victim on f1: the walk
    stops and preempts on f1 even though f2 fits outright."""
    d, clock = make_driver(use_device, [
        two_flavor_cq("cq", 0, 0, preemption=LOWER,
                      fungibility=ff(wcp=PREEMPT), resources={
                          "f1": {"cpu": 4 * K, "memory": 2 * GI},
                          "f2": {"cpu": 4 * K, "memory": 2 * GI}})])
    admit(d, "victim", "cq",
          {"cpu": ("f1", 1 * K), "memory": ("f1", 2 * GI)}, priority=-10)
    incoming(d, "w", "cq", {"cpu": 1 * K, "memory": 2 * GI}, priority=0)
    stats = cycle(d, clock)
    assert preempted(stats) == {"victim"}
    for _ in range(4):
        if d.workload("default/w").has_quota_reservation:
            break
        cycle(d, clock)
    assert flavor_of(d, "default/w", "memory") == "f1"


# -------------------------------------------------------------------- metrics

def test_flavor_walk_telemetry_gauges():
    """Driver.stats surfaces the classify/fallback counters and publishes
    them as kueue_burst_* gauges."""
    d, clock = make_driver(True, [
        two_flavor_cq("cq", 2 * K, 2 * K, preemption=LOWER,
                      fungibility=ff(wcp=PREEMPT))])
    admit(d, "occupant", "cq", {"cpu": ("f1", 2 * K)}, priority=50)
    incoming(d, "w", "cq", {"cpu": 2 * K})
    cycle(d, clock)
    cycle(d, clock)
    fw = d.stats["flavor_walk"]
    assert fw["resume_heads"] >= 1 and fw["walk_stop_heads"] >= 1, fw
    assert fw["host_cycles"] == 0, fw
    rendered = d.metrics.render()
    assert "kueue_burst_resume_heads" in rendered
    assert "kueue_burst_walk_stop_heads" in rendered


# ---------------------------------------------------------------- burst parity

def _matrix_spec(d):
    """One cohort, four CQs — one per (whenCanBorrow, whenCanPreempt)
    combo — two flavors each, plus pending load that exercises borrow
    headroom and in-CQ preemption."""
    for f in ("f1", "f2"):
        d.apply_resource_flavor(ResourceFlavor(name=f))
    combos = [("bb", BORROW, TRY_NEXT), ("bp", BORROW, PREEMPT),
              ("tb", TRY_NEXT, TRY_NEXT), ("tp", TRY_NEXT, PREEMPT)]
    for name, wcb, wcp in combos:
        d.apply_cluster_queue(two_flavor_cq(
            f"cq-{name}", 2 * K, 2 * K, cohort="co", preemption=LOWER,
            fungibility=ff(wcb=wcb, wcp=wcp)))
        d.apply_local_queue(LocalQueue(name=f"lq-{name}",
                                       cluster_queue=f"cq-{name}"))
    n = 0
    for name, _, _ in combos:
        for i in range(5):
            n += 1
            d.create_workload(Workload(
                name=f"w-{name}-{i}", queue_name=f"lq-{name}",
                priority=(i % 3) * 10, creation_time=float(n),
                pod_sets=[PodSet(name="main", count=1,
                                 requests={"cpu": 1500})]))


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_burst_parity_fungibility_matrix():
    """Fused burst == sequential host cycles across the full policy
    matrix with preemptions and finish-driven unparking."""
    from tests.test_burst import assert_parity
    assert_parity(_matrix_spec, cycles=14, runtime=3)


def test_burst_parity_mid_list_resume():
    """The carried resume plane must reproduce the host's requeue-and-
    resume behaviour inside one fused dispatch."""
    def spec(d):
        for f in ("f1", "f2"):
            d.apply_resource_flavor(ResourceFlavor(name=f))
        d.apply_cluster_queue(two_flavor_cq(
            "cq", 2 * K, 2 * K, preemption=LOWER,
            fungibility=ff(wcp=PREEMPT)))
        d.apply_local_queue(LocalQueue(name="lq-cq", cluster_queue="cq"))
        d.create_workload(Workload(
            name="occupant", queue_name="lq-cq", priority=50,
            creation_time=1.0,
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": 2 * K})]))
        d.create_workload(Workload(
            name="w", queue_name="lq-cq", priority=0, creation_time=2.0,
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": 2 * K})]))
    from tests.test_burst import assert_parity
    assert_parity(spec, cycles=6, runtime=0)
