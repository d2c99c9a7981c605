"""Scheduler cycle tests mirroring reference pkg/scheduler/scheduler_test.go
and preemption_test.go scenarios (fake-cluster harness style)."""

import pytest

from kueue_tpu.api.types import (
    BorrowWithinCohort,
    BorrowWithinCohortPolicy,
    ClusterQueue,
    FairSharing,
    FlavorFungibility,
    FlavorFungibilityPolicy,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    QueueingStrategy,
    ReclaimWithinCohort,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    WithinClusterQueue,
    Workload,
    WL_EVICTED,
)
from kueue_tpu.controller.driver import Driver
from kueue_tpu.resources import FlavorResource
from tests.conftest import FakeClock


def simple_cq(name, cohort=None, nominal=10_000, flavors=("default",),
              preemption=None, borrowing_limit=None, lending_limit=None,
              strategy=QueueingStrategy.BEST_EFFORT_FIFO, weight=None,
              fungibility=None):
    return ClusterQueue(
        name=name, cohort=cohort, queueing_strategy=strategy,
        preemption=preemption or PreemptionPolicy(),
        flavor_fungibility=fungibility or FlavorFungibility(),
        fair_sharing=FairSharing(weight=weight) if weight is not None else None,
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name=f, resources={
                "cpu": ResourceQuota(nominal=nominal,
                                     borrowing_limit=borrowing_limit,
                                     lending_limit=lending_limit)})
                     for f in flavors])])


def make_driver(clock=None, **kw):
    d = Driver(clock=clock or FakeClock(), **kw)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    return d


def wl(name, cpu_milli=1000, count=1, priority=0, queue="lq", created=None,
       clock=None, min_count=None):
    return Workload(
        name=name, queue_name=queue, priority=priority,
        creation_time=created if created is not None else (clock.t if clock else 0.0),
        pod_sets=[PodSet(name="main", count=count, min_count=min_count,
                         requests={"cpu": cpu_milli})])


FR = FlavorResource("default", "cpu")


def test_simple_admission_fifo():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq("cq", nominal=3000))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    for i in range(5):
        d.create_workload(wl(f"w{i}", cpu_milli=1000, created=float(i + 1)))
    stats = d.run_until_settled()
    # 3 fit, 2 pending
    assert d.admitted_keys() == {"default/w0", "default/w1", "default/w2"}
    assert d.queues.pending_workloads("cq") == 2
    # finishing one admits the next in FIFO order
    d.finish_workload("default/w0")
    d.run_until_settled()
    assert "default/w3" in d.admitted_keys()
    assert "default/w4" not in d.admitted_keys()


def test_priority_order_admission():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq("cq", nominal=1000))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("low", priority=1, created=1.0))
    d.create_workload(wl("high", priority=10, created=2.0))
    d.run_until_settled()
    assert d.admitted_keys() == {"default/high"}


def test_borrowing_within_cohort():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq("cq-a", cohort="team", nominal=2000))
    d.apply_cluster_queue(simple_cq("cq-b", cohort="team", nominal=2000))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq-a"))
    d.apply_local_queue(LocalQueue(name="lq-b", cluster_queue="cq-b"))
    d.create_workload(wl("big", cpu_milli=4000))
    d.run_until_settled()
    assert d.admitted_keys() == {"default/big"}  # borrows 2 from cq-b


def test_non_borrowing_entries_admitted_first():
    # entry ordering: request under nominal quota first (scheduler.go:571)
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq("cq-a", cohort="team", nominal=2000))
    d.apply_cluster_queue(simple_cq("cq-b", cohort="team", nominal=2000))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq-a"))
    d.apply_local_queue(LocalQueue(name="lq-b", cluster_queue="cq-b"))
    # borrower (3 CPU in cq-a) vs in-quota (2 CPU in cq-b), borrower higher prio
    d.create_workload(wl("borrower", cpu_milli=3000, priority=100, created=1.0))
    d.create_workload(wl("fits", cpu_milli=2000, queue="lq-b", created=2.0))
    stats = d.schedule_once()
    assert "default/fits" in stats.admitted
    # borrower sees cohort capacity shrink mid-cycle and is skipped
    assert "default/borrower" not in stats.admitted


def test_preemption_within_cluster_queue():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq(
        "cq", nominal=2000,
        preemption=PreemptionPolicy(
            within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("low", cpu_milli=2000, priority=1, created=1.0))
    d.run_until_settled()
    assert d.admitted_keys() == {"default/low"}
    clock.tick()
    d.create_workload(wl("high", cpu_milli=2000, priority=100, created=clock.t))
    d.run_until_settled()
    low = d.workload("default/low")
    assert low.condition_true(WL_EVICTED)
    assert d.admitted_keys() == {"default/high"}


def test_no_preemption_when_policy_never():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq("cq", nominal=2000))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("low", cpu_milli=2000, priority=1))
    d.run_until_settled()
    clock.tick()
    d.create_workload(wl("high", cpu_milli=2000, priority=100))
    d.run_until_settled()
    assert d.admitted_keys() == {"default/low"}
    assert not d.workload("default/low").condition_true(WL_EVICTED)


def test_reclaim_within_cohort():
    # cq-b borrows from cq-a; cq-a reclaims its nominal quota
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq(
        "cq-a", cohort="team", nominal=2000,
        preemption=PreemptionPolicy(reclaim_within_cohort=ReclaimWithinCohort.ANY)))
    d.apply_cluster_queue(simple_cq("cq-b", cohort="team", nominal=2000))
    d.apply_local_queue(LocalQueue(name="lq-a", cluster_queue="cq-a"))
    d.apply_local_queue(LocalQueue(name="lq-b", cluster_queue="cq-b"))
    d.create_workload(wl("borrower", cpu_milli=4000, queue="lq-b", priority=100))
    d.run_until_settled()
    assert d.admitted_keys() == {"default/borrower"}
    clock.tick()
    # lower priority, but reclaiming nominal quota: preempts the borrower
    d.create_workload(wl("owner", cpu_milli=2000, queue="lq-a", priority=1))
    d.run_until_settled()
    assert d.workload("default/borrower").condition_true(WL_EVICTED)
    assert "default/owner" in d.admitted_keys()


def test_reclaim_lower_priority_only():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq(
        "cq-a", cohort="team", nominal=2000,
        preemption=PreemptionPolicy(
            reclaim_within_cohort=ReclaimWithinCohort.LOWER_PRIORITY)))
    d.apply_cluster_queue(simple_cq("cq-b", cohort="team", nominal=2000))
    d.apply_local_queue(LocalQueue(name="lq-a", cluster_queue="cq-a"))
    d.apply_local_queue(LocalQueue(name="lq-b", cluster_queue="cq-b"))
    d.create_workload(wl("borrower", cpu_milli=4000, queue="lq-b", priority=100))
    d.run_until_settled()
    clock.tick()
    d.create_workload(wl("owner", cpu_milli=2000, queue="lq-a", priority=1))
    d.run_until_settled()
    # borrower has HIGHER priority -> cannot reclaim
    assert not d.workload("default/borrower").condition_true(WL_EVICTED)
    assert "default/owner" not in d.admitted_keys()


def test_preempted_workload_requeues_and_readmits():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq(
        "cq", nominal=2000,
        preemption=PreemptionPolicy(
            within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("low", cpu_milli=2000, priority=1, created=1.0))
    d.run_until_settled()
    clock.tick()
    d.create_workload(wl("high", cpu_milli=2000, priority=100, created=clock.t))
    d.run_until_settled()
    assert d.admitted_keys() == {"default/high"}
    # low is requeued; finishing high readmits low
    d.finish_workload("default/high")
    d.run_until_settled()
    assert d.admitted_keys() == {"default/low"}


def test_partial_admission():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq("cq", nominal=3000))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("elastic", cpu_milli=1000, count=10, min_count=2))
    d.run_until_settled()
    assert d.admitted_keys() == {"default/elastic"}
    admitted = d.workload("default/elastic")
    assert admitted.admission.pod_set_assignments[0].count == 3


def test_flavor_fungibility_try_next_flavor():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_resource_flavor(ResourceFlavor(name="spot"))
    d.apply_resource_flavor(ResourceFlavor(name="on-demand"))
    d.apply_cluster_queue(ClusterQueue(
        name="cq",
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[
                FlavorQuotas(name="spot",
                             resources={"cpu": ResourceQuota(nominal=1000)}),
                FlavorQuotas(name="on-demand",
                             resources={"cpu": ResourceQuota(nominal=5000)}),
            ])]))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    # spot is full after w1; w2 lands on on-demand
    d.create_workload(wl("w1", cpu_milli=1000, created=1.0))
    d.create_workload(wl("w2", cpu_milli=1000, created=2.0))
    d.run_until_settled()
    w1 = d.workload("default/w1")
    w2 = d.workload("default/w2")
    assert w1.admission.pod_set_assignments[0].flavors["cpu"] == "spot"
    assert w2.admission.pod_set_assignments[0].flavors["cpu"] == "on-demand"


@pytest.mark.parametrize("use_device_solver", [False, True])
def test_taints_block_flavor(use_device_solver):
    clock = FakeClock()
    d = make_driver(clock, use_device_solver=use_device_solver)
    from kueue_tpu.api.types import Taint, Toleration
    d.apply_resource_flavor(ResourceFlavor(
        name="tainted", node_taints=[Taint(key="gpu", value="true")]))
    d.apply_cluster_queue(simple_cq("cq", flavors=("tainted",)))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("plain"))
    d.run_until_settled()
    assert d.admitted_keys() == set()
    # a tolerating workload is admitted
    tol = Workload(name="tolerant", queue_name="lq", creation_time=5.0,
                   pod_sets=[PodSet(name="main", count=1,
                                    requests={"cpu": 1000},
                                    tolerations=[Toleration(key="gpu",
                                                            value="true")])])
    d.create_workload(tol)
    d.run_until_settled()
    assert d.admitted_keys() == {"default/tolerant"}
    if use_device_solver:
        # a taint is no reason for the host walk: the vector classify
        # decided both heads, the first under a mask that bars the flavor
        stats = d.scheduler.solver.stats
        assert stats["scalar_heads"] == 0 and stats["host_cycles"] == 0
        assert "cq_shape" not in stats["scalar_reasons"]
        assert stats["constrained_heads"] >= 1


def test_borrow_within_cohort_preemption():
    # preemptor borrows while preempting lower-priority workloads elsewhere
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq(
        "cq-a", cohort="team", nominal=2000,
        preemption=PreemptionPolicy(
            reclaim_within_cohort=ReclaimWithinCohort.ANY,
            borrow_within_cohort=BorrowWithinCohort(
                policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
                max_priority_threshold=50))))
    d.apply_cluster_queue(simple_cq("cq-b", cohort="team", nominal=2000))
    d.apply_local_queue(LocalQueue(name="lq-a", cluster_queue="cq-a"))
    d.apply_local_queue(LocalQueue(name="lq-b", cluster_queue="cq-b"))
    d.create_workload(wl("low-b", cpu_milli=3000, queue="lq-b", priority=10))
    d.run_until_settled()
    clock.tick()
    # needs 3 CPU: borrows 1 beyond its nominal 2 while preempting low-b
    d.create_workload(wl("pri-a", cpu_milli=3000, queue="lq-a", priority=100))
    d.run_until_settled()
    assert d.workload("default/low-b").condition_true(WL_EVICTED)
    assert "default/pri-a" in d.admitted_keys()


def test_fair_sharing_prefers_lower_share():
    clock = FakeClock()
    d = Driver(clock=clock, fair_sharing=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    d.apply_cluster_queue(simple_cq("cq-a", cohort="team", nominal=1000))
    d.apply_cluster_queue(simple_cq("cq-b", cohort="team", nominal=1000))
    d.apply_cluster_queue(simple_cq("cq-c", cohort="team", nominal=4000))
    d.apply_local_queue(LocalQueue(name="lq-a", cluster_queue="cq-a"))
    d.apply_local_queue(LocalQueue(name="lq-b", cluster_queue="cq-b"))
    # cq-a already borrowing heavily
    d.create_workload(wl("a-big", cpu_milli=3000, queue="lq-a", created=1.0))
    d.run_until_settled()
    # one more head in each queue; only 3 CPU left in cohort
    d.create_workload(wl("a-more", cpu_milli=3000, queue="lq-a", created=2.0))
    d.create_workload(wl("b-first", cpu_milli=3000, queue="lq-b", created=3.0))
    stats = d.schedule_once()
    # fair sharing admits the lower-share CQ's workload first
    assert "default/b-first" in stats.admitted
    assert "default/a-more" not in stats.admitted


def test_fair_sharing_preemption():
    clock = FakeClock()
    d = Driver(clock=clock, fair_sharing=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    prem = PreemptionPolicy(reclaim_within_cohort=ReclaimWithinCohort.ANY)
    d.apply_cluster_queue(simple_cq("cq-a", cohort="team", nominal=3000,
                                    preemption=prem))
    d.apply_cluster_queue(simple_cq("cq-b", cohort="team", nominal=3000,
                                    preemption=prem))
    d.apply_local_queue(LocalQueue(name="lq-a", cluster_queue="cq-a"))
    d.apply_local_queue(LocalQueue(name="lq-b", cluster_queue="cq-b"))
    # cq-b over its share: 3 × 2 CPU = 6 CPU (borrowing 3)
    for i in range(3):
        d.create_workload(wl(f"b{i}", cpu_milli=2000, queue="lq-b",
                             created=float(i + 1)))
    d.run_until_settled()
    assert len(d.admitted_keys()) == 3
    clock.tick()
    # cq-a at zero usage asks for its share: preempts from cq-b
    d.create_workload(wl("a0", cpu_milli=2000, queue="lq-a", created=clock.t))
    d.run_until_settled()
    assert "default/a0" in d.admitted_keys()
    evicted = [k for k in ("default/b0", "default/b1", "default/b2")
               if d.workload(k).condition_true(WL_EVICTED)]
    assert len(evicted) == 1


def test_strict_fifo_blocks_behind_head():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq("cq", nominal=3000,
                                    strategy=QueueingStrategy.STRICT_FIFO))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("huge", cpu_milli=5000, priority=10, created=1.0))
    d.create_workload(wl("tiny", cpu_milli=1000, priority=1, created=2.0))
    d.run_until_settled()
    # head-of-line blocking: tiny must NOT be admitted past the blocked head
    assert d.admitted_keys() == set()


def test_best_effort_fifo_skips_blocked_head():
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_cluster_queue(simple_cq("cq", nominal=3000))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("huge", cpu_milli=5000, priority=10, created=1.0))
    d.create_workload(wl("tiny", cpu_milli=1000, priority=1, created=2.0))
    d.run_until_settled()
    assert d.admitted_keys() == {"default/tiny"}


def test_admission_checks_two_phase():
    from kueue_tpu.api.types import AdmissionCheck, AdmissionCheckState
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_admission_check(AdmissionCheck(name="prov", controller_name="test"))
    cq = simple_cq("cq")
    cq.admission_checks = ["prov"]
    d.apply_cluster_queue(cq)
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("w1"))
    d.run_until_settled()
    w = d.workload("default/w1")
    assert w.condition_true("QuotaReserved")
    assert not w.is_admitted  # waiting on the check
    d.set_admission_check_state("default/w1", "prov", AdmissionCheckState.READY)
    assert d.workload("default/w1").is_admitted


def test_admission_check_retry_evicts():
    from kueue_tpu.api.types import AdmissionCheck, AdmissionCheckState
    clock = FakeClock()
    d = make_driver(clock)
    d.apply_admission_check(AdmissionCheck(name="prov", controller_name="test"))
    cq = simple_cq("cq")
    cq.admission_checks = ["prov"]
    d.apply_cluster_queue(cq)
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(wl("w1"))
    d.run_until_settled()
    d.set_admission_check_state("default/w1", "prov", AdmissionCheckState.RETRY)
    w = d.workload("default/w1")
    assert w.condition_true(WL_EVICTED)
    assert w.admission is None
    # it requeues and re-reserves
    d.run_until_settled()
    assert d.workload("default/w1").condition_true("QuotaReserved")
