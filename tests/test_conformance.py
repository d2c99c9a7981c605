"""Conformance replay of the reference scheduler test tables (VERDICT r2
item #8, SURVEY §7 stage 9).

Scenario data is transliterated from
/root/reference/pkg/scheduler/scheduler_test.go TestSchedule (the shared
sales / eng-alpha / eng-beta / lend fixture and its table cases); the
expectations below — scheduled sets, assigned flavors, preempted sets,
heap-vs-parking placement — are the REFERENCE's `want*` values, not
host-vs-device parity.  Every case runs on both the host path and the
device solver path and must produce the reference's decisions.
"""

import pytest

from kueue_tpu.api.types import (
    Admission,
    BorrowWithinCohort,
    BorrowWithinCohortPolicy,
    ClusterQueue,
    Cohort,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PodSetAssignment,
    PreemptionPolicy,
    QueueingStrategy,
    ReclaimWithinCohort,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    WithinClusterQueue,
    Workload,
)
from kueue_tpu.controller.driver import Driver
from kueue_tpu.workload import set_quota_reservation, sync_admitted_condition
from tests.conftest import FakeClock


NAMESPACES = {
    "sales": {"dep": "sales"},
    "eng-alpha": {"dep": "eng"},
    "eng-beta": {"dep": "eng"},
    "lend": {"dep": "lend"},
}


def fixture_driver(use_device, extra_cqs=(), extra_lqs=(), extra_cohorts=(),
                   fair_sharing=False):
    """The TestSchedule shared fixture (scheduler_test.go:78-180)."""
    clock = FakeClock()
    d = Driver(clock=clock, namespaces=NAMESPACES,
               use_device_solver=use_device, fair_sharing=fair_sharing)
    for cohort in extra_cohorts:
        d.apply_cohort(cohort)
    for f in ("default", "on-demand", "spot", "model-a"):
        d.apply_resource_flavor(ResourceFlavor(name=f))
    # the reference gives sales borrowingLimit "0" — with no cohort that
    # is semantically no-borrowing, which our webhook expresses as nil
    d.apply_cluster_queue(ClusterQueue(
        name="sales", namespace_selector={"dep": "sales"},
        queueing_strategy=QueueingStrategy.STRICT_FIFO,
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=50_000)})])]))
    d.apply_cluster_queue(ClusterQueue(
        name="eng-alpha", cohort="eng", namespace_selector={"dep": "eng"},
        queueing_strategy=QueueingStrategy.STRICT_FIFO,
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "cpu": ResourceQuota(nominal=50_000,
                                     borrowing_limit=50_000)}),
            FlavorQuotas(name="spot", resources={
                "cpu": ResourceQuota(nominal=100_000,
                                     borrowing_limit=0)})])]))
    d.apply_cluster_queue(ClusterQueue(
        name="eng-beta", cohort="eng", namespace_selector={"dep": "eng"},
        queueing_strategy=QueueingStrategy.STRICT_FIFO,
        preemption=PreemptionPolicy(
            reclaim_within_cohort=ReclaimWithinCohort.ANY,
            within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY),
        resource_groups=[
            ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="on-demand", resources={
                    "cpu": ResourceQuota(nominal=50_000,
                                         borrowing_limit=10_000)}),
                FlavorQuotas(name="spot", resources={
                    "cpu": ResourceQuota(nominal=0,
                                         borrowing_limit=100_000)})]),
            ResourceGroup(covered_resources=["example.com/gpu"], flavors=[
                FlavorQuotas(name="model-a", resources={
                    "example.com/gpu": ResourceQuota(
                        nominal=20, borrowing_limit=0)})]),
        ]))
    d.apply_cluster_queue(ClusterQueue(
        name="flavor-nonexistent-cq",
        queueing_strategy=QueueingStrategy.STRICT_FIFO,
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="nonexistent-flavor", resources={
                "cpu": ResourceQuota(nominal=50_000)})])]))
    d.apply_cluster_queue(ClusterQueue(
        name="lend-a", cohort="lend", namespace_selector={"dep": "lend"},
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=3_000, lending_limit=2_000)})])]))
    d.apply_cluster_queue(ClusterQueue(
        name="lend-b", cohort="lend", namespace_selector={"dep": "lend"},
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=2_000, lending_limit=2_000)})])]))
    for cq in extra_cqs:
        d.apply_cluster_queue(cq)
    for ns, name, cq in (
            ("sales", "main", "sales"), ("sales", "blocked", "eng-alpha"),
            ("eng-alpha", "main", "eng-alpha"),
            ("eng-beta", "main", "eng-beta"),
            ("sales", "flavor-nonexistent-queue", "flavor-nonexistent-cq"),
            ("sales", "cq-nonexistent-queue", "nonexistent-cq"),
            ("lend", "lend-a-queue", "lend-a"),
            ("lend", "lend-b-queue", "lend-b")) + tuple(extra_lqs):
        d.apply_local_queue(LocalQueue(name=name, namespace=ns,
                                       cluster_queue=cq))
    return d, clock


def pending(d, name, ns, queue, podsets, priority=0, created=None):
    seq = len(d.workloads) + 1
    d.create_workload(Workload(
        name=name, namespace=ns, queue_name=queue, priority=priority,
        creation_time=created if created is not None else float(seq),
        pod_sets=[PodSet(name=pn, count=c, requests=dict(req))
                  for pn, c, req in podsets]))


def admitted(d, name, ns, cq, assignments, priority=0, queue=""):
    """Pre-admitted workload (ReserveQuota in the reference builders).

    assignments: [(podset, count, {res: qty}, {res: flavor})]."""
    wl = Workload(
        name=name, namespace=ns, queue_name=queue, priority=priority,
        creation_time=0.5,
        pod_sets=[PodSet(name=pn, count=c, requests=dict(req))
                  for pn, c, req, _ in assignments])
    adm = Admission(cluster_queue=cq, pod_set_assignments=[
        PodSetAssignment(name=pn, flavors=dict(flv),
                         resource_usage=dict(req), count=c)
        for pn, c, req, flv in assignments])
    set_quota_reservation(wl, adm, 0.5)
    sync_admitted_condition(wl, 0.5)
    d.restore_workload(wl)


def flavors_of(d, key):
    wl = d.workload(key)
    return {a.name: dict(a.flavors) for a in wl.admission.pod_set_assignments}


def queue_state(d, cq_name):
    q = d.queues.queue_for(cq_name)
    heap = set(q.heap.keys()) if q else set()
    if q and q.inflight is not None:
        heap.add(q.inflight.key)
    parked = set(q.inadmissible.keys()) if q else set()
    return heap, parked


def run_case(d, clock, n_cycles=1):
    out = None
    for _ in range(n_cycles):
        clock.t += 1.0
        out = d.schedule_once()
    return out


@pytest.fixture(params=[False, True], ids=["host", "device"])
def use_device(request):
    return request.param


# --- scheduler_test.go:280 "workload fits in single clusterQueue" -------

def test_fits_in_single_cq(use_device):
    d, clock = fixture_driver(use_device)
    pending(d, "foo", "sales", "main", [("one", 10, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"sales/foo"}
    assert flavors_of(d, "sales/foo") == {"one": {"cpu": "default"}}


# --- :420 "single clusterQueue full" ------------------------------------

def test_single_cq_full(use_device):
    d, clock = fixture_driver(use_device)
    admitted(d, "assigned", "sales", "sales",
             [("one", 40, {"cpu": 40_000}, {"cpu": "default"})])
    pending(d, "new", "sales", "main", [("one", 11, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert not stats.admitted
    heap, parked = queue_state(d, "sales")
    assert "sales/new" in heap | parked


# --- :456 "failed to match clusterQueue selector" -----------------------

def test_namespace_selector_mismatch(use_device):
    d, clock = fixture_driver(use_device)
    pending(d, "new", "sales", "blocked", [("one", 1, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert not stats.admitted
    _, parked = queue_state(d, "eng-alpha")
    assert "sales/new" in parked     # wantInadmissibleLeft


# --- :469 "admit in different cohorts" ----------------------------------

def test_admit_in_different_cohorts(use_device):
    d, clock = fixture_driver(use_device)
    pending(d, "new", "sales", "main", [("one", 1, {"cpu": 1000})])
    pending(d, "new", "eng-alpha", "main", [("one", 51, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"sales/new", "eng-alpha/new"}
    assert flavors_of(d, "eng-alpha/new") == {"one": {"cpu": "on-demand"}}


# --- :518 "admit in same cohort with no borrowing" ----------------------

def test_admit_same_cohort_no_borrowing(use_device):
    d, clock = fixture_driver(use_device)
    pending(d, "new", "eng-alpha", "main", [("one", 40, {"cpu": 1000})])
    pending(d, "new", "eng-beta", "main", [("one", 40, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-alpha/new", "eng-beta/new"}
    assert flavors_of(d, "eng-alpha/new") == {"one": {"cpu": "on-demand"}}
    assert flavors_of(d, "eng-beta/new") == {"one": {"cpu": "on-demand"}}


# --- :567 "assign multiple resources and flavors" -----------------------

def test_assign_multiple_resources_and_flavors(use_device):
    """Multi-PodSet + multi-resource-group: pod set one lands on
    on-demand cpu + model-a gpu, pod set two overflows to spot."""
    d, clock = fixture_driver(use_device)
    pending(d, "new", "eng-beta", "main", [
        ("one", 10, {"cpu": 6000, "example.com/gpu": 1}),
        ("two", 40, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-beta/new"}
    assert flavors_of(d, "eng-beta/new") == {
        "one": {"cpu": "on-demand", "example.com/gpu": "model-a"},
        "two": {"cpu": "spot"}}


# --- :613/:650 overadmission-while-borrowing pair -----------------------

def test_cannot_borrow_when_overadmission(use_device):
    d, clock = fixture_driver(use_device)
    pending(d, "new", "eng-alpha", "main", [("one", 45, {"cpu": 1000})])
    pending(d, "new", "eng-beta", "main", [("one", 56, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-alpha/new"}
    heap, parked = queue_state(d, "eng-beta")
    assert "eng-beta/new" in heap | parked


def test_can_borrow_without_overadmission(use_device):
    d, clock = fixture_driver(use_device)
    pending(d, "new", "eng-alpha", "main", [("one", 45, {"cpu": 1000})])
    pending(d, "new", "eng-beta", "main", [("one", 55, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-alpha/new", "eng-beta/new"}
    assert flavors_of(d, "eng-beta/new") == {"one": {"cpu": "on-demand"}}


# --- :699 "can borrow if needs reclaim from cohort in different flavor" -

def test_borrow_while_other_needs_reclaim(use_device):
    d, clock = fixture_driver(use_device)
    admitted(d, "user-on-demand", "eng-beta", "eng-beta",
             [("main", 1, {"cpu": 50_000}, {"cpu": "on-demand"})])
    admitted(d, "user-spot", "eng-beta", "eng-beta",
             [("main", 1, {"cpu": 1000}, {"cpu": "spot"})])
    pending(d, "can-reclaim", "eng-alpha", "main",
            [("main", 1, {"cpu": 100_000})])
    pending(d, "needs-to-borrow", "eng-beta", "main",
            [("main", 1, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-beta/needs-to-borrow"}
    assert flavors_of(d, "eng-beta/needs-to-borrow") == {
        "main": {"cpu": "on-demand"}}
    heap, parked = queue_state(d, "eng-alpha")
    assert "eng-alpha/can-reclaim" in heap | parked


# --- :730 "workload exceeds lending limit when borrow in cohort" --------

def test_lending_limit_blocks_borrowing(use_device):
    d, clock = fixture_driver(use_device)
    admitted(d, "a", "lend", "lend-b",
             [("main", 1, {"cpu": 2000}, {"cpu": "default"})])
    pending(d, "b", "lend", "lend-b-queue", [("main", 1, {"cpu": 3000})])
    stats = run_case(d, clock)
    assert not stats.admitted
    heap, parked = queue_state(d, "lend-b")
    assert "lend/b" in heap | parked


# --- :768 "preempt workloads in ClusterQueue and cohort" ----------------

def test_preempt_in_cq_and_cohort(use_device):
    d, clock = fixture_driver(use_device)
    admitted(d, "use-all-spot", "eng-alpha", "eng-alpha",
             [("main", 1, {"cpu": 100_000}, {"cpu": "spot"})])
    admitted(d, "low-1", "eng-beta", "eng-beta",
             [("main", 1, {"cpu": 30_000}, {"cpu": "on-demand"})],
             priority=-1)
    admitted(d, "low-2", "eng-beta", "eng-beta",
             [("main", 1, {"cpu": 10_000}, {"cpu": "on-demand"})],
             priority=-2)
    admitted(d, "borrower", "eng-alpha", "eng-alpha",
             [("main", 1, {"cpu": 60_000}, {"cpu": "on-demand"})])
    pending(d, "preemptor", "eng-beta", "main",
            [("main", 1, {"cpu": 20_000})])
    stats = run_case(d, clock)
    assert not stats.admitted
    assert set(stats.preempted_targets) == {"eng-alpha/borrower",
                                            "eng-beta/low-2"}
    assert set(stats.preempting) == {"eng-beta/preemptor"}


# --- :806 "multiple CQs need preemption" --------------------------------

def test_multiple_cqs_need_preemption(use_device):
    extra_cqs = [
        ClusterQueue(
            name="other-alpha", cohort="other",
            resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="on-demand", resources={
                    "cpu": ResourceQuota(nominal=50_000,
                                         borrowing_limit=50_000)})])]),
        ClusterQueue(
            name="other-beta", cohort="other",
            preemption=PreemptionPolicy(
                reclaim_within_cohort=ReclaimWithinCohort.ANY,
                within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY),
            resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="on-demand", resources={
                    "cpu": ResourceQuota(nominal=50_000,
                                         borrowing_limit=10_000)})])]),
    ]
    extra_lqs = (("eng-alpha", "other", "other-alpha"),
                 ("eng-beta", "other", "other-beta"))
    d, clock = fixture_driver(use_device, extra_cqs, extra_lqs)
    admitted(d, "use-all", "eng-alpha", "other-alpha",
             [("main", 1, {"cpu": 100_000}, {"cpu": "on-demand"})])
    pending(d, "preemptor", "eng-beta", "other",
            [("main", 1, {"cpu": 1000})], priority=-1)
    pending(d, "pending", "eng-alpha", "other",
            [("main", 1, {"cpu": 1000})], priority=1)
    stats = run_case(d, clock)
    assert not stats.admitted
    assert set(stats.preempted_targets) == {"eng-alpha/use-all"}
    heap_b, parked_b = queue_state(d, "other-beta")
    assert "eng-beta/preemptor" in heap_b | parked_b
    heap_a, parked_a = queue_state(d, "other-alpha")
    assert "eng-alpha/pending" in heap_a | parked_a


# --- :860 "cannot borrow resource not listed in clusterQueue" -----------

def test_cannot_borrow_unlisted_resource(use_device):
    d, clock = fixture_driver(use_device)
    pending(d, "new", "eng-alpha", "main",
            [("main", 1, {"example.com/gpu": 1})])
    stats = run_case(d, clock)
    assert not stats.admitted
    heap, parked = queue_state(d, "eng-alpha")
    assert "eng-alpha/new" in heap | parked


# --- :871 "not enough resources to borrow, fallback to next flavor" -----

def test_borrow_fallback_to_next_flavor(use_device):
    d, clock = fixture_driver(use_device)
    admitted(d, "existing", "eng-beta", "eng-beta",
             [("one", 45, {"cpu": 45_000}, {"cpu": "on-demand"})])
    pending(d, "new", "eng-alpha", "main", [("one", 60, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-alpha/new"}
    assert flavors_of(d, "eng-alpha/new") == {"one": {"cpu": "spot"}}


# --- :920/:928 nonexistent CQ / flavor ----------------------------------

def test_nonexistent_cluster_queue(use_device):
    d, clock = fixture_driver(use_device)
    pending(d, "foo", "sales", "cq-nonexistent-queue",
            [("main", 1, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert not stats.admitted
    assert d.workload("sales/foo").admission is None


def test_nonexistent_flavor(use_device):
    d, clock = fixture_driver(use_device)
    pending(d, "foo", "sales", "flavor-nonexistent-queue",
            [("main", 1, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert not stats.admitted
    heap, parked = queue_state(d, "flavor-nonexistent-cq")
    assert "sales/foo" in heap | parked


# --- :1060 "partial admission single variable pod set" ------------------

def test_partial_admission_single_pod_set(use_device):
    """count=50 × 2cpu against the sales 50-cpu quota, min_count=20:
    the largest fitting count (25) is admitted."""
    d, clock = fixture_driver(use_device)
    d.create_workload(Workload(
        name="new", namespace="sales", queue_name="main", creation_time=1.0,
        pod_sets=[PodSet(name="one", count=50, min_count=20,
                         requests={"cpu": 2000})]))
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"sales/new"}
    adm = d.workload("sales/new").admission
    assert adm.pod_set_assignments[0].count == 25
    assert adm.pod_set_assignments[0].flavors == {"cpu": "default"}
    assert_partial_on_vector_path(d, searched=use_device)


def assert_partial_on_vector_path(d, searched):
    """The device engine decides a partial-admission head on the vector
    path: no host walk, and a count search where the full counts
    neither fit nor find targets."""
    if d.scheduler.solver is None:
        return
    solver = d.scheduler.solver.stats
    assert solver["scalar_heads"] == 0, solver
    assert (solver["partial_heads"] > 0) == searched, solver


# --- :1251/:1286/:1321 same-cycle borrowing trio ------------------------

def _borrow_trio_fixture(use_device, wl1_req, wl2_req):
    """cq1/cq2/cq3 in cohort co, each r1/r2 nominal 10 borrow 10."""
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
    extra_cqs = [ClusterQueue(
        name=f"cq{i}", cohort="co", preemption=pre,
        resource_groups=[ResourceGroup(covered_resources=["r1", "r2"],
                                       flavors=[FlavorQuotas(
                                           name="default", resources={
                                               "r1": ResourceQuota(nominal=10, borrowing_limit=10),
                                               "r2": ResourceQuota(nominal=10, borrowing_limit=10)})])])
        for i in (1, 2, 3)]
    extra_lqs = tuple(("sales", f"lq{i}", f"cq{i}") for i in (1, 2, 3))
    d, clock = fixture_driver(use_device, extra_cqs, extra_lqs)
    pending(d, "wl1", "sales", "lq1", [("main", 1, wl1_req)], priority=-1)
    pending(d, "wl2", "sales", "lq2", [("main", 1, wl2_req)], priority=-2)
    return d, clock


def test_two_borrowers_different_resources_same_cycle(use_device):
    d, clock = _borrow_trio_fixture(use_device, {"r1": 16}, {"r2": 16})
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"sales/wl1", "sales/wl2"}


def test_two_borrowers_same_resource_fits_cohort(use_device):
    d, clock = _borrow_trio_fixture(use_device, {"r1": 16}, {"r1": 14})
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"sales/wl1", "sales/wl2"}


def test_only_one_borrower_when_cohort_cannot_fit(use_device):
    """16+16 > the cohort's 30 r1 capacity: wl1 admits, wl2 is skipped
    after nomination and stays queued (wantLeft, :1321)."""
    d, clock = _borrow_trio_fixture(use_device, {"r1": 16}, {"r1": 16})
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"sales/wl1"}
    assert "sales/wl2" in set(stats.skipped)
    heap, parked = queue_state(d, "cq2")
    assert "sales/wl2" in heap | parked


# --- :1487 "with fair sharing: schedule workload with lowest share first"

def test_fs_lowest_share_first(use_device):
    extra_cqs = [ClusterQueue(
        name="eng-shared", cohort="eng",
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "cpu": ResourceQuota(nominal=10_000,
                                     borrowing_limit=0)})])])]
    d, clock = fixture_driver(use_device, extra_cqs, fair_sharing=True)
    admitted(d, "all_nominal", "eng-alpha", "eng-alpha",
             [("one", 50, {"cpu": 50_000}, {"cpu": "on-demand"})])
    admitted(d, "borrowing", "eng-beta", "eng-beta",
             [("one", 55, {"cpu": 55_000}, {"cpu": "on-demand"})])
    pending(d, "older-new", "eng-beta", "main", [("one", 1, {"cpu": 1000})],
            created=1.0)
    pending(d, "new", "eng-alpha", "main", [("one", 5, {"cpu": 1000})],
            created=2.0)
    stats = run_case(d, clock)
    # eng-beta borrows (share > 0), eng-alpha is all-nominal: alpha wins
    # the tournament despite the later timestamp
    assert set(stats.admitted) == {"eng-alpha/new"}
    heap, parked = queue_state(d, "eng-beta")
    assert "eng-beta/older-new" in heap | parked
    if use_device:
        # eng-beta is a 2-resource-group CQ: since PR 37 its head is
        # walked a group by the vector classify like any other, so this
        # FS cycle is decided in the device tournament
        stats = d.scheduler.solver.stats
        assert stats["scalar_heads"] == 0 and stats["fs_full_cycles"] > 0


# --- :1569 "hierarchical fair sharing ... wins tournament" ---------------

def _hier_fs_driver(use_device):
    cohorts = [
        Cohort(name="coh-a", resource_groups=[ResourceGroup(
            covered_resources=["cpu"], flavors=[FlavorQuotas(
                name="on-demand", resources={
                    "cpu": ResourceQuota(nominal=200_000)})])]),
        Cohort(name="coh-b", parent_name="coh-a"),
        Cohort(name="coh-c", parent_name="coh-a"),
    ]
    extra_cqs = [ClusterQueue(
        name=n, cohort=c,
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "cpu": ResourceQuota(nominal=0)})])])
        for n, c in (("d", "coh-b"), ("e", "coh-b"), ("f", "coh-c"), ("g", "coh-c"))]
    extra_lqs = tuple(("eng-alpha", f"lq-{n}", n) for n in "defg")
    return fixture_driver(use_device, extra_cqs, extra_lqs,
                          extra_cohorts=cohorts, fair_sharing=True)


def test_fs_hierarchical_tournament(use_device):
    """d1 wins: B's post-admission share (100) is below C's (101), and d
    beat e at the lower tournament level (scheduler_test.go:1539-1568)."""
    d, clock = _hier_fs_driver(use_device)
    admitted(d, "d0", "eng-alpha", "d",
             [("one", 1, {"cpu": 10_000}, {"cpu": "on-demand"})])
    admitted(d, "e0", "eng-alpha", "e",
             [("one", 1, {"cpu": 20_000}, {"cpu": "on-demand"})])
    admitted(d, "g0", "eng-alpha", "g",
             [("one", 1, {"cpu": 100_000}, {"cpu": "on-demand"})])
    pending(d, "d1", "eng-alpha", "lq-d", [("one", 1, {"cpu": 70_000})])
    pending(d, "e1", "eng-alpha", "lq-e", [("one", 1, {"cpu": 61_000})])
    pending(d, "f1", "eng-alpha", "lq-f", [("one", 1, {"cpu": 1000})])
    pending(d, "g1", "eng-alpha", "lq-g", [("one", 1, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-alpha/d1"}
    for cq, key in (("e", "eng-alpha/e1"), ("f", "eng-alpha/f1"),
                    ("g", "eng-alpha/g1")):
        heap, parked = queue_state(d, cq)
        assert key in heap | parked, (cq, key)
    if use_device:
        # verdict r3 item 3: plain-admission FS cycles reach FULL mode
        # on the device (the tournament ran in-scan)
        assert d.scheduler.solver.stats["fs_full_cycles"] > 0, \
            d.scheduler.solver.stats


# --- :1681 "lowest drf after admission" ----------------------------------

def test_fs_lowest_drf_after_admission(use_device):
    cohorts = [Cohort(name="coh-a", resource_groups=[ResourceGroup(
        covered_resources=["cpu"], flavors=[FlavorQuotas(
            name="on-demand", resources={
                "cpu": ResourceQuota(nominal=100_000)})])])]
    extra_cqs = [ClusterQueue(
        name=n, cohort="coh-a",
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "cpu": ResourceQuota(nominal=0)})])])
        for n in ("b", "c")]
    extra_lqs = (("eng-alpha", "lq-b", "b"), ("eng-alpha", "lq-c", "c"))
    d, clock = fixture_driver(use_device, extra_cqs, extra_lqs,
                              extra_cohorts=cohorts, fair_sharing=True)
    admitted(d, "b0", "eng-alpha", "b",
             [("one", 1, {"cpu": 10_000}, {"cpu": "on-demand"})])
    pending(d, "b1", "eng-alpha", "lq-b", [("one", 1, {"cpu": 50_000})])
    pending(d, "c1", "eng-alpha", "lq-c", [("one", 1, {"cpu": 75_000})])
    stats = run_case(d, clock)
    # b0+b1 = 60 < c1 = 75: b1 schedules first
    assert set(stats.admitted) == {"eng-alpha/b1"}


# --- :1816/:1870 FS priority and timestamp tie-breaks --------------------

def _two_cq_cohort_driver(use_device):
    cohorts = [Cohort(name="coh-a", resource_groups=[ResourceGroup(
        covered_resources=["cpu"], flavors=[FlavorQuotas(
            name="on-demand", resources={
                "cpu": ResourceQuota(nominal=10_000)})])])]
    extra_cqs = [ClusterQueue(
        name=n, cohort="coh-a",
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "cpu": ResourceQuota(nominal=0)})])])
        for n in ("b", "c")]
    extra_lqs = (("eng-alpha", "lq-b", "b"), ("eng-alpha", "lq-c", "c"))
    return fixture_driver(use_device, extra_cqs, extra_lqs,
                          extra_cohorts=cohorts, fair_sharing=True)


def test_fs_highest_priority_first(use_device):
    d, clock = _two_cq_cohort_driver(use_device)
    pending(d, "b1", "eng-alpha", "lq-b", [("one", 1, {"cpu": 10_000})],
            priority=99)
    pending(d, "c1", "eng-alpha", "lq-c", [("one", 1, {"cpu": 10_000})],
            priority=101)
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-alpha/c1"}
    heap, parked = queue_state(d, "b")
    assert "eng-alpha/b1" in heap | parked


def test_fs_earliest_timestamp_first(use_device):
    d, clock = _two_cq_cohort_driver(use_device)
    pending(d, "b1", "eng-alpha", "lq-b", [("one", 1, {"cpu": 10_000})],
            priority=101, created=2.0)
    pending(d, "c1", "eng-alpha", "lq-c", [("one", 1, {"cpu": 10_000})],
            priority=101, created=1.0)
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-alpha/c1"}
    heap, parked = queue_state(d, "b")
    assert "eng-alpha/b1" in heap | parked


# --- TestScheduleForTAS (scheduler_test.go:4222+) ------------------------

HOSTNAME = "kubernetes.io/hostname"


@pytest.fixture(autouse=True)
def _reset_tas_gate():
    yield
    from kueue_tpu import features
    features.set_feature_gates({"TopologyAwareScheduling": False})


def tas_driver(use_device, cq_flavors):
    """The TestScheduleForTAS fixture: one node x1 (1 cpu / 1Gi / 10
    pods), single-level topology over the hostname label, a TAS flavor
    selecting tas-node=true, and a non-TAS 'default' flavor."""
    from kueue_tpu import features
    from kueue_tpu.api.types import Topology
    from kueue_tpu.cache.tas_cache import NodeInfo
    features.set_feature_gates({"TopologyAwareScheduling": True})
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device)
    d.apply_topology(Topology(name="tas-single-level", levels=[HOSTNAME]))
    d.apply_resource_flavor(ResourceFlavor(
        name="tas-default", node_labels={"tas-node": "true"},
        topology_name="tas-single-level"))
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    d.cache.tas.add_or_update_node(NodeInfo(
        name="x1", labels={"tas-node": "true", HOSTNAME: "x1"},
        capacity={"cpu": 1000, "memory": 1 << 30, "pods": 10}))
    d.apply_cluster_queue(ClusterQueue(
        name="tas-main", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name=f, resources={
                "cpu": ResourceQuota(nominal=50_000)})
                for f in cq_flavors])]))
    d.apply_local_queue(LocalQueue(name="tas-main", cluster_queue="tas-main"))
    return d, clock


def tas_assignment_of(d, key):
    wl = d.workload(key)
    a = wl.admission.pod_set_assignments[0]
    ta = a.topology_assignment
    return (dict(a.flavors),
            None if ta is None else (tuple(ta.levels),
                                     tuple((tuple(dom.values), dom.count)
                                           for dom in ta.domains)))


def test_tas_implied_on_tas_only_cq(use_device):
    """:4288 — no TAS annotation, only-TAS-flavor CQ: admitted on the
    TAS flavor WITH an (implied, unconstrained) topology assignment."""
    d, clock = tas_driver(use_device, ["tas-default"])
    pending(d, "foo", "default", "tas-main", [("one", 1, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"default/foo"}
    flavors, ta = tas_assignment_of(d, "default/foo")
    assert flavors == {"cpu": "tas-default"}
    assert ta == ((HOSTNAME,), ((("x1",), 1),))


def test_tas_request_skips_non_tas_flavor(use_device):
    """:4337 — required hostname placement skips the non-TAS flavor."""
    d, clock = tas_driver(use_device, ["default", "tas-default"])
    seq = len(d.workloads) + 1
    d.create_workload(Workload(
        name="foo", namespace="default", queue_name="tas-main",
        creation_time=float(seq),
        pod_sets=[PodSet(name="one", count=1, requests={"cpu": 1000},
                         topology_request=__import__(
                             "kueue_tpu.api.types", fromlist=["x"]
                         ).PodSetTopologyRequest(required=HOSTNAME))]))
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"default/foo"}
    flavors, ta = tas_assignment_of(d, "default/foo")
    assert flavors == {"cpu": "tas-default"}
    assert ta == ((HOSTNAME,), ((("x1",), 1),))


def test_non_tas_workload_skips_tas_flavor(use_device):
    """:4389 — no TAS annotation with a non-TAS alternative available:
    the TAS flavor is skipped and no topology assignment is attached."""
    d, clock = tas_driver(use_device, ["tas-default", "default"])
    pending(d, "foo", "default", "tas-main", [("one", 1, {"cpu": 1000})])
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"default/foo"}
    flavors, ta = tas_assignment_of(d, "default/foo")
    assert flavors == {"cpu": "default"}
    assert ta is None


def test_tas_workload_exceeds_node_capacity(use_device):
    """:4648 — 2 pods x 1 cpu against a 1-cpu node: inadmissible."""
    from kueue_tpu.api.types import PodSetTopologyRequest
    d, clock = tas_driver(use_device, ["tas-default"])
    seq = len(d.workloads) + 1
    d.create_workload(Workload(
        name="foo", namespace="default", queue_name="tas-main",
        creation_time=float(seq),
        pod_sets=[PodSet(name="one", count=2, requests={"cpu": 1000},
                         topology_request=PodSetTopologyRequest(
                             required=HOSTNAME))]))
    stats = run_case(d, clock)
    assert not stats.admitted
    heap, parked = queue_state(d, "tas-main")
    assert "default/foo" in heap | parked


def test_tas_capacity_consumed_by_admitted_workload(use_device):
    """:4674 — the node's capacity is already held by an admitted TAS
    workload: the pending one is inadmissible despite free CQ quota."""
    from kueue_tpu.api.types import (PodSetTopologyRequest,
                                     TopologyAssignment,
                                     TopologyDomainAssignment)
    d, clock = tas_driver(use_device, ["tas-default"])
    wl = Workload(
        name="bar-admitted", namespace="default", queue_name="tas-main",
        creation_time=0.5,
        pod_sets=[PodSet(name="one", count=1, requests={"cpu": 1000},
                         topology_request=PodSetTopologyRequest(
                             required=HOSTNAME))])
    adm = Admission(cluster_queue="tas-main", pod_set_assignments=[
        PodSetAssignment(
            name="one", flavors={"cpu": "tas-default"},
            resource_usage={"cpu": 1000}, count=1,
            topology_assignment=TopologyAssignment(
                levels=[HOSTNAME],
                domains=[TopologyDomainAssignment(values=["x1"],
                                                  count=1)]))])
    set_quota_reservation(wl, adm, 0.5)
    sync_admitted_condition(wl, 0.5)
    d.restore_workload(wl)
    pending(d, "foo", "default", "tas-main", [("one", 1, {"cpu": 1000})])
    # implied TAS on the TAS-only CQ must see x1's cpu fully consumed
    stats = run_case(d, clock)
    assert not stats.admitted, stats
    heap, parked = queue_state(d, "tas-main")
    assert "default/foo" in heap | parked


# --- :2127+ multiple preemptions in one cycle ----------------------------

def _pre_cq(name, cohort, nominal_cpu, extra_res=None,
            reclaim=ReclaimWithinCohort.NEVER):
    resources = {"cpu": ResourceQuota(nominal=nominal_cpu)}
    covered = ["cpu"]
    for rname, q in (extra_res or {}).items():
        resources[rname] = ResourceQuota(nominal=q)
        covered.append(rname)
    return ClusterQueue(
        name=name, cohort=cohort,
        preemption=PreemptionPolicy(
            reclaim_within_cohort=reclaim,
            within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY),
        resource_groups=[ResourceGroup(covered_resources=covered, flavors=[
            FlavorQuotas(name="default", resources=resources)])])


def test_multiple_preemptions_without_borrowing(use_device):
    """:2127 — two CQs preempt within themselves in the SAME cycle."""
    extra_cqs = [_pre_cq("other-alpha", "other", 2000),
                 _pre_cq("other-beta", "other", 2000)]
    extra_lqs = (("eng-alpha", "other", "other-alpha"),
                 ("eng-beta", "other", "other-beta"))
    d, clock = fixture_driver(use_device, extra_cqs, extra_lqs)
    admitted(d, "a1", "eng-alpha", "other-alpha",
             [("main", 1, {"cpu": 2000}, {"cpu": "default"})], priority=0)
    admitted(d, "b1", "eng-beta", "other-beta",
             [("main", 1, {"cpu": 2000}, {"cpu": "default"})], priority=0)
    pending(d, "preemptor", "eng-alpha", "other",
            [("main", 1, {"cpu": 2000})], priority=100)
    pending(d, "preemptor", "eng-beta", "other",
            [("main", 1, {"cpu": 2000})], priority=100)
    stats = run_case(d, clock)
    assert set(stats.preempted_targets) == {"eng-alpha/a1", "eng-beta/b1"}
    assert set(stats.preempting) == {"eng-alpha/preemptor",
                                     "eng-beta/preemptor"}
    assert not stats.admitted


def test_preemption_possible_after_earlier_fit(use_device):
    """:2195 — a Fit workload earlier in the cycle doesn't block a
    preempting workload in the same cycle."""
    extra_cqs = [_pre_cq("other-alpha", "other", 1000),
                 _pre_cq("other-beta", "other", 2000)]
    extra_lqs = (("eng-alpha", "other", "other-alpha"),
                 ("eng-beta", "other", "other-beta"))
    d, clock = fixture_driver(use_device, extra_cqs, extra_lqs)
    admitted(d, "b1", "eng-beta", "other-beta",
             [("main", 1, {"cpu": 2000}, {"cpu": "default"})], priority=0)
    pending(d, "fit", "eng-alpha", "other", [("main", 1, {"cpu": 1000})],
            priority=100)
    pending(d, "preemptor", "eng-beta", "other",
            [("main", 1, {"cpu": 2000})], priority=99)
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-alpha/fit"}
    assert set(stats.preempted_targets) == {"eng-beta/b1"}
    assert flavors_of(d, "eng-alpha/fit") == {"main": {"cpu": "default"}}


def test_skip_overlapping_preemption_targets(use_device):
    """:2453 — two preemptors need the same over-share target; only the
    higher-priority one preempts, the other is skipped (fair sharing)."""
    # the reference case's CQs leave ReclaimWithinCohort un-defaulted
    # (its unit harness skips webhook defaulting; the empty value is NOT
    # "Never"), effectively enabling lower-priority cohort reclaim —
    # expressed here explicitly
    lp = ReclaimWithinCohort.LOWER_PRIORITY
    extra_cqs = [
        _pre_cq("other-alpha", "other", 0, {"alpha-resource": 1}, lp),
        _pre_cq("other-beta", "other", 0, {"beta-resource": 1}, lp),
        _pre_cq("other-gamma", "other", 0, {"gamma-resource": 1}, lp),
        ClusterQueue(name="resource-bank", cohort="other",
                     resource_groups=[ResourceGroup(
                         covered_resources=["cpu"],
                         flavors=[FlavorQuotas(name="default", resources={
                             "cpu": ResourceQuota(nominal=9000)})])]),
    ]
    extra_lqs = (("eng-alpha", "other", "other-alpha"),
                 ("eng-beta", "other", "other-beta"),
                 ("eng-gamma", "other", "other-gamma"))
    d, clock = fixture_driver(use_device, extra_cqs, extra_lqs,
                              fair_sharing=True)
    admitted(d, "a1", "eng-alpha", "other-alpha",
             [("main", 1, {"alpha-resource": 1}, {"alpha-resource": "default"})],
             priority=0)
    admitted(d, "b1", "eng-beta", "other-beta",
             [("main", 1, {"beta-resource": 1}, {"beta-resource": "default"})],
             priority=0)
    admitted(d, "c1", "eng-gamma", "other-gamma",
             [("main", 1, {"cpu": 9000}, {"cpu": "default"})], priority=0)
    pending(d, "preemptor", "eng-alpha", "other",
            [("main", 1, {"cpu": 3000, "alpha-resource": 1})], priority=100)
    pending(d, "pretending-preemptor", "eng-beta", "other",
            [("main", 1, {"cpu": 3000, "beta-resource": 1})], priority=99)
    stats = run_case(d, clock)
    assert set(stats.preempted_targets) == {"eng-alpha/a1", "eng-gamma/c1"}
    assert set(stats.preempting) == {"eng-alpha/preemptor"}
    assert not stats.admitted


def test_minimal_preemptions_target_queue_exhausted(use_device):
    """:1926 — incoming needs 2; its CQ is exhausted by its own lower-
    priority workloads: minimal preemption evicts exactly a1+a2 (the two
    lowest) and never touches the other CQs' equal-priority workloads."""
    reclaim = ReclaimWithinCohort.ANY
    extra_cqs = [_pre_cq("other-alpha", "other", 2000, reclaim=reclaim),
                 _pre_cq("other-beta", "other", 2000),
                 _pre_cq("other-gamma", "other", 2000)]
    extra_lqs = (("eng-alpha", "other", "other-alpha"),
                 ("eng-beta", "other", "other-beta"),
                 ("eng-gamma", "other", "other-gamma"))
    d, clock = fixture_driver(use_device, extra_cqs, extra_lqs)
    for name, prio in (("a1", -2), ("a2", -2), ("a3", -1)):
        admitted(d, name, "eng-alpha", "other-alpha",
                 [("main", 1, {"cpu": 1000}, {"cpu": "default"})],
                 priority=prio)
    for name in ("b1", "b2", "b3"):
        admitted(d, name, "eng-beta", "other-beta",
                 [("main", 1, {"cpu": 1000}, {"cpu": "default"})],
                 priority=0)
    pending(d, "incoming", "eng-alpha", "other",
            [("main", 1, {"cpu": 2000})], priority=0)
    stats = run_case(d, clock)
    assert set(stats.preempted_targets) == {"eng-alpha/a1", "eng-alpha/a2"}
    assert set(stats.preempting) == {"eng-alpha/incoming"}


def test_preemption_eligible_only_within_nominal(use_device):
    """:2015 — incoming (3 cpu) exceeds its CQ's 2-cpu nominal: not
    eligible to preempt at all; it parks inadmissible."""
    extra_cqs = [_pre_cq("other-alpha", "other", 2000,
                         reclaim=ReclaimWithinCohort.ANY),
                 _pre_cq("other-beta", "other", 2000)]
    extra_lqs = (("eng-alpha", "other", "other-alpha"),
                 ("eng-beta", "other", "other-beta"))
    d, clock = fixture_driver(use_device, extra_cqs, extra_lqs)
    admitted(d, "a1", "eng-alpha", "other-alpha",
             [("main", 1, {"cpu": 1000}, {"cpu": "default"})], priority=-1)
    admitted(d, "b1", "eng-beta", "other-beta",
             [("main", 1, {"cpu": 1000}, {"cpu": "default"})], priority=-1)
    pending(d, "incoming", "eng-alpha", "other",
            [("main", 1, {"cpu": 3000})], priority=1)
    stats = run_case(d, clock)
    assert not stats.admitted and not stats.preempting, stats
    heap, parked = queue_state(d, "other-alpha")
    assert "eng-alpha/incoming" in heap | parked


# --- :748 "lendingLimit should not affect assignments when disabled" ----

def test_lending_limit_ignored_when_gate_disabled(use_device):
    from kueue_tpu import features
    with features.set_feature_gate_during_test("LendingLimit", False):
        d, clock = fixture_driver(use_device)
        admitted(d, "a", "lend", "lend-b",
                 [("main", 1, {"cpu": 2000}, {"cpu": "default"})])
        pending(d, "b", "lend", "lend-b-queue",
                [("main", 1, {"cpu": 3000})])
        stats = run_case(d, clock)
        # with the gate off lend-a's full 3000 is borrowable, not just
        # its 2000 lendingLimit
        assert set(stats.admitted) == {"lend/b"}
    # control: with the gate on the same workload cannot fit
    d2, clock2 = fixture_driver(use_device)
    admitted(d2, "a", "lend", "lend-b",
             [("main", 1, {"cpu": 2000}, {"cpu": "default"})])
    pending(d2, "b", "lend", "lend-b-queue", [("main", 1, {"cpu": 3000})])
    stats2 = run_case(d2, clock2)
    assert not stats2.admitted


# --- :2579 "container does not satisfy limitRange constraints" ----------

def test_limitrange_constraints_block_admission(use_device):
    from kueue_tpu.limitrange import LimitRange, LimitRangeItem
    d, clock = fixture_driver(use_device)
    d.apply_limit_range(LimitRange(
        name="alpha", namespace="sales",
        items=[LimitRangeItem(type="Container", max={"cpu": 300})]))
    pending(d, "new", "sales", "main", [("one", 1, {"cpu": 500})])
    stats = run_case(d, clock)
    assert not stats.admitted
    heap, parked = queue_state(d, "sales")
    assert "sales/new" in heap | parked


# --- :2613 "container resource requests exceed limits" ------------------

def test_requests_exceeding_limits_block_admission(use_device):
    d, clock = fixture_driver(use_device)
    seq = len(d.workloads) + 1
    d.create_workload(Workload(
        name="new", namespace="sales", queue_name="main",
        creation_time=float(seq),
        pod_sets=[PodSet(name="one", count=1, requests={"cpu": 200},
                         limits={"cpu": 100})]))
    stats = run_case(d, clock)
    assert not stats.admitted
    heap, parked = queue_state(d, "sales")
    assert "sales/new" in heap | parked


# --- :1227 "partial admission disabled, variable pod set" ---------------

def test_partial_admission_disabled_gate(use_device):
    from kueue_tpu import features
    with features.set_feature_gate_during_test("PartialAdmission", False):
        d, clock = fixture_driver(use_device)
        # 60 pods x 1 cpu against sales' 50: with the gate on this would
        # partially admit at minCount; with it off the webhook drops
        # minCount at create (workload_webhook.go:61-64) and it parks
        seq = len(d.workloads) + 1
        d.create_workload(Workload(
            name="big", namespace="sales", queue_name="main",
            creation_time=float(seq),
            pod_sets=[PodSet(name="one", count=60, min_count=10,
                             requests={"cpu": 1000})]))
        assert d.workloads["sales/big"].pod_sets[0].min_count is None
        run_case(d, clock)
        heap, parked = queue_state(d, "sales")
        assert "sales/big" in heap | parked
        assert d.workloads["sales/big"].admission is None
    # control: same shape with the gate on partially admits at 50
    d2, clock2 = fixture_driver(use_device)
    d2.create_workload(Workload(
        name="big", namespace="sales", queue_name="main",
        creation_time=1.0,
        pod_sets=[PodSet(name="one", count=60, min_count=10,
                         requests={"cpu": 1000})]))
    stats2 = run_case(d2, clock2)
    assert set(stats2.admitted) == {"sales/big"}
    psa = d2.workloads["sales/big"].admission.pod_set_assignments[0]
    assert psa.count == 50


# --- :939 "no overadmission while borrowing" ----------------------------

def test_no_overadmission_while_borrowing(use_device):
    gamma = ClusterQueue(
        name="eng-gamma", cohort="eng",
        preemption=PreemptionPolicy(
            reclaim_within_cohort=ReclaimWithinCohort.ANY,
            within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY),
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "cpu": ResourceQuota(nominal=50_000,
                                     borrowing_limit=10_000)}),
            FlavorQuotas(name="spot", resources={
                "cpu": ResourceQuota(nominal=0,
                                     borrowing_limit=100_000)})])])
    d, clock = fixture_driver(
        use_device, extra_cqs=[gamma],
        extra_lqs=[("eng-gamma", "main", "eng-gamma")])
    # admitted() usage is the podset TOTAL; pending() requests are per pod
    admitted(d, "existing", "eng-gamma", "eng-gamma", [
        ("borrow-on-demand", 51, {"cpu": 51_000}, {"cpu": "on-demand"}),
        ("use-all-spot", 100, {"cpu": 100_000}, {"cpu": "spot"})])
    pending(d, "new", "eng-beta", "main", [("one", 50, {"cpu": 1000})],
            created=1.0)
    pending(d, "new-alpha", "eng-alpha", "main",
            [("one", 1, {"cpu": 1000})], created=2.0)
    pending(d, "new-gamma", "eng-gamma", "main",
            [("one", 50, {"cpu": 1000})], created=3.0)
    stats = run_case(d, clock)
    assert set(stats.admitted) == {"eng-beta/new", "eng-alpha/new-alpha"}
    assert not stats.preempted_targets
    assert flavors_of(d, "eng-beta/new") == {"one": {"cpu": "on-demand"}}
    assert flavors_of(d, "eng-alpha/new-alpha") \
        == {"one": {"cpu": "on-demand"}}
    heap, parked = queue_state(d, "eng-gamma")
    assert "eng-gamma/new-gamma" in heap | parked
    # the pre-admitted borrower keeps both pod sets untouched
    assert flavors_of(d, "eng-gamma/existing") == {
        "borrow-on-demand": {"cpu": "on-demand"},
        "use-all-spot": {"cpu": "spot"}}


# --- :2655 "prefer reclamation over cq priority based preemption" -------

def test_prefer_reclamation_over_cq_priority_preemption(use_device):
    policy = PreemptionPolicy(
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
        reclaim_within_cohort=ReclaimWithinCohort.LOWER_PRIORITY)
    mk = lambda name, nominal: ClusterQueue(
        name=name, cohort="other", preemption=policy,
        resource_groups=[ResourceGroup(covered_resources=["gpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "gpu": ResourceQuota(nominal=nominal)}),
            FlavorQuotas(name="spot", resources={
                "gpu": ResourceQuota(nominal=nominal)})])])
    d, clock = fixture_driver(
        use_device, extra_cqs=[mk("other-alpha", 10), mk("other-beta", 0)],
        extra_lqs=[("eng-alpha", "other", "other-alpha"),
                   ("eng-beta", "other", "other-beta")])
    admitted(d, "a1", "eng-alpha", "other-alpha",
             [("main", 1, {"gpu": 5}, {"gpu": "on-demand"})], priority=50)
    admitted(d, "b1", "eng-beta", "other-beta",
             [("main", 1, {"gpu": 5}, {"gpu": "spot"})], priority=50)
    pending(d, "preemptor", "eng-alpha", "other",
            [("main", 1, {"gpu": 6})], priority=100)
    stats = run_case(d, clock)
    # flavor 1 (on-demand) would preempt a1 inside the CQ; flavor 2
    # (spot) reclaims the borrower b1 from the cohort — reclamation wins
    assert set(stats.preempted_targets) == {"eng-beta/b1"}
    assert "eng-alpha/preemptor" not in stats.admitted
    assert flavors_of(d, "eng-alpha/a1") == {"main": {"gpu": "on-demand"}}


# --- :1089/:1129 partial admission preempt variants ----------------------

def test_partial_admission_preempt_first(use_device):
    d, clock = fixture_driver(use_device)
    admitted(d, "old", "eng-beta", "eng-beta",
             [("one", 10, {"example.com/gpu": 10},
               {"example.com/gpu": "model-a"})], priority=-4)
    seq = len(d.workloads) + 1
    d.create_workload(Workload(
        name="new", namespace="eng-beta", queue_name="main", priority=4,
        creation_time=float(seq),
        pod_sets=[PodSet(name="one", count=20, min_count=10,
                         requests={"example.com/gpu": 1})]))
    stats = run_case(d, clock)
    # the full 20 fits once old's 10 are preempted — no count reduction
    assert set(stats.preempted_targets) == {"eng-beta/old"}
    assert "eng-beta/new" not in stats.admitted
    heap, parked = queue_state(d, "eng-beta")
    assert "eng-beta/new" in heap | parked
    # the full counts found their targets: no count search
    assert_partial_on_vector_path(d, searched=False)


def test_partial_admission_preempt_with_reduction(use_device):
    d, clock = fixture_driver(use_device)
    admitted(d, "old", "eng-beta", "eng-beta",
             [("one", 10, {"example.com/gpu": 10},
               {"example.com/gpu": "model-a"})], priority=-4)
    seq = len(d.workloads) + 1
    d.create_workload(Workload(
        name="new", namespace="eng-beta", queue_name="main", priority=4,
        creation_time=float(seq),
        pod_sets=[PodSet(name="one", count=30, min_count=10,
                         requests={"example.com/gpu": 1})]))
    stats = run_case(d, clock)
    # 30 can never fit the 20-gpu nominal; the reducer finds a count
    # that becomes feasible after preempting old
    assert set(stats.preempted_targets) == {"eng-beta/old"}
    assert "eng-beta/new" not in stats.admitted
    heap, parked = queue_state(d, "eng-beta")
    assert "eng-beta/new" in heap | parked
    assert_partial_on_vector_path(d, searched=use_device)


# --- :2716/:2779 flavor preference among preemption kinds ---------------

def _other_cohort_driver(use_device):
    policy = PreemptionPolicy(
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
        reclaim_within_cohort=ReclaimWithinCohort.LOWER_PRIORITY)
    mk = lambda name, nominal, pre: ClusterQueue(
        name=name, cohort="other", preemption=pre,
        resource_groups=[ResourceGroup(covered_resources=["gpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "gpu": ResourceQuota(nominal=nominal)}),
            FlavorQuotas(name="spot", resources={
                "gpu": ResourceQuota(nominal=nominal)})])])
    return fixture_driver(
        use_device,
        extra_cqs=[mk("other-alpha", 10, policy),
                   mk("other-beta", 0, PreemptionPolicy())],
        extra_lqs=[("eng-alpha", "other", "other-alpha"),
                   ("eng-beta", "other", "other-beta")])


def test_prefer_first_flavor_when_second_needs_reclaim_and_cq(use_device):
    d, clock = _other_cohort_driver(use_device)
    admitted(d, "a1", "eng-alpha", "other-alpha",
             [("main", 1, {"gpu": 5}, {"gpu": "on-demand"})], priority=50)
    admitted(d, "a2", "eng-alpha", "other-alpha",
             [("main", 1, {"gpu": 5}, {"gpu": "spot"})], priority=50)
    admitted(d, "b1", "eng-beta", "other-beta",
             [("main", 1, {"gpu": 5}, {"gpu": "spot"})], priority=50)
    pending(d, "preemptor", "eng-alpha", "other",
            [("main", 1, {"gpu": 6})], priority=100)
    stats = run_case(d, clock)
    # spot would need BOTH cohort reclaim and in-CQ preemption — it does
    # not improve on on-demand's single in-CQ preemption
    assert set(stats.preempted_targets) == {"eng-alpha/a1"}
    assert flavors_of(d, "eng-alpha/a2") == {"main": {"gpu": "spot"}}
    assert flavors_of(d, "eng-beta/b1") == {"main": {"gpu": "spot"}}


def test_prefer_first_flavor_when_second_also_needs_cq_preemption(use_device):
    d, clock = _other_cohort_driver(use_device)
    admitted(d, "a1", "eng-alpha", "other-alpha",
             [("main", 1, {"gpu": 6}, {"gpu": "on-demand"})], priority=50)
    admitted(d, "a2", "eng-alpha", "other-alpha",
             [("main", 1, {"gpu": 5}, {"gpu": "spot"})], priority=50)
    admitted(d, "b1", "eng-beta", "other-beta",
             [("main", 1, {"gpu": 5}, {"gpu": "spot"})], priority=9001)
    pending(d, "preemptor", "eng-alpha", "other",
            [("main", 1, {"gpu": 5})], priority=100)
    stats = run_case(d, clock)
    # the spot borrower is too high priority to reclaim, so spot also
    # needs in-CQ preemption — flavor order breaks the tie
    assert set(stats.preempted_targets) == {"eng-alpha/a1"}
    assert flavors_of(d, "eng-alpha/a2") == {"main": {"gpu": "spot"}}


# --- :2844 "workload requiring reclamation prioritized over wl in
#            another full cq" (issue #3405) ------------------------------

def test_reclaiming_workload_prioritized_over_full_cq_workload(use_device):
    mk = lambda name, nominal, pre: ClusterQueue(
        name=name, cohort="other", preemption=pre or PreemptionPolicy(),
        resource_groups=[ResourceGroup(covered_resources=["gpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "gpu": ResourceQuota(nominal=nominal)})])])
    d, clock = fixture_driver(
        use_device,
        extra_cqs=[
            mk("cq1", 10, None),
            mk("cq2", 10, PreemptionPolicy(
                reclaim_within_cohort=ReclaimWithinCohort.ANY)),
            mk("cq3", 0, None)],
        extra_lqs=[("eng-alpha", "lq", "cq1"), ("eng-beta", "lq", "cq2"),
                   ("eng-gamma", "lq", "cq3")])
    admitted(d, "aw1", "eng-alpha", "cq1",
             [("main", 1, {"gpu": 5}, {"gpu": "on-demand"})])
    admitted(d, "aw2", "eng-gamma", "cq3",
             [("main", 1, {"gpu": 5}, {"gpu": "on-demand"})], priority=0)
    admitted(d, "aw3", "eng-gamma", "cq3",
             [("main", 1, {"gpu": 5}, {"gpu": "on-demand"})], priority=1)
    pending(d, "wl1", "eng-alpha", "lq", [("main", 1, {"gpu": 10})],
            created=100.0)
    pending(d, "wl2", "eng-beta", "lq", [("main", 1, {"gpu": 10})],
            created=101.0)
    stats = run_case(d, clock)
    # wl2 reclaims its nominal capacity (preempting the borrower) even
    # though the earlier-created wl1 would otherwise reserve first and
    # invalidate the preemption calculation (issue #3405)
    assert set(stats.preempted_targets) == {"eng-gamma/aw2"}
    assert not stats.admitted
    h1, p1 = queue_state(d, "cq1")
    assert "eng-alpha/wl1" in h1 | p1
    h2, p2 = queue_state(d, "cq2")
    assert "eng-beta/wl2" in h2 | p2


# --- :1751 "fair sharing schedule singleton cqs and cq without cohort" --

def test_fs_singleton_cqs_and_no_cohort(use_device):
    d, clock = fixture_driver(
        use_device, fair_sharing=True,
        extra_cohorts=[
            Cohort(name="cohort-a", resource_groups=[ResourceGroup(
                covered_resources=["cpu"], flavors=[
                    FlavorQuotas(name="on-demand", resources={
                        "cpu": ResourceQuota(nominal=10_000)})])]),
            Cohort(name="cohort-b")],
        extra_cqs=[
            ClusterQueue(name="a", cohort="cohort-a",
                         resource_groups=[ResourceGroup(
                             covered_resources=["cpu"], flavors=[
                                 FlavorQuotas(name="on-demand", resources={
                                     "cpu": ResourceQuota(nominal=0)})])]),
            ClusterQueue(name="b", cohort="cohort-b",
                         resource_groups=[ResourceGroup(
                             covered_resources=["cpu"], flavors=[
                                 FlavorQuotas(name="on-demand", resources={
                                     "cpu": ResourceQuota(
                                         nominal=10_000)})])]),
            ClusterQueue(name="c",
                         resource_groups=[ResourceGroup(
                             covered_resources=["cpu"], flavors=[
                                 FlavorQuotas(name="on-demand", resources={
                                     "cpu": ResourceQuota(
                                         nominal=10_000)})])])],
        extra_lqs=[("eng-alpha", "lq-a", "a"), ("eng-alpha", "lq-b", "b"),
                   ("eng-alpha", "lq-c", "c")])
    pending(d, "a1", "eng-alpha", "lq-a", [("one", 1, {"cpu": 10_000})])
    pending(d, "b1", "eng-alpha", "lq-b", [("one", 1, {"cpu": 10_000})])
    pending(d, "c1", "eng-alpha", "lq-c", [("one", 1, {"cpu": 10_000})])
    stats = run_case(d, clock)
    # a borrows the cohort-level quota; singleton cohorts and the
    # cohortless CQ all admit in one cycle under fair sharing
    assert set(stats.admitted) == {"eng-alpha/a1", "eng-alpha/b1",
                                   "eng-alpha/c1"}
    assert flavors_of(d, "eng-alpha/a1") == {"one": {"cpu": "on-demand"}}


# --- :2067 "with fair sharing: preempt workload from CQ with the
#            highest share" ----------------------------------------------

def test_fs_preempt_from_cq_with_highest_share(use_device):
    gamma = ClusterQueue(
        name="eng-gamma", cohort="eng",
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="on-demand", resources={
                "cpu": ResourceQuota(nominal=50_000,
                                     borrowing_limit=0)})])])
    d, clock = fixture_driver(use_device, fair_sharing=True,
                              extra_cqs=[gamma])
    admitted(d, "all-spot", "eng-alpha", "eng-alpha",
             [("main", 1, {"cpu": 100_000}, {"cpu": "spot"})])
    for i in range(1, 5):
        admitted(d, f"alpha{i}", "eng-alpha", "eng-alpha",
                 [("main", 1, {"cpu": 20_000}, {"cpu": "on-demand"})])
    admitted(d, "gamma1", "eng-gamma", "eng-gamma",
             [("main", 1, {"cpu": 10_000}, {"cpu": "on-demand"})])
    for i in range(2, 5):
        admitted(d, f"gamma{i}", "eng-gamma", "eng-gamma",
                 [("main", 1, {"cpu": 20_000}, {"cpu": "on-demand"})])
    pending(d, "preemptor", "eng-beta", "main",
            [("main", 1, {"cpu": 30_000})])
    stats = run_case(d, clock)
    # fair preemption takes the cheapest workloads from BOTH borrowers
    # (alpha and gamma carry the highest DRS)
    assert set(stats.preempted_targets) == {"eng-alpha/alpha1",
                                            "eng-gamma/gamma1"}
    assert "eng-beta/preemptor" not in stats.admitted
    heap, parked = queue_state(d, "eng-beta")
    assert "eng-beta/preemptor" in heap | parked


# --- :2343 "multiple preemptions within cq when fair sharing" -----------

def test_fs_multiple_within_cq_preemptions_one_cycle(use_device):
    # the reference fixture leaves reclaimWithinCohort UNSET, which its
    # canPreemptWhileBorrowing treats as != Never (flavorassigner.go:
    # canPreemptWhileBorrowing); with CRD defaulting the effective
    # policy is reclaim Any, which our defaulted model states explicitly
    lower = PreemptionPolicy(
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
        reclaim_within_cohort=ReclaimWithinCohort.ANY)
    mk = lambda name, nominal, pre=None: ClusterQueue(
        name=name, cohort="other",
        preemption=pre or PreemptionPolicy(),
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=nominal)})])])
    d, clock = fixture_driver(
        use_device, fair_sharing=True,
        extra_cqs=[mk("other-alpha", 2000, lower),
                   mk("other-beta", 2000, lower),
                   mk("other-gamma", 2000, lower),
                   mk("resource-bank", 3000)],
        extra_lqs=[("eng-alpha", "other", "other-alpha"),
                   ("eng-beta", "other", "other-beta"),
                   ("eng-gamma", "other", "other-gamma")])
    admitted(d, "a1", "eng-alpha", "other-alpha",
             [("main", 1, {"cpu": 3000}, {"cpu": "default"})])
    admitted(d, "b1", "eng-beta", "other-beta",
             [("main", 1, {"cpu": 3000}, {"cpu": "default"})])
    admitted(d, "c1", "eng-gamma", "other-gamma",
             [("main", 1, {"cpu": 3000}, {"cpu": "default"})])
    pending(d, "preemptor", "eng-alpha", "other",
            [("main", 1, {"cpu": 3000})], priority=100)
    pending(d, "preemptor", "eng-beta", "other",
            [("main", 1, {"cpu": 3000})], priority=100)
    pending(d, "preemptor", "eng-gamma", "other",
            [("main", 1, {"cpu": 3000})], priority=100)
    stats = run_case(d, clock)
    # every CQ preempts within itself in the SAME cycle — fair sharing
    # must not serialize non-overlapping preemptions
    assert set(stats.preempted_targets) == {
        "eng-alpha/a1", "eng-beta/b1", "eng-gamma/c1"}
    assert not stats.admitted


# --- :1356 "preemption while borrowing, workload waiting for preemption
#            should not block a borrowing workload in another CQ" --------

def test_waiting_preemptor_does_not_block_borrower(use_device):
    borrow_lp = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.LOWER_PRIORITY,
        borrow_within_cohort=BorrowWithinCohort(
            policy=BorrowWithinCohortPolicy.LOWER_PRIORITY))
    mk = lambda name, nominal, blimit, pre: ClusterQueue(
        name=name, cohort="preemption-while-borrowing",
        preemption=pre or PreemptionPolicy(),
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=nominal,
                                     borrowing_limit=blimit)})])])
    d, clock = fixture_driver(
        use_device,
        extra_cqs=[mk("cq-shared", 4000, 0, None),
                   mk("cq-a", 0, 3000, borrow_lp),
                   mk("cq-b", 0, None, borrow_lp)],
        extra_lqs=[("eng-alpha", "lq-a", "cq-a"),
                   ("eng-beta", "lq-b", "cq-b")])
    admitted(d, "admitted-a", "eng-alpha", "cq-a",
             [("main", 1, {"cpu": 2000}, {"cpu": "default"})])
    pending(d, "a", "eng-alpha", "lq-a", [("main", 1, {"cpu": 3000})],
            created=100.0)
    pending(d, "b", "eng-beta", "lq-b", [("main", 1, {"cpu": 1000})],
            created=101.0)
    stats = run_case(d, clock)
    # "a" can't fit (cq-a would exceed its borrowingLimit) and reserves
    # nothing — the later-created borrower "b" still admits this cycle
    assert set(stats.admitted) == {"eng-beta/b"}
    assert not stats.preempted_targets
    heap, parked = queue_state(d, "cq-a")
    assert "eng-alpha/a" in heap | parked
    assert flavors_of(d, "eng-alpha/admitted-a") == {
        "main": {"cpu": "default"}}


# --- :2257 "multiple preemptions skip preemption when shared limited
#            resource" ---------------------------------------------------

def test_skip_wasteful_preemption_on_shared_limited_resource(use_device):
    # the reference fixture's borrowWithinCohort with unset (zero-value)
    # reclaimWithinCohort would be rejected by the CQ webhook
    # (clusterqueue_webhook.go); the valid equivalent sets reclaim
    pre = PreemptionPolicy(
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
        reclaim_within_cohort=ReclaimWithinCohort.LOWER_PRIORITY,
        borrow_within_cohort=BorrowWithinCohort(
            policy=BorrowWithinCohortPolicy.LOWER_PRIORITY))
    mk = lambda name, nominal, p=None: ClusterQueue(
        name=name, cohort="other", preemption=p or PreemptionPolicy(),
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=nominal)})])])
    d, clock = fixture_driver(
        use_device,
        extra_cqs=[mk("other-alpha", 2000, pre), mk("other-beta", 2000, pre),
                   mk("resource-bank", 1000)],
        extra_lqs=[("eng-alpha", "other", "other-alpha"),
                   ("eng-beta", "other", "other-beta")])
    admitted(d, "a1", "eng-alpha", "other-alpha",
             [("main", 1, {"cpu": 2000}, {"cpu": "default"})])
    admitted(d, "b1", "eng-beta", "other-beta",
             [("main", 1, {"cpu": 2000}, {"cpu": "default"})])
    pending(d, "preemptor", "eng-alpha", "other",
            [("main", 1, {"cpu": 3000})], priority=100)
    pending(d, "pretending-preemptor", "eng-beta", "other",
            [("main", 1, {"cpu": 3000})], priority=99)
    stats = run_case(d, clock)
    # cohort capacity 5: only one 3-cpu preemptor can ever fit even
    # after both evictions — the second must NOT wastefully preempt b1
    assert set(stats.preempted_targets) == {"eng-alpha/a1"}
    assert not stats.admitted
    ha, pa = queue_state(d, "other-alpha")
    assert "eng-alpha/preemptor" in ha | pa
    hb, pb = queue_state(d, "other-beta")
    assert "eng-beta/pretending-preemptor" in hb | pb
    assert flavors_of(d, "eng-beta/b1") == {"main": {"cpu": "default"}}
