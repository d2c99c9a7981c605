"""Conformance replay of the reference's TestPreemption tables
(/root/reference/pkg/scheduler/preemption/preemption_test.go:299-1427),
end to end through the scheduler on both the host and device paths.

The reference drives Preemptor.GetTargets with a PINNED flavor
assignment; here each case runs the full cycle (nominate → assign →
preempt), so only tables whose assignment the real flavorassigner
reproduces unambiguously are included — the `want` sets are the
reference's own expectations, transliterated.
"""

import pytest

from kueue_tpu.api.types import (
    Admission,
    BorrowWithinCohort,
    BorrowWithinCohortPolicy,
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PodSetAssignment,
    PreemptionPolicy,
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

# one flavor a head can preempt in: the reclaim oracle is never asked
pytestmark = pytest.mark.usefixtures("no_oracle_specs")


K = 1000          # "1" cpu = 1000 milli
GI = 1024         # "1Gi" memory = 1024 units

LOWER = PreemptionPolicy(within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
LOWER_BOTH = PreemptionPolicy(
    within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
    reclaim_within_cohort=ReclaimWithinCohort.LOWER_PRIORITY)
NEVER_ANY = PreemptionPolicy(
    within_cluster_queue=WithinClusterQueue.NEVER,
    reclaim_within_cohort=ReclaimWithinCohort.ANY)
BORROW_LP = BorrowWithinCohort(policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
                               max_priority_threshold=0)


def cq(name, quotas, cohort=None, preemption=None, groups=None):
    """quotas: [(flavor, {res: (nominal, borrowing, lending)})] in one
    resource group, or pass groups directly."""
    if groups is None:
        by_resources = {}
        for flavor, res in quotas:
            key = tuple(sorted(res))
            by_resources.setdefault(key, []).append(FlavorQuotas(
                name=flavor,
                resources={r: ResourceQuota(nominal=n, borrowing_limit=b,
                                            lending_limit=l)
                           for r, (n, b, l) in res.items()}))
        groups = [ResourceGroup(covered_resources=list(key), flavors=fls)
                  for key, fls in by_resources.items()]
    return ClusterQueue(name=name, cohort=cohort,
                        preemption=preemption or PreemptionPolicy(),
                        resource_groups=groups)


def make_driver(use_device, cqs):
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device)
    for f in ("default", "alpha", "beta"):
        d.apply_resource_flavor(ResourceFlavor(name=f))
    for c in cqs:
        d.apply_cluster_queue(c)
        d.apply_local_queue(LocalQueue(name=f"lq-{c.name}",
                                       cluster_queue=c.name))
    return d, clock


def admit(d, name, cq_name, usage, priority=0, reserved_at=0.5):
    """ReserveQuotaAt: usage = {res: (flavor, amount)}."""
    wl = Workload(
        name=name, namespace="default", priority=priority,
        creation_time=reserved_at,
        pod_sets=[PodSet(name="main", count=1,
                         requests={r: a for r, (_, a) in usage.items()})])
    adm = Admission(cluster_queue=cq_name, pod_set_assignments=[
        PodSetAssignment(name="main",
                         flavors={r: f for r, (f, _) in usage.items()},
                         resource_usage={r: a for r, (_, a) in usage.items()},
                         count=1)])
    set_quota_reservation(wl, adm, reserved_at)
    sync_admitted_condition(wl, reserved_at)
    d.restore_workload(wl)


def incoming(d, name, cq_name, requests, priority=0, created=None):
    d.create_workload(Workload(
        name=name, namespace="default", queue_name=f"lq-{cq_name}",
        priority=priority,
        creation_time=created if created is not None else 999.0,
        pod_sets=[PodSet(name="main", count=1, requests=dict(requests))]))


def cycle(d, clock):
    clock.t += 1.0
    return d.schedule_once()


def preempted(stats):
    return {k.split("/", 1)[1] for k in stats.preempted_targets}


@pytest.fixture(params=[False, True], ids=["host", "device"])
def use_device(request):
    return request.param


def standalone():
    # preemption_test.go:84 — cpu on default + memory on alpha|beta
    return cq("standalone", None, preemption=LOWER, groups=[
        ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=6 * K)})]),
        ResourceGroup(covered_resources=["memory"], flavors=[
            FlavorQuotas(name="alpha", resources={
                "memory": ResourceQuota(nominal=3 * GI)}),
            FlavorQuotas(name="beta", resources={
                "memory": ResourceQuota(nominal=3 * GI)})])])


def c1c2():
    # :100-123 — cohort "cohort", cpu 6/6 + memory 3Gi/3Gi each
    return [
        cq("c1", [("default", {"cpu": (6 * K, 6 * K, None),
                               "memory": (3 * GI, 3 * GI, None)})],
           cohort="cohort", preemption=LOWER_BOTH),
        cq("c2", [("default", {"cpu": (6 * K, 6 * K, None),
                               "memory": (3 * GI, 3 * GI, None)})],
           cohort="cohort", preemption=NEVER_ANY),
    ]


# --- :299 "preempt lowest priority" -------------------------------------

def test_preempt_lowest_priority(use_device):
    d, clock = make_driver(use_device, [standalone()])
    admit(d, "low", "standalone", {"cpu": ("default", 2 * K)}, priority=-1)
    admit(d, "mid", "standalone", {"cpu": ("default", 2 * K)})
    admit(d, "high", "standalone", {"cpu": ("default", 2 * K)}, priority=1)
    incoming(d, "in", "standalone", {"cpu": 2 * K}, priority=1)
    stats = cycle(d, clock)
    assert preempted(stats) == {"low"}


# --- :339 "preempt multiple" --------------------------------------------

def test_preempt_multiple(use_device):
    d, clock = make_driver(use_device, [standalone()])
    admit(d, "low", "standalone", {"cpu": ("default", 2 * K)}, priority=-1)
    admit(d, "mid", "standalone", {"cpu": ("default", 2 * K)})
    admit(d, "high", "standalone", {"cpu": ("default", 2 * K)}, priority=1)
    incoming(d, "in", "standalone", {"cpu": 3 * K}, priority=1)
    stats = cycle(d, clock)
    assert preempted(stats) == {"low", "mid"}


# --- :380 "no preemption for low priority" ------------------------------

def test_no_preemption_for_low_priority(use_device):
    d, clock = make_driver(use_device, [standalone()])
    admit(d, "low", "standalone", {"cpu": ("default", 3 * K)}, priority=-1)
    admit(d, "mid", "standalone", {"cpu": ("default", 3 * K)})
    incoming(d, "in", "standalone", {"cpu": 1 * K}, priority=-1)
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :411 "not enough low priority workloads" ---------------------------

def test_not_enough_low_priority_workloads(use_device):
    d, clock = make_driver(use_device, [standalone()])
    admit(d, "low", "standalone", {"cpu": ("default", 3 * K)}, priority=-1)
    admit(d, "mid", "standalone", {"cpu": ("default", 3 * K)})
    incoming(d, "in", "standalone", {"cpu": 4 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :441 "some free quota, preempt low priority" -----------------------

def test_some_free_quota_preempt_low_priority(use_device):
    d, clock = make_driver(use_device, [standalone()])
    admit(d, "low", "standalone", {"cpu": ("default", 1 * K)}, priority=-1)
    admit(d, "mid", "standalone", {"cpu": ("default", 1 * K)})
    admit(d, "high", "standalone", {"cpu": ("default", 1 * K)}, priority=1)
    incoming(d, "in", "standalone", {"cpu": 4 * K}, priority=1)
    stats = cycle(d, clock)
    assert preempted(stats) == {"low"}


# --- :481 "minimal set excludes low priority" ---------------------------

def test_minimal_set_excludes_low_priority(use_device):
    d, clock = make_driver(use_device, [standalone()])
    admit(d, "low", "standalone", {"cpu": ("default", 1 * K)}, priority=-1)
    admit(d, "mid", "standalone", {"cpu": ("default", 2 * K)})
    admit(d, "high", "standalone", {"cpu": ("default", 3 * K)}, priority=1)
    incoming(d, "in", "standalone", {"cpu": 2 * K}, priority=1)
    stats = cycle(d, clock)
    assert preempted(stats) == {"mid"}


# --- :566 "reclaim quota from borrower" ---------------------------------

def test_reclaim_quota_from_borrower(use_device):
    d, clock = make_driver(use_device, c1c2())
    admit(d, "c1-low", "c1", {"cpu": ("default", 3 * K)}, priority=-1)
    admit(d, "c2-mid", "c2", {"cpu": ("default", 3 * K)})
    admit(d, "c2-high", "c2", {"cpu": ("default", 6 * K)}, priority=1)
    incoming(d, "in", "c1", {"cpu": 3 * K}, priority=1)
    stats = cycle(d, clock)
    assert preempted(stats) == {"c2-mid"}


# --- :643 "no workloads borrowing" (admits by borrowing instead) --------

def test_no_workloads_borrowing(use_device):
    d, clock = make_driver(use_device, c1c2())
    admit(d, "c1-high", "c1", {"cpu": ("default", 4 * K)}, priority=1)
    admit(d, "c2-low", "c2", {"cpu": ("default", 4 * K)}, priority=-1)
    incoming(d, "in", "c1", {"cpu": 4 * K}, priority=1)
    stats = cycle(d, clock)
    # nobody is above nominal, so nothing can be reclaimed; end to end
    # the workload simply borrows the cohort's free 4 cpu
    assert not preempted(stats)
    assert set(stats.admitted) == {"default/in"}


# --- :930 "do not reclaim borrowed quota from same priority
#           for withinCohort=ReclaimFromLowerPriority" -------------------

def test_no_reclaim_same_priority_lower_policy(use_device):
    d, clock = make_driver(use_device, c1c2())
    admit(d, "c1", "c1", {"cpu": ("default", 2 * K)})
    admit(d, "c2-1", "c2", {"cpu": ("default", 4 * K)})
    admit(d, "c2-2", "c2", {"cpu": ("default", 4 * K)})
    incoming(d, "in", "c1", {"cpu": 4 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :966 "reclaim borrowed quota from same priority
#           for withinCohort=ReclaimFromAny" -----------------------------

def test_reclaim_same_priority_any_policy(use_device):
    d, clock = make_driver(use_device, c1c2())
    admit(d, "c1-1", "c1", {"cpu": ("default", 4 * K)})
    admit(d, "c1-2", "c1", {"cpu": ("default", 4 * K)}, priority=1)
    admit(d, "c2", "c2", {"cpu": ("default", 2 * K)})
    incoming(d, "in", "c2", {"cpu": 4 * K})
    stats = cycle(d, clock)
    assert preempted(stats) == {"c1-1"}


# --- :1129 "preempt newer workloads with the same priority" -------------

def test_preempt_newer_equal_priority(use_device):
    prevent = cq("prevent-starvation",
                 [("default", {"cpu": (6 * K, None, None)})],
                 preemption=PreemptionPolicy(
                     within_cluster_queue=
                     WithinClusterQueue.LOWER_OR_NEWER_EQUAL_PRIORITY))
    d, clock = make_driver(use_device, [prevent])
    now = 100.0
    admit(d, "wl1", "prevent-starvation", {"cpu": ("default", 2 * K)},
          priority=2, reserved_at=now)
    admit(d, "wl2", "prevent-starvation", {"cpu": ("default", 2 * K)},
          priority=1, reserved_at=now + 1.0)
    admit(d, "wl3", "prevent-starvation", {"cpu": ("default", 2 * K)},
          priority=1, reserved_at=now)
    incoming(d, "in", "prevent-starvation", {"cpu": 2 * K}, priority=1,
             created=now - 15.0)
    stats = cycle(d, clock)
    assert preempted(stats) == {"wl2"}


# --- shared-cq fixture (:170-235) ---------------------------------------

def shared_cq_fixture():
    mk = lambda name, nominal, within, reclaim: cq(
        name, [("default", {"cpu": (nominal, 12 * K, None)})],
        cohort="with-shared-cq",
        preemption=PreemptionPolicy(
            within_cluster_queue=within, reclaim_within_cohort=reclaim,
            borrow_within_cohort=BORROW_LP))
    return [
        mk("a-standard", 1 * K, WithinClusterQueue.NEVER,
           ReclaimWithinCohort.LOWER_PRIORITY),
        mk("b-standard", 1 * K, WithinClusterQueue.LOWER_PRIORITY,
           ReclaimWithinCohort.ANY),
        mk("a-best-effort", 1 * K, WithinClusterQueue.NEVER,
           ReclaimWithinCohort.LOWER_PRIORITY),
        cq("b-best-effort", [("default", {"cpu": (0, 13 * K, None)})],
           cohort="with-shared-cq",
           preemption=PreemptionPolicy(
               within_cluster_queue=WithinClusterQueue.NEVER,
               reclaim_within_cohort=ReclaimWithinCohort.LOWER_PRIORITY,
               borrow_within_cohort=BORROW_LP)),
        cq("shared", [("default", {"cpu": (10 * K, None, None)})],
           cohort="with-shared-cq"),
    ]


# --- :1183 "BorrowWithinCohort: preempt lower-priority in another CQ
#            while borrowing" --------------------------------------------

def test_borrow_within_cohort_preempts_other_cq(use_device):
    d, clock = make_driver(use_device, shared_cq_fixture())
    admit(d, "a-best-effort-low", "a-best-effort",
          {"cpu": ("default", 10 * K)}, priority=-1)
    admit(d, "b-best-effort-low", "b-best-effort",
          {"cpu": ("default", 1 * K)}, priority=-1)
    incoming(d, "in", "a-standard", {"cpu": 10 * K})
    stats = cycle(d, clock)
    assert preempted(stats) == {"a-best-effort-low"}


# --- :1266 "BorrowWithinCohort: no preemption of lower-priority
#            workload from the SAME ClusterQueue" ------------------------

def test_borrow_within_cohort_not_same_cq(use_device):
    d, clock = make_driver(use_device, shared_cq_fixture())
    admit(d, "a-standard_old", "a-standard",
          {"cpu": ("default", 13 * K)}, priority=1)
    incoming(d, "in", "a-standard", {"cpu": 1 * K}, priority=2)
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :1388 "reclaim quota from lender" ----------------------------------

def test_reclaim_quota_from_lender(use_device):
    lend = [
        cq("lend1", [("default", {"cpu": (6 * K, None, 4 * K)})],
           cohort="cohort-lend", preemption=LOWER_BOTH),
        cq("lend2", [("default", {"cpu": (6 * K, None, 2 * K)})],
           cohort="cohort-lend", preemption=LOWER_BOTH),
    ]
    d, clock = make_driver(use_device, lend)
    admit(d, "lend1-low", "lend1", {"cpu": ("default", 3 * K)}, priority=-1)
    admit(d, "lend2-mid", "lend2", {"cpu": ("default", 3 * K)})
    admit(d, "lend2-high", "lend2", {"cpu": ("default", 4 * K)}, priority=1)
    incoming(d, "in", "lend1", {"cpu": 3 * K}, priority=1)
    stats = cycle(d, clock)
    assert preempted(stats) == {"lend2-mid"}


# --- :2713 TestCandidatesOrdering ---------------------------------------

def test_candidates_ordering():
    """Transliterates the reference's ordering table exactly: evicted
    first, then other-CQ, then lower priority, then later admission,
    then uid.  The key written out (tests/candidate_reference.py) and
    the preemptor's one sort over the queues' candidate tables give
    the same order."""
    import numpy as np
    from kueue_tpu.cache.state import CQState
    from kueue_tpu.scheduler.preemption import _ordered, _Part
    from kueue_tpu.workload import (WL_EVICTED, Condition, ConditionStatus,
                                    Info)
    from tests.candidate_reference import candidates_ordering_key

    now = 1000.0

    def info(name, cq="self", priority=0, at=now, evicted=False):
        wl = Workload(name=name, namespace="", priority=priority,
                      creation_time=at)
        if evicted:
            wl.conditions[WL_EVICTED] = Condition(
                type=WL_EVICTED, status=ConditionStatus.TRUE,
                last_transition_time=now)
        else:
            adm = Admission(cluster_queue=cq, pod_set_assignments=[])
            set_quota_reservation(wl, adm, at)
        return Info(wl)

    candidates = [
        info("high", priority=10),
        info("low", priority=-10),
        info("other", cq="other", priority=10),
        info("evicted", evicted=True),
        info("old-a"),
        info("old-b"),
        info("current", at=now + 1.0),
    ]
    queues = {name: CQState(ClusterQueue(name=name))
              for name in ("self", "other")}
    for c in candidates:
        queues[c.cluster_queue or "self"].add_workload(c)
    parts = [_Part(q, np.arange(q.candidates.n), now)
             for q in queues.values()]
    from_tables = _ordered(parts, parts[0])
    candidates.sort(key=candidates_ordering_key("self", now))
    got = [c.obj.name for c in candidates]
    assert got == ["evicted", "other", "low", "current", "old-a",
                   "old-b", "high"], got
    assert [c.obj.name for c in from_tables] == got
