"""Conformance replay of the reference's TestFairPreemptions tables
(/root/reference/pkg/scheduler/preemption/preemption_test.go:1891-2200),
end to end through the fair-sharing scheduler on both paths.

Fixture: CQs a/b/c (nominal 3 cpu each) + preemptible (nominal 0) in one
cohort "all" (total 9), borrowWithinCohort LowerPriority threshold -3,
withinClusterQueue LowerPriority, reclaimWithinCohort Any — the `want`
sets are the reference's own expectations, transliterated."""

import pytest

from kueue_tpu.api.types import (
    BorrowWithinCohort,
    BorrowWithinCohortPolicy,
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PreemptionPolicy,
    ReclaimWithinCohort,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    WithinClusterQueue,
)
from kueue_tpu.controller.driver import Driver
from tests.conftest import FakeClock
from tests.test_conformance_preemption import admit, cycle, incoming, preempted

K = 1000


def make_driver(use_device):
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device, fair_sharing=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    policy = PreemptionPolicy(
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        borrow_within_cohort=BorrowWithinCohort(
            policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
            max_priority_threshold=-3))
    for name in ("a", "b", "c"):
        d.apply_cluster_queue(ClusterQueue(
            name=name, cohort="all", preemption=policy,
            resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=3 * K)})])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{name}", cluster_queue=name))
    d.apply_cluster_queue(ClusterQueue(
        name="preemptible", cohort="all",
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=0)})])]))
    d.apply_local_queue(LocalQueue(name="lq-preemptible",
                                   cluster_queue="preemptible"))
    return d, clock


def units(d, cq_name, names, cpu=1 * K, priority=0):
    for n in names:
        admit(d, n, cq_name, {"cpu": ("default", cpu)}, priority=priority)


@pytest.fixture(params=[False, True], ids=["host", "device"])
def use_device(request):
    return request.param


# --- :1952 "reclaim nominal from user using the most" -------------------

def test_reclaim_nominal_from_biggest_user(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


# --- :1969 "can reclaim from queue using less, if taking the latest
#            workload from the biggest user isn't enough" ----------------

def test_reclaim_from_queue_using_less(use_device):
    d, clock = make_driver(use_device)
    admit(d, "a1", "a", {"cpu": ("default", 3 * K)})
    admit(d, "a2", "a", {"cpu": ("default", 1 * K)})
    admit(d, "b1", "b", {"cpu": ("default", 2 * K)})
    admit(d, "b2", "b", {"cpu": ("default", 3 * K)})
    incoming(d, "c-incoming", "c", {"cpu": 3 * K})
    assert preempted(cycle(d, clock)) == {"a1"}


# --- :1981 "reclaim borrowable quota from user using the most" ----------

def test_reclaim_borrowable_from_biggest_user(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1"])
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


# --- :1998 "preempt one from each CQ borrowing" -------------------------

def test_preempt_one_from_each_borrowing_cq(use_device):
    d, clock = make_driver(use_device)
    admit(d, "a1", "a", {"cpu": ("default", 500)})
    admit(d, "a2", "a", {"cpu": ("default", 500)})
    admit(d, "a3", "a", {"cpu": ("default", 3 * K)})
    admit(d, "b1", "b", {"cpu": ("default", 500)})
    admit(d, "b2", "b", {"cpu": ("default", 500)})
    admit(d, "b3", "b", {"cpu": ("default", 3 * K)})
    incoming(d, "c-incoming", "c", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"a1", "b1"}


# --- :2015 "can't preempt when everyone under nominal" ------------------

def test_no_preemption_when_everyone_under_nominal(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3"])
    units(d, "c", ["c1", "c2", "c3"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :2031 "can't preempt when it would switch the imbalance" -----------

def test_no_preemption_when_it_switches_imbalance(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    incoming(d, "a-incoming", "a", {"cpu": 2 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :2046 "can preempt lower priority workloads from same CQ" ----------

def test_preempt_lower_priority_same_cq(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1-low", "a2-low"], priority=-1)
    units(d, "a", ["a3", "a4"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    incoming(d, "a-incoming", "a", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"a1-low", "a2-low"}


# --- :2066 "can preempt a combination of same CQ and highest user" ------

def test_preempt_combination_same_cq_and_biggest_user(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a-low"], priority=-1)
    units(d, "a", ["a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5", "b6"])
    incoming(d, "a-incoming", "a", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"a-low", "b1"}


# --- :2086 "preempt huge workload if there is no other option" ----------

def test_preempt_huge_workload_when_only_option(use_device):
    d, clock = make_driver(use_device)
    admit(d, "b1", "b", {"cpu": ("default", 9 * K)})
    incoming(d, "a-incoming", "a", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


# --- :2095 "can't preempt huge workload if the incoming is also huge" ---

def test_no_preempt_huge_for_huge_incoming(use_device):
    d, clock = make_driver(use_device)
    admit(d, "a1", "a", {"cpu": ("default", 2 * K)})
    admit(d, "b1", "b", {"cpu": ("default", 7 * K)})
    incoming(d, "a-incoming", "a", {"cpu": 5 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :2104 "can't preempt 2 smaller workloads if the incoming is huge" --

def test_no_preempt_two_smaller_for_huge_incoming(use_device):
    d, clock = make_driver(use_device)
    admit(d, "b1", "b", {"cpu": ("default", 2 * K)})
    admit(d, "b2", "b", {"cpu": ("default", 2 * K)})
    admit(d, "b3", "b", {"cpu": ("default", 3 * K)})
    incoming(d, "a-incoming", "a", {"cpu": 6 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :2113 "preempt from target and others even if over nominal" --------

def test_preempt_target_and_others_over_nominal(use_device):
    d, clock = make_driver(use_device)
    admit(d, "a1-low", "a", {"cpu": ("default", 2 * K)}, priority=-1)
    admit(d, "a2-low", "a", {"cpu": ("default", 1 * K)}, priority=-1)
    admit(d, "b1", "b", {"cpu": ("default", 3 * K)})
    admit(d, "b2", "b", {"cpu": ("default", 3 * K)})
    incoming(d, "a-incoming", "a", {"cpu": 4 * K})
    assert preempted(cycle(d, clock)) == {"a1-low", "b1"}


# --- :2129 "prefer to preempt workloads that don't make the target CQ
#            have the biggest share" -------------------------------------

def test_prefer_not_making_target_biggest_share(use_device):
    d, clock = make_driver(use_device)
    admit(d, "b1", "b", {"cpu": ("default", 2 * K)})
    admit(d, "b2", "b", {"cpu": ("default", 1 * K)})
    admit(d, "b3", "b", {"cpu": ("default", 2 * K)})
    admit(d, "c1", "c", {"cpu": ("default", 1 * K)})
    incoming(d, "a-incoming", "a", {"cpu": 3500})
    assert preempted(cycle(d, clock)) == {"b2"}


# --- :2144 "preempt from different cluster queues if the end result has
#            a smaller max share" ----------------------------------------

def test_preempt_from_different_cqs_smaller_max_share(use_device):
    d, clock = make_driver(use_device)
    admit(d, "b1", "b", {"cpu": ("default", 2 * K)})
    admit(d, "b2", "b", {"cpu": ("default", 2500)})
    admit(d, "c1", "c", {"cpu": ("default", 2 * K)})
    admit(d, "c2", "c", {"cpu": ("default", 2500)})
    incoming(d, "a-incoming", "a", {"cpu": 3500})
    assert preempted(cycle(d, clock)) == {"b1", "c1"}


# --- :2159 "scenario above does not flap" -------------------------------

def test_no_flapping(use_device):
    d, clock = make_driver(use_device)
    admit(d, "a1", "a", {"cpu": ("default", 3500)})
    admit(d, "b2", "b", {"cpu": ("default", 2500)})
    admit(d, "c2", "c", {"cpu": ("default", 2500)})
    incoming(d, "b-incoming", "b", {"cpu": 2 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :2171 "cannot preempt if it would make the candidate CQ go under
#            nominal after preempting one element" -----------------------

def test_no_preempt_below_nominal_candidate(use_device):
    d, clock = make_driver(use_device)
    admit(d, "b1", "b", {"cpu": ("default", 3 * K)})
    admit(d, "b2", "b", {"cpu": ("default", 3 * K)})
    admit(d, "c1", "c", {"cpu": ("default", 3 * K)})
    incoming(d, "a-incoming", "a", {"cpu": 4 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- :2186 "workloads under priority threshold not capriciously
#            preempted" --------------------------------------------------

def test_priority_threshold_not_capricious(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3"])
    units(d, "preemptible", ["p1", "p2", "p3"], priority=-3)
    incoming(d, "a-incoming", "a", {"cpu": 2 * K})
    stats = cycle(d, clock)
    assert not preempted(stats)


# ========================================================================
# Second TestFairPreemptions table: strategy-specific rows (S2-a vs S2-b
# applied alone), threshold-boundary borrowing rows, tournament-ordering
# rows, and multi-cycle stability rows — same fixture, transliterated
# from the upstream table's second half.
# ========================================================================


def make_driver_strategies(use_device, strategies):
    """Same fixture as make_driver but with an explicit fair-sharing
    preemption-strategy list (reference parseStrategies)."""
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device, fair_sharing=True,
               fs_preemption_strategies=list(strategies))
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    policy = PreemptionPolicy(
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        borrow_within_cohort=BorrowWithinCohort(
            policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
            max_priority_threshold=-3))
    for name in ("a", "b", "c"):
        d.apply_cluster_queue(ClusterQueue(
            name=name, cohort="all", preemption=policy,
            resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=3 * K)})])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{name}", cluster_queue=name))
    d.apply_cluster_queue(ClusterQueue(
        name="preemptible", cohort="all",
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=0)})])]))
    d.apply_local_queue(LocalQueue(name="lq-preemptible",
                                   cluster_queue="preemptible"))
    return d, clock


# --- "reclaim two units in one cycle" -----------------------------------

def test_reclaim_two_units_one_cycle(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"b1", "b2"}


# --- "candidate ordering prefers lower priority within the chosen CQ" ---

def test_reclaim_prefers_lower_priority_candidate(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1"], priority=5)
    units(d, "b", ["b2", "b3", "b4", "b5"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b2"}


# --- "cross-CQ reclaim ignores candidate priority entirely" -------------

def test_cross_cq_reclaim_ignores_candidate_priority(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"], priority=9)
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


# --- "preemptible CQ (nominal 0) pays first when over-borrowed" ---------

def test_preemptible_borrower_reclaimed_for_nominal_incoming(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3"])
    units(d, "c", ["c1"])
    units(d, "preemptible", ["p1", "p2"], priority=-4)
    incoming(d, "c-incoming", "c", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"p1", "p2"}


# --- "borrowing incoming may preempt a sub-threshold borrower" ----------

def test_borrowing_incoming_preempts_below_threshold(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3"])
    units(d, "c", ["c1"])
    units(d, "preemptible", ["p1", "p2"], priority=-4)
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"p1"}


# --- "threshold boundary: priority exactly at maxPriorityThreshold" -----

def test_borrowing_incoming_preempts_at_threshold_boundary(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3"])
    units(d, "c", ["c1"])
    units(d, "preemptible", ["p1", "p2"], priority=-3)
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"p1"}


# --- "within-CQ candidates: lower priority first, then newest" ----------

def test_within_cq_prefers_newest_among_equal_priority(use_device):
    d, clock = make_driver(use_device)
    admit(d, "a1", "a", {"cpu": ("default", 1 * K)}, priority=-1,
          reserved_at=0.2)
    admit(d, "a2", "a", {"cpu": ("default", 1 * K)}, priority=-1,
          reserved_at=0.9)
    admit(d, "a3", "a", {"cpu": ("default", 1 * K)})
    units(d, "b", ["b1", "b2", "b3"])
    units(d, "c", ["c1", "c2", "c3"])
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"a2"}


# --- "no preemption when free quota suffices" ---------------------------

def test_no_preemption_when_free_quota_suffices(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    stats = cycle(d, clock)
    assert not preempted(stats)
    assert stats.admitted == ["default/c-incoming"]


# --- "tournament descends into the highest-share CQ first" --------------

def test_tournament_picks_highest_share_cq_first(use_device):
    d, clock = make_driver(use_device)
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1", "c2", "c3", "c4"])
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


# --- "tournament equalizes across borrowers" ----------------------------

def test_tournament_equalizes_across_borrowing_cqs(use_device):
    d, clock = make_driver(use_device)
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1", "c2", "c3", "c4"])
    incoming(d, "a-incoming", "a", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"b1", "c1"}


# --- "sole big borrower: S2-a fails, S2-b retry preempts it" ------------

def test_default_strategies_preempt_sole_big_borrower(use_device):
    d, clock = make_driver(use_device)
    admit(d, "b-big", "b", {"cpu": ("default", 5 * K)})
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "c", ["c1"])
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b-big"}


def test_final_share_only_blocks_sole_big_borrower(use_device):
    d, clock = make_driver_strategies(
        use_device, ["LessThanOrEqualToFinalShare"])
    admit(d, "b-big", "b", {"cpu": ("default", 5 * K)})
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "c", ["c1"])
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


def test_initial_share_only_preempts_sole_big_borrower(use_device):
    d, clock = make_driver_strategies(use_device, ["LessThanInitialShare"])
    admit(d, "b-big", "b", {"cpu": ("default", 5 * K)})
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "c", ["c1"])
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b-big"}


# --- "S2-b needs STRICT inequality: equal shares don't preempt" ---------

def test_initial_share_strict_inequality_blocks_equal_shares(use_device):
    d, clock = make_driver_strategies(use_device, ["LessThanInitialShare"])
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4"])
    units(d, "c", ["c1", "c2"])
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


def test_default_strategies_block_equal_share_borrower(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4"])
    units(d, "c", ["c1", "c2"])
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- "S2-a alone still reclaims from the biggest user" ------------------

def test_final_share_only_reclaims_biggest_user(use_device):
    d, clock = make_driver_strategies(
        use_device, ["LessThanOrEqualToFinalShare"])
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


def test_initial_share_only_reclaims_biggest_user(use_device):
    d, clock = make_driver_strategies(use_device, ["LessThanInitialShare"])
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


# --- "a borrow that only equalizes shares is blocked" -------------------
# a at 6/9 would reach DRS 333 == b's current 333: S2-a fails after the
# removal drops b to 222, S2-b fails on the strict inequality, and the
# within-CQ eviction of a-low alone cannot free 3 units — so nothing
# is preempted at all.

def test_three_unit_borrow_blocked_at_equal_share(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a-low"], priority=-1)
    units(d, "a", ["a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5", "b6"])
    incoming(d, "a-incoming", "a", {"cpu": 3 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- "preempted workloads requeue; the system does not flap" ------------

def test_reclaim_converges_without_flapping(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    s1 = cycle(d, clock)
    assert preempted(s1) == {"b1"}
    admitted = set()
    for _ in range(4):
        s = cycle(d, clock)
        admitted.update(s.admitted)
        assert not preempted(s)   # no second round of evictions
    assert "default/c-incoming" in admitted


# --- "freed quota is re-lent after the reclaimer finishes" --------------
# The b units are admitted through the real scheduling path (one head
# per cycle) so they carry distinct admission timestamps and a queue
# route: the reclaim then targets the most recently admitted unit, the
# victim requeues, and once the reclaimer finishes it borrows again.

def test_requeued_victim_readmits_after_finish(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "c", ["c1"])
    for i in range(1, 6):
        incoming(d, f"b{i}", "b", {"cpu": 1 * K}, created=float(i))
    admitted = []
    for _ in range(5):
        admitted += cycle(d, clock).admitted
    assert admitted == [f"default/b{i}" for i in range(1, 6)]
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    # newest admitted unit pays (candidate ordering: priority, then
    # most recently admitted first)
    assert preempted(cycle(d, clock)) == {"b5"}
    readmitted = []
    for _ in range(3):
        s = cycle(d, clock)
        readmitted += s.admitted
        assert not preempted(s)
    assert "default/c-incoming" in readmitted
    d.finish_workload("default/c-incoming")
    got = []
    for _ in range(12):   # ride out the requeue backoff
        clock.t += 10.0
        got += d.schedule_once().admitted
        if got:
            break
    assert got == ["default/b5"]


# --- "reclaim within nominal ignores incoming priority" -----------------

def test_reclaim_ignores_incoming_priority(use_device):
    d, clock = make_driver(use_device)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K}, priority=-2)
    assert preempted(cycle(d, clock)) == {"b1"}


# --- "single larger candidate preferred when one eviction suffices" -----

def test_single_larger_candidate_for_two_unit_incoming(use_device):
    d, clock = make_driver(use_device)
    admit(d, "b-big", "b", {"cpu": ("default", 2 * K)})
    admit(d, "b2", "b", {"cpu": ("default", 1 * K)})
    admit(d, "b3", "b", {"cpu": ("default", 1 * K)})
    admit(d, "b4", "b", {"cpu": ("default", 1 * K)})
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"b-big"}


# ========================================================================
# Third table: cohort-borrowing × FS-preemption × sharded-dispatch grid.
# Every row below runs in three modes — host, device, and device with
# the solver routed through an 8-way (wl, cq) mesh on the conftest's
# virtual CPU devices — and the `want` sets must hold in all three:
# sharded dispatch is a deployment choice, never a semantics change.
# ========================================================================


@pytest.fixture(params=["host", "device", "sharded"])
def fs_mode(request):
    return request.param


def make_driver_mode(mode):
    d, clock = make_driver(use_device=(mode != "host"))
    if mode == "sharded":
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices (conftest XLA flag)")
        from kueue_tpu.parallel.sharded import make_mesh
        d.scheduler.solver.set_mesh(make_mesh(8))
    return d, clock


# --- "reclaim one unit from the biggest borrower, deeper imbalance" -----

def test_sharded_reclaim_from_deeper_borrower(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1", "a2"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5", "b6"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


# --- "reclaim two units from the sole borrower" -------------------------

def test_sharded_reclaim_two_from_sole_borrower(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5", "b6"])
    units(d, "c", ["c1", "c2"])
    incoming(d, "a-incoming", "a", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"b1", "b2"}


# --- "borrowing incoming preempts two from a deep sub-threshold
#      borrower (a's post-borrow share stays strictly under p's)" --------

def test_sharded_borrowing_preempts_two_below_threshold(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1"])
    units(d, "c", ["c1"])
    units(d, "preemptible", ["p1", "p2", "p3", "p4"], priority=-4)
    incoming(d, "a-incoming", "a", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"p1", "p2"}


# --- "while borrowing, the FS share strategies arbitrate — the
#      borrowWithinCohort priority threshold does not shield a deeper
#      borrower above it" ------------------------------------------------

def test_sharded_fs_strategies_override_borrow_threshold(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3"])
    units(d, "c", ["c1"])
    units(d, "preemptible", ["p1", "p2"], priority=-2)
    incoming(d, "a-incoming", "a", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"p1"}


# --- "reclaim targets the only borrowing CQ even when small" ------------

def test_sharded_reclaim_targets_only_borrower(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4"])
    units(d, "c", ["c1", "c2"])
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


# --- "borrowing incoming with no sub-threshold candidates is blocked" ---

def test_sharded_borrowing_incoming_blocked_without_candidates(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "c", ["c1"])
    incoming(d, "c-incoming", "c", {"cpu": 3 * K})
    stats = cycle(d, clock)
    assert not stats.admitted and not preempted(stats)


# --- "reclaim picks the bigger borrower over the preemptible CQ" --------

def test_sharded_reclaim_prefers_bigger_borrower_over_preemptible(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5"])
    units(d, "preemptible", ["p1"], priority=-4)
    incoming(d, "c-incoming", "c", {"cpu": 1 * K})
    assert preempted(cycle(d, clock)) == {"b1"}


# --- "huge preemptible workload reclaimed when it is the only option" ---

def test_sharded_huge_preemptible_reclaimed(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1", "a2", "a3"])
    admit(d, "p-big", "preemptible", {"cpu": ("default", 6 * K)},
          priority=-4)
    incoming(d, "c-incoming", "c", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"p-big"}


# --- "two-unit reclaim equalizes across equal borrowers" ----------------

def test_sharded_two_unit_reclaim_equalizes_borrowers(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1"])
    units(d, "b", ["b1", "b2", "b3", "b4"])
    units(d, "c", ["c1", "c2", "c3", "c4"])
    incoming(d, "a-incoming", "a", {"cpu": 2 * K})
    assert preempted(cycle(d, clock)) == {"b1", "c1"}


# --- "reclaim converges and the incoming admits without flapping" -------

def test_sharded_reclaim_converges_without_flapping(fs_mode):
    d, clock = make_driver_mode(fs_mode)
    units(d, "a", ["a1", "a2", "a3"])
    units(d, "b", ["b1", "b2", "b3", "b4", "b5", "b6"])
    incoming(d, "c-incoming", "c", {"cpu": 2 * K})
    s1 = cycle(d, clock)
    assert preempted(s1) == {"b1", "b2"}
    admitted = set()
    for _ in range(4):
        s = cycle(d, clock)
        admitted.update(s.admitted)
        assert not preempted(s)
    assert "default/c-incoming" in admitted
