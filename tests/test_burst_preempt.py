"""In-kernel burst preemption parity: the fused kernel's candidate
discovery + ordering + greedy/fillback search + scan-time overlap/fits
discipline must be decision-identical to the host preemption path
(reference preemption.go:127-342, scheduler.go:211-284), with the cycles
decided INSIDE bursts (not via the dirty fallback).

Every scenario runs on two identically-built drivers — host per-cycle vs
Driver.schedule_burst — and asserts per-cycle admitted/preempted/skipped
/inadmissible sets match, plus burst stats proving the kernel decided
the preempt cycles.
"""

from __future__ import annotations

import pytest

from kueue_tpu.api.types import (
    ClusterQueue,
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
)
from kueue_tpu.controller.driver import Driver

from test_burst import (
    Clock,
    add_workloads,
    assert_parity,
    build,
    mk,
    run_burst,
    run_host,
    simple_cluster,
    _quota,
)

# one flavor a head can preempt in: the reclaim oracle is never asked
pytestmark = pytest.mark.usefixtures("no_oracle_specs")

PRE_ANY = PreemptionPolicy(
    reclaim_within_cohort=ReclaimWithinCohort.ANY,
    within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
PRE_LOWER = PreemptionPolicy(
    reclaim_within_cohort=ReclaimWithinCohort.LOWER_PRIORITY,
    within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
PRE_RECLAIM_ONLY = PreemptionPolicy(
    reclaim_within_cohort=ReclaimWithinCohort.ANY,
    within_cluster_queue=WithinClusterQueue.NEVER)


def run_pair(spec, prelude, cycles, runtime=0):
    """Build two drivers, run ``prelude`` on both (admissions +
    injections), then host cycles vs one schedule_burst call."""
    da, ca = build(spec)
    db, cb = build(spec)
    for d, clock in ((da, ca), (db, cb)):
        prelude(d, clock)
    host = run_host(da, ca, cycles, runtime)
    burst = run_burst(db, cb, cycles, runtime)
    for k, (h, b) in enumerate(zip(host, burst)):
        assert sorted(h.admitted) == sorted(b.admitted), \
            f"cycle {k} admitted: {sorted(h.admitted)} vs {sorted(b.admitted)}"
        assert sorted(h.preempted_targets) == sorted(b.preempted_targets), \
            f"cycle {k} targets: {sorted(h.preempted_targets)} vs " \
            f"{sorted(b.preempted_targets)}"
        assert sorted(h.preempting) == sorted(b.preempting), f"cycle {k}"
        assert sorted(h.skipped) == sorted(b.skipped), f"cycle {k}"
        assert sorted(h.inadmissible) == sorted(b.inadmissible), f"cycle {k}"
    for s in host[len(burst):]:
        assert not (s.admitted or s.skipped or s.inadmissible
                    or s.preempting), "burst ended while host still active"
    assert da.admitted_keys() == db.admitted_keys()
    return da, db, burst


def kernel_decided(db, min_preempt_cycles=1):
    st = db._burst_solver.stats
    assert st["burst_preempt_cycles"] >= min_preempt_cycles, st
    assert st["burst_dirty_preempt"] == 0, st


def test_within_cq_two_targets_and_fillback():
    """A preemptor that needs two of three lower-priority victims: the
    greedy walk takes newest-first and fill-back keeps the minimal set
    (preemption.go:275-342)."""
    def spec(d):
        simple_cluster(n_cohorts=1, cqs=1, nominal=6000,
                       preemption=PRE_ANY)(d)

    def prelude(d, clock):
        for i in range(3):
            d.create_workload(mk(f"low-{i}", "lq-0-0", 2000, prio=0,
                                 t=float(i)))
        for _ in range(3):     # one admission per cycle (one CQ)
            clock.t += 1.0
            d.schedule_once()
        d.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=50.0))

    da, db, burst = run_pair(spec, prelude, cycles=5)
    kernel_decided(db)
    # exactly two victims die (4000 needs 2x2000), one low survives
    preempted = {k for s in burst for k in s.preempted_targets}
    assert len(preempted) == 2
    assert "default/boss" in db.admitted_keys()


def test_newest_admission_preempted_first():
    """Equal-priority candidates: the most recently admitted goes first
    (candidatesOrdering, preemption.go:591)."""
    def spec(d):
        simple_cluster(n_cohorts=1, cqs=1, nominal=4000,
                       preemption=PRE_ANY)(d)

    def prelude(d, clock):
        d.create_workload(mk("old", "lq-0-0", 2000, prio=0, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("new", "lq-0-0", 2000, prio=0, t=2.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("boss", "lq-0-0", 2000, prio=100, t=50.0))

    da, db, burst = run_pair(spec, prelude, cycles=4)
    kernel_decided(db)
    preempted = {k for s in burst for k in s.preempted_targets}
    assert preempted == {"default/new"}


def test_cross_cq_reclaim():
    """Reclaim within cohort: the borrowing CQ's workloads are the
    targets, even at higher priority (ReclaimWithinCohort.ANY)."""
    def spec(d):
        simple_cluster(n_cohorts=1, cqs=2, nominal=4000, borrowing=4000,
                       preemption=PRE_ANY)(d)

    def prelude(d, clock):
        # cq-0-1 borrows the whole cohort: 2x 4000 (one nominal, one
        # borrowed at higher priority than the reclaimer)
        d.create_workload(mk("b-own", "lq-0-1", 4000, prio=50, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("b-borrow", "lq-0-1", 4000, prio=50, t=2.0))
        clock.t += 1.0
        d.schedule_once()
        # cq-0-0 reclaims its nominal share at LOWER priority than the
        # borrower: reclaim ANY allows it
        d.create_workload(mk("claim", "lq-0-0", 4000, prio=0, t=50.0))

    da, db, burst = run_pair(spec, prelude, cycles=4)
    kernel_decided(db)
    preempted = {k for s in burst for k in s.preempted_targets}
    assert len(preempted) == 1 and list(preempted)[0].startswith("default/b-")
    assert "default/claim" in db.admitted_keys()


def test_cross_cq_reclaim_lower_priority_only():
    """ReclaimWithinCohort.LowerPriority: a same-or-higher-priority
    borrower is untouchable; the reclaimer reserves instead."""
    def spec(d):
        simple_cluster(n_cohorts=1, cqs=2, nominal=4000, borrowing=4000,
                       preemption=PRE_LOWER)(d)

    def prelude(d, clock):
        d.create_workload(mk("b-own", "lq-0-1", 4000, prio=50, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("b-borrow", "lq-0-1", 4000, prio=50, t=2.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("claim", "lq-0-0", 4000, prio=10, t=50.0))

    da, db, burst = run_pair(spec, prelude, cycles=3)
    preempted = {k for s in burst for k in s.preempted_targets}
    assert preempted == set()
    assert "default/claim" not in db.admitted_keys()


def test_reclaim_only_policy_ignores_same_cq():
    """withinClusterQueue == Never: same-CQ lower-priority workloads are
    not candidates; only the cohort borrower is reclaimed."""
    def spec(d):
        simple_cluster(n_cohorts=1, cqs=2, nominal=4000, borrowing=4000,
                       preemption=PRE_RECLAIM_ONLY)(d)

    def prelude(d, clock):
        d.create_workload(mk("own-low", "lq-0-0", 2000, prio=0, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("borrower", "lq-0-1", 6000, prio=0, t=2.0))
        clock.t += 1.0
        d.schedule_once()
        # needs 2000 within nominal: own-low (2000) is NOT a candidate
        # (wcq Never); the cohort borrower is, and the staged no-borrow
        # search succeeds once it is gone
        d.create_workload(mk("boss", "lq-0-0", 2000, prio=100, t=50.0))

    da, db, burst = run_pair(spec, prelude, cycles=4)
    kernel_decided(db)
    preempted = {k for s in burst for k in s.preempted_targets}
    assert preempted == {"default/borrower"}


def test_overlapping_targets_second_preemptor_skips():
    """Two preemptors in the same cycle whose searches picked the same
    victim: the second is skipped with the overlap message
    (scheduler.go:235)."""
    def spec(d):
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        for q in range(2):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-0-{q}", cohort="co-0", preemption=PRE_ANY,
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": _quota(2000, 4000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-0-{q}",
                                           cluster_queue=f"cq-0-{q}"))

    def prelude(d, clock):
        # cq-0-0 borrows the whole cohort with one big workload
        d.create_workload(mk("victim", "lq-0-0", 4000, prio=0, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        # two reclaimers, one per CQ, both need the same victim gone
        d.create_workload(mk("r0", "lq-0-0", 2000, prio=100, t=50.0))
        d.create_workload(mk("r1", "lq-0-1", 2000, prio=100, t=51.0))

    da, db, burst = run_pair(spec, prelude, cycles=4)
    kernel_decided(db)
    assert any(s.skipped for s in burst)   # the overlap skip
    assert "default/r0" in db.admitted_keys()
    assert "default/r1" in db.admitted_keys()


def test_reserve_blocks_lower_priority_entry():
    """A preempt head with no candidates reserves capacity in-scan, so a
    lower-priority fit head in the same cohort can't jump ahead
    (resourcesToReserve, scheduler.go:383-408)."""
    def spec(d):
        simple_cluster(n_cohorts=1, cqs=2, nominal=4000, borrowing=4000,
                       preemption=PRE_ANY)(d)

    def prelude(d, clock):
        # the cohort is 6000/8000 used by HIGHER-priority work and the
        # other CQ is exactly at nominal (not borrowing): boss has no
        # candidates anywhere
        d.create_workload(mk("high-a", "lq-0-0", 2000, prio=200, t=1.0))
        d.create_workload(mk("high-b", "lq-0-1", 4000, prio=200, t=2.0))
        clock.t += 1.0
        d.schedule_once()
        # boss (prio 100) preempt-classifies but finds no targets →
        # reserves the remaining cohort headroom; tiny (prio 0, other
        # CQ, would borrow that headroom) must not jump ahead
        d.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=50.0))
        d.create_workload(mk("tiny", "lq-0-1", 2000, prio=0, t=51.0))

    da, db, burst = run_pair(spec, prelude, cycles=2)
    assert "default/boss" not in db.admitted_keys()
    # cycle 0: the reserve holds the headroom — tiny is skipped (host
    # message: no longer fits) even though it nominated Fit.  Once the
    # reserving boss parks, cycle 1 admits tiny (host-identical).
    assert "default/tiny" in burst[0].skipped
    assert "default/boss" in burst[0].inadmissible


def test_preempted_target_requeues_and_readmits():
    """A preempted workload re-enters the queue at its original rank and
    re-admits once the preemptor finishes (runtime-modeled)."""
    def spec(d):
        simple_cluster(n_cohorts=1, cqs=1, nominal=4000,
                       preemption=PRE_ANY)(d)

    def prelude(d, clock):
        d.create_workload(mk("victim", "lq-0-0", 4000, prio=0, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=50.0))

    da, db, burst = run_pair(spec, prelude, cycles=8, runtime=2)
    kernel_decided(db)
    assert any("default/victim" in s.preempted_targets for s in burst)
    # boss admits, runs 2 cycles, finishes; victim re-admits
    readmit = [k for s in burst for k in s.admitted].count("default/victim")
    assert readmit == 1   # the prelude admission happened pre-burst


def test_staged_search_under_nominal():
    """Cross-CQ candidates + queue under nominal: the host first tries
    all candidates WITHOUT borrowing, then same-queue with borrowing
    (preemption.go:144-191 staged specs) — kernel must pick the same
    winner set."""
    def spec(d):
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        for q in range(2):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-0-{q}", cohort="co-0", preemption=PRE_ANY,
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": _quota(4000, 4000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-0-{q}",
                                           cluster_queue=f"cq-0-{q}"))

    def prelude(d, clock):
        # own CQ partially used (under nominal), cohort exhausted by the
        # other CQ borrowing
        d.create_workload(mk("own", "lq-0-0", 2000, prio=0, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("b1", "lq-0-1", 4000, prio=0, t=2.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("b2", "lq-0-1", 2000, prio=0, t=3.0))
        clock.t += 1.0
        d.schedule_once()
        # boss needs 4000 in cq-0-0: under nominal (2000 < 4000), cross
        # candidates exist → staged search
        d.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=50.0))

    da, db, burst = run_pair(spec, prelude, cycles=5)
    kernel_decided(db)
    assert "default/boss" in db.admitted_keys()


def test_strict_fifo_preemptor():
    """StrictFIFO CQ: the preemptor stays head while pending preemption
    and admits once targets are gone; the CQ stays blocked meanwhile."""
    def spec(d):
        simple_cluster(n_cohorts=1, cqs=1, nominal=4000,
                       strategy=QueueingStrategy.STRICT_FIFO,
                       preemption=PRE_ANY)(d)

    def prelude(d, clock):
        d.create_workload(mk("victim", "lq-0-0", 4000, prio=0, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=50.0))
        d.create_workload(mk("behind", "lq-0-0", 100, prio=0, t=51.0))

    da, db, burst = run_pair(spec, prelude, cycles=4)
    kernel_decided(db)
    assert "default/boss" in db.admitted_keys()


def test_preemptor_wave_many_cqs():
    """A north-star-shaped wave: per-CQ high-priority gangs preempt the
    running low-priority wave across many CQs in one burst — the
    kernel's forest-parallel preempt scan at (small) scale."""
    n_cqs = 6

    def spec(d):
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        for i in range(n_cqs):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-{i}", cohort=f"co-{i // 3}", preemption=PRE_ANY,
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": _quota(4000, 8000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                           cluster_queue=f"cq-{i}"))

    def prelude(d, clock):
        n = 0
        for i in range(n_cqs):
            for j in range(2):
                n += 1
                d.create_workload(mk(f"low-{i}-{j}", f"lq-{i}", 2000,
                                     prio=0, t=float(n)))
        for _ in range(2):
            clock.t += 1.0
            d.schedule_once()
        for i in range(n_cqs):
            d.create_workload(mk(f"pre-{i}", f"lq-{i}", 4000, prio=100,
                                 t=100.0 + i))

    da, db, burst = run_pair(spec, prelude, cycles=6, runtime=3)
    kernel_decided(db)
    preempted = {k for s in burst for k in s.preempted_targets}
    assert len(preempted) == 2 * n_cqs
    admitted_all = {k for s in burst for k in s.admitted}
    for i in range(n_cqs):
        assert f"default/pre-{i}" in admitted_all


def test_two_resources_partial_preempt_need():
    """Two resources where only one needs preemption: candidate
    filtering uses the shortfall resource only
    (frsNeedingPreemption, preemption.go:466)."""
    def spec(d):
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        d.apply_cluster_queue(ClusterQueue(
            name="cq", cohort="co", preemption=PRE_ANY,
            resource_groups=[ResourceGroup(
                covered_resources=["cpu", "mem"],
                flavors=[FlavorQuotas(name="default", resources={
                    "cpu": _quota(4000), "mem": _quota(8000)})])]))
        d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))

    def prelude(d, clock):
        d.create_workload(Workload(
            name="low", queue_name="lq", priority=0, creation_time=1.0,
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": 4000, "mem": 1000})]))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(Workload(
            name="boss", queue_name="lq", priority=100, creation_time=50.0,
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": 2000, "mem": 2000})]))

    da, db, burst = run_pair(spec, prelude, cycles=4)
    kernel_decided(db)
    preempted = {k for s in burst for k in s.preempted_targets}
    assert preempted == {"default/low"}


def test_evicted_row_afterlife_honors_limit_range():
    """An in-burst-evicted workload whose namespace gained a LimitRange
    after its original admission must NOT be re-admitted by the kernel:
    its afterlife row is gated out of the vectorized envelope and the
    host path (which rules it inadmissible) decides — the r5 review
    repro (pack ok_l for admitted rows skipping the LimitRange gate)."""
    from kueue_tpu.limitrange import LimitRange, LimitRangeItem

    def spec(d):
        simple_cluster(n_cohorts=1, cqs=1, nominal=4000,
                       preemption=PRE_ANY)(d)

    def prelude(d, clock):
        d.create_workload(mk("victim", "lq-0-0", 4000, prio=0, t=1.0))
        clock.t += 1.0
        d.schedule_once()          # victim admitted pre-LimitRange
        d.apply_limit_range(LimitRange(
            name="lr", namespace="default",
            items=[LimitRangeItem(type="Container",
                                  max={"cpu": 3500})]))
        d.create_workload(mk("boss", "lq-0-0", 3000, prio=100, t=50.0))

    da, db, burst = run_pair(spec, prelude, cycles=8, runtime=2)
    assert any("default/victim" in s.preempted_targets for s in burst)
    # after eviction the 4000-cpu victim exceeds the namespace max of
    # 3500: never re-admitted on either path
    assert "default/victim" not in {k for s in burst for k in s.admitted}
