"""Fused-burst parity: K cycles in one dispatch == K sequential cycles.

Every scenario runs twice on identically-built drivers: once through the
normal per-cycle path (schedule_once + harness-style finishes) and once
through Driver.schedule_burst.  Per-cycle decision sets must be
identical — admissions, skips, parks, preemptions — as must the final
admitted set.  Reference semantics: scheduler.go:176-302 cycles with
queue/manager.go heads + cluster_queue.go requeue rules.
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

# one flavor a head can preempt in: the reclaim oracle is never asked
pytestmark = pytest.mark.usefixtures("no_oracle_specs")


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def build(spec_fn, use_device=True):
    clock = Clock()
    d = Driver(clock=clock, use_device_solver=use_device)
    spec_fn(d)
    return d, clock


def run_host(d, clock, cycles, runtime):
    """The harness contract: schedule, then finish admissions whose
    modeled runtime elapsed (runner/controller/controller.go:113)."""
    out = []
    for c in range(cycles):
        clock.t += 1.0
        stats = d.schedule_once()
        out.append(stats)
        if runtime > 0 and c - runtime >= 0:
            for key in out[c - runtime].admitted:
                wl = d.workloads.get(key)
                if wl is not None and wl.has_quota_reservation:
                    d.finish_workload(key)
    return out


def run_burst(d, clock, cycles, runtime):
    def on_cycle_start(_k):
        clock.t += 1.0
    return d.schedule_burst(cycles, runtime=runtime,
                            on_cycle_start=on_cycle_start)


def assert_parity(spec_fn, cycles, runtime=0):
    da, ca = build(spec_fn)
    db, cb = build(spec_fn)
    host = run_host(da, ca, cycles, runtime)
    burst = run_burst(db, cb, cycles, runtime)
    # the burst may stop early only once the cluster is quiescent: every
    # host cycle past that point must be decision-free
    for s in host[len(burst):]:
        assert not (s.admitted or s.skipped or s.inadmissible
                    or s.preempting), "burst ended while host still active"
    for k, (h, b) in enumerate(zip(host, burst)):
        assert sorted(h.admitted) == sorted(b.admitted), \
            f"cycle {k} admitted: host={sorted(h.admitted)} " \
            f"burst={sorted(b.admitted)}"
        assert sorted(h.skipped) == sorted(b.skipped), \
            f"cycle {k} skipped differ"
        assert sorted(h.inadmissible) == sorted(b.inadmissible), \
            f"cycle {k} inadmissible differ"
        assert sorted(h.preempted_targets) == sorted(b.preempted_targets), \
            f"cycle {k} preempted differ"
    assert da.admitted_keys() == db.admitted_keys()
    return da, db, burst


def _quota(nominal, borrowing=None):
    return ResourceQuota(nominal=nominal, borrowing_limit=borrowing)


def simple_cluster(n_cohorts=2, cqs=2, nominal=4000, borrowing=None,
                   strategy=None, preemption=None):
    def fn(d):
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        for c in range(n_cohorts):
            for q in range(cqs):
                name = f"cq-{c}-{q}"
                d.apply_cluster_queue(ClusterQueue(
                    name=name, cohort=f"co-{c}",
                    queueing_strategy=(strategy or
                                       QueueingStrategy.BEST_EFFORT_FIFO),
                    preemption=preemption or PreemptionPolicy(),
                    resource_groups=[ResourceGroup(
                        covered_resources=["cpu"],
                        flavors=[FlavorQuotas(name="default", resources={
                            "cpu": _quota(nominal, borrowing)})])]))
                d.apply_local_queue(LocalQueue(name=f"lq-{c}-{q}",
                                               cluster_queue=name))
    return fn


def add_workloads(spec_fn, wls):
    def fn(d):
        spec_fn(d)
        for wl in wls:
            d.create_workload(wl)
    return fn


def mk(name, lq, cpu, prio=0, t=0.0, count=1):
    return Workload(name=name, queue_name=lq, priority=prio,
                    creation_time=t,
                    pod_sets=[PodSet(name="main", count=count,
                                     requests={"cpu": cpu})])


def preempting_cluster(cqs=3):
    """One cohort of ``cqs`` queues, each filled to its quota with four
    low-priority workloads (four settled cycles), then one high-priority
    head a queue that fits only by evicting three of them: the next
    cycle runs one preemption search a head, four candidates each.
    Returns (driver, clock) with the preemptors pending."""
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
    d, clock = build(add_workloads(
        simple_cluster(n_cohorts=1, cqs=cqs, nominal=4000, preemption=pre),
        [mk(f"low-{q}-{i}", f"lq-0-{q}", 1000, t=float(4 * q + i + 1))
         for q in range(cqs) for i in range(4)]))
    run_host(d, clock, 4, 0)
    for q in range(cqs):
        d.create_workload(mk(f"high-{q}", f"lq-0-{q}", 3000, prio=100,
                             t=50.0 + q))
    return d, clock


def test_burst_simple_drain():
    """More pending than quota: admissions, in-cycle skips, parking,
    finish-driven unparking across several fused cycles."""
    wls = []
    n = 0
    for c in range(2):
        for q in range(2):
            for i in range(6):
                n += 1
                wls.append(mk(f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 1500,
                              prio=(i % 3) * 10, t=float(n)))
    spec = add_workloads(simple_cluster(), wls)
    da, db, burst = assert_parity(spec, cycles=12, runtime=2)
    admitted = sum(len(s.admitted) for s in burst)
    assert admitted >= len(wls)  # everything eventually admits (re-admits
    # never happen: finished workloads leave the store)
    assert db._burst_solver.stats["burst_dispatches"] >= 1


def test_burst_borrowing_order():
    """Borrowing entries order after non-borrowing (entryOrdering
    primary key) and charge the cohort plane."""
    wls = [
        mk("big-a", "lq-0-0", 6000, prio=5, t=1.0),   # borrows from cohort
        mk("small-b", "lq-0-1", 2000, prio=0, t=2.0),  # nominal fit
        mk("small-c", "lq-0-1", 2000, prio=0, t=3.0),
    ]
    spec = add_workloads(
        simple_cluster(n_cohorts=1, cqs=2, nominal=4000, borrowing=4000),
        wls)
    assert_parity(spec, cycles=4, runtime=0)


def test_burst_strict_fifo_blocks():
    """StrictFIFO: a NoFit head blocks its CQ instead of parking."""
    wls = [
        mk("huge", "lq-0-0", 50_000, prio=10, t=1.0),   # never fits
        mk("tiny", "lq-0-0", 100, prio=0, t=2.0),       # blocked behind it
        mk("other", "lq-0-1", 100, prio=0, t=3.0),
    ]
    spec = add_workloads(
        simple_cluster(n_cohorts=1, cqs=2,
                       strategy=QueueingStrategy.STRICT_FIFO), wls)
    da, db, burst = assert_parity(spec, cycles=3, runtime=0)
    assert "default/tiny" not in db.admitted_keys()
    assert "default/other" in db.admitted_keys()


def test_burst_parking_and_unpark_on_finish():
    """BestEffortFIFO parks NoFit heads; a finish in the cohort unparks
    them (manager.go:490) and they admit in a later fused cycle."""
    wls = [
        mk("first", "lq-0-0", 4000, t=1.0),
        mk("waits", "lq-0-1", 4000, t=2.0),
    ]

    def spec(d):
        # one cohort, shared quota via borrowing: cq-0-1's head NoFits
        # until cq-0-0's workload finishes
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        for q in range(2):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-0-{q}", cohort="co-0",
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": _quota(2000, 2000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-0-{q}",
                                           cluster_queue=f"cq-0-{q}"))
        for wl in wls:
            d.create_workload(wl)

    da, db, burst = assert_parity(spec, cycles=6, runtime=2)
    assert "default/waits" not in db.admitted_keys() or \
        sum(len(s.admitted) for s in burst) == 2


def test_burst_preemption_goes_dirty():
    """A preempt-capable head makes the cycle dirty: the burst truncates
    and the normal path issues the preemptions — identical outcomes."""
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
    wls = [mk(f"low-{i}", "lq-0-0", 2000, prio=0, t=float(i))
           for i in range(2)]
    spec0 = add_workloads(
        simple_cluster(n_cohorts=1, cqs=1, nominal=4000, preemption=pre),
        wls)

    def spec(d):
        spec0(d)

    da, ca = build(spec)
    db, cb = build(spec)
    # admit the low-priority pair, then inject a high-priority preemptor
    for d, clock in ((da, ca), (db, cb)):
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("high", "lq-0-0", 4000, prio=100, t=50.0))
    host = run_host(da, ca, 4, 0)
    burst = run_burst(db, cb, 4, 0)
    for h, b in zip(host, burst):
        assert sorted(h.admitted) == sorted(b.admitted)
        assert sorted(h.preempted_targets) == sorted(b.preempted_targets)
    assert da.admitted_keys() == db.admitted_keys()
    assert any(s.preempted_targets for s in burst)


def test_burst_repack_carries_finish_schedule():
    """A dirty cycle truncates the burst mid-call while admissions from
    the applied prefix still hold quota; the re-packed dispatch must
    model their upcoming releases (else parked heads never unpark and
    the burst diverges from the host path)."""
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)

    def spec(d):
        simple_cluster(n_cohorts=1, cqs=2, nominal=4000,
                       preemption=pre)(d)
        # cq-0-0: filler admits at cycle 0 (runtime 3), then a preemptor
        # arrives -> dirty; cq-0-1: "later" parks (NoFit) until the
        # filler's finish unparks it cycles after the re-pack
        d.create_workload(mk("filler", "lq-0-0", 4000, prio=0, t=1.0))
        d.create_workload(mk("later", "lq-0-1", 4000, prio=0, t=2.0))
        d.create_workload(mk("blocked", "lq-0-1", 4000, prio=0, t=3.0))

    da, ca = build(spec)
    db, cb = build(spec)
    for d, clock in ((da, ca), (db, cb)):
        clock.t += 1.0
        d.schedule_once()     # admits filler + later (borrowing)
        d.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=60.0))
    host = run_host(da, ca, 8, 3)
    burst = run_burst(db, cb, 8, 3)
    for k, (h, b) in enumerate(zip(host, burst)):
        assert sorted(h.admitted) == sorted(b.admitted), f"cycle {k}"
        assert sorted(h.preempted_targets) == sorted(b.preempted_targets)
    assert da.admitted_keys() == db.admitted_keys()


def test_burst_external_finish_of_preempted_workload_is_skipped():
    """An external finish schedule built before a preemption must not
    finish the (now evicted and re-pending) workload — the northstar
    divergence regression: segment 1 admits W, segment 2's external
    schedule says W finishes at cycle f, but a preemptor evicts W at
    cycle e < f.  W must survive, requeue, and re-admit later."""
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)

    def spec(d):
        simple_cluster(n_cohorts=1, cqs=1, nominal=4000,
                       preemption=pre)(d)
        d.create_workload(mk("victim", "lq-0-0", 4000, prio=0, t=1.0))

    db, cb = build(spec)
    cb.t += 1.0
    db.schedule_once()          # victim admitted
    db.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=50.0))
    # external schedule claims victim finishes at offset 2, but the boss
    # preempts it at cycle 0 — the admission-identity guard must skip
    # the stale finish
    ext = {2: ["default/victim"]}
    stats = db.schedule_burst(8, runtime=3, external_finishes=ext,
                              on_cycle_start=lambda k: setattr(
                                  cb, "t", cb.t + 1.0))
    wl = db.workloads["default/victim"]
    assert not wl.is_finished, \
        "external finish must not apply to an evicted workload"
    assert any("default/victim" in s.preempted_targets for s in stats)
    # victim re-admits after boss's modeled runtime elapses
    assert any("default/victim" in s.admitted for s in stats)


def test_burst_multi_flavor_and_resume_dirty():
    """Multi-flavor CQs: fit-slot selection matches; skipped heads with
    untried flavors force dirty cycles (resume state is host-only)."""
    def spec(d):
        d.apply_resource_flavor(ResourceFlavor(name="f0"))
        d.apply_resource_flavor(ResourceFlavor(name="f1"))
        d.apply_cluster_queue(ClusterQueue(
            name="cq", resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[
                    FlavorQuotas(name="f0",
                                 resources={"cpu": _quota(2000)}),
                    FlavorQuotas(name="f1",
                                 resources={"cpu": _quota(8000)}),
                ])]))
        d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
        for i in range(5):
            d.create_workload(mk(f"w{i}", "lq", 1900, t=float(i)))

    assert_parity(spec, cycles=6, runtime=1)
