"""Partial admission's count search on the per-cycle engine's vector
path (``Scheduler._search_counts``), held to the host walk.

Every head that neither fits nor finds targets at its full counts and
has a PodSet with ``min_count`` under ``count`` searches its counts in
lockstep passes: one vector classify of every searching head's probe a
pass, one batched target search of the pass's Preempt-mode probes
(podset_reducer.go's binary search over the reduction index).  Each
case runs a toy cluster cycle by cycle on the host scheduler and on the
device-decided cycle, and holds them equal: every cycle's decisions
with the admitted counts, and, head by head, the count search's
answer (counts, Assignment, targets, resume state) against the host
``_assign_and_search`` on the same snapshot."""

import math

import pytest

from kueue_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    ReclaimWithinCohort,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    WithinClusterQueue,
    Workload,
)
from kueue_tpu.controller.driver import Driver
from kueue_tpu.scheduler.scheduler import Scheduler
from kueue_tpu.workload import set_quota_reservation, sync_admitted_condition
from kueue_tpu.api.types import Admission, PodSetAssignment
from tests.conftest import FakeClock

K = 1000
LOWER = PreemptionPolicy(within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
RECLAIM = PreemptionPolicy(
    reclaim_within_cohort=ReclaimWithinCohort.ANY,
    within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)


def queue(d, name, cpu, cohort=None, borrowing=None, policy=LOWER,
          flavors=("default",)):
    d.apply_cluster_queue(ClusterQueue(
        name=name, cohort=cohort, preemption=policy,
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name=f, resources={
                "cpu": ResourceQuota(nominal=cpu, borrowing_limit=borrowing)})
            for f in flavors])]))
    d.apply_local_queue(LocalQueue(name="lq-" + name, cluster_queue=name))


def pending(name, q, pod_sets, priority=0, created=1.0):
    """pod_sets: [(name, count, min_count, cpu a pod)]."""
    return Workload(
        name=name, queue_name="lq-" + q, priority=priority,
        creation_time=created,
        pod_sets=[PodSet(name=pn, count=c, min_count=m,
                         requests={"cpu": cpu})
                  for pn, c, m, cpu in pod_sets])


def running(d, name, q, pods, cpu, priority=0, flavor="default",
            min_count=None):
    wl = Workload(name=name, queue_name="lq-" + q, priority=priority,
                  creation_time=0.5,
                  pod_sets=[PodSet(name="main", count=pods,
                                   min_count=min_count,
                                   requests={"cpu": cpu})])
    set_quota_reservation(wl, Admission(cluster_queue=q, pod_set_assignments=[
        PodSetAssignment(name="main", flavors={"cpu": flavor},
                         resource_usage={"cpu": pods * cpu}, count=pods)]),
        0.5)
    sync_admitted_condition(wl, 0.5)
    d.restore_workload(wl)


# --- the cases ------------------------------------------------------------

def fit_partial(d):
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    queue(d, "cq", 5 * K)
    return [pending(f"wl-{i}", "cq", [("main", 8, 2, 1 * K)],
                    created=float(i + 1)) for i in range(4)]


def preempt_partial(d):
    # 30 pods never fit the 20-cpu nominal; at a reduced count the
    # lower-priority running job is the target
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    queue(d, "cq", 20 * K)
    running(d, "old", "cq", 10, 1 * K, priority=-4)
    running(d, "peer", "cq", 5, 1 * K, priority=4)
    return [pending("new", "cq", [("main", 30, 10, 1 * K)], priority=4)]


def no_count_fits(d):
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    queue(d, "cq", 6 * K)
    running(d, "held", "cq", 3, 1 * K, priority=9)
    return [pending("big", "cq", [("main", 8, 6, 1 * K)], priority=5),
            pending("small", "cq", [("main", 2, 1, 1 * K)], priority=1,
                    created=2.0)]


def min_count_minus_one(d):
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    queue(d, "cq", 4 * K)
    return [pending(f"wl-{i}", "cq", [("main", 5, 4, 1 * K)],
                    created=float(i + 1)) for i in range(3)]


def two_podsets_uneven(d):
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    # deltas 4 and 1: only the search's last index reduces b
    queue(d, "cq", 3500)
    return [pending(f"wl-{i}", "cq", [("a", 6, 2, 1 * K), ("b", 3, None, 500)],
                    created=float(i + 1)) for i in range(3)]


def _b_elastic_too(d):
    # upstream's webhook lets one PodSet carry minCount; the reducer
    # takes any number (podset_reducer.go), and both engines are held
    # to it with a second one set behind the webhook
    for i in range(3):
        d.workload(f"default/wl-{i}").pod_sets[1].min_count = 2


two_podsets_uneven.after = _b_elastic_too


def gang_workers_elastic(d):
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    queue(d, "cq", 6 * K)
    return [pending(f"wl-{i}", "cq", [("launcher", 1, None, 1 * K),
                                      ("workers", 8, 2, 1 * K)],
                    created=float(i + 1)) for i in range(3)]


def reclaim_any(d):
    # cq-b borrows 4 cpus of cq-a's nominal; cq-a's elastic head is
    # Preempt at its full 8 pods with nobody to evict (it would borrow),
    # and reclaims from cq-b at a reduced count
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    queue(d, "cq-a", 10 * K, cohort="team", borrowing=0, policy=RECLAIM)
    queue(d, "cq-b", 10 * K, cohort="team", borrowing=10 * K,
          policy=RECLAIM)
    running(d, "a-own", "cq-a", 6, 1 * K, priority=5)
    for i in range(4):
        running(d, f"b-{i}", "cq-b", 1, 3500, priority=1)
    return [pending("elastic", "cq-a", [("main", 8, 2, 1 * K)], priority=5)]


def slack_taken_first(d):
    # one cohort's slack of 4 cpus: the plain head of cq-x (not
    # borrowing, so first in the cycle's order) takes it before the
    # elastic head of cq-y, whose reduced count fits at nominate
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    queue(d, "cq-x", 4 * K, cohort="team", borrowing=4 * K)
    queue(d, "cq-y", 0, cohort="team", borrowing=8 * K)
    return [pending("plain", "cq-x", [("main", 4, None, 1 * K)],
                    created=2.0),
            pending("elastic", "cq-y", [("main", 8, 2, 1 * K)],
                    created=1.0),
            pending("later", "cq-y", [("main", 4, 1, 1 * K)],
                    created=3.0)]


def full_queue_then_finishes(d):
    # the benchmark's shape: every queue full at its nominal with jobs
    # of its own priority, so a head is Preempt with no candidate at
    # its full count until enough finishes free quota for part of it
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    for q in ("q0", "q1"):
        queue(d, q, 8 * K, cohort="team", borrowing=8 * K, policy=RECLAIM)
        for i in range(4):
            running(d, f"{q}-run-{i}", q, 2, 1 * K, priority=50)
    return [pending(f"{q}-wl-{i}", q, [("main", 6, 2, 500)], priority=50,
                    created=float(i + 1))
            for q in ("q0", "q1") for i in range(6)]


def scaled_units(d):
    # a quota past the int32 headroom, so the packed cpu scale is not
    # 1: the usage alone (multiples of 500 m) would make it 4, which a
    # 250-m pod does not divide; the elastic Jobs' pod makes it 2, and
    # the probes at odd counts scale exactly
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    queue(d, "cq", 40_000 * K)
    running(d, "big", "cq", 1, 40_000 * K - 2000)
    running(d, "elastic-old", "cq", 2, 250, min_count=1)
    return [pending(f"wl-{i}", "cq", [("main", 8, 2, 250)],
                    created=float(i + 1)) for i in range(2)]


CASES = {f.__name__: f for f in (
    fit_partial, preempt_partial, no_count_fits, min_count_minus_one,
    two_podsets_uneven, gang_workers_elastic, reclaim_any,
    slack_taken_first, full_queue_then_finishes, scaled_units)}


# --- the drive --------------------------------------------------------------

def drive(use_device, build, n_cycles=12, finish=()):
    """Run ``build``'s cluster; ``finish`` [(cycle, key)] finishes a
    running workload after that cycle.  Returns (log, driver)."""
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device)
    for wl in build(d):
        d.create_workload(wl)
    if hasattr(build, "after"):
        build.after(d)
    log = []
    for cycle in range(n_cycles):
        clock.t += 1.0
        stats = d.schedule_once()
        log.append({
            "admitted": sorted(
                (k, tuple((a.name, a.count, ps.count,
                           a.resource_usage.get("cpu"))
                          for a, ps in zip(
                              d.workload(k).admission.pod_set_assignments,
                              d.workload(k).pod_sets)))
                for k in stats.admitted),
            "skipped": sorted(stats.skipped),
            "inadmissible": sorted(stats.inadmissible),
            "preempting": sorted(stats.preempting),
            "targets": sorted(stats.preempted_targets),
        })
        for at, key in finish:
            if at == cycle:
                wl = d.workload(key)
                if wl is not None and wl.has_quota_reservation:
                    d.finish_workload(key)
    return log, d


def facts(assignment, targets):
    """What the count search decides of a head, as the host walk
    writes it: the counts and requests a PodSet, each resource's flavor,
    mode and the walk's resume index, the borrowing, the usage, the
    resume state, and the eviction targets in their order."""
    return {
        "mode": assignment.representative_mode(),
        "borrowing": assignment.borrows(),
        "usage": dict(assignment.usage),
        "pod_sets": [(ps.name, ps.count, dict(ps.requests),
                      {r: (f.name, f.mode, f.tried_flavor_idx)
                       for r, f in ps.flavors.items()})
                     for ps in assignment.pod_sets],
        "last_state": ([dict(x) for x in
                        assignment.last_state.last_tried_flavor_idx],
                       assignment.last_state.cluster_queue_generation),
        "targets": [t.info.key for t in targets],
    }


@pytest.fixture
def witnessed(monkeypatch):
    """Every count search of the device path, held head by head to the
    host ``_assign_and_search`` on the same snapshot; yields the list
    of (heads searched, passes, widest total delta) a cycle."""
    seen = []
    search = Scheduler._search_counts

    def checked(self, deferred, cls, snapshot, heads, *rest):
        passes = self.solver.stats["partial_passes"]
        out = search(self, deferred, cls, snapshot, heads, *rest)
        seen.append((len(heads), self.solver.stats["partial_passes"] - passes,
                     max(sum(ps.count - ps.min_count
                             for ps in deferred[wi].obj.pod_sets
                             if ps.min_count is not None)
                         for wi in heads)))
        pre = dict(self.preemptor.stats)
        for wi in heads:
            info = deferred[wi].info
            resume = info.last_assignment
            want = self._get_assignments(info, snapshot)
            info.last_assignment = resume
            if wi in out:
                got = out[wi]
            else:       # Preempt-mode at full counts, none found: kept
                got = (self.solver.reserve_details(cls, wi)[0], [])
            assert facts(*got) == facts(*want), (info.key, got, want)
        # the witness's own searches are not the engine's
        self.preemptor.stats.clear()
        self.preemptor.stats.update(pre)
        return out

    monkeypatch.setattr(Scheduler, "_search_counts", checked)
    return seen


FINISH = {"preempt_partial": [(3, "default/new")],
          "full_queue_then_finishes": [(0, "default/q0-run-0"),
                                       (1, "default/q1-run-1"),
                                       (2, "default/q0-run-2"),
                                       (4, "default/q1-run-3")]}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_vector_count_search_is_the_host_walks(case, witnessed):
    build, finish = CASES[case], FINISH.get(case, ())
    host, _ = drive(False, build, finish=finish)
    dev, d = drive(True, build, finish=finish)
    for cycle, (h, v) in enumerate(zip(host, dev)):
        assert h == v, (case, cycle, h, v)
    solver, pre = d.scheduler.solver.stats, d.scheduler.preemptor.stats
    assert witnessed, "no head searched its counts"
    assert solver["host_cycles"] == 0, solver
    assert solver["scalar_heads"] == 0, solver
    assert solver["scalar_reasons"] == {}, solver
    assert pre["host_searches"] == 0, pre
    assert solver["partial_heads"] == sum(n for n, _, _ in witnessed)
    reduced = sum(any(count < full for _, count, full, _ in pss)
                  for c in dev for _, pss in c["admitted"])
    assert solver["partial_admitted"] == reduced, (solver, dev)
    if case == "no_count_fits":
        assert not any(k == "default/big" for c in host
                       for k, _ in c["admitted"]), host
    elif case in ("preempt_partial", "reclaim_any"):
        assert any(c["targets"] for c in host), host
    elif case == "slack_taken_first":
        assert host[0]["admitted"][0][0] == "default/plain", host
        assert "default/elastic" in host[0]["skipped"], host
    else:
        assert reduced, host


def test_the_passes_a_cycle_are_bounded(witnessed):
    """A cycle's lockstep passes stay within the binary search's depth
    over the widest reduction, one more for the full counts; the heads
    counted are the heads that searched."""
    total = 0
    for case in sorted(CASES):
        n0 = len(witnessed)
        _, d = drive(True, CASES[case], finish=FINISH.get(case, ()))
        cycles = witnessed[n0:]
        for heads, passes, delta in cycles:
            assert 1 <= passes <= math.ceil(math.log2(delta + 1)) + 1, (
                case, heads, passes, delta)
        stats = d.scheduler.solver.stats
        assert stats["partial_heads"] == sum(h for h, _, _ in cycles)
        assert stats["partial_passes"] == sum(p for _, p, _ in cycles)
        assert stats["partial_probes"] >= stats["partial_passes"]
        total += len(cycles)
    assert total >= len(CASES)


def test_the_scale_divides_an_elastic_pod():
    """The packed structure's scale, chosen before any head is seen,
    divides one pod of an admitted elastic Job, so that a reduced
    count's request and the usage it leaves are whole scaled units."""
    from kueue_tpu.ops.packing import pack_structure
    d = Driver(clock=FakeClock(), use_device_solver=True)
    scaled_units(d)
    st = pack_structure(d.cache.snapshot(), [])
    scale = int(st.resource_scale[st.r_index["cpu"]])
    assert scale > 1 and 250 % scale == 0 and st.exact_static, scale
