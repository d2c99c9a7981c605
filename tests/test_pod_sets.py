"""A pass a PodSet, on every engine.

A Workload has 1 to 8 PodSets (upstream docs concepts/workload, "Pod
sets"; tasks/run mpijobs, rayjobs, jobsets, leaderworkerset: a launcher
and its workers), each with its own node selector and tolerations.
``assignFlavors`` (flavorassigner.go) walks them in order; each PodSet
gets one flavor a resource group, and its walk tests ``val = request +
assignment.usage[flavor, resource]``: what the earlier PodSets of the
same Workload already took there.  The Workload is as good as its worst
PodSet, a PodSet with no flavor ends the walk, and eviction targets are
found over the union of the pairs short of quota, against the summed
usage (scheduler/flavorassigner.py is the oracle).  Until PR 41 the
device path gave every such head to the host walk
(``scalar_reasons["multi_podset"]``) and a window with one went dirty.

Each case runs through the host scalar scheduler, the per-cycle device
engine and ``schedule_burst`` on identically built clusters, which have
to agree cycle by cycle on what is admitted, evicted, on which flavor
each resource of each PodSet lands and where each walk starts next.
There is no copy of upstream's ``flavorassigner_test.go`` on this
machine: the rows below follow its multi-PodSet cases as the rule above
gives them, and say which case each stands for.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from kueue_tpu.api.types import (
    Admission,
    PodSet,
    PodSetAssignment,
    Workload,
)
from kueue_tpu.controller.driver import Driver
from kueue_tpu.workload import set_quota_reservation, sync_admitted_condition
from tests.conftest import FakeClock
from tests.test_conformance_preemption import admit
from tests.test_resource_groups import (ARM, CASES as GROUP_CASES, DEFAULT,
                                        GI, K, X86, cluster, docs_groups,
                                        quotas)

CYCLES = 4
X, A = {"cpu-arch": "x86"}, {"cpu-arch": "arm"}


def gang(d, name, queue, *pod_sets, priority=10, created=999.0):
    """A Workload of the PodSets given, in order: (name, count, cpu a
    pod, memory a pod, node selector)."""
    d.create_workload(Workload(
        name=name, namespace="default", queue_name=f"lq-{queue}",
        priority=priority, creation_time=created,
        pod_sets=[PodSet(name=n, count=count,
                         requests={"cpu": cpu, "memory": memory},
                         node_selector=dict(sel or {}))
                  for n, count, cpu, memory, sel in pod_sets]))


# ---- the cases ---------------------------------------------------------------

def both_podsets_on_one_flavor(d):
    """Launcher and workers both pinned to x86, 1 + 3 of its 4 cpu: the
    workers fit only because exactly what the launcher took is counted,
    and the next gang, which would fit x86 uncharged, goes to arm.
    After flavorassigner_test "multiple specs, fit": the second spec's
    quota check includes the first's usage."""
    cluster(d, docs_groups())
    gang(d, "first", "a", ("launcher", 1, 1 * K, 1 * GI, X),
         ("workers", 3, 1 * K, 1 * GI, X), created=1.0)
    gang(d, "second", "a", ("launcher", 1, 1 * K, 1 * GI, None),
         ("workers", 3, 1 * K, 1 * GI, None), created=2.0)


def workers_pushed_to_the_next_flavor(d):
    """The launcher takes 2 of x86's 4 cpu; the workers ask 3 and would
    fit x86 alone, but 3 + 2 is over its capacity: NoFit there, arm
    next.  One admission on two flavors of one group.  After "multiple
    specs, fit different flavors"."""
    cluster(d, docs_groups())
    gang(d, "head", "a", ("launcher", 1, 2 * K, 1 * GI, X),
         ("workers", 1, 3 * K, 1 * GI, None))


def podsets_meet_on_the_shared_memory(d):
    """Launcher on x86, workers on arm, and both on default-flavor's
    memory: 2 + 15 GiB is over its 16, so the workers are NoFit in the
    memory group and the Workload with them: whole or not at all.  The
    smaller gang behind it fits.  After "multiple specs, one doesn't
    fit": no flavor for a PodSet ends the walk."""
    cluster(d, docs_groups())
    gang(d, "head", "a", ("launcher", 1, 1 * K, 2 * GI, X),
         ("workers", 1, 2 * K, 15 * GI, A), created=1.0)
    gang(d, "small", "a", ("launcher", 1, 1 * K, 2 * GI, X),
         ("workers", 1, 2 * K, 13 * GI, A), priority=5, created=2.0)


def first_podset_nofit_ends_the_walk(d):
    """The launcher asks more x86 cpu than the queue can ever have: the
    workers are never walked, the head parks and nothing is recorded.
    After "multiple specs, first doesn't fit"."""
    cluster(d, docs_groups())
    gang(d, "head", "a", ("launcher", 1, 5 * K, 1 * GI, X),
         ("workers", 2, 1 * K, 1 * GI, None), created=1.0)
    gang(d, "fits", "a", ("launcher", 1, 1 * K, 1 * GI, X),
         ("workers", 2, 1 * K, 1 * GI, A), priority=5, created=2.0)


def short_pairs_in_two_podsets(d):
    """x86 and arm are each full of a's own lower-priority work; the
    launcher is pinned to x86 and the workers to arm: the pairs short
    of quota are one in each PodSet, the search runs over their union
    against the summed usage, and both victims go.  After
    preemption_test "preempt in several flavors for one workload"."""
    cluster(d, docs_groups())
    admit(d, "own-x86", "a", {"cpu": ("x86", 4 * K),
                              "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    admit(d, "own-arm", "a", {"cpu": ("arm", 4 * K),
                              "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    gang(d, "head", "a", ("launcher", 1, 1 * K, 1 * GI, X),
         ("workers", 2, 1 * K, 1 * GI, A))


def second_podset_preempts_because_charged(d):
    """3 of x86's 4 cpu are free.  The launcher's 1 fits; the workers'
    3 would fit too, but at 3 + 1 they are short: Preempt, within
    nominal.  The Workload's mode is its worst PodSet's, the launcher's
    cpu stays Fit, and the one victim frees the pair.  After "multiple
    specs, fit with different modes"."""
    cluster(d, docs_groups())
    admit(d, "own-x86", "a", {"cpu": ("x86", 1 * K),
                              "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    gang(d, "head", "a", ("launcher", 1, 1 * K, 1 * GI, X),
         ("workers", 3, 1 * K, 1 * GI, X))


def each_podset_resumes_its_own_walk(d):
    """Cycle 1: both PodSets of a's gang fit x86 only by borrowing b's
    quota and stop there (slot 0 of the cpu group, recorded a PodSet);
    b's own head takes that cpu first and the gang is skipped.  Cycle
    2: both cpu walks resume at arm, the memory walks start at 0.
    After "multiple specs, resume from last tried flavor"."""
    cluster(d, docs_groups(x86=0, arm=4 * K), docs_groups(x86=4 * K, arm=0))
    gang(d, "head", "a", ("launcher", 1, 1 * K, 2 * GI, None),
         ("workers", 2, 1 * K, 2 * GI, None), created=5.0)
    gang(d, "first", "b", ("main", 1, 4 * K, 2 * GI, None),
         priority=20, created=1.0)


def oracle_asked_at_the_charged_quantity(d):
    """The launcher is pinned to arm, which is under a's nominal but
    lent to b: Preempt, its only flavor.  The workers may take either:
    x86 is full of a's own higher-priority work (the request would
    borrow: no question), arm is asked about at 1 + 1 cpu, the workers'
    and the launcher's, and is Reclaim: arm over x86, and b's borrower
    goes."""
    cluster(d, docs_groups(), docs_groups(x86=0, arm=0))
    admit(d, "own-x86", "a", {"cpu": ("x86", 4 * K),
                              "memory": ("default-flavor", 1 * GI)},
          priority=50)
    admit(d, "lent-arm", "b", {"cpu": ("arm", 4 * K),
                               "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    gang(d, "head", "a", ("launcher", 1, 1 * K, 1 * GI, A),
         ("workers", 1, 1 * K, 1 * GI, None))


def oracle_in_an_earlier_podset_is_the_hosts(d):
    """As above with the launcher free to take either flavor: its pick
    is the oracle's, and the workers' walk is charged with it, so the
    vector classify hands the head to the host walk, which asks as it
    goes (``podset_oracle_order``); the decision is the same."""
    cluster(d, docs_groups(), docs_groups(x86=0, arm=0))
    admit(d, "own-x86", "a", {"cpu": ("x86", 4 * K),
                              "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    admit(d, "lent-arm", "b", {"cpu": ("arm", 4 * K),
                               "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    gang(d, "head", "a", ("launcher", 1, 1 * K, 1 * GI, None),
         ("workers", 1, 1 * K, 1 * GI, None))


def three_podsets(d):
    """A head, a launcher and workers (a RayCluster with two worker
    groups): three passes, the third charged with both before it; the
    planes hold four."""
    cluster(d, docs_groups())
    gang(d, "head", "a", ("head", 1, 1 * K, 1 * GI, X),
         ("group-a", 2, 1 * K, 1 * GI, X),
         ("group-b", 2, 1 * K, 1 * GI, None))


X86_DEF = {"cpu": "x86", "memory": "default-flavor"}
ARM_DEF = {"cpu": "arm", "memory": "default-flavor"}
CASES = {
    # name: (builder, {workload: [{resource: flavor} a PodSet] it ends
    #        on, or None}, evicted, oracle questions, scalar heads)
    "both_podsets_on_one_flavor": (
        both_podsets_on_one_flavor,
        {"first": [X86_DEF, X86_DEF], "second": [ARM_DEF, ARM_DEF]},
        [], False, 0),
    "workers_pushed_to_the_next_flavor": (
        workers_pushed_to_the_next_flavor,
        {"head": [X86_DEF, ARM_DEF]}, [], False, 0),
    "podsets_meet_on_the_shared_memory": (
        podsets_meet_on_the_shared_memory,
        {"head": None, "small": [X86_DEF, ARM_DEF]}, [], False, 0),
    "first_podset_nofit_ends_the_walk": (
        first_podset_nofit_ends_the_walk,
        {"head": None, "fits": [X86_DEF, ARM_DEF]}, [], False, 0),
    "short_pairs_in_two_podsets": (
        short_pairs_in_two_podsets,
        {"head": [X86_DEF, ARM_DEF]}, ["own-arm", "own-x86"], False, 0),
    "second_podset_preempts_because_charged": (
        second_podset_preempts_because_charged,
        {"head": [X86_DEF, X86_DEF]}, ["own-x86"], False, 0),
    "each_podset_resumes_its_own_walk": (
        each_podset_resumes_its_own_walk,
        {"head": [ARM_DEF, ARM_DEF], "first": [X86_DEF]}, [], False, 0),
    "oracle_asked_at_the_charged_quantity": (
        oracle_asked_at_the_charged_quantity,
        {"head": [ARM_DEF, ARM_DEF]}, ["lent-arm"], True, 0),
    "oracle_in_an_earlier_podset_is_the_hosts": (
        oracle_in_an_earlier_podset_is_the_hosts,
        {"head": [ARM_DEF, ARM_DEF]}, ["lent-arm"], None, 1),
    "three_podsets": (
        three_podsets,
        {"head": [X86_DEF, X86_DEF, ARM_DEF]}, [], False, 0),
}


def flavors_of(d, keys):
    return {k: [dict(ps.flavors)
                for ps in d.workload(k).admission.pod_set_assignments]
            for k in keys}


def run(engine, build, cycles=CYCLES):
    """[(admitted, evicted, {admitted key: [{resource: flavor} a
    PodSet]}, {pending key: the slot each (PodSet, group)'s next walk
    starts on})] a cycle, and the driver."""
    from kueue_tpu.ops.solver import resume_starts
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=engine != "host")
    build(d)
    out = []

    def record(stats):
        resume = {}
        for q in d.queues.cluster_queue_names():
            cq = d.queues.queue_for(q)
            live = d.cache.cluster_queue(q)
            for info in list(cq.heap.items()) + list(
                    cq.inadmissible.values()):
                resume[info.key] = resume_starts(
                    info, live, False, len(live.spec.resource_groups),
                    len(info.total_requests))
        out.append((sorted(stats.admitted), sorted(stats.preempted_targets),
                    flavors_of(d, stats.admitted), resume))

    def tick(_k=None):
        clock.t += 1.0

    if engine == "burst":
        d.schedule_burst(cycles, on_cycle_start=tick,
                         on_cycle=lambda _k, stats: record(stats))
    else:
        for _ in range(cycles):
            tick()
            record(d.schedule_once())
    return out, d


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_engine_passes_over_the_podsets(case):
    build, want, evicted, asks, scalar = CASES[case]
    host, dh = run("host", build)
    for name, flavors in want.items():
        wl = dh.workload(f"default/{name}")
        if flavors is None:
            assert not wl.has_quota_reservation, host
        else:
            assert flavors_of(dh, [f"default/{name}"]) == {
                f"default/{name}": flavors}, host
    assert sorted(k for _, ev, _, _ in host for k in ev) == [
        f"default/{k}" for k in sorted(evicted)], host

    for engine in ("device", "burst"):
        got, d = run(engine, build)
        # the burst stops once nothing is left to decide
        assert got == host[:len(got)], (engine, got, host)
        assert all(not (a or ev) for a, ev, _, _ in host[len(got):])
        solver, pre = d.scheduler.solver.stats, d.scheduler.preemptor.stats
        assert solver["host_cycles"] == 0, solver
        assert solver["cq_shape_heads"] == 0, solver
        assert pre["host_searches"] == 0, pre
        if asks is not None:
            assert (pre["oracle_specs"] > 0) == asks, pre
        if scalar:
            assert solver["scalar_reasons"] == {
                "podset_oracle_order": solver["scalar_heads"]}, solver
            assert solver["podset_scalar_heads"] == solver["scalar_heads"]
            assert solver["scalar_heads"] >= scalar, solver
        else:
            assert solver["scalar_heads"] == 0, solver
            assert solver["scalar_reasons"] == {}, solver
            assert solver["podset_scalar_heads"] == 0, solver
        if engine == "device" and not scalar:
            assert solver["gang_heads"] > 0, solver
            assert solver["podset_walks"] > solver["walk_heads"], solver


# (c) a fused window that applies cycles whose heads are gangs
@pytest.mark.parametrize("case", [
    "both_podsets_on_one_flavor", "workers_pushed_to_the_next_flavor",
    "podsets_meet_on_the_shared_memory", "three_podsets",
    "each_podset_resumes_its_own_walk"])
def test_the_window_applies_gang_cycles(case):
    """Fit, skip and park cycles of gangs are the window's own: it is
    not dirty, the per-cycle engine never runs, and what it applied is
    what the per-cycle engine decides."""
    build = CASES[case][0]
    per_cycle, _ = run("device", build)
    got, d = run("burst", build)
    assert got == per_cycle[:len(got)]
    assert any(a for a, _, _, _ in got)
    b, s = d._burst_solver.stats, d.scheduler.solver.stats
    assert b["burst_dispatches"] >= 1, b
    assert b["burst_dirty_cycles"] == 0, b
    assert b["burst_dirty_scalar"] == 0, b
    assert s["full_cycles"] == s["classify_cycles"] == 0, s
    P = d.scheduler.solver._structure.pod_sets
    assert P == (4 if case == "three_podsets" else 2)


def test_a_preempting_gang_inside_the_window():
    """One queue alone in its cohort is inside the window's preemption
    envelope: the gang whose second PodSet is short only because the
    first was charged evicts its victim in the kernel."""
    host, _ = run("host", second_podset_preempts_because_charged)
    got, d = run("burst", second_podset_preempts_because_charged)
    assert got == host[:len(got)]
    assert [ev for _, ev, _, _ in got if ev] == [["default/own-x86"]]
    b = d._burst_solver.stats
    assert b["burst_preempt_cycles"] >= 1 and b["burst_dirty_cycles"] == 0, b


# (d) the search of a gang short in two PodSets
def test_short_pairs_of_two_podsets_find_the_hosts_targets():
    from kueue_tpu.scheduler.preemption import (
        flavor_resources_need_preemption)
    from kueue_tpu.resources import FlavorResource
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    short_pairs_in_two_podsets(d)
    snap = d.cache.snapshot()
    heads = d.queues.queue_for("a").heap.items()
    for h in heads:
        h.cluster_queue = "a"
    solver = d.scheduler.solver
    cls = solver.classify(snap, heads)
    assert cls.preempt0[0] and not cls.scalar_mask[0]
    # launcher: cpu short on x86, memory fits; workers: cpu short on arm
    st = cls.packed.structure
    cpu, mem = st.r_index["cpu"], st.r_index["memory"]
    assert cls.preempt_res_fit[0, :2, cpu].tolist() == [False, False]
    assert cls.preempt_res_fit[0, :2, mem].tolist() == [True, True]
    a = solver.build_preempt_assignment(cls, 0)
    assert flavor_resources_need_preemption(a) == {
        FlavorResource("x86", "cpu"), FlavorResource("arm", "cpu")}
    assert dict(a.usage) == {
        FlavorResource("x86", "cpu"): 1 * K,
        FlavorResource("arm", "cpu"): 2 * K,
        FlavorResource("default-flavor", "memory"): 3 * GI}
    d.scheduler.preemptor.set_cycle_pack(snap, cls.packed)
    (vec,) = d.scheduler.preemptor.get_targets_batch([(heads[0], a)], snap)
    # the host: its own walk, its own search
    host = Driver(clock=FakeClock(), use_device_solver=False)
    short_pairs_in_two_podsets(host)
    hsnap = host.cache.snapshot()
    (hh,) = host.queues.queue_for("a").heap.items()
    hh.cluster_queue = "a"
    from kueue_tpu.scheduler.scheduler import Entry
    e = Entry(info=hh)
    host.scheduler._assign_entry(e, hsnap)
    assert sorted(t.info.key for t in vec) == sorted(
        t.info.key for t in e.preemption_targets) == [
            "default/own-arm", "default/own-x86"]
    assert dict(e.assignment.usage) == dict(a.usage)
    assert d.scheduler.preemptor.stats["host_searches"] == 0


# (a) P = 1 is the walk of PR 40, field by field
WALK_FIELDS_PER_WALK = ("chosen", "walked", "tried", "has_stop", "pre_g",
                        "oracle_groups", "preempt_slots", "res_fr",
                        "res_fit", "slot_res_fit", "slot_borrows",
                        "oracle_ask")
WALK_FIELDS_PER_HEAD = ("has_fit", "has_preempt", "borrows", "walk_slots",
                        "walk_ineligible", "group_walks", "split_mode")


def _walk_inputs(d, rng=None):
    """``walk_groups``' arguments for every pending workload of ``d``
    as one cycle's heads, as ``classify_np`` makes them."""
    from kueue_tpu.ops.cycle import available_all_np
    from kueue_tpu.ops.packing import pack_cycle
    from kueue_tpu.workload import Ordering
    snap = d.cache.snapshot()
    heads = []
    for q in d.queues.cluster_queue_names():
        for info in d.queues.queue_for(q).heap.items():
            info.cluster_queue = q
            heads.append(info)
    st = d.scheduler.solver._structure_for(snap, heads)
    packed = pack_cycle(snap, heads, Ordering(), structure=st)
    u = packed.usage0
    quota = (st.subtree_quota, st.guaranteed, st.borrow_cap,
             st.has_borrow_limit, st.parent, st.depth)
    av = available_all_np(u, *quota)
    pot = available_all_np(np.zeros_like(u), *quota)
    cqs = np.maximum(packed.wl_cq, 0)
    frs = st.slot_fr[cqs]
    at = (cqs[:, None, None], np.maximum(frs, 0))
    W, G, S = len(cqs), st.n_groups, frs.shape[1]
    eligible = np.ones((W, G, S), dtype=bool)
    start = np.zeros((W, G), dtype=np.int32)
    if rng is not None:
        eligible = rng.random((W, G, S)) < 0.7
        start = rng.integers(0, S, (W, G)).astype(np.int32)
    return packed, dict(
        frs=frs, grp=st.res_group[cqs], slot_ok=st.slot_valid[cqs],
        slot_count=st.slot_count_cq[cqs], av=av[at], pot=pot[at],
        nom=st.nominal_cq[at], use=u[at], sq=st.subtree_quota[at],
        can_preempt_borrow=st.cq_can_preempt_borrow[cqs],
        has_parent=st.parent[cqs] >= 0, wcb=st.cq_wcb_borrow[cqs],
        wcp=st.cq_wcp_preempt[cqs], valid=packed.wl_cq >= 0), eligible, start


@pytest.mark.parametrize("case", sorted(GROUP_CASES))
@pytest.mark.parametrize("planes", ["plain", "random"])
def test_one_podset_is_the_walk_of_pr_40(case, planes):
    from kueue_tpu.ops.cycle import walk_groups
    from tests.walk_groups_pr40 import walk_groups as walk_pr40
    d = Driver(clock=FakeClock(), use_device_solver=True)
    GROUP_CASES[case][0](d)
    rng = np.random.default_rng(41) if planes == "random" else None
    packed, common, eligible, start = _walk_inputs(d, rng)
    assert packed.wl_requests.shape[1] == 1
    req = packed.wl_requests.astype(np.int64)
    old = walk_pr40(np, req=req[:, 0], eligible=eligible, start=start,
                    **common)
    new = walk_groups(np, req=req, eligible=eligible[:, None],
                      start=start[:, None], **common)
    for name in WALK_FIELDS_PER_WALK:
        assert new[name].shape[1] == 1, name
        assert new[name].dtype == old[name].dtype, name
        assert np.array_equal(new[name][:, 0], old[name]), name
    for name in WALK_FIELDS_PER_HEAD:
        assert np.array_equal(new[name], old[name]), name
    assert set(old) == set(WALK_FIELDS_PER_WALK + WALK_FIELDS_PER_HEAD)
    n = packed.wl_count
    assert np.array_equal(new["podset_walks"][:n], np.ones(n))
    assert not new["charged_walks"].any() and not new["split_flavor"].any()


def test_one_podset_is_the_walk_of_pr_40_under_jax():
    """The same at ``xp=jax.numpy``: what the fused window traces."""
    import jax.numpy as jnp
    from kueue_tpu.ops.cycle import walk_groups
    from tests.walk_groups_pr40 import walk_groups as walk_pr40
    d = Driver(clock=FakeClock(), use_device_solver=True)
    GROUP_CASES["oracle_asked_in_one_group_only"][0](d)
    packed, common, eligible, start = _walk_inputs(
        d, np.random.default_rng(7))
    req = packed.wl_requests
    old = walk_pr40(jnp, req=jnp.asarray(req[:, 0]), eligible=eligible,
                    start=start, **common)
    new = walk_groups(jnp, req=jnp.asarray(req), eligible=eligible[:, None],
                      start=start[:, None], **common)
    for name in WALK_FIELDS_PER_WALK:
        assert np.array_equal(np.asarray(new[name])[:, 0],
                              np.asarray(old[name])), name
    for name in WALK_FIELDS_PER_HEAD:
        assert np.array_equal(np.asarray(new[name]),
                              np.asarray(old[name])), name


# (b) the vector classify against the host FlavorAssigner, random gangs
TOY = os.path.join(os.path.dirname(__file__), "data", "toy-1kcq-gangs.json")


def toy_cluster(d, toy):
    for f in (X86, ARM, DEFAULT):
        d.apply_resource_flavor(f)
    from kueue_tpu.api.types import (ClusterQueue, FlavorFungibility,
                                     FlavorFungibilityPolicy, LocalQueue,
                                     PreemptionPolicy, ReclaimWithinCohort,
                                     ResourceGroup, WithinClusterQueue)
    for q in toy["queues"]:
        bl = q["borrowing_limit"]
        d.apply_cluster_queue(ClusterQueue(
            name=q["name"], cohort=f"cohort-{q['cohort']}",
            flavor_fungibility=FlavorFungibility(
                when_can_preempt=FlavorFungibilityPolicy.TRY_NEXT_FLAVOR),
            preemption=PreemptionPolicy(
                within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
                reclaim_within_cohort=ReclaimWithinCohort.ANY),
            resource_groups=[
                ResourceGroup(covered_resources=["cpu"], flavors=[
                    quotas("x86", cpu=(q["x86"], bl["cpu"])),
                    quotas("arm", cpu=(q["arm"], bl["cpu"]))]),
                ResourceGroup(covered_resources=["memory"], flavors=[
                    quotas("default-flavor",
                           memory=(q["memory"] * GI, bl["memory"] * GI))])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{q['name']}",
                                       cluster_queue=q["name"]))
    for i, r in enumerate(toy["running"]):
        flavor, cpu = r["cpu"]
        admit(d, f"run-{i}", r["queue"],
              {"cpu": (flavor, cpu),
               "memory": ("default-flavor", r["memory"] * GI)},
              priority=r["priority"], reserved_at=0.5 + i * 0.01)


class AskedOracle:
    """The preemption oracle as a table: Reclaim where a hash of the
    question says so and the quantity does not borrow (the real one's
    first test), every question written down."""

    def __init__(self):
        self.asked = []

    @staticmethod
    def says(fr, qty) -> bool:
        return (hash((fr.flavor, fr.resource)) + qty // 500) % 3 != 0

    def is_reclaim_possible(self, cq, wl, fr, quantity) -> bool:
        self.asked.append((wl.key, fr, quantity))
        return not cq.borrowing_with(fr, quantity) and self.says(
            fr, quantity)


@pytest.mark.parametrize("seed", [2, 5, 6, 10, 11, 12])
def test_vector_classify_against_the_host_walk(seed):
    """Random two- and three-PodSet heads on the toy cluster: slots,
    modes, ``tried``, the oracle's questions and their quantities, and
    the usage charged, head by head."""
    from kueue_tpu.scheduler.flavorassigner import FlavorAssigner, Mode
    with open(TOY) as f:
        toy = json.load(f)
    d = Driver(clock=FakeClock(), use_device_solver=True)
    toy_cluster(d, toy)
    rng = np.random.default_rng(seed)
    draw = toy["heads"]
    pick = lambda xs: xs[int(rng.integers(len(xs)))]      # noqa: E731
    for q in toy["queues"]:
        for j in range(3):
            n = pick(draw["pod_sets"])
            gang(d, f"{q['name']}-g{j}", q["name"], *[
                (f"ps{p}", int(rng.integers(1, 3)), pick(draw["cpu_m"]),
                 pick(draw["memory_gib"]) * GI, pick(draw["selectors"]))
                for p in range(n)],
                priority=pick(draw["priorities"]), created=10.0 + j)
    snap = d.cache.snapshot()
    seen = {"fit": 0, "preempt": 0, "nofit": 0, "asked": 0, "charged": 0,
            "scalar": 0}
    solver = d.scheduler.solver
    # one structure for all the heads, scaled to hold every request
    solver._structure_for(snap, [
        i for q in toy["queues"]
        for i in d.queues.queue_for(q["name"]).heap.items()])
    # every pending gang in turn as a cycle's heads, three a queue
    for j in range(3):
        heads = []
        for q in toy["queues"]:
            info = next(i for i in d.queues.queue_for(q["name"]).heap.items()
                        if i.obj.name == f"{q['name']}-g{j}")
            info.cluster_queue = q["name"]
            heads.append(info)
        cls = solver.classify(snap, heads)
        st = cls.packed.structure
        assert st.pod_sets == 4 and cls.slots0.shape[1:] == (4, 2)
        pre = np.nonzero(cls.preempt0[:cls.n])[0]
        reclaim = np.zeros((len(pre),) + cls.oracle_ask.shape[1:], bool)
        vec_asked = {}
        for hi, wi in enumerate(pre):
            for p, s, ri, fr, qty in solver.oracle_queries(cls, int(wi)):
                vec_asked.setdefault(int(wi), set()).add((fr, qty))
                reclaim[hi, p, s, ri] = AskedOracle.says(fr, qty)
        solver.pick_preempt_slots(cls, pre, reclaim)
        seen["charged"] += int(solver.stats["charged_walks"])
        for wi, h in enumerate(heads):
            oracle = AskedOracle()
            cq = snap.cq(h.cluster_queue)
            want = FlavorAssigner(h, cq, snap.resource_flavors,
                                  oracle=oracle).assign()
            mode = want.representative_mode()
            if cls.scalar_mask[wi]:
                seen["scalar"] += 1
                continue        # an earlier PodSet's pick was the oracle's
            assert (mode == Mode.FIT) == bool(cls.fit0[wi]), h.key
            assert (mode == Mode.PREEMPT) == bool(cls.preempt0[wi]), h.key
            if mode == Mode.NO_FIT:
                seen["nofit"] += 1
                assert (cls.slots0[wi] == -1).all()
                continue
            seen["fit" if mode == Mode.FIT else "preempt"] += 1
            got = (solver.build_fit_assignment(cls, wi)
                   if mode == Mode.FIT
                   else solver.build_preempt_assignment(cls, wi))
            assert dict(got.usage) == dict(want.usage), h.key
            assert got.borrows() == want.borrows(), h.key
            for gp, wp in zip(got.pod_sets, want.pod_sets, strict=True):
                assert {r: (f.name, f.mode, f.tried_flavor_idx)
                        for r, f in gp.flavors.items()} == {
                    r: (f.name, f.mode, f.tried_flavor_idx)
                    for r, f in wp.flavors.items()}, (h.key, gp.name)
            assert (got.last_state.last_tried_flavor_idx
                    == want.last_state.last_tried_flavor_idx), h.key
            # what the vector walk asks is what the host asked, at the
            # same quantities (the host also asks where the answer
            # cannot move the pick)
            mine = vec_asked.get(wi, set())
            theirs = {(fr, qty) for _, fr, qty in oracle.asked}
            assert mine <= theirs, (h.key, mine - theirs)
            seen["asked"] += len(mine)
    assert seen["fit"] and seen["preempt"] and seen["nofit"], seen
    assert seen["charged"] > 0, seen
    if seed in (10, 12):
        assert seen["asked"] > 0, seen


def test_more_podsets_than_a_plane_holds_stay_scalar(monkeypatch):
    """More PodSets than ``MAX_POD_SETS`` (upstream's eight, which the
    webhook enforces; two here) are the host walk's, and counted; the
    planes do not grow for them, and a window with such a head is
    dirty."""
    from kueue_tpu.ops import burst, packing, solver
    for mod in (burst, packing, solver):
        monkeypatch.setattr(mod, "MAX_POD_SETS", 2)

    def build(d):
        cluster(d, docs_groups(x86=16 * K, arm=16 * K, memory=64 * GI))
        gang(d, "three", "a", *[(f"ps{p}", 1, 1 * K, 4 * GI, None)
                                for p in range(3)])
        gang(d, "two", "a", ("launcher", 1, 1 * K, 4 * GI, X),
             ("workers", 1, 1 * K, 4 * GI, A), created=1000.0)
    host, _ = run("host", build)
    assert host[0][0] == ["default/three"] and host[1][0] == ["default/two"]
    for engine in ("device", "burst"):
        got, d = run(engine, build)
        assert got == host[:len(got)]
        s = d.scheduler.solver.stats
        assert s["scalar_reasons"] == {"multi_podset": 1}, s
        assert s["podset_scalar_heads"] == s["scalar_heads"] == 1, s
        assert d.scheduler.solver._structure.pod_sets == 2
        if engine == "burst":
            assert d._burst_solver.stats["burst_dirty_scalar"] == 1


def test_the_planes_grow_with_the_population():
    """A structure's planes start one PodSet wide; the first pack that
    meets a gang lays the rows out again two wide, a delta window that
    meets a three-PodSet row packs in full four wide, and both packs
    agree."""
    from kueue_tpu.ops.burst import pack_burst, pack_burst_cached
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    cluster(d, docs_groups(x86=16 * K, arm=16 * K, memory=64 * GI))
    gang(d, "plain", "a", ("main", 1, 1 * K, 1 * GI, A), created=1.0)
    st = d.scheduler.solver._structure_for(d.cache.snapshot(), [])
    assert st.pod_sets == 1
    plan, state, _ = pack_burst_cached(st, d.queues, d.cache, d.scheduler,
                                       d.clock, state=None)
    C, M = plan.C, plan.M
    assert plan.arrays["wl_req"].shape == (C, M, 2)
    assert plan.arrays["resume0"].shape == (C, M, 2)
    gang(d, "two", "a", ("launcher", 1, 1 * K, 4 * GI, X),
         ("workers", 2, 1 * K, 4 * GI, A), created=2.0)
    plan, state, was_delta = pack_burst_cached(
        st, d.queues, d.cache, d.scheduler, d.clock, state=state)
    assert not was_delta and st.pod_sets == 2
    assert plan.arrays["wl_req"].shape == (C, plan.M, 4)
    assert plan.arrays["wl_flavor_skip"].shape == (C, plan.M, 4)
    c, m = plan.row_of_key["default/two"]
    cpu, mem = st.r_index["cpu"], st.r_index["memory"]
    row = plan.arrays["wl_req"][c, m].reshape(2, 2)
    scale = st.resource_scale
    assert (row[:, cpu] * scale[cpu]).tolist() == [1 * K, 2 * K]
    assert (row[:, mem] * scale[mem]).tolist() == [4 * GI, 8 * GI]
    # launcher barred from arm (bit 1), workers from x86 (bit 0), in the
    # cpu group; nothing in the memory group
    assert plan.arrays["wl_flavor_skip"][c, m].tolist() == [0b10, 0, 0b01, 0]
    assert plan.arrays["vec_ok"][c, m]
    c, m = plan.row_of_key["default/plain"]
    assert plan.arrays["wl_flavor_skip"][c, m].tolist() == [0b01, 0, 0, 0]
    gang(d, "three", "a", ("head", 1, 1 * K, 1 * GI, X),
         ("a", 1, 1 * K, 1 * GI, None), ("b", 1, 1 * K, 1 * GI, None),
         created=3.0)
    plan, state, was_delta = pack_burst_cached(
        st, d.queues, d.cache, d.scheduler, d.clock, state=state)
    assert not was_delta and st.pod_sets == 4
    full = pack_burst(st, d.queues, d.cache, d.scheduler, d.clock)
    for name in ("wl_req", "resume0", "wl_flavor_skip", "vec_ok", "elig0"):
        assert np.array_equal(full.arrays[name], plan.arrays[name]), name
    assert full.arrays["wl_req"].shape == (C, full.M, 8)
    # and a window after it is a delta again
    gang(d, "late", "a", ("launcher", 1, 1 * K, 1 * GI, X),
         ("workers", 1, 1 * K, 1 * GI, A), created=4.0)
    again, _, was_delta = pack_burst_cached(
        st, d.queues, d.cache, d.scheduler, d.clock, state=state)
    assert was_delta
    fresh = pack_burst(st, d.queues, d.cache, d.scheduler, d.clock)
    for name in ("wl_req", "resume0", "wl_flavor_skip", "vec_ok", "elig0"):
        assert np.array_equal(fresh.arrays[name], again.arrays[name]), name


def test_the_resume_state_is_written_a_podset():
    """After cycle 1 of ``each_podset_resumes_its_own_walk`` the skipped
    gang carries the host's own record: each PodSet's cpu stopped on
    slot 0, each memory walked its whole list."""
    for engine in ("host", "device", "burst"):
        got, d = run(engine, each_podset_resumes_its_own_walk)
        assert got[0][3] == {"default/head": (1, 0, 1, 0)}, (engine, got)
        assert got[1][0] == ["default/head"], (engine, got)


def test_gang_counters():
    _, d = run("device", workers_pushed_to_the_next_flavor, cycles=1)
    s = d.scheduler.solver.stats
    assert s["walk_heads"] == 1 and s["podset_walks"] == 2, s
    assert s["gang_heads"] == 1 and s["charged_walks"] == 1, s
    assert s["split_flavor_gangs"] == 1, s
    _, d = run("device", both_podsets_on_one_flavor, cycles=1)
    s = d.scheduler.solver.stats
    # the memory walk of a second PodSet always meets the first's usage
    assert s["charged_walks"] == 1 and s["split_flavor_gangs"] == 0, s
    _, d = run("device", first_podset_nofit_ends_the_walk, cycles=1)
    s = d.scheduler.solver.stats
    # the NoFit launcher ended its head's walk: one pass, not two
    assert s["podset_walks"] == 1 and s["gang_heads"] == 1, s


def test_an_admitted_gang_is_a_candidate_and_a_finish():
    """A restored two-PodSet workload holds quota on both flavors; a
    higher-priority gang evicts it through the device search, and its
    finish releases both."""
    def build(d):
        cluster(d, docs_groups())
        wl = Workload(
            name="held", namespace="default", priority=-10,
            creation_time=0.5,
            pod_sets=[PodSet(name="launcher", count=1,
                             requests={"cpu": 1 * K, "memory": 1 * GI}),
                      PodSet(name="workers", count=3,
                             requests={"cpu": 1 * K, "memory": 1 * GI})])
        adm = Admission(cluster_queue="a", pod_set_assignments=[
            PodSetAssignment(name="launcher", flavors=dict(X86_DEF),
                             resource_usage={"cpu": 1 * K,
                                             "memory": 1 * GI}, count=1),
            PodSetAssignment(name="workers", flavors=dict(ARM_DEF),
                             resource_usage={"cpu": 3 * K,
                                             "memory": 3 * GI}, count=3)])
        set_quota_reservation(wl, adm, 0.5)
        sync_admitted_condition(wl, 0.5)
        d.restore_workload(wl)
        gang(d, "head", "a", ("launcher", 1, 4 * K, 1 * GI, X),
             ("workers", 2, 1 * K, 1 * GI, A))
    host, _ = run("host", build)
    assert host[0][1] == ["default/held"]
    for engine in ("device", "burst"):
        got, d = run(engine, build)
        assert got == host[:len(got)], (engine, got, host)
        assert d.scheduler.preemptor.stats["host_searches"] == 0
