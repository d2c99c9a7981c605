"""A flavor walk a resource group, on every engine.

A ClusterQueue may declare several resource groups (upstream docs
concepts/cluster_queue, "Resource groups"; tasks/manage/
administer_cluster_quotas, "Multiple ResourceFlavors": cpu under ``x86``
and ``arm``, memory under ``default-flavor``).  A resource and a flavor
belong to one group each, a PodSet gets one flavor a group, and the
groups are walked independently: each from its own resume index, over
the flavors the PodSet may take *in that group*, under the queue's
stop rules; the head is as good as its worst group, borrows if any
does, and searches for eviction targets over the flavor-resources
short of quota in any group (flavorassigner.go assignFlavors /
findFlavorForPodSetResource; scheduler/flavorassigner.py is the
oracle).  Until PR 37 the device path gave every head of such a queue
to the host walk (``scalar_reasons["cq_shape"]``).

Each case runs through the host scalar scheduler, the per-cycle device
engine and ``schedule_burst`` on identically built clusters, which have
to agree cycle by cycle on what is admitted, evicted, on which flavor
each resource lands and where each group's next walk starts; the device
engines have to decide every head with no host walk and no host search.
Rows follow upstream's ``TestAssignFlavors`` table where one is named.
"""

from __future__ import annotations

import numpy as np
import pytest

from kueue_tpu.api.types import (
    ClusterQueue,
    FlavorFungibility,
    FlavorFungibilityPolicy,
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
from tests.conftest import FakeClock
from tests.test_conformance_preemption import admit

K = 1000
GI = 1 << 30
TRY_NEXT = FlavorFungibilityPolicy.TRY_NEXT_FLAVOR
X86 = ResourceFlavor(name="x86", node_labels={"cpu-arch": "x86"})
ARM = ResourceFlavor(name="arm", node_labels={"cpu-arch": "arm"})
DEFAULT = ResourceFlavor(name="default-flavor")
CYCLES = 4


def quotas(name, **res):
    """``res``: resource -> nominal, or (nominal, borrowing limit)."""
    return FlavorQuotas(name=name, resources={
        r: ResourceQuota(*(q if isinstance(q, tuple) else (q,)))
        for r, q in res.items()})


def cluster(d, a, b=None, flavors=(X86, ARM, DEFAULT), ff=None):
    """Queues a (and b) in one cohort; ``a`` / ``b``: the queue's
    resource groups as [(covered resources, [FlavorQuotas])]."""
    for f in flavors:
        d.apply_resource_flavor(f)
    for name, groups in (("a", a), ("b", b)):
        if groups is None:
            continue
        d.apply_cluster_queue(ClusterQueue(
            name=name, cohort="co",
            flavor_fungibility=ff or FlavorFungibility(
                when_can_preempt=TRY_NEXT),
            preemption=PreemptionPolicy(
                within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
                reclaim_within_cohort=ReclaimWithinCohort.ANY),
            resource_groups=[ResourceGroup(covered_resources=list(cov),
                                           flavors=list(fqs))
                             for cov, fqs in groups]))
        d.apply_local_queue(LocalQueue(name=f"lq-{name}",
                                       cluster_queue=name))


def docs_groups(x86=4 * K, arm=4 * K, memory=16 * GI):
    """The document's ClusterQueue: cpu under x86 and arm, memory under
    default-flavor."""
    return [(["cpu"], [quotas("x86", cpu=x86), quotas("arm", cpu=arm)]),
            (["memory"], [quotas("default-flavor", memory=memory)])]


def head(d, name, queue, cpu=4 * K, memory=4 * GI, selector=None,
         priority=10, created=999.0, **more):
    d.create_workload(Workload(
        name=name, namespace="default", queue_name=f"lq-{queue}",
        priority=priority, creation_time=created,
        pod_sets=[PodSet(name="main", count=1,
                         requests=dict({"cpu": cpu, "memory": memory}
                                       if cpu else {"memory": memory},
                                       **more),
                         node_selector=dict(selector or {}))]))


# ---- the cases ---------------------------------------------------------------

def cpu_fits_memory_preempts(d):
    """cpu fits on x86; memory is full of a's own lower-priority work:
    the head's mode is its memory group's, and the victim frees memory
    on default-flavor (and the arm cpu it held, which nobody asked for).
    Upstream: "multiple resource groups, one could fit with preemption"."""
    cluster(d, docs_groups())
    admit(d, "own-mem", "a", {"cpu": ("arm", 1 * K),
                              "memory": ("default-flavor", 16 * GI)},
          priority=-10)
    head(d, "head", "a")


def memory_fits_cpu_preempts(d):
    """The reverse: memory has room, both cpu flavors are full of a's
    own lower-priority work; Preempt on x86, the first, with no oracle
    (the request would borrow)."""
    cluster(d, docs_groups())
    admit(d, "own-x86", "a", {"cpu": ("x86", 4 * K),
                              "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    admit(d, "own-arm", "a", {"cpu": ("arm", 4 * K),
                              "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    head(d, "head", "a")


def nofit_in_one_group(d):
    """cpu fits, memory can never fit (over nominal with nothing to
    borrow): NoFit in one group is NoFit, the head parks, nothing is
    evicted.  Upstream: "multiple resource groups, one doesn't fit"."""
    cluster(d, docs_groups(memory=2 * GI))
    head(d, "head", "a")
    head(d, "fits", "a", memory=1 * GI, priority=5, created=1000.0)


def cpu_resumes_midlist_memory_starts_at_0(d):
    """Cycle 1: a's head fits x86 only by borrowing b's quota and stops
    there (slot 0 of the cpu group, recorded); its memory fits on the
    one flavor of its group (nothing recorded: the whole list).  b's own
    head takes that cpu first, so a's head is skipped.  Cycle 2: the cpu
    group resumes at arm, the memory group starts at 0 again."""
    cluster(d, docs_groups(x86=0, arm=4 * K), docs_groups(x86=4 * K, arm=0))
    head(d, "head", "a", created=5.0)
    head(d, "first", "b", priority=20, created=1.0)


def selector_key_only_group_1_carries(d):
    """``cpu-arch: arm`` bars x86 in the cpu group and nothing in the
    memory group, whose flavor has no such label: the head lands on arm
    and default-flavor.  Upstream: "multiple flavors, ignore non-flavor
    nodeSelectors", a group."""
    cluster(d, docs_groups())
    head(d, "head", "a", selector={"cpu-arch": "arm"})


def borrowing_in_one_group(d):
    """a has no memory of its own and borrows b's; its cpu is its own:
    the head borrows because one group does, and is ordered after b's
    head that does not."""
    cluster(d, docs_groups(memory=(0, 16 * GI)), docs_groups())
    head(d, "head", "a", created=1.0)
    head(d, "own", "b", created=2.0)


def oracle_asked_in_one_group_only(d):
    """x86 is full of a's own higher-priority work (Preempt, and the
    request would borrow: no question); arm is under a's nominal but
    lent to b (a question, and Reclaim); memory fits.  The oracle is
    asked in the cpu group alone, picks arm over x86, and b's borrower
    goes."""
    cluster(d, docs_groups(), docs_groups(x86=0, arm=0))
    admit(d, "own-x86", "a", {"cpu": ("x86", 4 * K),
                              "memory": ("default-flavor", 1 * GI)},
          priority=50)
    admit(d, "lent-arm", "b", {"cpu": ("arm", 4 * K),
                               "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    head(d, "head", "a")


def victims_free_cpu_and_memory_together(d):
    """Both groups are short: arm cpu (the head is pinned to it) and
    default-flavor memory, each held by a different lower-priority
    workload of a.  The search runs over the union of the two
    flavor-resources and both go."""
    cluster(d, docs_groups())
    admit(d, "own-arm", "a", {"cpu": ("arm", 4 * K),
                              "memory": ("default-flavor", 1 * GI)},
          priority=-10)
    admit(d, "own-mem", "a", {"cpu": ("x86", 1 * K),
                              "memory": ("default-flavor", 14 * GI)},
          priority=-10)
    head(d, "head", "a", selector={"cpu-arch": "arm"})


def three_groups(d):
    """cpu, memory and gpu each under its own group; the gpu group has
    two flavors and the first is full of a's own lower-priority work
    while the second is free: the head fits everywhere.  Upstream:
    "multiple resource groups, fits"."""
    gpus = (ResourceFlavor(name="gpu-a"), ResourceFlavor(name="gpu-b"))
    cluster(d, docs_groups() + [
        (["gpu"], [quotas("gpu-a", gpu=2), quotas("gpu-b", gpu=2)])],
        flavors=(X86, ARM, DEFAULT) + gpus)
    admit(d, "own-gpu", "a", {"gpu": ("gpu-a", 2)}, priority=-10)
    head(d, "head", "a", gpu=2)
    head(d, "no-gpu", "a", created=1000.0)


def two_resources_beside_one(d):
    """cpu and memory share a group of two flavors, gpu has its own:
    the first flavor has cpu but not the memory, so both resources move
    to the second together, and gpu is walked apart.  Upstream:
    "multiple resources in a group, doesn't fit in the first flavor"."""
    pools = (ResourceFlavor(name="pool-a"), ResourceFlavor(name="pool-b"),
             ResourceFlavor(name="gpu-x"))
    cluster(d, [
        (["cpu", "memory"], [quotas("pool-a", cpu=8 * K, memory=2 * GI),
                             quotas("pool-b", cpu=8 * K, memory=8 * GI)]),
        (["gpu"], [quotas("gpu-x", gpu=4)])], flavors=pools)
    head(d, "head", "a", gpu=1)
    head(d, "small", "a", cpu=1 * K, memory=1 * GI, created=1000.0)


CASES = {
    # name: (builder, {workload: {resource: flavor} it ends on, or
    #        None}, evicted, the oracle is asked)
    "cpu_fits_memory_preempts": (
        cpu_fits_memory_preempts,
        {"head": {"cpu": "x86", "memory": "default-flavor"}},
        ["own-mem"], False),
    "memory_fits_cpu_preempts": (
        memory_fits_cpu_preempts,
        {"head": {"cpu": "x86", "memory": "default-flavor"}},
        ["own-x86"], False),
    "nofit_in_one_group": (
        nofit_in_one_group,
        {"head": None, "fits": {"cpu": "x86", "memory": "default-flavor"}},
        [], False),
    "cpu_resumes_midlist_memory_starts_at_0": (
        cpu_resumes_midlist_memory_starts_at_0,
        {"head": {"cpu": "arm", "memory": "default-flavor"},
         "first": {"cpu": "x86", "memory": "default-flavor"}}, [], False),
    "selector_key_only_group_1_carries": (
        selector_key_only_group_1_carries,
        {"head": {"cpu": "arm", "memory": "default-flavor"}}, [], False),
    "borrowing_in_one_group": (
        borrowing_in_one_group,
        {"head": {"cpu": "x86", "memory": "default-flavor"},
         "own": {"cpu": "x86", "memory": "default-flavor"}}, [], False),
    "oracle_asked_in_one_group_only": (
        oracle_asked_in_one_group_only,
        {"head": {"cpu": "arm", "memory": "default-flavor"}},
        ["lent-arm"], True),
    "victims_free_cpu_and_memory_together": (
        victims_free_cpu_and_memory_together,
        {"head": {"cpu": "arm", "memory": "default-flavor"}},
        ["own-arm", "own-mem"], False),
    "three_groups": (
        three_groups,
        {"head": {"cpu": "x86", "memory": "default-flavor",
                  "gpu": "gpu-b"},
         "no-gpu": {"cpu": "arm", "memory": "default-flavor"}}, [], False),
    "two_resources_beside_one": (
        two_resources_beside_one,
        {"head": {"cpu": "pool-b", "memory": "pool-b", "gpu": "gpu-x"},
         "small": {"cpu": "pool-a", "memory": "pool-a"}}, [], False),
}


def flavors_of(d, keys):
    return {k: dict(d.workload(k).admission.pod_set_assignments[0].flavors)
            for k in keys}


def run(engine, build):
    """[(admitted, evicted, {admitted key: {resource: flavor}}, {pending
    key: the slot each group's next walk starts on})] a cycle, and the
    driver."""
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
                    info, live, False, len(live.spec.resource_groups))
        out.append((sorted(stats.admitted), sorted(stats.preempted_targets),
                    flavors_of(d, stats.admitted), resume))

    def tick(_k=None):
        clock.t += 1.0

    if engine == "burst":
        d.schedule_burst(CYCLES, on_cycle_start=tick,
                         on_cycle=lambda _k, stats: record(stats))
    else:
        for _ in range(CYCLES):
            tick()
            record(d.schedule_once())
    return out, d


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_engine_walks_a_group(case):
    build, want, evicted, asks = CASES[case]
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
        assert solver["scalar_heads"] == 0, solver
        assert solver["scalar_reasons"] == {}, solver
        assert solver["cq_shape_heads"] == 0, solver
        assert solver["host_cycles"] == 0, solver
        assert pre["host_searches"] == 0, pre
        assert (pre["oracle_specs"] > 0) == asks, pre
        if engine == "device":
            # every head was walked a group it requests from
            assert solver["group_walks"] > solver["walk_heads"] > 0, solver


def test_the_resume_state_is_written_a_resource():
    """After cycle 1 of ``cpu_resumes_midlist_memory_starts_at_0`` the
    skipped head carries the host's own record: cpu stopped on slot 0 of
    its group, memory walked its whole list."""
    for engine in ("host", "device", "burst"):
        got, d = run(engine, cpu_resumes_midlist_memory_starts_at_0)
        assert got[0][3] == {"default/head": (1, 0)}, (engine, got)
        assert got[1][0] == ["default/head"], (engine, got)


def test_split_mode_heads_are_counted():
    """One head whose cpu fits and whose memory must preempt: the join,
    not one walk, sets its mode."""
    _, d = run("device", cpu_fits_memory_preempts)
    s = d.scheduler.solver.stats
    assert s["split_mode_heads"] == 1, s
    _, d = run("device", memory_fits_cpu_preempts)
    assert d.scheduler.solver.stats["split_mode_heads"] == 1
    _, d = run("device", selector_key_only_group_1_carries)
    assert d.scheduler.solver.stats["split_mode_heads"] == 0


def test_one_group_is_the_same_walk():
    """A one-group queue runs the same code: one walk a head, no split
    modes, and the structure's planes carry a unit group axis."""
    def build(d):
        cluster(d, [(["cpu", "memory"], [
            quotas("x86", cpu=4 * K, memory=16 * GI),
            quotas("arm", cpu=4 * K, memory=16 * GI)])],
            flavors=(X86, ARM))
        head(d, "head", "a", selector={"cpu-arch": "arm"})
    got, d = run("device", build)
    assert got[0][2] == {"default/head": {"cpu": "arm", "memory": "arm"}}
    s = d.scheduler.solver.stats
    assert s["group_walks"] == s["walk_heads"] == 1
    assert s["split_mode_heads"] == 0
    st = d.scheduler.solver._structure
    assert st.slot_valid.shape == (1, 1, 2)
    assert st.res_group[0, [st.r_index["cpu"],
                            st.r_index["memory"]]].tolist() == [0, 0]


def test_structure_planes_a_group():
    """``pack_structure`` on the document's queue: a slot is read a
    resource, in the resource's own group."""
    _, d = run("device", selector_key_only_group_1_carries)
    st = d.scheduler.solver._structure
    cpu, mem = st.r_index["cpu"], st.r_index["memory"]
    assert st.res_group[0, [cpu, mem]].tolist() == [0, 1]
    assert st.slot_count_cq[0].tolist() == [2, 1]
    assert st.slot_valid[0].tolist() == [[True, True], [True, False]]
    fr = {(fr.flavor, fr.resource): i for fr, i in st.fr_index.items()}
    assert st.slot_fr[0, :, cpu].tolist() == [fr["x86", "cpu"],
                                              fr["arm", "cpu"]]
    assert st.slot_fr[0, :, mem].tolist() == [
        fr["default-flavor", "memory"], -1]
    # the selector's key is a label of the cpu group only
    lists = [st.flavor_lists[li] for li in st.flavor_list_of_cq[0]]
    assert lists[0].allowed_keys == {"cpu-arch"}
    assert lists[1].allowed_keys == set() and not lists[1].declared


def test_the_jitted_classify_walks_a_group():
    """``solve_cycle`` (the one-call probe surface) against
    ``classify_np`` on a two-group structure."""
    from kueue_tpu.ops.cycle import classify_np, solve_cycle
    from kueue_tpu.ops.packing import pack_cycle
    from kueue_tpu.parallel.sharded import cycle_args
    from kueue_tpu.workload import Ordering
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    cpu_fits_memory_preempts(d)
    head(d, "arm-only", "a", cpu=1 * K, memory=0, created=5.0)
    snap = d.cache.snapshot()
    heads = d.queues.queue_for("a").heap.items()
    for h in heads:
        h.cluster_queue = "a"
    st = d.scheduler.solver._structure_for(snap, heads)
    packed = pack_cycle(snap, heads, Ordering(), structure=st)
    want = classify_np(packed)
    got = solve_cycle(*cycle_args(packed), res_group=packed.res_group,
                      depth=packed.depth, run_scan=False)
    assert np.array_equal(np.asarray(got[4]), want["fit_slot0"])
    assert np.array_equal(np.asarray(got[3]), want["preempt0"])
    n = packed.wl_count
    assert want["preempt0"][:n].sum() == 1 and want["fit0"][:n].sum() == 1
    # the head that asks for no memory walks one group
    assert sorted(want["group_walks"][:n].tolist()) == [1, 2]


def test_both_packs_carry_the_planes_a_group():
    """The full pack and the streaming arena write ``resume0`` and
    ``wl_flavor_skip`` with a group axis, and agree."""
    from kueue_tpu.ops.burst import pack_burst, pack_burst_cached
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    cpu_resumes_midlist_memory_starts_at_0(d)
    head(d, "pinned", "a", selector={"cpu-arch": "arm"}, created=6.0)
    clock.t += 1.0
    d.schedule_once()          # a's head is skipped, resume recorded
    st = d.scheduler.solver._structure_for(d.cache.snapshot(), [])
    full = pack_burst(st, d.queues, d.cache, d.scheduler, d.clock)
    plan, state, _ = pack_burst_cached(st, d.queues, d.cache, d.scheduler,
                                       d.clock, state=None)
    for name in ("resume0", "wl_flavor_skip", "res_group", "slot_valid"):
        assert np.array_equal(full.arrays[name], plan.arrays[name]), name
    C, M = full.C, full.M
    assert full.arrays["resume0"].shape == (C, M, 2)
    assert full.arrays["wl_flavor_skip"].shape == (C, M, 2)
    c, m = full.row_of_key["default/head"]
    assert full.arrays["resume0"][c, m].tolist() == [1, 0]
    c, m = full.row_of_key["default/pinned"]
    assert full.arrays["wl_flavor_skip"][c, m].tolist() == [0b01, 0]
    # a delta window keeps a kept row's per-group resume and mask
    clock.t += 1.0
    d.schedule_once()
    again, _, was_delta = pack_burst_cached(
        st, d.queues, d.cache, d.scheduler, d.clock, state=state)
    assert was_delta
    fresh = pack_burst(st, d.queues, d.cache, d.scheduler, d.clock)
    for name in ("resume0", "wl_flavor_skip", "elig0", "adm0"):
        assert np.array_equal(fresh.arrays[name], again.arrays[name]), name
