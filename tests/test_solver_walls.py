"""Parity for every lifted solver eligibility wall (VERDICT r2 item #3).

Each scenario that previously forced the whole cycle onto the host —
multi-resource-group CQs, multi-PodSet workloads, taints/affinity,
non-default fungibility, resume state, partial admission — must now run
as a device-decided cycle (scalar heads host-walked at nominate, the
admit scan deciding the cycle) with decisions identical to the pure host
path.  Reference semantics: flavorassigner.go:499-640."""

import random

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
    Taint,
    Toleration,
    WithinClusterQueue,
    Workload,
)
from kueue_tpu.controller.driver import Driver
from tests.conftest import FakeClock


def new_driver(use_device):
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device)
    return d, clock


def drive(d, clock, workloads, n_cycles=30, runtime=2):
    """Create workloads, run cycles with fake execution, log decisions."""
    for wl in workloads:
        d.create_workload(wl)
    log = []
    running = []
    for cycle in range(n_cycles):
        clock.t += 1.0
        stats = d.schedule_once()
        admissions = []
        for key in stats.admitted:
            wl = d.workload(key)
            flavors = tuple(sorted(
                (a.name, a.count, tuple(sorted(a.flavors.items())))
                for a in wl.admission.pod_set_assignments))
            admissions.append((key, flavors))
            running.append((cycle + runtime, key))
        log.append({
            "admitted": admissions,
            "skipped": sorted(stats.skipped),
            "inadmissible": sorted(stats.inadmissible),
            "preempting": sorted(stats.preempting),
            "targets": sorted(stats.preempted_targets),
        })
        still = []
        for fin, key in running:
            wl = d.workload(key)
            if wl is None or not wl.has_quota_reservation:
                continue
            if fin <= cycle:
                d.finish_workload(key)
            else:
                still.append((fin, key))
        running = still
    return log


def assert_parity(build, *, expect_scalar=True, n_cycles=30):
    """build(driver) -> workloads; runs host vs device, asserts per-cycle
    decision equality and that the device path stayed device-decided."""
    host, hclock = new_driver(False)
    hwl = build(host)
    dev, dclock = new_driver(True)
    dwl = build(dev)
    hlog = drive(host, hclock, hwl, n_cycles=n_cycles)
    dlog = drive(dev, dclock, dwl, n_cycles=n_cycles)
    for cyc, (h, dv) in enumerate(zip(hlog, dlog)):
        assert h == dv, (f"cycle {cyc} diverged:\nhost={h}\ndevice={dv}\n"
                         f"stats={dev.scheduler.solver.stats}")
    stats = dev.scheduler.solver.stats
    assert stats["host_cycles"] == 0, stats
    assert stats["full_cycles"] >= 1, stats
    if expect_scalar:
        assert stats["scalar_heads"] >= 1, stats
    assert any(c["admitted"] for c in hlog), "scenario admitted nothing"
    return hlog, stats


# ---------------------------------------------------------------------------
# Multi-resource-group CQs
# ---------------------------------------------------------------------------

def test_multi_resource_group_cq():
    def build(d):
        d.apply_resource_flavor(ResourceFlavor(name="cpu-a"))
        d.apply_resource_flavor(ResourceFlavor(name="cpu-b"))
        d.apply_resource_flavor(ResourceFlavor(name="gpu-x"))
        for i in range(2):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-{i}", cohort="team",
                resource_groups=[
                    ResourceGroup(covered_resources=["cpu"], flavors=[
                        FlavorQuotas(name="cpu-a", resources={
                            "cpu": ResourceQuota(nominal=2000)}),
                        FlavorQuotas(name="cpu-b", resources={
                            "cpu": ResourceQuota(nominal=4000,
                                                 borrowing_limit=2000)}),
                    ]),
                    ResourceGroup(covered_resources=["gpu"], flavors=[
                        FlavorQuotas(name="gpu-x", resources={
                            "gpu": ResourceQuota(nominal=4)}),
                    ]),
                ]))
            d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                           cluster_queue=f"cq-{i}"))
        rng = random.Random(7)
        out = []
        for i in range(24):
            q = rng.randrange(2)
            reqs = {"cpu": rng.choice([1000, 2000, 3000])}
            if i % 2 == 0:
                reqs["gpu"] = rng.choice([1, 2])
            out.append(Workload(
                name=f"wl-{i}", queue_name=f"lq-{q}",
                priority=rng.choice([10, 50]), creation_time=float(i + 1),
                pod_sets=[PodSet(name="main", count=1, requests=reqs)]))
        return out

    _, stats = assert_parity(build, expect_scalar=False)
    # the wall PR 37 took down: every head is walked a group on the
    # vector path, none on the host
    assert stats["scalar_heads"] == 0, stats
    assert "cq_shape" not in stats["scalar_reasons"], stats
    assert stats["cq_shape_heads"] == 0, stats
    assert stats["group_walks"] > stats["walk_heads"] > 0, stats


# ---------------------------------------------------------------------------
# Multi-PodSet workloads
# ---------------------------------------------------------------------------

def test_multi_podset_workloads():
    def build(d):
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        for i in range(2):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-{i}", cohort="team",
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu", "memory"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": ResourceQuota(nominal=8000,
                                             borrowing_limit=4000),
                        "memory": ResourceQuota(nominal=16_000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                           cluster_queue=f"cq-{i}"))
        rng = random.Random(11)
        out = []
        for i in range(20):
            q = rng.randrange(2)
            out.append(Workload(
                name=f"wl-{i}", queue_name=f"lq-{q}",
                priority=rng.choice([10, 50]), creation_time=float(i + 1),
                pod_sets=[
                    PodSet(name="driver", count=1,
                           requests={"cpu": 1000, "memory": 2000}),
                    PodSet(name="workers", count=rng.choice([2, 3]),
                           requests={"cpu": 1000, "memory": 1000}),
                ]))
        return out

    # since PR 41 a two-PodSet head is the vector walk's: one pass a
    # PodSet, the second charged with the first's choice
    _, stats = assert_parity(build, expect_scalar=False)
    assert stats["gang_heads"] >= 1 and stats["charged_walks"] >= 1, stats
    assert stats["podset_walks"] == 2 * stats["walk_heads"], stats
    assert stats["scalar_heads"] == 0, stats


# ---------------------------------------------------------------------------
# Taints / tolerations / node affinity
# ---------------------------------------------------------------------------

def test_taints_tolerations_affinity():
    def build(d):
        d.apply_resource_flavor(ResourceFlavor(
            name="spot",
            node_labels={"tier": "spot"},
            node_taints=[Taint(key="spot", value="true",
                               effect="NoSchedule")]))
        d.apply_resource_flavor(ResourceFlavor(
            name="ondemand", node_labels={"tier": "ondemand"}))
        d.apply_cluster_queue(ClusterQueue(
            name="cq",
            resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="spot", resources={
                    "cpu": ResourceQuota(nominal=4000)}),
                FlavorQuotas(name="ondemand", resources={
                    "cpu": ResourceQuota(nominal=2000)}),
            ])]))
        d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
        out = []
        rng = random.Random(13)
        for i in range(16):
            tolerates = i % 3 != 0
            ps = PodSet(name="main", count=1,
                        requests={"cpu": rng.choice([1000, 2000])},
                        tolerations=([Toleration(key="spot",
                                                 operator="Equal",
                                                 value="true")]
                                     if tolerates else []))
            if i % 4 == 0:
                # node selector pinning to the on-demand tier
                ps.node_selector["tier"] = "ondemand"
            out.append(Workload(
                name=f"wl-{i}", queue_name="lq",
                priority=rng.choice([10, 50]), creation_time=float(i + 1),
                pod_sets=[ps]))
        return out

    hlog, stats = assert_parity(build, expect_scalar=False)
    # both flavors must actually be used for the scenario to mean anything
    used = {f for c in hlog for _, fl in c["admitted"]
            for _, _, pairs in fl for _, f in pairs}
    assert used == {"spot", "ondemand"}, used
    # ... and every head was decided by the vector walk, under its own
    # eligibility mask: labels and taints are no reason for the host walk
    assert stats["scalar_heads"] == 0, stats
    assert "cq_shape" not in stats["scalar_reasons"], stats
    assert stats["constrained_heads"] > 0, stats
    assert stats["walk_ineligible_slots"] > 0, stats
    # four signatures (tolerates or not, pinned or not), one flavor list
    assert stats["eligibility_masks_built"] == 4, stats


def _two_groups_declared(d, gpu_flavor):
    d.apply_resource_flavor(ResourceFlavor(
        name="spot", node_labels={"tier": "spot"},
        node_taints=[Taint(key="spot", value="true",
                           effect="NoSchedule")]))
    d.apply_resource_flavor(ResourceFlavor(
        name="ondemand", node_labels={"tier": "ondemand"}))
    d.apply_resource_flavor(gpu_flavor)
    d.apply_cluster_queue(ClusterQueue(
        name="cq",
        resource_groups=[
            ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="spot", resources={
                    "cpu": ResourceQuota(nominal=4000)}),
                FlavorQuotas(name="ondemand", resources={
                    "cpu": ResourceQuota(nominal=2000)})]),
            ResourceGroup(covered_resources=["gpu"], flavors=[
                FlavorQuotas(name="gpu-x", resources={
                    "gpu": ResourceQuota(nominal=4)})])]))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    rng = random.Random(19)
    out = []
    for i in range(12):
        reqs = {"cpu": rng.choice([1000, 2000])}
        if i % 2:
            reqs["gpu"] = 1
        out.append(Workload(
            name=f"wl-{i}", queue_name="lq",
            priority=rng.choice([10, 50]), creation_time=float(i + 1),
            pod_sets=[PodSet(
                name="main", count=1, requests=reqs,
                tolerations=([Toleration(key="spot", operator="Exists")]
                             if i % 3 else []))]))
    return out


def test_two_resource_groups_with_declared_flavors_walk_a_group():
    """The sibling: the same labelled, tainted flavors under a queue with
    two resource groups.  Until PR 37 that shape was the host walk's;
    now each head is walked a group on the vector path, its mask matched
    in the cpu group alone (the gpu group's flavor carries no label)."""
    _, stats = assert_parity(
        lambda d: _two_groups_declared(d, ResourceFlavor(name="gpu-x")),
        expect_scalar=False)
    assert stats["scalar_heads"] == 0, stats
    assert "cq_shape" not in stats["scalar_reasons"], stats
    assert stats["cq_shape_heads"] == 0, stats
    assert stats["constrained_heads"] > 0, stats
    assert stats["group_walks"] > stats["walk_heads"] > 0, stats


def test_two_resource_groups_with_a_topology_flavor_stay_scalar():
    """A flavor that binds a topology in one of the two groups no longer
    sends the queue's heads to the host walk: with the
    TopologyAwareScheduling gate off (the default) the host walk reads
    it as a plain flavor, and so does the vector walk; ``cq_shape``
    counts nobody (tests/test_tas_batched.py holds the gate on)."""
    _, stats = assert_parity(lambda d: _two_groups_declared(
        d, ResourceFlavor(name="gpu-x", topology_name="rack")),
        expect_scalar=False)
    assert stats["scalar_heads"] == stats["cq_shape_heads"] == 0, stats
    assert stats["walk_heads"] > 0 and stats["tas_heads"] == 0, stats


# ---------------------------------------------------------------------------
# Non-default FlavorFungibility
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("borrow_policy,preempt_policy", [
    (FlavorFungibilityPolicy.TRY_NEXT_FLAVOR,
     FlavorFungibilityPolicy.TRY_NEXT_FLAVOR),
    (FlavorFungibilityPolicy.BORROW, FlavorFungibilityPolicy.PREEMPT),
    (FlavorFungibilityPolicy.TRY_NEXT_FLAVOR,
     FlavorFungibilityPolicy.PREEMPT),
])
def test_flavor_fungibility_policies(borrow_policy, preempt_policy):
    def build(d):
        d.apply_resource_flavor(ResourceFlavor(name="f1"))
        d.apply_resource_flavor(ResourceFlavor(name="f2"))
        ff = FlavorFungibility(when_can_borrow=borrow_policy,
                               when_can_preempt=preempt_policy)
        pre = PreemptionPolicy(
            reclaim_within_cohort=ReclaimWithinCohort.ANY,
            within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
        for i in range(2):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-{i}", cohort="team", flavor_fungibility=ff,
                preemption=pre,
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[
                        FlavorQuotas(name="f1", resources={
                            "cpu": ResourceQuota(nominal=2000,
                                                 borrowing_limit=2000)}),
                        FlavorQuotas(name="f2", resources={
                            "cpu": ResourceQuota(nominal=4000)}),
                    ])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                           cluster_queue=f"cq-{i}"))
        rng = random.Random(17)
        out = []
        for i in range(24):
            q = rng.randrange(2)
            out.append(Workload(
                name=f"wl-{i}", queue_name=f"lq-{q}",
                priority=rng.choice([10, 10, 100]),
                creation_time=float(i + 1),
                pod_sets=[PodSet(name="main", count=1,
                                 requests={"cpu": rng.choice(
                                     [1000, 2000, 3000])})]))
        return out

    # non-default fungibility combos run the in-kernel walk now — the
    # wall moved: decisions must still match the host, with NO scalar
    # fallback heads
    _, stats = assert_parity(build, expect_scalar=False)
    assert stats["scalar_heads"] == 0, stats


# ---------------------------------------------------------------------------
# Partial admission (min_count)
# ---------------------------------------------------------------------------

def test_partial_admission_in_device_cycle():
    def build(d):
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        d.apply_cluster_queue(ClusterQueue(
            name="cq",
            resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=5000)})])]))
        d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
        out = []
        for i in range(6):
            # count=8 never fits 5 cpu; min_count=2 admits reduced
            out.append(Workload(
                name=f"wl-{i}", queue_name="lq",
                priority=10, creation_time=float(i + 1),
                pod_sets=[PodSet(name="main", count=8, min_count=2,
                                 requests={"cpu": 1000})]))
        return out

    hlog, stats = assert_parity(build, expect_scalar=False, n_cycles=20)
    # the count search runs on the vector path, and no head is walked
    # on the host for it
    assert stats["scalar_heads"] == 0, stats
    assert stats["partial_heads"] > 0, stats
    assert stats["partial_admitted"] > 0, stats
    # reduced-count admissions must actually happen
    counts = {cnt for c in hlog for _, fl in c["admitted"]
              for _, cnt, _ in fl}
    assert any(cnt < 8 for cnt in counts), counts


# ---------------------------------------------------------------------------
# Fungibility resume state (pending flavors across requeues)
# ---------------------------------------------------------------------------

def test_resume_state_heads_stay_in_device_cycle():
    """Two flavors + borrowing races: skipped heads requeue with
    last-tried flavor state; the next cycle's walk starts mid-list.
    Those heads route scalar and the cycle stays device-decided."""
    def build(d):
        d.apply_resource_flavor(ResourceFlavor(name="f1"))
        d.apply_resource_flavor(ResourceFlavor(name="f2"))
        for i in range(3):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-{i}", cohort="team",
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[
                        FlavorQuotas(name="f1", resources={
                            "cpu": ResourceQuota(nominal=1000,
                                                 borrowing_limit=2000)}),
                        FlavorQuotas(name="f2", resources={
                            "cpu": ResourceQuota(nominal=1000,
                                                 borrowing_limit=2000)}),
                    ])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                           cluster_queue=f"cq-{i}"))
        rng = random.Random(23)
        out = []
        for i in range(18):
            q = rng.randrange(3)
            out.append(Workload(
                name=f"wl-{i}", queue_name=f"lq-{q}",
                priority=rng.choice([10, 50]), creation_time=float(i + 1),
                pod_sets=[PodSet(name="main", count=1,
                                 requests={"cpu": rng.choice(
                                     [1000, 2000])})]))
        return out

    assert_parity(build, expect_scalar=False)


# ---------------------------------------------------------------------------
# Mixed cycles: vector and scalar heads together
# ---------------------------------------------------------------------------

def test_mixed_vector_and_scalar_heads(monkeypatch):
    from kueue_tpu.ops import burst, packing, solver
    # planes of two PodSets (upstream's eight, which the webhook
    # enforces), so that a three-PodSet head is the host walk's
    for mod in (burst, packing, solver):
        monkeypatch.setattr(mod, "MAX_POD_SETS", 2)

    def build(d):
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        d.apply_resource_flavor(ResourceFlavor(name="gpu-x"))
        # cq-0: plain single-RG (vector heads)
        d.apply_cluster_queue(ClusterQueue(
            name="cq-0", cohort="team",
            resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=4000,
                                         borrowing_limit=4000)})])]))
        # cq-1: two resource groups (vector heads); its two-PodSet
        # workloads are vector heads, and so are those with a partial
        # admission, whose count search runs on the vector path; one of
        # more PodSets than the planes hold is the scalar head
        d.apply_cluster_queue(ClusterQueue(
            name="cq-1", cohort="team",
            resource_groups=[
                ResourceGroup(covered_resources=["cpu"], flavors=[
                    FlavorQuotas(name="default", resources={
                        "cpu": ResourceQuota(nominal=4000,
                                             borrowing_limit=4000)})]),
                ResourceGroup(covered_resources=["gpu"], flavors=[
                    FlavorQuotas(name="gpu-x", resources={
                        "gpu": ResourceQuota(nominal=4)})]),
            ]))
        for i in range(2):
            d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                           cluster_queue=f"cq-{i}"))
        rng = random.Random(29)
        out = []
        for i in range(24):
            q = rng.randrange(2)
            reqs = {"cpu": rng.choice([1000, 2000, 3000])}
            if q == 1 and i % 2 == 0:
                reqs["gpu"] = 1
            pod_sets = [PodSet(name="main", count=1, requests=reqs)]
            if q == 1 and i % 4 == 0:
                pod_sets.append(PodSet(name="aux", count=1,
                                       requests={"cpu": 1000}))
            if q == 1 and i % 4 == 2:
                pod_sets = [PodSet(name="main", count=4, min_count=1,
                                   requests={"cpu": 1500})]
            out.append(Workload(
                name=f"wl-{i}", queue_name=f"lq-{q}",
                priority=rng.choice([10, 50]), creation_time=float(i + 1),
                pod_sets=pod_sets))
        out.append(Workload(
            name="wl-gang", queue_name="lq-1", priority=50,
            creation_time=2.5,
            pod_sets=[PodSet(name=f"p{p}", count=1, requests={"cpu": 500})
                      for p in range(3)]))
        return out

    _, stats = assert_parity(build)
    assert stats["partial_heads"] > 0, stats
    assert set(stats["scalar_reasons"]) == {"multi_podset"}, stats
