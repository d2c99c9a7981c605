"""Per-workload flavor eligibility, on every engine.

A ResourceFlavor declares node labels and taints; a PodSet carries a
node selector, a required node affinity and tolerations.  The host walk
skips a flavor the PodSet may not take before it looks at quota
(flavorassigner.go:553-575 and flavorSelector, :640): the flavor is
visited, is no stop and no candidate for the oracle.  The device path
carries the same rule as a mask a head (ops/eligibility.py).  The cases
follow upstream's ``TestAssignFlavors`` table where a row names one; each
runs through the host scalar scheduler, the per-cycle device engine and
``schedule_burst`` on identically built clusters, which have to agree
cycle by cycle, and the device engines have to decide every head with
no host walk and no host search.
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
    Taint,
    Toleration,
    WithinClusterQueue,
    Workload,
)
from kueue_tpu.controller.driver import Driver
from kueue_tpu.ops.eligibility import FlavorList, MASK_BITS, slots_of_mask
from tests.conftest import FakeClock
from tests.test_conformance_preemption import admit

K = 1000
TRY_NEXT = FlavorFungibilityPolicy.TRY_NEXT_FLAVOR
SPOT = Taint(key="spot", value="true", effect="NoSchedule")
TOLERATES_SPOT = Toleration(key="spot", operator="Exists",
                            effect="NoSchedule")

# the flavors of the third deployment, in a queue's order
RESERVED = ResourceFlavor(name="reserved",
                          node_labels={"instance-type": "reserved"})
ON_DEMAND = ResourceFlavor(name="on-demand",
                           node_labels={"instance-type": "on-demand"})
SPOT_A = ResourceFlavor(name="spot-a", node_taints=[SPOT], node_labels={
    "instance-type": "spot", "topology.kubernetes.io/zone": "zone-a"})
SPOT_B = ResourceFlavor(name="spot-b", node_taints=[SPOT], node_labels={
    "instance-type": "spot", "topology.kubernetes.io/zone": "zone-b"})
DECLARED = (RESERVED, ON_DEMAND, SPOT_A, SPOT_B)


# ---- the rule itself: a mask a (PodSet, flavor list) --------------------

def pod_set(selector=None, affinity=None, tolerations=()):
    return PodSet(name="main", count=1, requests={"cpu": 4 * K},
                  node_selector=dict(selector or {}),
                  required_node_affinity=dict(affinity or {}),
                  tolerations=list(tolerations))


ELIGIBILITY = {
    # name: (flavors, PodSet, the flavors it may take); the upstream
    # TestAssignFlavors row in the comment where there is one
    # "multiple flavors, skips untolerated flavors"
    "untolerated_taint_skipped": (
        DECLARED, pod_set(), ["reserved", "on-demand"]),
    "toleration_opens_the_tainted_flavors": (
        DECLARED, pod_set(tolerations=[TOLERATES_SPOT]),
        ["reserved", "on-demand", "spot-a", "spot-b"]),
    # "multiple flavors, fits a node selector"
    "selector_fits_one_flavor": (
        DECLARED, pod_set(selector={"instance-type": "on-demand"}),
        ["on-demand"]),
    "selector_on_two_keys": (
        DECLARED, pod_set(
            selector={"instance-type": "spot",
                      "topology.kubernetes.io/zone": "zone-b"},
            tolerations=[TOLERATES_SPOT]), ["spot-b"]),
    "selector_matches_but_taint_bars": (
        DECLARED, pod_set(selector={"instance-type": "spot"}), []),
    # "multiple flavors, node affinity fits any flavor"
    "affinity_fits_any_of_several": (
        DECLARED, pod_set(affinity={
            "instance-type": ["on-demand", "spot"]},
            tolerations=[TOLERATES_SPOT]),
        ["on-demand", "spot-a", "spot-b"]),
    # "multiple flavor, doesn't fit node affinity"
    "affinity_fits_none": (
        DECLARED, pod_set(affinity={"instance-type": ["dedicated"]},
                          tolerations=[TOLERATES_SPOT]), []),
    # "multiple flavors, ignore non-flavor nodeSelectors" (flavorSelector
    # keeps only the label keys some flavor of the group carries)
    "selector_key_no_flavor_carries_is_ignored": (
        DECLARED, pod_set(selector={"kubernetes.io/arch": "amd64"}),
        ["reserved", "on-demand"]),
    # a key some flavor carries binds on a flavor that lacks it
    "selector_key_one_flavor_lacks": (
        DECLARED, pod_set(
            selector={"topology.kubernetes.io/zone": "zone-a"},
            tolerations=[TOLERATES_SPOT]), ["spot-a"]),
    # ResourceFlavor.spec.tolerations are added to the PodSet's own
    "flavor_level_toleration": (
        (RESERVED, ResourceFlavor(
            name="spot-own", node_taints=[SPOT],
            tolerations=[Toleration(key="spot", operator="Equal",
                                    value="true")])),
        pod_set(), ["reserved", "spot-own"]),
    "prefer_no_schedule_never_bars": (
        (ResourceFlavor(name="soft", node_taints=[Taint(
            key="spot", value="true", effect="PreferNoSchedule")]),),
        pod_set(), ["soft"]),
    "equal_toleration_of_another_value": (
        DECLARED, pod_set(tolerations=[Toleration(
            key="spot", operator="Equal", value="false")]),
        ["reserved", "on-demand"]),
}


@pytest.mark.parametrize("case", sorted(ELIGIBILITY))
def test_mask_is_the_host_walks_rule(case):
    """The mask against the rule written out by the host walk: its two
    checks, called on the same flavor list and PodSet."""
    from kueue_tpu.scheduler.flavorassigner import FlavorAssigner
    from kueue_tpu.api.types import taints_tolerated
    flavors, ps, want = ELIGIBILITY[case]
    fl = FlavorList(list(flavors))
    mask = fl.skip_mask(ps)
    may = [f.name for s, f in enumerate(flavors) if not mask >> s & 1]
    assert may == want
    keys = {k for f in flavors for k in f.node_labels}
    host = [f.name for f in flavors
            if taints_tolerated(f.node_taints,
                                list(ps.tolerations) + list(f.tolerations))
            and FlavorAssigner._flavor_matches_affinity(None, ps, f, keys)]
    assert host == want
    assert slots_of_mask(np.array([mask]), len(flavors))[0].tolist() == [
        f.name in want for f in flavors]


def test_slots_past_the_mask_are_never_barred():
    """No mask has a bit at or above MASK_BITS; a plain list longer than
    that reads every slot eligible."""
    plane = slots_of_mask(np.array([0, 0b1010_0101]), MASK_BITS + 5)
    assert plane[0].all()
    assert plane[1].tolist() == [
        False, True, False, True, True, False, True, False] + [True] * 5


# ---- through the three engines ---------------------------------------------

def declared_cluster(d, flavors, a, b):
    """Queues a and b in one cohort over ``flavors`` in one resource
    group under the default flavorFungibility; ``a`` / ``b``: {flavor
    name: cpu nominal}, in walk order."""
    for f in flavors:
        d.apply_resource_flavor(f)
    ff = FlavorFungibility(when_can_preempt=TRY_NEXT)
    for name, nominal in (("a", a), ("b", b)):
        d.apply_cluster_queue(ClusterQueue(
            name=name, cohort="co", flavor_fungibility=ff,
            preemption=PreemptionPolicy(
                within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
                reclaim_within_cohort=ReclaimWithinCohort.ANY),
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name=f, resources={
                    "cpu": ResourceQuota(nominal=q)})
                    for f, q in nominal.items()])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{name}",
                                       cluster_queue=name))


def head(d, name, queue, ps, priority=10, created=999.0):
    d.create_workload(Workload(
        name=name, namespace="default", queue_name=f"lq-{queue}",
        priority=priority, creation_time=created, pod_sets=[ps]))


ROOM = {f.name: 8 * K for f in DECLARED}
NONE = {f.name: 0 for f in DECLARED}


def lands_on_its_first_eligible(case):
    def build(d):
        flavors, ps, _ = ELIGIBILITY[case]
        declared_cluster(d, flavors, {f.name: 8 * K for f in flavors},
                         {f.name: 0 for f in flavors})
        head(d, "head", "a", ps)
    return build


def skipped_flavor_is_no_stop(d):
    """reserved has room but the head may not take it (it selects spot);
    on-demand neither; spot-a is full of a's own lower-priority work
    (Preempt), spot-b free.  The walk passes the two skipped flavors
    and the preempt-capable one and fits on spot-b."""
    declared_cluster(d, DECLARED, ROOM, NONE)
    admit(d, "own-spot-a", "a", {"cpu": ("spot-a", 8 * K)}, priority=-10)
    head(d, "head", "a", pod_set(selector={"instance-type": "spot"},
                                 tolerations=[TOLERATES_SPOT]))


def resume_index_counts_skipped_flavors(d):
    """Cycle 1: a's head may not take reserved (tainted here), fits
    on-demand only by borrowing b's quota and stops there, slot 1; b's
    own head takes that quota first, so a's head is skipped with its
    resume slot recorded.  Cycle 2: it resumes at slot 2 (Preempt, its
    own lower-priority work) and slot 3 (Reclaim, lent to b) and takes
    the oracle's pick, as the host's walk does."""
    flavors = (ResourceFlavor(name="reserved", node_taints=[SPOT]),
               ResourceFlavor(name="on-demand"),
               ResourceFlavor(name="spot-a"), ResourceFlavor(name="spot-b"))
    declared_cluster(
        d, flavors,
        {"reserved": 8 * K, "on-demand": 0, "spot-a": 4 * K,
         "spot-b": 4 * K},
        {"reserved": 0, "on-demand": 4 * K, "spot-a": 0, "spot-b": 0})
    admit(d, "own-spot-a", "a", {"cpu": ("spot-a", 4 * K)}, priority=-10)
    admit(d, "lent-spot-b", "b", {"cpu": ("spot-b", 4 * K)}, priority=-10)
    head(d, "head", "a", pod_set(), created=5.0)
    head(d, "first", "b", pod_set(), priority=20, created=1.0)


def one_eligible_preempt_flavor_asks_no_oracle(d):
    """Every flavor of a is full: reserved and on-demand of its own
    lower-priority work (Preempt), the spot pools lent to b (Reclaim).
    A head pinned to spot-b has one preempt-capable flavor it may take,
    and the pick needs no oracle."""
    declared_cluster(d, DECLARED, {f.name: 4 * K for f in DECLARED}, NONE)
    for f in ("reserved", "on-demand"):
        admit(d, f"own-{f}", "a", {"cpu": (f, 4 * K)}, priority=-10)
    for f in ("spot-a", "spot-b"):
        admit(d, f"lent-{f}", "b", {"cpu": (f, 4 * K)}, priority=-10)
    head(d, "head", "a", pod_set(
        selector={"instance-type": "spot",
                  "topology.kubernetes.io/zone": "zone-b"},
        tolerations=[TOLERATES_SPOT]))


def two_eligible_preempt_flavors_keep_the_first(d):
    """The same cluster, a head that may take reserved and on-demand
    only (no toleration): two preempt-capable flavors, each full of a's
    own work, so the request would borrow: nothing the oracle could
    reclaim, no question, and the first wins."""
    one_eligible_preempt_flavor_asks_no_oracle(d)
    d.delete_workload("default/head")
    head(d, "head", "a", pod_set())


def tolerant_head_reclaims_past_its_own_work(d):
    """The same cluster, a head that may take all four: Reclaim on
    spot-a beats Preempt on reserved and on-demand."""
    one_eligible_preempt_flavor_asks_no_oracle(d)
    d.delete_workload("default/head")
    head(d, "head", "a", pod_set(tolerations=[TOLERATES_SPOT]))


def heads_of_one_cohort_search_different_columns(d):
    """a's head is pinned to spot-b, b's may take reserved and
    on-demand: both preempt in one cycle, in different flavor columns of
    the same candidate tables."""
    declared_cluster(d, DECLARED, {f.name: 4 * K for f in DECLARED},
                     {f.name: 4 * K for f in DECLARED})
    for q in ("a", "b"):
        for f in DECLARED:
            admit(d, f"own-{q}-{f.name}", q, {"cpu": (f.name, 4 * K)},
                  priority=-10)
    head(d, "head", "a", pod_set(
        selector={"topology.kubernetes.io/zone": "zone-b"},
        tolerations=[TOLERATES_SPOT]))
    head(d, "other", "b", pod_set(), created=998.0)


SCENARIOS = {
    # name: (builder, {workload: flavor it ends on or None}, evicted,
    #        the oracle is asked)
    **{case: (lands_on_its_first_eligible(case),
              {"head": (ELIGIBILITY[case][2] or [None])[0]}, [], False)
       for case in ELIGIBILITY},
    "skipped_flavor_is_no_stop": (
        skipped_flavor_is_no_stop, {"head": "spot-b"}, [], False),
    "resume_index_counts_skipped_flavors": (
        resume_index_counts_skipped_flavors,
        {"head": "spot-b", "first": "on-demand"}, ["lent-spot-b"], True),
    "one_eligible_preempt_flavor_asks_no_oracle": (
        one_eligible_preempt_flavor_asks_no_oracle,
        {"head": "spot-b"}, ["lent-spot-b"], False),
    "two_eligible_preempt_flavors_keep_the_first": (
        two_eligible_preempt_flavors_keep_the_first,
        {"head": "reserved"}, ["own-reserved"], False),
    "tolerant_head_reclaims_past_its_own_work": (
        tolerant_head_reclaims_past_its_own_work,
        {"head": "spot-a"}, ["lent-spot-a"], True),
    "heads_of_one_cohort_search_different_columns": (
        heads_of_one_cohort_search_different_columns,
        {"head": "spot-b", "other": "reserved"},
        ["own-a-spot-b", "own-b-reserved"], False),
}
CYCLES = 5


def flavors_of(d, keys):
    return {k: sorted(set(
        d.workload(k).admission.pod_set_assignments[0].flavors.values()))
        for k in keys}


def run(engine, build):
    """[(admitted, evicted, {admitted key: flavors}, {pending key: the
    slot its next walk starts on})] a cycle, and the driver."""
    from kueue_tpu.ops.solver import resume_starts
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=engine != "host")
    build(d)
    out = []

    def record(stats):
        resume = {}
        for q in d.queues.cluster_queue_names():
            cq = d.queues.queue_for(q)
            for info in list(cq.heap.items()) + list(
                    cq.inadmissible.values()):
                resume[info.key] = resume_starts(
                    info, d.cache.cluster_queue(q), False, 1)[0]
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


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_every_engine_decides_under_the_heads_own_mask(scenario):
    build, want, evicted, asks = SCENARIOS[scenario]
    host, dh = run("host", build)
    for name, flavor in want.items():
        wl = dh.workload(f"default/{name}")
        if flavor is None:
            assert not wl.has_quota_reservation, host
        else:
            assert flavors_of(dh, [f"default/{name}"]) == {
                f"default/{name}": [flavor]}, host
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
        assert solver["host_cycles"] == 0, solver
        assert pre["host_searches"] == 0, pre
        assert (pre["oracle_specs"] > 0) == asks, pre
        burst = d._burst_solver.stats if engine == "burst" else None
        if burst and not evicted:
            # no cycle fell out of the fused window: its in-kernel walk
            # read the rows' masks
            assert burst["burst_dirty_cycles"] == 0, burst
            assert burst["burst_dispatches"] >= 1, burst
            assert solver["walk_heads"] == 0, solver
        if burst and scenario.startswith("one_eligible"):
            # ... and with one preempt-capable flavor left to the head,
            # the window searched and preempted in its kernel
            assert burst["burst_dirty_cycles"] == 0, burst
            assert burst["burst_preempt_cycles"] == 1, burst


def test_walk_counters_read_the_masks():
    """One cycle of the per-cycle engine over four heads of four queues:
    what the walk visited, what it skipped, how many masks it built."""
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    for f in DECLARED:
        d.apply_resource_flavor(f)
    heads = {
        "plain": pod_set(),                                  # 2 of 4
        "any": pod_set(tolerations=[TOLERATES_SPOT]),        # 4 of 4
        "spot": pod_set(selector={"instance-type": "spot"},
                        tolerations=[TOLERATES_SPOT]),       # 2 of 4
        "again": pod_set(selector={"instance-type": "spot"},
                         tolerations=[TOLERATES_SPOT]),      # cached
    }
    for name in heads:
        # nothing fits anywhere: every walk visits all four flavors
        d.apply_cluster_queue(ClusterQueue(
            name=name, resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name=f.name, resources={
                    "cpu": ResourceQuota(nominal=K)}) for f in DECLARED])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{name}",
                                       cluster_queue=name))
        head(d, name, name, heads[name])
    clock.t += 1.0
    stats = d.schedule_once()
    assert not stats.admitted
    s = d.scheduler.solver.stats
    assert s["walk_heads"] == 4 and s["walk_slots"] == 16
    assert s["walk_ineligible_slots"] == 2 + 0 + 2 + 2
    assert s["constrained_heads"] == 3
    assert s["eligibility_masks_built"] == 3
    # the next cycle reads every mask off its Info
    for info in [i for q in heads for i in
                 d.queues.queue_for(q).inadmissible.values()]:
        assert info._flavor_skip[2] in ((0b1100,), (0,), (0b0011,))
    d.queues.queue_inadmissible_workloads(set(heads))
    clock.t += 1.0
    d.schedule_once()
    assert s["walk_heads"] == 8 and s["eligibility_masks_built"] == 3


def test_more_declared_flavors_than_the_mask_holds_stay_scalar():
    """Nine labelled flavors in one group are outside the row's byte:
    the queue's heads take the host walk and are counted as such; nine
    plain ones are the vector walk's."""
    for declared in (True, False):
        clock = FakeClock()
        d = Driver(clock=clock, use_device_solver=True)
        names = [f"f{i}" for i in range(MASK_BITS + 1)]
        for n in names:
            d.apply_resource_flavor(ResourceFlavor(
                name=n, node_labels={"pool": n} if declared else {}))
        d.apply_cluster_queue(ClusterQueue(
            name="a", resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name=n, resources={
                    "cpu": ResourceQuota(nominal=8 * K)}) for n in names])]))
        d.apply_local_queue(LocalQueue(name="lq-a", cluster_queue="a"))
        head(d, "head", "a", pod_set(selector={"pool": "f8"})
             if declared else pod_set())
        clock.t += 1.0
        assert d.schedule_once().admitted == ["default/head"]
        assert flavors_of(d, ["default/head"]) == {
            "default/head": ["f8" if declared else "f0"]}
        s = d.scheduler.solver.stats
        assert s["scalar_reasons"] == ({"cq_shape": 1} if declared else {})
        assert s["scalar_heads"] == int(declared)


# ---- the vector math's twins ---------------------------------------------------

def test_classify_np_and_the_jitted_classify_agree_under_a_plane():
    """``solve_cycle``'s classify is ``classify_np``'s twin with the
    plane as without it."""
    from kueue_tpu.ops.cycle import classify_np, solve_cycle
    from kueue_tpu.ops.packing import pack_cycle
    from kueue_tpu.parallel.sharded import cycle_args
    from kueue_tpu.workload import Ordering
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    one_eligible_preempt_flavor_asks_no_oracle(d)
    head(d, "other", "b", pod_set(), created=998.0)
    snap = d.cache.snapshot()
    heads = [i for q in ("a", "b")
             for i in d.queues.queue_for(q).heap.items()]
    for h in heads:
        h.cluster_queue = "a" if h.obj.name == "head" else "b"
    solver = d.scheduler.solver
    st = solver._structure_for(snap, heads)
    packed = pack_cycle(snap, heads, Ordering(), structure=st)
    W, S = packed.wl_cq.shape[0], st.slot_fr.shape[1]
    rng = np.random.default_rng(7)
    for _ in range(8):
        eligible = rng.random((W, S)) < 0.6
        want = classify_np(packed, eligible=eligible)
        got = solve_cycle(*cycle_args(packed), eligible=eligible,
                          depth=packed.depth, run_scan=False)
        assert np.array_equal(np.asarray(got[4]), want["fit_slot0"])
        assert np.array_equal(np.asarray(got[3]), want["preempt0"])
        # nothing the pick or the oracle reads names a barred slot
        assert not (want["preempt_slots"][:, 0, 0] & ~eligible).any()
        assert not (want["oracle_ask"][:, 0] & ~eligible[:, :, None]).any()
        n = packed.wl_count
        assert (want["walk_ineligible"][:n] <= want["walk_slots"][:n]).all()


# ---- the fused window's plane, in both packs --------------------------------------

def test_both_packs_write_the_rows_masks():
    """The full pack and the streaming arena carry ``wl_flavor_skip``, a
    row's mask under its key, for pending and admitted rows alike, and
    agree plane for plane after a delta pack."""
    from kueue_tpu.ops.burst import pack_burst, pack_burst_cached
    from tests.test_delta_pack import assert_plans_equal, current_structure
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    heads_of_one_cohort_search_different_columns(d)
    head(d, "late", "a", pod_set(selector={"instance-type": "on-demand"}),
         priority=0, created=1000.0)
    stats, state = {}, None
    for step in range(3):
        st = current_structure(d)
        plan, state, _ = pack_burst_cached(
            st, d.queues, d.cache, d.scheduler, d.clock, state=state,
            window=0, stats=stats)
        full = pack_burst(st, d.queues, d.cache, d.scheduler, d.clock,
                          window=0)
        assert_plans_equal(plan, full, f"step {step}")
        skip = plan.arrays["wl_flavor_skip"]
        assert skip.dtype == np.uint8
        at = {k.split("/")[1]: int(skip[c, m, 0])
              for k, (c, m) in plan.row_of_key.items()}
        assert at["head"] == 0b0111 and at["late"] == 0b1101
        if "other" in at:
            assert at["other"] == 0b1100
        assert at["own-a-reserved"] == 0b1100     # an admitted row's too
        clock.t += 1.0
        d.schedule_once()
        head(d, f"more-{step}", "b",
             pod_set(tolerations=[TOLERATES_SPOT]), created=2000.0 + step)
    assert stats["burst_delta_packs"] >= 1


def test_sharded_window_carries_the_rows_masks(monkeypatch):
    """Two cohorts on a two-shard mesh: the masks ride in the scatter
    tier with the other row planes (verified against a full permute at
    every window), and the sharded window decides what the serial one
    and the host decide."""
    monkeypatch.setenv("KUEUE_TPU_RESIDENT_VERIFY", "1")
    from kueue_tpu.ops.burst import BurstSolver

    def build(d):
        for f in DECLARED:
            d.apply_resource_flavor(f)
        jobs = [pod_set(), pod_set(tolerations=[TOLERATES_SPOT]),
                pod_set(selector={"instance-type": "spot"},
                        tolerations=[TOLERATES_SPOT]),
                pod_set(selector={"topology.kubernetes.io/zone": "zone-b"},
                        tolerations=[TOLERATES_SPOT])]
        for c in range(2):
            for q in range(2):
                name = f"cq-{c}-{q}"
                d.apply_cluster_queue(ClusterQueue(
                    name=name, cohort=f"co-{c}",
                    resource_groups=[ResourceGroup(
                        covered_resources=["cpu"],
                        flavors=[FlavorQuotas(name=f.name, resources={
                            "cpu": ResourceQuota(nominal=8 * K)})
                            for f in DECLARED])]))
                d.apply_local_queue(LocalQueue(name=f"lq-{name}",
                                               cluster_queue=name))
                for i in range(10):
                    head(d, f"w-{c}-{q}-{i}", name, jobs[(i + q) % 4],
                         priority=(i % 3) * 10,
                         created=float(10 * c + 3 * q + i))

    def run_shards(shards):
        clock = FakeClock()
        d = Driver(clock=clock, use_device_solver=True)
        build(d)
        if shards:
            d._burst_solver = BurstSolver()
            d._burst_solver.set_shards(shards)
        out = []
        for _ in range(3):
            d.schedule_burst(
                4, runtime=2,
                on_cycle_start=lambda k: setattr(clock, "t", clock.t + 1.0),
                on_cycle=lambda k, s: out.append(
                    (sorted(s.admitted), flavors_of(d, s.admitted))))
        return out, d

    host, _ = run("host", build)
    serial, _ = run_shards(0)
    sharded, d = run_shards(2)
    assert serial == sharded
    # (the host's run finishes nothing: the same until a window's does)
    assert [a for a, _ in serial[:2]] == [a for a, _, _, _ in host[:2]]
    assert sum(len(a) for a, _ in serial) >= 24
    assert {f for _, fl in serial for v in fl.values() for f in v} == {
        f.name for f in DECLARED}
    st = d._burst_solver.stats
    assert st["burst_sharded_dispatches"] >= 2, st
    assert st["burst_resident_hits"] >= 1, st
