"""The batched topology stage (``CycleSolver.tas_stage`` over
``ops/tas_kernel.place_queries``) against the host walk
(``TASFlavorSnapshot.find_topology_assignment`` with the kernel gate
off, the scalar tree walk).

- placement for placement on seeded random trees of three levels with
  uneven racks: required / preferred / unconstrained requests, every
  TAS profile, against the free nodes and against the empty tree, and a
  second PodSet charged with the first's placement;
- a table of hand-worked cases in the spirit of upstream's
  tas_flavor_snapshot_test.go (BestFit on a small tree);
- the Driver at toy size: topology-aware queues through
  ``schedule_burst``, decisions and placements identical to
  ``use_device_solver=False``, every head on the vector path;
- two heads whose placements want one tight node in one cycle: the
  second is skipped, on both paths, and no node is overcommitted.
"""

import random

import numpy as np
import pytest

from kueue_tpu import features
from kueue_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PodSetTopologyRequest,
    PreemptionPolicy,
    ReclaimWithinCohort,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Topology,
    WithinClusterQueue,
    Workload,
)
from kueue_tpu.cache.tas_cache import NodeInfo
from kueue_tpu.cache.tas_snapshot import TASFlavorSnapshot
from kueue_tpu.controller.driver import Driver
from kueue_tpu.ops import tas_kernel as tk
from tests.conftest import FakeClock

LEVELS = ["block", "rack", "host"]
PROFILES = {
    "bestfit": {},
    "mostfree": {"TASProfileMostFreeCapacity": True},
    "leastfree": {"TASProfileLeastFreeCapacity": True},
    "mixed": {"TASProfileMixed": True},
}
_PROFILE_GATES = ("TASProfileMostFreeCapacity", "TASProfileLeastFreeCapacity",
                  "TASProfileMixed")


@pytest.fixture
def profile_gates():
    def set_profile(name):
        features.set_feature_gates({**{g: False for g in _PROFILE_GATES},
                                    **PROFILES[name]})
    yield set_profile
    features.set_feature_gates({g: False for g in _PROFILE_GATES})
    features.set_feature_gates({"TASDeviceKernel": True,
                                "TopologyAwareScheduling": False})


def _node(b, r, h, cpu, mem_gib, pods, memory=None):
    return NodeInfo(name=f"n-{b}-{r}-{h}",
                    labels={"block": f"b{b}", "rack": f"r{b}-{r}",
                            "host": f"h{b}-{r}-{h}"},
                    capacity={"cpu": cpu, "pods": pods,
                              "memory": (mem_gib << 30 if memory is None
                                         else memory)})


def random_tree(seed):
    """Three levels, racks of uneven size, some nodes part used."""
    rng = random.Random(seed)
    nodes = [_node(b, r, h, rng.choice([2000, 4000, 8000]),
                   rng.choice([4, 8, 16]), rng.choice([3, 8, 110]))
             for b in range(rng.randint(1, 4))
             for r in range(rng.randint(1, 5))
             for h in range(rng.randint(1, 6))]
    usage = {}
    for n in nodes:
        if rng.random() < 0.5:
            usage[tuple(n.labels[lvl] for lvl in LEVELS)] = {
                "cpu": rng.choice([0, 1000, 2000]),
                "memory": rng.choice([0, 2 << 30]),
                "pods": rng.choice([0, 1, 2])}
    return TASFlavorSnapshot.build("f", LEVELS, nodes, usage), rng


def _request(kind, level):
    if kind == tk.QUERY_REQUIRED:
        return PodSetTopologyRequest(required=LEVELS[level])
    if kind == tk.QUERY_PREFERRED:
        return PodSetTopologyRequest(preferred=LEVELS[level])
    return PodSetTopologyRequest(unconstrained=True)


def _host_walk(snap, count, per_pod, request, empty=False, assumed=None):
    features.set_feature_gates({"TASDeviceKernel": False})
    try:
        return snap.find_topology_assignment(
            count, per_pod, request, assumed=assumed, simulate_empty=empty)
    finally:
        features.set_feature_gates({"TASDeviceKernel": True})


def _vec(layout, per_pod):
    v = np.zeros(len(layout.resource_names), dtype=np.int64)
    for r, q in per_pod.items():
        v[layout.r_index[r]] = q
    return v


def _leaves(layout, assignment):
    if assignment is None:
        return None
    return [(layout.leaf_index[tuple(d.values)], d.count)
            for d in assignment.domains]


def _answer(snap, queries):
    return tk.answer_queries(snap.layout, snap.free, queries,
                             snap._device_profile(False),
                             snap._device_profile(True))


@pytest.mark.parametrize("profile", sorted(PROFILES))
@pytest.mark.parametrize("kind", [tk.QUERY_REQUIRED, tk.QUERY_PREFERRED,
                                  tk.QUERY_UNCONSTRAINED],
                         ids=["required", "preferred", "unconstrained"])
@pytest.mark.parametrize("empty", [False, True], ids=["fit", "empty"])
def test_stage_matches_the_host_walk_on_random_trees(profile, kind, empty,
                                                     profile_gates):
    profile_gates(profile)
    bad = []
    for seed in range(12):
        snap, rng = random_tree(1000 * kind + seed)
        lay = snap.layout
        queries, expect = [], []
        for _ in range(10):
            count = rng.choice([1, 2, 3, 5, 8])
            per_pod = {"cpu": rng.choice([500, 1000, 2000]),
                       "memory": rng.choice([1, 2]) << 30, "pods": 1}
            level = rng.choice([0, 1, 2])
            a, _ = _host_walk(snap, count, per_pod, _request(kind, level),
                              empty=empty)
            queries.append(tk.TASQuery(
                _vec(lay, per_pod), count, kind,
                -1 if kind == tk.QUERY_UNCONSTRAINED else level, empty))
            expect.append(_leaves(lay, a))
        for q, e, ans in zip(queries, expect, _answer(snap, queries)):
            got = ans.placement if ans.ok else None
            if got != e:
                bad.append((seed, q.count, q.level, e, got))
    assert not bad, bad[:3]


@pytest.mark.parametrize("empty", [False, True], ids=["fit", "empty"])
def test_a_second_podset_is_charged_with_the_first(empty, profile_gates):
    """``_find_tas``' ``assumed``: the second PodSet's query sees the
    nodes less what the first was placed on, in both stages."""
    profile_gates("bestfit")
    bad = []
    for seed in range(20):
        snap, rng = random_tree(7000 + seed)
        lay = snap.layout
        pp1 = {"cpu": 1000, "memory": 1 << 30, "pods": 1}
        pp2 = {"cpu": rng.choice([500, 1000]), "memory": 1 << 30, "pods": 1}
        r1 = _request(tk.QUERY_REQUIRED, 1)
        r2 = _request(rng.choice([0, 1, 2]), rng.choice([1, 2]))
        n1, n2 = rng.choice([1, 2, 3]), rng.choice([1, 2, 4])
        first, _ = _host_walk(snap, n1, pp1, r1, empty=empty)
        if first is None:
            continue
        assumed = {}
        for d in first.domains:
            assumed[tuple(d.values)] = {r: v * d.count
                                        for r, v in pp1.items()}
        second, _ = _host_walk(snap, n2, pp2, r2, empty=empty,
                               assumed=assumed)
        charges = tuple((lay.leaf_index[tuple(d.values)],
                         d.count * _vec(lay, pp1)) for d in first.domains)
        kind = (tk.QUERY_UNCONSTRAINED if r2.unconstrained else
                tk.QUERY_REQUIRED if r2.required else tk.QUERY_PREFERRED)
        level = LEVELS.index(r2.required or r2.preferred or "host")
        ans, = _answer(snap, [tk.TASQuery(_vec(lay, pp2), n2, kind, level,
                                          empty, charges)])
        if (ans.placement if ans.ok else None) != _leaves(lay, second):
            bad.append(seed)
    assert not bad, bad


# ---------------------------------------------------------------------------
# Hand-worked cases, upstream tas_flavor_snapshot_test.go style
# ---------------------------------------------------------------------------
#
# b1: r1 {x1: 1 cpu, x2: 2 cpu}, r2 {x3: 3 cpu}
# b2: r3 {x4: 1 cpu, x5: 1 cpu}, r4 {x6: 4 cpu}
# one pod = 1 cpu: hosts hold 1/2/3/1/1/4, racks 3/3/2/4, blocks 6/6

_CPU = {"x1": 1, "x2": 2, "x3": 3, "x4": 1, "x5": 1, "x6": 4}
_WHERE = {"x1": ("b1", "r1"), "x2": ("b1", "r1"), "x3": ("b1", "r2"),
          "x4": ("b2", "r3"), "x5": ("b2", "r3"), "x6": ("b2", "r4")}


def _hand_snap(used=None):
    nodes = [NodeInfo(name=h, labels={"block": b, "rack": r, "host": h},
                      capacity={"cpu": c * 1000, "pods": 10})
             for h, c in _CPU.items() for b, r in [_WHERE[h]]]
    usage = {(*_WHERE[h], h): {"cpu": c * 1000}
             for h, c in (used or {}).items()}
    return TASFlavorSnapshot.build("f", LEVELS, nodes, usage)


TAS_SNAPSHOT_CASES = [
    pytest.param("bestfit", "required", "host", 1, None, False,
                 {"x1": 1}, id="host required; one pod takes the tightest "
                               "host, lowest id on a tie"),
    pytest.param("bestfit", "required", "host", 3, None, False,
                 {"x3": 3}, id="host required; the tightest host that "
                               "holds all of them"),
    pytest.param("bestfit", "required", "host", 5, None, False, None,
                 id="host required; no host holds five"),
    pytest.param("bestfit", "required", "rack", 2, None, False,
                 {"x4": 1, "x5": 1}, id="rack required; the tightest rack, "
                                        "split over its hosts"),
    pytest.param("bestfit", "required", "rack", 4, None, False,
                 {"x6": 4}, id="rack required; four pods fit one rack's "
                               "one host"),
    pytest.param("bestfit", "required", "rack", 5, None, False, None,
                 id="rack required; too many pods for any rack"),
    pytest.param("bestfit", "required", "block", 5, None, False,
                 {"x1": 1, "x2": 2, "x3": 2},
                 id="block required; fewest racks, then fewest hosts"),
    pytest.param("bestfit", "preferred", "host", 5, None, False,
                 {"x1": 1, "x2": 2, "x3": 2},
                 id="host preferred; only a block holds the workload"),
    pytest.param("bestfit", "preferred", "rack", 7, None, False,
                 {"x1": 1, "x2": 2, "x3": 3, "x4": 1},
                 id="rack preferred; only several blocks hold it"),
    pytest.param("bestfit", "unconstrained", None, 1, None, False,
                 {"x1": 1}, id="no annotation on a TAS-only queue; "
                               "unconstrained, tightest all the way down"),
    pytest.param("bestfit", "unconstrained", None, 13, None, False, None,
                 id="unconstrained; more pods than the tree holds"),
    pytest.param("mostfree", "preferred", "host", 3, None, False,
                 {"x6": 3}, id="MostFreeCapacity; the emptiest host"),
    pytest.param("leastfree", "required", "rack", 2, None, False,
                 {"x4": 1, "x5": 1},
                 id="LeastFreeCapacity; the fullest rack that holds it"),
    pytest.param("bestfit", "required", "host", 4, {"x6": 4}, False, None,
                 id="host required; the only big host is in use"),
    pytest.param("bestfit", "required", "host", 4, {"x6": 4}, True,
                 {"x6": 4}, id="host required against the empty tree; "
                               "the big host counts whole"),
]


@pytest.mark.parametrize("profile,kind,level,count,used,empty,expect",
                         TAS_SNAPSHOT_CASES)
def test_hand_worked_case(profile, kind, level, count, used, empty, expect,
                          profile_gates):
    profile_gates(profile)
    request = (PodSetTopologyRequest(unconstrained=True)
               if kind == "unconstrained" else
               PodSetTopologyRequest(**{kind: level}))
    per_pod = {"cpu": 1000, "pods": 1}
    snap = _hand_snap(used)
    host, _ = _host_walk(snap, count, per_pod, request, empty=empty)
    got_host = (None if host is None else
                {tuple(d.values)[-1]: d.count for d in host.domains})
    assert got_host == expect
    qkind = {"required": tk.QUERY_REQUIRED, "preferred": tk.QUERY_PREFERRED,
             "unconstrained": tk.QUERY_UNCONSTRAINED}[kind]
    lay = snap.layout
    ans, = _answer(snap, [tk.TASQuery(
        _vec(lay, per_pod), count, qkind,
        LEVELS.index(level) if level else -1, empty)])
    got = ({lay.leaf_ids[i][-1]: c for i, c in ans.placement}
           if ans.ok else None)
    assert got == expect
    kernel, _ = snap.find_topology_assignment(count, per_pod, request,
                                              simulate_empty=empty)
    assert (None if kernel is None else
            {tuple(d.values)[-1]: d.count for d in kernel.domains}) == expect


# ---------------------------------------------------------------------------
# Values past int32: exact on the kernel, or walked on the host
# ---------------------------------------------------------------------------

_TIB = 1 << 40


def _big_snap(case):
    """``terabyte``: 1.5 TiB nodes asked for 1000M a pod (memory in
    bytes over the greatest common divisor, 2^9, is 3 * 2^30: past
    int32); ``past_old_cap``: eight leaves of 250-257 M pods each (the
    states are exact in int32, and their sum is too); ``sum_past_int32``:
    sixteen such leaves (the roots' sum is not)."""
    if case == "terabyte":
        nodes = [_node(b, r, h, 192_000, 0, 110, memory=3 * _TIB // 2)
                 for b in range(2) for r in range(2) for h in range(3)]
        return TASFlavorSnapshot.build("f", LEVELS, nodes, {}), {
            "cpu": 1000, "memory": 1_000_000_000}
    n = 8 if case == "past_old_cap" else 16
    # leaf 0 the largest: a tie of clipped states would pick it
    nodes = [_node(i // 4, (i // 2) % 2, i % 2,
                   (257_000_000 - 1_000_000 * ((i * 3) % n)), 1, 1)
             for i in range(n)]
    for nd in nodes:
        nd.capacity.pop("pods")
        nd.capacity.pop("memory")
    return TASFlavorSnapshot.build("f", LEVELS, nodes, {}), {"cpu": 1}


@pytest.mark.parametrize("case,exact", [
    ("terabyte", False), ("past_old_cap", True), ("sum_past_int32", False)])
def test_large_values_are_answered_exactly_or_walked(case, exact,
                                                     profile_gates):
    """The kernel answers a question only where int32 holds it exactly
    (no value clipped, no level's sum past int32); else it answers None
    and ``find_topology_assignment`` walks it.  Either way the
    placement is the scalar walk's."""
    profile_gates("bestfit")
    snap, per_pod = _big_snap(case)
    lay = snap.layout
    total = 6 * 1649 if case == "terabyte" else 10
    for request, count in [
            (PodSetTopologyRequest(required="host"), 3),
            (PodSetTopologyRequest(required="host"), total),
            (PodSetTopologyRequest(preferred="rack"), 5),
            (PodSetTopologyRequest(unconstrained=True), 7)]:
        q = snap.question(count, per_pod, request)
        ans, = _answer(snap, [q])
        walk, why = _host_walk(snap, count, per_pod, request)
        if exact:
            assert ans is not None
            got = snap.answered(q, ans)
            assert _leaves(lay, got[0]) == _leaves(lay, walk)
            assert got[1] == why
        else:
            assert ans is None
        kernel_path = snap.find_topology_assignment(count, per_pod, request)
        assert _leaves(lay, kernel_path[0]) == _leaves(lay, walk)
        assert kernel_path[1] == why


# ---------------------------------------------------------------------------
# The Driver: topology-aware queues on the per-cycle engine
# ---------------------------------------------------------------------------

def build_driver(use_device, seed, n_cqs=8, hosts=(2, 3, 4),
                 fair_sharing=False, terabyte=False):
    """TAS-only queues in cohorts of two over a block/rack/host tree of
    small nodes; heads with required-rack, preferred-host and no
    requests (implied), and gangs of two PodSets.  ``terabyte``: nodes
    of 3 TiB less 1 Ki (an odd number of Ki, as reservations leave
    allocatable), past the kernel's int32 at any common divisor."""
    features.set_feature_gates({"TopologyAwareScheduling": True})
    rng = random.Random(seed)
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device,
               fair_sharing=fair_sharing)
    d.apply_topology(Topology(name="dc", levels=list(LEVELS)))
    d.apply_resource_flavor(ResourceFlavor(name="tas", topology_name="dc"))
    blocks, racks, per_rack = hosts
    for b in range(blocks):
        for r in range(racks):
            for h in range(per_rack - (r % 2)):      # uneven racks
                d.cache.tas.add_or_update_node(_node(
                    b, r, h, 4000, 16, 6,
                    memory=3 * _TIB - 1024 if terabyte else None))
    policy = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
    for i in range(n_cqs):
        d.apply_cluster_queue(ClusterQueue(
            name=f"cq-{i}", cohort=f"co-{i // 2}", preemption=policy,
            resource_groups=[ResourceGroup(
                covered_resources=["cpu", "memory"],
                flavors=[FlavorQuotas(name="tas", resources={
                    "cpu": ResourceQuota(nominal=12_000,
                                         borrowing_limit=4_000),
                    # a quota whose scale divides every request
                    # (ops/packing.py _choose_scale): the cycle packs exact
                    "memory": ResourceQuota(nominal=12 << 30)})])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                       cluster_queue=f"cq-{i}"))
    workloads = []
    for k in range(60):
        q = rng.randrange(n_cqs)
        shape = rng.choice(["required", "preferred", "implied", "gang"])
        n = rng.choice([1, 2, 3])
        per_pod = {"cpu": rng.choice([500, 1000, 2000]),
                   "memory": rng.choice([1, 2]) << 30}
        if shape == "gang":
            pod_sets = [
                PodSet(name="launcher", count=1, requests=dict(per_pod),
                       topology_request=PodSetTopologyRequest(
                           required="rack")),
                PodSet(name="workers", count=n, requests=dict(per_pod),
                       topology_request=PodSetTopologyRequest(
                           required="rack"))]
        else:
            req = {"required": PodSetTopologyRequest(required="rack"),
                   "preferred": PodSetTopologyRequest(preferred="host"),
                   "implied": None}[shape]
            pod_sets = [PodSet(name="main", count=n, requests=per_pod,
                               topology_request=req)]
        workloads.append(Workload(
            name=f"wl-{k}", queue_name=f"lq-{q}",
            priority=rng.choice([10, 50, 100]), creation_time=float(k + 1),
            pod_sets=pod_sets))
    return d, clock, workloads


def _placements(d, key):
    adm = d.workload(key).admission
    return tuple((a.name, None if a.topology_assignment is None else tuple(
        (tuple(dom.values), dom.count)
        for dom in a.topology_assignment.domains))
        for a in adm.pod_set_assignments)


def drive_bursts(d, clock, workloads, rounds=6, seed=0):
    rng = random.Random(seed)
    for wl in workloads:
        d.create_workload(wl)
    log = []
    running: list[str] = []

    def on_cycle_start(_k):
        clock.t += 1.0

    for _ in range(rounds):
        done = [k for k in running if rng.random() < 0.3]
        d.finish_workloads(done)
        running = [k for k in running if k not in done]
        cycles = []

        def on_cycle(_k, stats, cycles=cycles):
            cycles.append({
                "admitted": sorted((k, _placements(d, k))
                                   for k in stats.admitted),
                "skipped": sorted(stats.skipped),
                "preempting": sorted(stats.preempting),
                "evicted": sorted(stats.preempted_targets)})
        d.schedule_burst(8, runtime=0, on_cycle_start=on_cycle_start,
                         on_cycle=on_cycle)
        for c in cycles:
            running = [k for k in running if k not in c["evicted"]]
            running += [k for k, _ in c["admitted"]]
        log.append(cycles)
    return log


def _node_usage_ok(d) -> bool:
    """No node holds more than its allocatable."""
    snap = d.cache.tas.snapshot()["tas"]
    return bool((snap.free >= 0).all())


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_driver_tas_queues_match_the_host_path(seed):
    try:
        host, hclock, hwl = build_driver(False, seed)
        hlog = drive_bursts(host, hclock, hwl, seed=seed)
        dev, dclock, dwl = build_driver(True, seed)
        dlog = drive_bursts(dev, dclock, dwl, seed=seed)
    finally:
        features.set_feature_gates({"TopologyAwareScheduling": False})
    assert hlog == dlog
    admitted = [a for r in hlog for c in r for a in c["admitted"]]
    assert admitted and any(p is not None for _, pl in admitted
                            for _, p in pl)
    assert _node_usage_ok(host) and _node_usage_ok(dev)
    stats = dev.scheduler.solver.stats
    assert stats["scalar_heads"] == 0, stats
    assert stats["cq_shape_heads"] == 0, stats
    assert stats["tas_host_queries"] == 0, stats
    assert stats["tas_queries"] >= stats["tas_heads"] > 0, stats
    assert stats["tas_launches"] > 0, stats


@pytest.mark.parametrize("seed", [3, 4])
def test_driver_tas_queues_under_fair_sharing_match_the_host_path(seed):
    """Under fair sharing the stage places the Fit heads, and the
    tournament (on the device scan, or the host's where two placements
    share a node) admits them: the decisions and placements are the host
    path's."""
    try:
        host, hclock, hwl = build_driver(False, seed, fair_sharing=True)
        hlog = drive_bursts(host, hclock, hwl, seed=seed)
        dev, dclock, dwl = build_driver(True, seed, fair_sharing=True)
        dlog = drive_bursts(dev, dclock, dwl, seed=seed)
    finally:
        features.set_feature_gates({"TopologyAwareScheduling": False})
    assert hlog == dlog
    assert any(p is not None for r in hlog for c in r
               for _, pl in c["admitted"] for _, p in pl)
    assert _node_usage_ok(host) and _node_usage_ok(dev)
    stats = dev.scheduler.solver.stats
    assert stats["scalar_heads"] == 0, stats
    assert stats["tas_heads"] > 0 and stats["tas_launches"] > 0, stats


def test_driver_on_terabyte_nodes_matches_the_host_path():
    """Nodes of 3 TiB less 1 Ki: memory over its greatest common
    divisor (1 Ki) does not fit the kernel's int32, so the stage's
    questions are walked on the host, with the host path's decisions
    and placements."""
    try:
        host, hclock, hwl = build_driver(False, 5, terabyte=True)
        hlog = drive_bursts(host, hclock, hwl, rounds=3, seed=5)
        dev, dclock, dwl = build_driver(True, 5, terabyte=True)
        dlog = drive_bursts(dev, dclock, dwl, rounds=3, seed=5)
    finally:
        features.set_feature_gates({"TopologyAwareScheduling": False})
    assert hlog == dlog
    assert any(p is not None for r in hlog for c in r
               for _, pl in c["admitted"] for _, p in pl)
    assert _node_usage_ok(host) and _node_usage_ok(dev)
    stats = dev.scheduler.solver.stats
    assert stats["scalar_heads"] == 0, stats
    assert stats["tas_host_queries"] >= stats["tas_queries"] > 0, stats


def _tight_driver(use_device):
    """One rack of two nodes; the tightest holds one 1-cpu pod."""
    features.set_feature_gates({"TopologyAwareScheduling": True})
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device)
    d.apply_topology(Topology(name="dc", levels=list(LEVELS)))
    d.apply_resource_flavor(ResourceFlavor(name="tas", topology_name="dc"))
    d.cache.tas.add_or_update_node(_node(0, 0, 0, 1000, 8, 10))
    d.cache.tas.add_or_update_node(_node(0, 0, 1, 4000, 8, 10))
    for i in range(2):
        d.apply_cluster_queue(ClusterQueue(
            name=f"cq-{i}", resource_groups=[ResourceGroup(
                covered_resources=["cpu"], flavors=[FlavorQuotas(
                    name="tas", resources={
                        "cpu": ResourceQuota(nominal=8000)})])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                       cluster_queue=f"cq-{i}"))
        d.create_workload(Workload(
            name=f"wl-{i}", queue_name=f"lq-{i}", creation_time=float(i + 1),
            pod_sets=[PodSet(name="main", count=1, requests={"cpu": 1000},
                             topology_request=PodSetTopologyRequest(
                                 required="host"))]))
    return d, clock


@pytest.mark.parametrize("use_device", [False, True],
                         ids=["host", "device"])
def test_two_heads_want_one_tight_host(use_device):
    """Both heads are placed on the tightest host against one snapshot;
    the admit loop admits the first and skips the second ("no longer
    fits"), which the next cycle places on the other host."""
    try:
        d, clock = _tight_driver(use_device)
        clock.t += 1.0
        first = d.schedule_once()
        clock.t += 1.0
        second = d.schedule_once()
    finally:
        features.set_feature_gates({"TopologyAwareScheduling": False})
    assert first.admitted == ["default/wl-0"]
    assert first.skipped == ["default/wl-1"]
    assert _placements(d, "default/wl-0") == (
        ("main", ((("b0", "r0-0", "h0-0-0"), 1),)),)
    assert second.admitted == ["default/wl-1"]
    assert _placements(d, "default/wl-1") == (
        ("main", ((("b0", "r0-0", "h0-0-1"), 1),)),)
    assert _node_usage_ok(d)
    if use_device:
        stats = d.scheduler.solver.stats
        assert stats["tas_refit_skips"] == 1, stats
        assert stats["tas_host_admit_cycles"] == 1, stats
        assert stats["scalar_heads"] == 0, stats
