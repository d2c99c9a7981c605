"""Device TAS kernel parity vs the host TASFlavorSnapshot (reference
tas_flavor_snapshot.go semantics), plus the topology ungater.  The
kernel is the batched one (``place_queries`` through
``answer_queries``), asked one question at a time; the host is the
scalar tree walk (the ``TASDeviceKernel`` gate off)."""

import random

import jax
import numpy as np
import pytest

from kueue_tpu import features
from kueue_tpu.api.types import (
    PodSetTopologyRequest,
    TopologyAssignment,
    TopologyDomainAssignment,
)
from kueue_tpu.cache.tas_cache import NodeInfo
from kueue_tpu.cache.tas_snapshot import TASFlavorSnapshot
from kueue_tpu.controller.tas_ungater import (
    TAS_SCHEDULING_GATE,
    assign_pods_to_domains,
    pod_rank,
)
from kueue_tpu.ops.tas_kernel import answer_queries, level_states

LEVELS = ["block", "rack", "host"]


def random_snapshot(rng, n_blocks=3, racks_per_block=3, hosts_per_rack=4):
    nodes = []
    for b in range(n_blocks):
        for r in range(rng.randint(1, racks_per_block)):
            for h in range(rng.randint(1, hosts_per_rack)):
                nodes.append(NodeInfo(
                    name=f"n-{b}-{r}-{h}",
                    labels={"block": f"b{b}", "rack": f"r{b}-{r}",
                            "host": f"h{b}-{r}-{h}"},
                    capacity={"cpu": rng.choice([4000, 8000, 16000]),
                              "tpu": rng.choice([0, 4, 8])}))
    return TASFlavorSnapshot.build("tas-flavor", LEVELS, nodes, {})


def host_walk(snap, count, per_pod, request):
    features.set_feature_gates({"TASDeviceKernel": False})
    try:
        return snap.find_topology_assignment(count, per_pod, request)
    finally:
        features.set_feature_gates({"TASDeviceKernel": True})


def kernel(snap, count, per_pod, request):
    """One question of the batched kernel: (ok, {leaf id: pods})."""
    q = snap.question(count, per_pod, request)
    ans, = answer_queries(snap.layout, snap.free, [q])
    return ans.ok, {snap.layout.leaf_ids[i]: c for i, c in ans.placement}


@pytest.mark.parametrize("seed", [11, 22, 33])
def test_fill_counts_matches_host(seed):
    """Phase 1 (``level_states``) against the walk's fillInCounts."""
    rng = random.Random(seed)
    snap = random_snapshot(rng)
    lay = snap.layout
    per_pod_map = {"cpu": 2000, "tpu": 1}
    per_pod = np.array([per_pod_map.get(r, 0)
                        for r in lay.resource_names], dtype=np.int32)
    starts = tuple(np.asarray(c, dtype=np.int32) for c in lay.child_start)
    states = jax.jit(level_states)(
        np.clip(snap.free, 0, None).astype(np.int32), per_pod, starts)
    snap._fill_in_counts(per_pod_map)
    for lvl in range(len(LEVELS)):
        host = {d.id: d.state for d in snap.domains_per_level[lvl]}
        dev = np.asarray(states[lvl])
        for i, did in enumerate(lay.domain_ids[lvl]):
            assert dev[i] == host[did], (lvl, did)


@pytest.mark.parametrize("seed", [7, 8, 9, 10])
@pytest.mark.parametrize("level_name", ["block", "rack", "host"])
def test_best_fit_descend_matches_host(seed, level_name):
    """A required level: the best domain there and the descent."""
    rng = random.Random(seed)
    snap = random_snapshot(rng)
    per_pod_map = {"cpu": 2000}
    count = rng.choice([1, 2, 5, 9])
    request = PodSetTopologyRequest(required=level_name)
    ok, dev_counts = kernel(snap, count, per_pod_map, request)
    host_asg, _ = host_walk(snap, count, per_pod_map, request)
    if host_asg is None:
        assert not ok
        return
    assert ok
    assert dev_counts == {tuple(d.values): d.count for d in host_asg.domains}


@pytest.mark.parametrize("seed", [41, 42])
def test_split_across_roots_matches_host(seed):
    """Unconstrained: the split over the roots and the descent."""
    rng = random.Random(seed)
    snap = random_snapshot(rng)
    per_pod_map = {"cpu": 4000}
    count = 11
    request = PodSetTopologyRequest(unconstrained=True)
    ok, dev_counts = kernel(snap, count, per_pod_map, request)
    host_asg, _ = host_walk(snap, count, per_pod_map, request)
    if host_asg is None:
        assert not ok
        return
    assert ok
    assert dev_counts == {tuple(d.values): d.count for d in host_asg.domains}


# ---------------------------------------------------------------------------
# Ungater
# ---------------------------------------------------------------------------

class FakePod:
    def __init__(self, name, pod_set="main"):
        self.name = name
        self.pod_set = pod_set
        self.annotations = {}
        self.node_selector = {}
        self.scheduling_gates = [TAS_SCHEDULING_GATE]
        self.phase = "Pending"


def test_ungater_rank_ordered_assignment():
    ta = TopologyAssignment(
        levels=["block", "rack"],
        domains=[TopologyDomainAssignment(values=["b0", "r0"], count=2),
                 TopologyDomainAssignment(values=["b0", "r1"], count=1)])
    pods = [FakePod("w-2"), FakePod("w-0"), FakePod("w-1")]
    decisions = assign_pods_to_domains(ta, pods)
    assert [(d.pod_name, d.rank) for d in decisions] == [
        ("w-0", 0), ("w-1", 1), ("w-2", 2)]
    # ranks 0,1 → first domain; rank 2 → second
    assert decisions[0].node_selector == {"block": "b0", "rack": "r0"}
    assert decisions[1].node_selector == {"block": "b0", "rack": "r0"}
    assert decisions[2].node_selector == {"block": "b0", "rack": "r1"}


def test_ungater_excess_pods_stay_gated():
    ta = TopologyAssignment(
        levels=["host"],
        domains=[TopologyDomainAssignment(values=["h0"], count=1)])
    pods = [FakePod("p-0"), FakePod("p-1")]
    decisions = assign_pods_to_domains(ta, pods)
    assert len(decisions) == 1
    assert decisions[0].pod_name == "p-0"
