"""End-to-end TAS device-kernel parity (VERDICT r2 item #9).

With the TASDeviceKernel gate on, find_topology_assignment routes
through ops/tas_kernel; full scheduling runs (driver + flavorassigner +
admit cycles, TAS usage accounting across admissions) must produce
decisions AND topology assignments identical to the scalar tree walk."""

import random

import pytest

from kueue_tpu import features
from kueue_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PodSetTopologyRequest,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Topology,
    Workload,
)
from kueue_tpu.cache.tas_cache import NodeInfo
from kueue_tpu.controller.driver import Driver
from tests.conftest import FakeClock


@pytest.fixture
def tas_kernel_gate():
    features.set_feature_gates({"TopologyAwareScheduling": True,
                                "TASDeviceKernel": True})
    yield
    # restore the shipped defaults (TASDeviceKernel defaults ON; leaving
    # a False override would disable the kernel for later tests)
    features.set_feature_gates({"TopologyAwareScheduling": False,
                                "TASDeviceKernel": True})


def build_tas_driver(seed, n_blocks=2, racks=2, hosts=3):
    rng = random.Random(seed)
    clock = FakeClock()
    d = Driver(clock=clock)
    d.apply_topology(Topology(name="dc", levels=["block", "rack", "host"]))
    d.apply_resource_flavor(ResourceFlavor(name="tas-flavor",
                                           topology_name="dc"))
    for b in range(n_blocks):
        for r in range(racks):
            for h in range(hosts):
                d.cache.tas.add_or_update_node(NodeInfo(
                    name=f"n-{b}-{r}-{h}",
                    labels={"block": f"b{b}", "rack": f"r{b}-{r}",
                            "host": f"h{b}-{r}-{h}"},
                    # nodes always expose pods capacity (the implicit
                    # "pods" resource participates in TAS fitting)
                    capacity={"cpu": rng.choice([4000, 8000]),
                              "pods": 16}))
    d.apply_cluster_queue(ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="tas-flavor", resources={
                "cpu": ResourceQuota(nominal=200_000)})])]))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    workloads = []
    for i in range(14):
        req = rng.choice([
            PodSetTopologyRequest(required="rack"),
            PodSetTopologyRequest(required="block"),
            PodSetTopologyRequest(preferred="rack"),
            PodSetTopologyRequest(unconstrained=True),
        ])
        workloads.append(Workload(
            name=f"wl-{i}", queue_name="lq",
            priority=rng.choice([10, 50]), creation_time=float(i + 1),
            pod_sets=[PodSet(name="main", count=rng.choice([1, 2, 4, 6]),
                             requests={"cpu": 2000},
                             topology_request=req)]))
    return d, clock, workloads


def drive(d, clock, workloads, n_cycles=30, runtime=3):
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
            tas = tuple(
                (a.name, a.count,
                 tuple((tuple(dom.values), dom.count)
                       for dom in a.topology_assignment.domains)
                 if a.topology_assignment else None)
                for a in wl.admission.pod_set_assignments)
            admissions.append((key, tas))
            running.append((cycle + runtime, key))
        log.append({"admitted": admissions,
                    "skipped": sorted(stats.skipped),
                    "inadmissible": sorted(stats.inadmissible)})
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


@pytest.mark.parametrize("seed", [61, 62, 63])
def test_tas_device_kernel_end_to_end_parity(seed, tas_kernel_gate):
    features.set_feature_gates({"TASDeviceKernel": False})
    host, hclock, hwl = build_tas_driver(seed)
    hlog = drive(host, hclock, hwl)

    features.set_feature_gates({"TASDeviceKernel": True})
    dev, dclock, dwl = build_tas_driver(seed)
    dlog = drive(dev, dclock, dwl)

    for cyc, (h, dv) in enumerate(zip(hlog, dlog)):
        assert h == dv, f"seed {seed} cycle {cyc}:\nhost={h}\ndevice={dv}"
    admitted = [a for c in hlog for a in c["admitted"]]
    assert admitted, "scenario admitted nothing"
    # the scenario must actually produce topology assignments
    assert any(tas for _, pa in admitted for _, _, tas in pa), admitted


def _profile_snap():
    from kueue_tpu.cache.tas_snapshot import TASFlavorSnapshot
    nodes = []
    caps = [(0, 0, 7000), (0, 1, 3000), (1, 0, 5000), (1, 1, 5000),
            (2, 0, 2000), (2, 1, 9000)]
    for r, h, cpu in caps:
        nodes.append(NodeInfo(
            name=f"n-{r}-{h}",
            labels={"rack": f"r{r}", "host": f"h{r}-{h}"},
            capacity={"cpu": cpu}))
    return TASFlavorSnapshot.build("f", ["rack", "host"], nodes, {})


def test_tas_device_kernel_all_profiles_match_scalar(tas_kernel_gate):
    """The device kernel implements all three TAS profiles
    (tas_flavor_snapshot.go:551-568); assignments bit-match the scalar
    tree walk under every gate combination and request shape."""
    requests = [
        PodSetTopologyRequest(required="rack"),
        PodSetTopologyRequest(required="host"),
        PodSetTopologyRequest(preferred="host"),
        PodSetTopologyRequest(preferred="rack"),
        PodSetTopologyRequest(unconstrained=True),
    ]
    profiles = [
        {},
        {"TASProfileMostFreeCapacity": True},
        {"TASProfileLeastFreeCapacity": True},
        {"TASProfileMixed": True},
    ]
    for gates in profiles:
        features.set_feature_gates({**{g: False for g in (
            "TASProfileMostFreeCapacity", "TASProfileLeastFreeCapacity",
            "TASProfileMixed")}, **gates})
        try:
            for request in requests:
                for count in (1, 3, 5, 9, 14, 31):
                    snap_d = _profile_snap()
                    snap_h = _profile_snap()
                    assert snap_d._device_kernel_eligible(request)
                    a_dev, m_dev = snap_d.find_topology_assignment(
                        count, {"cpu": 1000}, request)
                    features.set_feature_gates({"TASDeviceKernel": False})
                    try:
                        a_host, m_host = snap_h.find_topology_assignment(
                            count, {"cpu": 1000}, request)
                    finally:
                        features.set_feature_gates(
                            {"TASDeviceKernel": True})
                    if a_host is None:
                        assert a_dev is None, (gates, request, count)
                        continue
                    assert a_dev is not None, (gates, request, count,
                                               m_dev)
                    assert [(d.values, d.count) for d in a_dev.domains] \
                        == [(d.values, d.count) for d in a_host.domains], \
                        (gates, request, count)
        finally:
            features.set_feature_gates({g: False for g in (
                "TASProfileMostFreeCapacity",
                "TASProfileLeastFreeCapacity", "TASProfileMixed")})


def test_tas_thousand_heads_full_cycle(tas_kernel_gate):
    """Verdict r4 item 4 'done' criterion: a TAS scenario at >=1k heads
    where the cycle is FULL-mode on the device solver and every TAS
    assignment bit-matches the host tree walk end-to-end."""
    N_CQS = 1000

    def build(use_device):
        clock = FakeClock()
        d = Driver(clock=clock, use_device_solver=use_device)
        d.apply_topology(Topology(name="dc", levels=["rack", "host"]))
        d.apply_resource_flavor(ResourceFlavor(name="tas-flavor",
                                               topology_name="dc"))
        for r in range(4):
            for h in range(4):
                d.cache.tas.add_or_update_node(NodeInfo(
                    name=f"n-{r}-{h}",
                    labels={"rack": f"r{r}", "host": f"h{r}-{h}"},
                    capacity={"cpu": 4_000_000, "pods": 100_000}))
        rng = random.Random(7)
        wls = []
        for i in range(N_CQS):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-{i}", resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="tas-flavor", resources={
                        "cpu": ResourceQuota(nominal=100_000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                           cluster_queue=f"cq-{i}"))
            req = rng.choice([
                PodSetTopologyRequest(required="rack"),
                PodSetTopologyRequest(preferred="host"),
                PodSetTopologyRequest(unconstrained=True),
            ])
            wls.append(Workload(
                name=f"wl-{i}", queue_name=f"lq-{i}",
                priority=rng.choice([10, 50]),
                creation_time=float(i + 1),
                pod_sets=[PodSet(name="main",
                                 count=rng.choice([1, 2, 3]),
                                 requests={"cpu": 1000},
                                 topology_request=req)]))
        for wl in wls:
            d.create_workload(wl)
        return d, clock

    def assignments(d):
        out = {}
        for key, wl in d.workloads.items():
            if wl.admission is None:
                continue
            out[key] = tuple(
                (a.name, a.count,
                 tuple((tuple(dom.values), dom.count)
                       for dom in a.topology_assignment.domains)
                 if a.topology_assignment else None)
                for a in wl.admission.pod_set_assignments)
        return out

    dd, cd = build(True)
    dh, ch = build(False)
    cd.t += 1.0
    ch.t += 1.0
    sd = dd.schedule_once()
    sh = dh.schedule_once()
    assert len(sd.admitted) >= 1000
    assert sd.admitted == sh.admitted
    assert assignments(dd) == assignments(dh)
    stats = dd.scheduler.solver.stats
    assert stats["full_cycles"] == 1, stats       # FULL-mode cycle
    # every TAS head on the vector path, placed by the batched stage
    assert stats["tas_heads"] >= 1000, stats
    assert stats["scalar_heads"] == 0, stats
    assert stats["tas_host_queries"] == 0, stats
    assert stats["tas_launches"] == 1, stats
