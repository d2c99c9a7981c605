"""Batched fair-sharing tournament parity (VERDICT r2 item #4).

The TournamentDRS-backed iterator (one vectorized DRS pass per round,
incremental usage mirroring) must make exactly the decisions of the
scalar per-entry computeDRS oracle — across nested cohorts, weights,
preemption, and multi-cycle drains — and fair-sharing cycles must use
the device solver for nominate (classify mode)."""

import random

import pytest

from kueue_tpu.api.types import (
    ClusterQueue,
    Cohort,
    FairSharing,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    ReclaimWithinCohort,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.controller.driver import Driver
from tests.conftest import FakeClock


def build_fs_driver(seed, *, batched, use_device=False, n_cohorts=2,
                    cqs_per_cohort=3, n_wl=60, nested=False,
                    lending_and_memory=False):
    rng = random.Random(seed)
    clock = FakeClock()
    d = Driver(clock=clock, fair_sharing=True,
               use_device_solver=use_device)
    d.scheduler.fs_batched = batched
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    pre = PreemptionPolicy(reclaim_within_cohort=ReclaimWithinCohort.ANY)
    if nested:
        for c in range(n_cohorts):
            d.apply_cohort(Cohort(name=f"cohort-{c}", parent_name="org"))
    weights = [500, 1000, 2000, 1000]
    for c in range(n_cohorts):
        for q in range(cqs_per_cohort):
            name = f"cq-{c}-{q}"
            resources = {"cpu": ResourceQuota(
                nominal=4000, borrowing_limit=8000,
                # lending limits make guaranteed_quota nonzero — the
                # carry-attenuation branch of note_add/drs_for
                lending_limit=2000 if lending_and_memory and q % 2 else None)}
            covered = ["cpu"]
            if lending_and_memory:
                covered.append("memory")
                resources["memory"] = ResourceQuota(nominal=8000,
                                                    borrowing_limit=8000)
            d.apply_cluster_queue(ClusterQueue(
                name=name, cohort=f"cohort-{c}", preemption=pre,
                fair_sharing=FairSharing(
                    weight=weights[(c * cqs_per_cohort + q) % len(weights)]),
                resource_groups=[ResourceGroup(
                    covered_resources=covered,
                    flavors=[FlavorQuotas(name="default",
                                          resources=resources)])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{c}-{q}",
                                           cluster_queue=name))
    workloads = []
    for i in range(n_wl):
        c = rng.randrange(n_cohorts)
        q = rng.randrange(cqs_per_cohort)
        reqs = {"cpu": rng.choice([1000, 2000, 4000])}
        if lending_and_memory:
            reqs["memory"] = rng.choice([1000, 4000, 8000])
        workloads.append(Workload(
            name=f"wl-{i}", queue_name=f"lq-{c}-{q}",
            priority=rng.choice([10, 10, 50, 100]),
            creation_time=float(i + 1),
            pod_sets=[PodSet(name="main", count=1, requests=reqs)]))
    return d, clock, workloads


def drive(d, clock, workloads, n_cycles=40, runtime=2):
    for wl in workloads:
        d.create_workload(wl)
    log = []
    running = []
    for cycle in range(n_cycles):
        clock.t += 1.0
        stats = d.schedule_once()
        log.append({
            "admitted": list(stats.admitted),
            "skipped": sorted(stats.skipped),
            "inadmissible": sorted(stats.inadmissible),
            "preempting": sorted(stats.preempting),
            "targets": sorted(stats.preempted_targets),
        })
        for key in stats.admitted:
            running.append((cycle + runtime, key))
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


@pytest.mark.parametrize("seed", [31, 32, 33])
@pytest.mark.parametrize("nested", [False, True])
def test_batched_tournament_matches_scalar(seed, nested):
    ref, rclock, rwl = build_fs_driver(seed, batched=False, nested=nested)
    bat, bclock, bwl = build_fs_driver(seed, batched=True, nested=nested)
    rlog = drive(ref, rclock, rwl)
    blog = drive(bat, bclock, bwl)
    for cyc, (r, b) in enumerate(zip(rlog, blog)):
        assert r == b, f"seed {seed} cycle {cyc}:\nscalar={r}\nbatched={b}"
    assert any(c["admitted"] for c in rlog)


@pytest.mark.parametrize("seed", [51, 52, 53])
def test_batched_tournament_lending_limits_and_two_resources(seed):
    """Lending limits (nonzero guaranteed quota → carry attenuation in
    the chain-add) and a second resource (per-resource dominant
    selection) must stay bit-identical to the scalar oracle."""
    ref, rclock, rwl = build_fs_driver(seed, batched=False,
                                       lending_and_memory=True)
    bat, bclock, bwl = build_fs_driver(seed, batched=True,
                                       lending_and_memory=True)
    rlog = drive(ref, rclock, rwl)
    blog = drive(bat, bclock, bwl)
    for cyc, (r, b) in enumerate(zip(rlog, blog)):
        assert r == b, f"seed {seed} cycle {cyc}:\nscalar={r}\nbatched={b}"
    assert any(c["admitted"] for c in rlog)


@pytest.mark.parametrize("seed", [41, 42])
def test_fair_sharing_cycles_use_device_nominate(seed):
    host, hclock, hwl = build_fs_driver(seed, batched=True, use_device=False)
    dev, dclock, dwl = build_fs_driver(seed, batched=True, use_device=True)
    hlog = drive(host, hclock, hwl)
    dlog = drive(dev, dclock, dwl)
    for cyc, (h, dv) in enumerate(zip(hlog, dlog)):
        assert h == dv, (f"seed {seed} cycle {cyc}:\nhost={h}\ndevice={dv}\n"
                         f"stats={dev.scheduler.solver.stats}")
    stats = dev.scheduler.solver.stats
    # FS cycles route through device classify (nominate), host tournament
    assert stats["classify_cycles"] >= 1, stats
    assert stats["host_cycles"] == 0, stats


def test_zero_weight_cq_always_loses():
    """weight=0 → MAX_DRS: the zero-weight CQ's entry loses the
    tournament whenever any sibling has one (fair_sharing.go:55)."""
    clock = FakeClock()
    d = Driver(clock=clock, fair_sharing=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    d.apply_cluster_queue(ClusterQueue(
        name="cq-z", cohort="team", fair_sharing=FairSharing(weight=0),
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=0, borrowing_limit=4000)})])]))
    d.apply_cluster_queue(ClusterQueue(
        name="cq-w", cohort="team", fair_sharing=FairSharing(weight=1000),
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=4000)})])]))
    d.apply_local_queue(LocalQueue(name="lq-z", cluster_queue="cq-z"))
    d.apply_local_queue(LocalQueue(name="lq-w", cluster_queue="cq-w"))
    # both want the cohort's last 4 cpu; zero-weight must lose
    d.create_workload(Workload(
        name="z", queue_name="lq-z", creation_time=1.0,
        pod_sets=[PodSet(name="m", count=1, requests={"cpu": 4000})]))
    d.create_workload(Workload(
        name="w", queue_name="lq-w", creation_time=2.0,
        pod_sets=[PodSet(name="m", count=1, requests={"cpu": 4000})]))
    stats = d.schedule_once()
    assert "default/w" in stats.admitted
    assert "default/z" not in stats.admitted
