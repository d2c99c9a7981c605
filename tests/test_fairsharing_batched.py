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
                    lending_and_memory=False, departments=0):
    """``n_cohorts`` team cohorts of ``cqs_per_cohort`` queues: flat roots,
    under one ``org`` root (``nested``), or under ``departments`` root
    cohorts, team c in ``dept-{c % departments}``, every other team of
    weight 2 — several trees of three levels."""
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
    for c in range(n_cohorts if departments else 0):
        d.apply_cohort(Cohort(
            name=f"cohort-{c}", parent_name=f"dept-{c % departments}",
            fair_sharing=FairSharing(weight=2000 if c % 2 else 1000)))
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


DEPARTMENTS = dict(departments=3, n_cohorts=6, cqs_per_cohort=2, n_wl=240)


@pytest.mark.parametrize("seed", [61, 62, 63])
@pytest.mark.parametrize("lending_and_memory", [False, True])
def test_batched_tournament_matches_scalar_over_departments(
        seed, lending_and_memory):
    """Three department trees of two weighted teams each: a round reads
    only the picked tree's entries, on either path, and every cycle's
    decisions agree."""
    ref, rclock, rwl = build_fs_driver(
        seed, batched=False, lending_and_memory=lending_and_memory,
        **DEPARTMENTS)
    bat, bclock, bwl = build_fs_driver(
        seed, batched=True, lending_and_memory=lending_and_memory,
        **DEPARTMENTS)
    rlog = drive(ref, rclock, rwl)
    blog = drive(bat, bclock, bwl)
    for cyc, (r, b) in enumerate(zip(rlog, blog)):
        assert r == b, f"seed {seed} cycle {cyc}:\nscalar={r}\nbatched={b}"
    assert any(c["admitted"] for c in rlog)


def watch_rounds(sched, rounds):
    """Wrap ``sched``'s fair-sharing iterator; for each tournament round
    it runs, append (remaining heads in the first head's tree, remaining
    heads of every tree, trees with a remaining head), read off the
    entries themselves before the round."""
    real = sched._fair_sharing_iterator

    def wrapped(entries, snapshot):
        left = {e.info.cluster_queue: e for e in entries
                if e.cq_snapshot is not None}
        inner = real(entries, snapshot)
        while True:
            pending = None
            first = next(iter(left.values()), None)
            if first is not None and first.cq_snapshot.parent is not None:
                roots = [e.cq_snapshot.parent.root() for e in left.values()
                         if e.cq_snapshot.parent is not None]
                root = first.cq_snapshot.parent.root()
                pending = (sum(r is root for r in roots), len(roots),
                           len({id(r) for r in roots}))
            try:
                e = next(inner)
            except StopIteration:
                return
            if e.cq_snapshot is not None:
                left.pop(e.info.cluster_queue, None)
                if pending is not None:
                    rounds.append(pending)
            yield e
    sched._fair_sharing_iterator = wrapped


@pytest.mark.parametrize("batched", [True, False])
@pytest.mark.parametrize("seed", [71, 72])
def test_drs_entries_count_the_picked_tree(seed, batched):
    """``fs_drs_entries`` grows, a cycle, by the remaining heads of each
    round's picked tree, and by less than every remaining head wherever
    a round had two trees or more to pick from."""
    d, clock, wls = build_fs_driver(seed, batched=batched, use_device=True,
                                    **DEPARTMENTS)
    for wl in wls:
        d.create_workload(wl)
    stats = d.scheduler.solver.stats
    rounds = []
    watch_rounds(d.scheduler, rounds)
    several = 0
    for _ in range(12):
        clock.t += 1.0
        rounds.clear()
        before = stats["fs_drs_entries"]
        d.schedule_once()
        counted = stats["fs_drs_entries"] - before
        assert counted == sum(r[0] for r in rounds)
        if any(r[2] >= 2 for r in rounds):
            several += 1
            assert counted < sum(r[1] for r in rounds)
    assert several >= 1
    assert stats["fs_host_cycles"] >= 1


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
