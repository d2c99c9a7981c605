"""Device preemption-search parity: the lax.scan minimalPreemptions twin
must pick the same targets as the host greedy+fillback
(reference preemption.go:275-342)."""

import random

import pytest

from kueue_tpu.api.types import (
    BorrowWithinCohort,
    BorrowWithinCohortPolicy,
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
from tests.conftest import FakeClock


def build_preemption_driver(seed, device_search, n_cqs=4, n_low=10):
    """Cohort with borrowing CQs full of low-priority admitted workloads,
    then high-priority arrivals that must preempt/reclaim."""
    rng = random.Random(seed)
    clock = FakeClock()
    d = Driver(clock=clock)
    d.scheduler.preemptor.device_search = device_search
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    for i in range(n_cqs):
        d.apply_cluster_queue(ClusterQueue(
            name=f"cq-{i}", cohort="team",
            preemption=PreemptionPolicy(
                reclaim_within_cohort=ReclaimWithinCohort.ANY,
                within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
                borrow_within_cohort=BorrowWithinCohort(
                    policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
                    max_priority_threshold=50)
                if i % 2 == 0 else BorrowWithinCohort()),
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=4000,
                                         borrowing_limit=8000)})])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                       cluster_queue=f"cq-{i}"))
    # fill with low-priority workloads (some borrow)
    for k in range(n_low):
        q = rng.randrange(n_cqs)
        d.create_workload(Workload(
            name=f"low-{k}", queue_name=f"lq-{q}",
            priority=rng.choice([0, 10, 20, 60]),
            creation_time=float(k + 1),
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": rng.choice([1000, 2000])})]))
    d.run_until_settled()
    # high-priority arrivals needing preemption
    for k in range(n_cqs):
        d.create_workload(Workload(
            name=f"high-{k}", queue_name=f"lq-{k}", priority=100,
            creation_time=100.0 + k,
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": 3000})]))
    clock.t += 10.0
    d.run_until_settled()
    return d


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_device_preemption_search_matches_host(seed):
    results = []
    for device in (False, True):
        d = build_preemption_driver(seed, device)
        admitted = frozenset(d.admitted_keys())
        evicted = frozenset(
            k for k, wl in d.workloads.items()
            if wl.conditions.get("Evicted") is not None)
        results.append((admitted, evicted, d))
    (h_adm, h_ev, _), (d_adm, d_ev, d_dev) = results
    assert h_adm == d_adm
    assert h_ev == d_ev
    assert d_dev.scheduler.preemptor.stats["device_searches"] >= 1, \
        d_dev.scheduler.preemptor.stats


def test_device_search_stats_fallback_for_fair_sharing():
    # fair-sharing preemption stays on host
    clock = FakeClock()
    d = Driver(clock=clock, fair_sharing=True)
    d.scheduler.preemptor.device_search = True
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    d.apply_cluster_queue(ClusterQueue(
        name="cq-a", cohort="team",
        preemption=PreemptionPolicy(
            reclaim_within_cohort=ReclaimWithinCohort.ANY),
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=2000,
                                     borrowing_limit=2000)})])]))
    d.apply_cluster_queue(ClusterQueue(
        name="cq-b", cohort="team",
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=2000,
                                     borrowing_limit=2000)})])]))
    for q in ("a", "b"):
        d.apply_local_queue(LocalQueue(name=f"lq-{q}",
                                       cluster_queue=f"cq-{q}"))
    d.create_workload(Workload(
        name="borrower", queue_name="lq-b", creation_time=1.0,
        pod_sets=[PodSet(name="main", count=1, requests={"cpu": 4000})]))
    d.run_until_settled()
    d.create_workload(Workload(
        name="reclaimer", queue_name="lq-a", creation_time=2.0,
        pod_sets=[PodSet(name="main", count=1, requests={"cpu": 2000})]))
    clock.t += 1.0
    d.run_until_settled()
    # fair-sharing path never reaches the device search
    assert d.scheduler.preemptor.stats["device_searches"] == 0
    assert "default/reclaimer" in d.admitted_keys()


def test_head_over_the_top_rung_is_counted_and_searched_a_head(monkeypatch):
    """One head with more candidates than ``K_LADDER``'s top rung turns
    the whole cycle's batch away: the refusal is counted under its one
    reason, every head that searched gets a launch of its own, and the
    targets are those of the batched route."""
    from kueue_tpu.ops import preemption_solver
    from tests.test_burst import preempting_cluster, run_host

    def cycle(k_ladder=None):
        d, clock = preempting_cluster()     # 3 heads, 4 candidates each
        if k_ladder is not None:
            monkeypatch.setattr(preemption_solver, "K_LADDER", k_ladder)
        (stats,) = run_host(d, clock, 1, 0)
        return d.scheduler.preemptor.stats, stats

    batched, b_cycle = cycle()
    assert batched["search_batch_launches"] == 1
    assert batched["search_batch_refusals"] == 0
    assert batched["search_single_launches"] == 0
    assert batched["search_candidate_slots"] == 12
    assert batched["search_padded_slots"] == 32 * 16      # S x K rungs
    assert batched["device_searches"] == 3

    single, s_cycle = cycle(k_ladder=(2,))
    assert single["search_batch_refusals"] == 1
    assert single["search_refused_over_k"] == 1
    assert single["search_refused_over_s"] == 0
    assert single["search_refused_unpackable"] == 0
    assert single["search_batch_launches"] == 0
    assert single["search_single_launches"] == 3          # heads that searched
    assert single["device_searches"] == 3 and single["host_searches"] == 0
    assert single["search_padded_slots"] == 0

    assert s_cycle.preempted_targets == b_cycle.preempted_targets
    assert len(b_cycle.preempted_targets) == 9
    assert s_cycle.preempting == b_cycle.preempting


def test_batch_refusal_reasons_sum_to_the_refusals(monkeypatch):
    """Every ``return None`` of the batched search lands in exactly one
    reason: too many specs, and a plane that cannot hold a spec."""
    from kueue_tpu.ops import preemption_solver
    from tests.test_burst import preempting_cluster, run_host

    d, clock = preempting_cluster()
    monkeypatch.setattr(preemption_solver, "S_LADDER", (2,))
    run_host(d, clock, 1, 0)
    stats = d.scheduler.preemptor.stats
    assert stats["search_refused_over_s"] == 1

    d2, clock2 = preempting_cluster()
    monkeypatch.undo()
    monkeypatch.setattr(preemption_solver, "_planes_for", lambda packed: None)
    run_host(d2, clock2, 1, 0)
    stats2 = d2.scheduler.preemptor.stats
    assert stats2["search_refused_unpackable"] == 1
    for s in (stats, stats2):
        assert s["search_batch_refusals"] == 1 == (
            s["search_refused_over_k"] + s["search_refused_over_s"]
            + s["search_refused_unpackable"])
        assert s["search_single_launches"] == 3
