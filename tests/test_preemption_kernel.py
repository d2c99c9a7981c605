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


def _cohort_then_heads(cqs, lows, heads):
    """One cohort of ``cqs`` queues (quota 4,000 m each, reclaim Any,
    LowerPriority within the queue) that admits ``lows``, a cycle a
    workload so that any split over the queues settles; then ``heads``
    arrive.  Returns (driver, clock) with the heads pending."""
    from tests.test_burst import (add_workloads, build, run_host,
                                  simple_cluster)
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
    d, clock = build(add_workloads(
        simple_cluster(n_cohorts=1, cqs=cqs, nominal=4000, preemption=pre),
        lows))
    run_host(d, clock, len(lows), 0)
    for wl in heads:
        d.create_workload(wl)
    return d, clock


def uneven_cluster():
    """Three full queues holding 8, 4 and 2 low-priority workloads,
    then one high-priority head a queue: three searches of 8, 4 and 2
    candidates (no queue borrows, so none crosses queues)."""
    from tests.test_burst import mk
    return _cohort_then_heads(
        3,
        [mk(f"low-{q}-{i}", f"lq-0-{q}", 4000 // n, t=float(8 * q + i + 1))
         for q, n in enumerate((8, 4, 2)) for i in range(n)],
        [mk(f"high-{q}", f"lq-0-{q}", 3000, prio=100, t=50.0 + q)
         for q in range(3)])


def staged_cluster():
    """Two queues: the second borrows (six workloads of 1,000 m on a
    quota of 4,000), the first runs one and is under its quota.  Its
    head of 4,000 m plans a staged search: first all seven candidates
    without borrowing, then (only if that finds no fit) its own
    queue's one.  The first fits, with three targets."""
    from tests.test_burst import mk
    return _cohort_then_heads(
        2,
        [mk("own", "lq-0-0", 1000, t=1.0)]
        + [mk(f"lent-{i}", "lq-0-1", 1000, t=float(i + 2))
           for i in range(6)],
        [mk("high", "lq-0-0", 4000, prio=100, t=50.0)])


# (cluster, shrunk K ladder) -> the launches, and the Preemptor.stats
# that do not follow from them
LAUNCH_PLANS = {
    "no_head_over": (uneven_cluster, None, dict(
        batch=1, single=0, in_batch=3, slots=14, padded=32 * 16)),
    "one_head_of_three_over": (uneven_cluster, (4,), dict(
        batch=1, single=1, in_batch=2, slots=6, padded=32 * 4)),
    "every_head_over": (uneven_cluster, (1,), dict(
        batch=0, single=3, in_batch=0, slots=0, padded=0)),
    # the retry (one candidate) rides in the batch; the first is alone
    "staged_first_over": (staged_cluster, (4,), dict(
        batch=1, single=1, in_batch=1, slots=1, padded=32 * 4)),
    # the first fits, so its oversized retry is never launched
    "staged_both_over": (staged_cluster, (0,), dict(
        batch=0, single=1, in_batch=0, slots=0, padded=0)),
}


@pytest.mark.parametrize("case", sorted(LAUNCH_PLANS))
def test_spec_over_the_top_rung_is_searched_alone_and_the_rest_batched(
        monkeypatch, case):
    """Size never turns a cycle's batch away: a search with more
    candidates than ``K_LADDER``'s top rung gets a launch of its own
    over the candidates already found, the others share the one batched
    launch, and the targets are those of the unshrunk batched run."""
    from kueue_tpu.ops import preemption_solver
    from kueue_tpu.scheduler.preemption import Preemptor
    from tests.test_burst import run_host

    cluster, k_ladder, want = LAUNCH_PLANS[case]
    finds = []
    find = Preemptor._find_candidates
    monkeypatch.setattr(
        Preemptor, "_find_candidates",
        lambda self, ctx: finds.append(ctx.preemptor.key) or find(self, ctx))

    def cycle(k_ladder):
        d, clock = cluster()
        if k_ladder is not None:
            monkeypatch.setattr(preemption_solver, "K_LADDER", k_ladder)
        del finds[:]
        (stats,) = run_host(d, clock, 1, 0)
        return d.scheduler.preemptor.stats, stats, len(finds)

    batched, b_cycle, b_finds = cycle(None)
    assert batched["search_batch_launches"] == 1
    assert batched["search_single_launches"] == 0
    assert batched["search_alone_over_k"] == 0
    assert len(b_cycle.preempted_targets) == (
        11 if cluster is uneven_cluster else 3)

    stats, s_cycle, s_finds = cycle(k_ladder)
    assert stats["search_batch_launches"] == want["batch"]
    assert stats["search_single_launches"] == want["single"]
    assert stats["search_alone_over_k"] == want["single"]
    assert stats["search_batch_refusals"] == 0
    assert stats["device_searches"] == want["in_batch"] + want["single"]
    assert stats["host_searches"] == 0
    assert stats["search_candidate_slots"] == want["slots"]
    assert stats["search_padded_slots"] == want["padded"]
    # candidates are found once a head, whatever the launch plan
    assert s_finds == b_finds == len(b_cycle.preempting)

    assert s_cycle.preempted_targets == b_cycle.preempted_targets
    assert s_cycle.preempting == b_cycle.preempting


def test_more_specs_than_the_top_rung_go_out_in_launches(monkeypatch):
    """The count of a cycle's specs never sends it to a launch a head:
    over ``S_LADDER``'s top rung they go out in launches of at most that
    many, with the targets of the one-launch run."""
    from kueue_tpu.ops import preemption_solver
    from tests.test_burst import preempting_cluster, run_host

    d, clock = preempting_cluster()
    (whole,) = run_host(d, clock, 1, 0)
    assert d.scheduler.preemptor.stats["search_batch_launches"] == 1

    d, clock = preempting_cluster()
    monkeypatch.setattr(preemption_solver, "S_LADDER", (2,))
    (split,) = run_host(d, clock, 1, 0)
    stats = d.scheduler.preemptor.stats
    assert stats["search_batch_launches"] == 2          # 3 specs: 2 + 1
    assert stats["search_single_launches"] == 0
    assert stats["search_batch_refusals"] == 0
    assert stats["device_searches"] == 3 and stats["host_searches"] == 0
    assert stats["search_padded_slots"] == 2 * (2 * 16)
    assert len(split.preempted_targets) == 9
    assert split.preempted_targets == whole.preempted_targets
    assert split.preempting == whole.preempting


def test_batch_refusal_reasons_sum_to_the_refusals(monkeypatch):
    """Every ``return None`` of the batched search lands in exactly one
    reason: a launch of more specs than the S ladder's top rung (the
    preemptor never asks for one), and a plane that cannot hold a spec.
    A spec searched alone for its size is no refusal: the whole-batch
    count and ``search_alone_over_k`` move apart."""
    from kueue_tpu.ops import preemption_solver
    from tests.test_burst import preempting_cluster, run_host

    d, clock = preempting_cluster()
    monkeypatch.setattr(preemption_solver, "S_LADDER", (2,))
    stats = dict(d.scheduler.preemptor.stats)
    assert preemption_solver.device_minimal_preemptions_batch(
        [[(None, [], True, None)] * 3],
        d.scheduler.solver.classify(d.cache.snapshot(),
                                    d.queues.heads_nonblocking()).packed,
        stats=stats) is None
    assert stats["search_refused_over_s"] == 1

    d2, clock2 = preempting_cluster()
    monkeypatch.undo()
    monkeypatch.setattr(preemption_solver, "_planes_for", lambda packed: None)
    run_host(d2, clock2, 1, 0)
    stats2 = d2.scheduler.preemptor.stats
    assert stats2["search_refused_unpackable"] == 1
    assert stats2["search_single_launches"] == 3
    for s in (stats, stats2):
        assert s["search_batch_refusals"] == 1 == (
            s["search_refused_over_s"] + s["search_refused_unpackable"])
        assert s["search_alone_over_k"] == 0

    # one head over the K rung beside a refused batch: the refusal is
    # the batch's own, and each_head searches every head once
    d3, clock3 = uneven_cluster()
    monkeypatch.setattr(preemption_solver, "_planes_for", lambda packed: None)
    monkeypatch.setattr(preemption_solver, "K_LADDER", (4,))
    run_host(d3, clock3, 1, 0)
    stats3 = d3.scheduler.preemptor.stats
    assert stats3["search_batch_refusals"] == 1
    assert stats3["search_refused_unpackable"] == 1
    assert stats3["search_alone_over_k"] == 0
    assert stats3["search_single_launches"] == 3
    assert stats3["device_searches"] == 3


def empty_retry_cluster():
    """Two queues: the second borrows (six workloads of 1,000 m on a
    quota of 4,000), the first runs nothing.  Its head of 4,000 m plans
    a staged search whose retry, its own queue's candidates, is empty:
    the first spec (the six lent, no borrowing) fits with two targets."""
    from tests.test_burst import mk
    return _cohort_then_heads(
        2,
        [mk(f"lent-{i}", "lq-0-1", 1000, t=float(i + 1)) for i in range(6)],
        [mk("high", "lq-0-0", 4000, prio=100, t=50.0)])


# (cluster, K ladder, S ladder, step floor) -> the launches as (specs,
# longest candidate list) in dispatch order, and the Preemptor.stats
# that do not follow from them; None leaves a module constant alone
SIZE_PLANS = {
    # 8, 4 and 2 candidates, a rung each, and a step costs its rows
    # alone: three launches without one padded slot, the longest first
    "a_launch_a_rung": (uneven_cluster, (2, 4, 8), (1, 2, 4), 0, dict(
        launches=[(1, 8), (1, 4), (1, 2)], padded=14, split=1, empty=0)),
    # the same under the real floor: a launch costs 50 rows a step
    # before its first spec, so one launch of three is cheapest
    "floor_merges_every_rung": (uneven_cluster, (2, 4, 8), (1, 2, 4), None,
                                dict(launches=[(3, 8)], padded=4 * 8,
                                     split=0, empty=0)),
    # a floor of two rows: 2 * 3 + 8 * (2 + 2) = 38 beats 42 (apart),
    # 40 (the two short ones together) and 48 (one launch)
    "floor_merges_neighbours": (uneven_cluster, (2, 4, 8), (1, 2, 4), 2,
                                dict(launches=[(2, 8), (1, 2)],
                                     padded=2 * 8 + 2, split=1, empty=0)),
    "one_rung_is_one_launch": (uneven_cluster, (8, 16), (1, 2, 4), 0, dict(
        launches=[(3, 8)], padded=4 * 8, split=0, empty=0)),
    # the staged head's two specs (7 and 1 candidates) part
    "staged_retry_in_its_own_rung": (staged_cluster, (1, 8), (1, 2), 0, dict(
        launches=[(1, 7), (1, 1)], padded=8 + 1, split=1, empty=0)),
    "empty_retry_is_never_packed": (empty_retry_cluster, None, None, None,
                                    dict(launches=[(1, 6)], padded=32 * 16,
                                         split=0, empty=1)),
}


@pytest.mark.parametrize("case", sorted(SIZE_PLANS))
def test_batched_search_launches_by_size(monkeypatch, case):
    """The launch plan reads the specs' candidate counts and nothing
    else: no candidate, no launch; a launch a group of K rungs, merged
    where the stated cost says so, all dispatched before one is read;
    and the targets are the one-launch plan's and the host search's."""
    from kueue_tpu.ops import preemption_solver
    from tests.test_burst import run_host

    cluster, k_ladder, s_ladder, floor, want = SIZE_PLANS[case]
    packs, events = [], []
    pack = preemption_solver._pack_batch
    decode = preemption_solver._decode_batch

    def packing(specs, packed, stats):
        packs.append((len(specs), max(len(s[1]) for s in specs),
                      min(len(s[1]) for s in specs)))
        events.append("pack")
        return pack(specs, packed, stats)

    def decoding(*args):
        events.append("decode")
        return decode(*args)

    monkeypatch.setattr(preemption_solver, "_pack_batch", packing)
    monkeypatch.setattr(preemption_solver, "_decode_batch", decoding)

    as_they_are = {name: getattr(preemption_solver, name)
                   for name in ("K_LADDER", "S_LADDER", "STEP_FLOOR_ROWS")}

    def cycle(device_search, floor):
        d, clock = cluster()
        d.scheduler.preemptor.device_search = device_search
        for name, value in (("K_LADDER", k_ladder), ("S_LADDER", s_ladder),
                            ("STEP_FLOOR_ROWS", floor)):
            monkeypatch.setattr(preemption_solver, name,
                                as_they_are[name] if value is None else value)
        del packs[:], events[:]
        (stats,) = run_host(d, clock, 1, 0)
        return d.scheduler.preemptor.stats, stats

    host, h_cycle = cycle(False, floor)
    assert host["host_searches"] >= 1 and host["device_searches"] == 0
    assert h_cycle.preempted_targets

    # a floor no launch is worth: every rung merged, one launch
    one, o_cycle = cycle("auto", 10**9)
    assert one["search_batch_launches"] == 1
    assert one["search_split_plans"] == 0

    stats, s_cycle = cycle("auto", floor)
    assert [p[:2] for p in packs] == want["launches"]
    assert min(p[2] for p in packs) >= 1        # no empty spec is packed
    assert events == (["pack"] * len(packs) + ["decode"] * len(packs))
    assert stats["search_batch_launches"] == len(want["launches"])
    assert stats["search_padded_slots"] == want["padded"]
    assert stats["search_candidate_slots"] == one["search_candidate_slots"]
    assert stats["search_split_plans"] == want["split"]
    assert stats["search_empty_specs"] == want["empty"] == (
        one["search_empty_specs"])
    assert stats["device_searches"] == sum(n for n, _ in want["launches"])
    assert stats["search_batch_refusals"] == 0
    assert stats["search_single_launches"] == stats["host_searches"] == 0

    for got in (o_cycle, s_cycle):
        assert got.preempted_targets == h_cycle.preempted_targets
        assert got.preempting == h_cycle.preempting


# candidate counts -> [(K rung, specs)] at the ladders as they are
REAL_LADDER_PLANS = {
    "nothing_to_launch": ([], []),
    # a Zipf cycle: many short lists, some long
    "short_and_long_part": ([50] * 400 + [500] * 100,
                            [(1024, 100), (128, 400)]),
    # three long lists do not take 400 short ones through their scan
    "a_few_long_ones_part": ([500] * 3 + [50] * 400,
                             [(1024, 3), (128, 400)]),
    # 16 * 82 + 128 * 82 apart, 128 * 82 together
    "a_handful_is_one_launch": ([8] * 3 + [100] * 2, [(128, 5)]),
    "all_three_rungs": ([5] * 900 + [60] * 300 + [700] * 40,
                        [(1024, 40), (128, 300), (16, 900)]),
    # over the S ladder's top rung: launches of at most that many
    "more_than_the_top_rung": ([10] * 5000, [(16, 4096), (16, 904)]),
}


@pytest.mark.parametrize("case", sorted(REAL_LADDER_PLANS))
def test_plan_launches_at_the_real_ladders(case):
    from kueue_tpu.ops.preemption_solver import plan_launches
    counts, want = REAL_LADDER_PLANS[case]
    plan = plan_launches(counts)
    assert [(k, len(members)) for k, members in plan] == want
    # every spec is in exactly one launch, under its launch's rung
    assert sorted(i for _, members in plan for i in members) == list(
        range(len(counts)))
    assert all(counts[i] <= k for k, members in plan for i in members)
