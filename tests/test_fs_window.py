"""Fair-sharing cycles inside the fused window.

Under fair sharing (upstream ``fair_sharing_iterator.go``) a cycle's
heads are taken one round at a time: the first remaining head names its
cohort tree, the tournament over that tree picks the survivor at every
node (the DRS of its child-of-this-node ancestor with the head's usage
added, then priority descending, timestamp ascending, then child
order), a NoFit pick is dropped and a fit one is admitted where it
still fits its chain.  ``ops/fs_scan.py`` holds the one tournament
(``tournament``): ``fs_admit_scan`` takes the first head's tree a round,
the fused window (``ops/burst.py``, ``fair=True``) every tree's pick a
round.  Trees share no usage, so both yield each tree's heads in the
same order.

Each case runs the host scalar scheduler, the per-cycle device engine
and ``schedule_burst`` on identically built clusters, which have to
agree cycle by cycle on what is admitted, skipped and evicted; the
window's cycles have to be its own (applied, not handed back), except
where a preempt-capable head hands the cycle to the per-cycle engine.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from kueue_tpu.api import types as T
from kueue_tpu.api.types import WL_QUOTA_RESERVED
from kueue_tpu.controller.driver import Driver
from tests.conftest import FakeClock

CYCLES = 10
RUNTIME = 2


def _cq(d, name, cohort, weight, nominal, preempt):
    pol = (T.PreemptionPolicy(
        reclaim_within_cohort=T.ReclaimWithinCohort.ANY,
        within_cluster_queue=T.WithinClusterQueue.LOWER_PRIORITY)
        if preempt else T.PreemptionPolicy())
    d.apply_cluster_queue(T.ClusterQueue(
        name=name, cohort=cohort, preemption=pol,
        fair_sharing=(None if weight is None
                      else T.FairSharing(weight=weight)),
        resource_groups=[T.ResourceGroup(
            covered_resources=["cpu", "memory"],
            flavors=[T.FlavorQuotas(name="default", resources={
                "cpu": T.ResourceQuota(nominal=nominal[0]),
                "memory": T.ResourceQuota(nominal=nominal[1])})])]))
    d.apply_local_queue(T.LocalQueue(name="lq-" + name, cluster_queue=name))


def random_tree(seed, depth, preempt=False, parentless=True, n_wl=70):
    """Departments of teams of queues (depth 3) or departments of
    queues (depth 2), weights drawn from 0, 1 and 2, priorities and
    creation times drawn from few values so that they tie.  Without
    ``preempt`` every request is above every nominal quota, so that no
    head is preempt-capable: a head fits in what its tree lends, or
    does not fit."""
    def build(d):
        rng = random.Random(seed)
        d.apply_resource_flavor(T.ResourceFlavor(name="default"))
        names = []
        with d.bulk_apply():
            for dep in range(2):
                root = f"dep-{dep}"
                d.apply_cohort(T.Cohort(name=root))
                for t in range(2):
                    team = root
                    if depth == 3:
                        team = f"team-{dep}-{t}"
                        d.apply_cohort(T.Cohort(
                            name=team, parent_name=root,
                            fair_sharing=T.FairSharing(
                                weight=rng.choice([0.0, 1.0, 2.0]))))
                    for q in range(3):
                        name = f"cq-{dep}-{t}-{q}"
                        _cq(d, name, team, rng.choice([0.0, 1.0, 2.0]),
                            (rng.choice([0, 500, 1000]),
                             rng.choice([0, 4, 8])), preempt)
                        names.append(name)
            if parentless:
                _cq(d, "solo", None, None, (100000, 800), preempt)
                names.append("solo")
        for i in range(n_wl):
            q = rng.choice(names)
            d.create_workload(T.Workload(
                name=f"w{i}", queue_name="lq-" + q,
                priority=rng.choice([0, 10]),
                creation_time=float(rng.choice(range(6))),
                pod_sets=[T.PodSet(name="main", count=1, requests={
                    "cpu": rng.choice([1500, 2000, 2500]),
                    "memory": rng.choice([9, 12])})]))
    return build


def run(engine, build, cycles=CYCLES, runtime=RUNTIME):
    """[(admitted, skipped, evicted)] a cycle, and the driver."""
    clock = FakeClock()
    d = Driver(clock=clock, fair_sharing=True,
               use_device_solver=engine != "host")
    build(d)
    out = []

    def record(stats):
        out.append((sorted(stats.admitted), sorted(stats.skipped),
                    sorted(stats.preempted_targets)))

    def tick(_k=None):
        clock.t += 1.0

    if engine == "burst":
        d.schedule_burst(cycles, runtime=runtime, on_cycle_start=tick,
                         on_cycle=lambda _k, stats: record(stats))
    else:
        done = []
        admitted_at: list = []

        def reserved(key):
            c = d.workloads[key].conditions.get(WL_QUOTA_RESERVED)
            return None if c is None else c.last_transition_time

        for c in range(cycles):
            tick()
            stats = d.schedule_once()
            record(stats)
            done.append(stats)
            admitted_at.append({k: reserved(k) for k in stats.admitted})
            # the burst's own finishes, ``runtime`` cycles after the
            # admission that began the run: one preempted and admitted
            # again in between runs afresh (Driver.schedule_burst)
            if runtime > 0 and c - runtime >= 0:
                began = admitted_at[c - runtime]
                due = [k for k in done[c - runtime].admitted
                       if d.workloads[k].has_quota_reservation
                       and reserved(k) == began[k]]
                if due:
                    d.finish_workloads(due)
    return out, d


def assert_engines_agree(build, cycles=CYCLES):
    host, _ = run("host", build, cycles)
    device, _ = run("device", build, cycles)
    burst, d = run("burst", build, cycles)
    assert device == host
    assert burst == host[:len(burst)], (burst, host)
    assert all(not (a or s or e) for a, s, e in host[len(burst):])
    return host, d


@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("seed", range(6))
def test_the_window_applies_fair_sharing_cycles(seed, depth):
    host, d = assert_engines_agree(random_tree(seed, depth))
    assert any(a for a, _, _ in host)
    b, s = d._burst_solver.stats, d.scheduler.solver.stats
    assert b["burst_dispatches"] >= 1, b
    assert b["burst_dirty_cycles"] == 0, b
    assert s["fs_window_cycles"] > 0, s
    assert s["fs_host_cycles"] == s["fs_full_cycles"] == 0, s


def test_ties_fall_to_child_order():
    """Two teams of one department, one queue each, equal in weight,
    quota, priority and creation time: every DRS, priority and
    timestamp ties, and the team listed first takes the department's
    one free slot."""
    def build(d):
        d.apply_resource_flavor(T.ResourceFlavor(name="default"))
        d.apply_cohort(T.Cohort(name="dep"))
        for t in ("team-a", "team-b"):
            d.apply_cohort(T.Cohort(name=t, parent_name="dep"))
        for t, q in (("team-a", "a"), ("team-b", "b"), ("team-b", "c")):
            _cq(d, q, t, 1.0, (1000, 8), False)
        for q in ("a", "b", "c"):
            for i in range(3):
                d.create_workload(T.Workload(
                    name=f"{q}{i}", queue_name="lq-" + q, priority=5,
                    creation_time=1.0, pod_sets=[T.PodSet(
                        name="main", count=1,
                        requests={"cpu": 1500, "memory": 9})]))
    host, d = assert_engines_agree(build, cycles=6)
    assert host[0][0] == ["default/a0", "default/b0"], host
    assert d.scheduler.solver.stats["fs_window_cycles"] > 0


def test_weight_zero_goes_last():
    """A queue of weight 0 has the maximal share once it borrows: the
    weighted queue's head wins the department's spare quota first."""
    def build(d):
        d.apply_resource_flavor(T.ResourceFlavor(name="default"))
        d.apply_cohort(T.Cohort(name="dep"))
        _cq(d, "zero", "dep", 0.0, (1000, 8), False)
        _cq(d, "two", "dep", 2.0, (1000, 8), False)
        _cq(d, "idle", "dep", 1.0, (3000, 24), False)
        for q, t in (("zero", 0.0), ("two", 5.0)):
            for i in range(3):
                d.create_workload(T.Workload(
                    name=f"{q}{i}", queue_name="lq-" + q, priority=0,
                    creation_time=t + i, pod_sets=[T.PodSet(
                        name="main", count=1,
                        requests={"cpu": 2500, "memory": 9})]))
    host, d = assert_engines_agree(build, cycles=6)
    assert "default/two0" in host[0][0], host
    assert d.scheduler.solver.stats["fs_window_cycles"] > 0


@pytest.mark.parametrize("seed", range(4))
def test_a_fair_preemption_hands_the_cycle_back(seed):
    """Preempt-capable heads: the window goes dirty at the cycle
    (``DIRTY_FAIR_PREEMPT``), the per-cycle engine decides it with the
    host tournament and ``_fair_preemptions``, and the decisions stay
    the host's."""
    build = random_tree(seed, 3, preempt=True, n_wl=90)
    host, d = assert_engines_agree(build)
    b, s = d._burst_solver.stats, d.scheduler.solver.stats
    assert b["burst_fair_dirty_cycles"] >= 1, b
    assert b["burst_dirty_preempt"] == 0, b
    assert s["fs_host_cycles"] >= 1, s
    pre = d.scheduler.preemptor.stats
    assert pre["fs_preempt_heads"] >= 1, pre
    assert pre["fs_candidates"] >= pre["fs_second_strategy_heads"]


def test_the_tracker_carries_its_fallbacks_into_the_solver():
    build = random_tree(0, 3, preempt=True, n_wl=90)
    _, d = run("device", build)
    s, fs = d.scheduler.solver.stats, d.scheduler.fs_stats
    assert s["fs_tracker_unavailable_cycles"] == fs[
        "tracker_unavailable_cycles"] == 0


def test_the_window_statics_follow_the_tree():
    """Levels, child order and weights of a three-level tree, as the
    tournament reads them."""
    from kueue_tpu.ops.burst import fs_window_statics
    _, d = run("device", random_tree(1, 3), cycles=1)
    st = d.scheduler.solver._structure
    fs = fs_window_statics(st, d.cache.snapshot)
    assert fs is fs_window_statics(st, d.cache.snapshot)   # built once
    sq_mask, lendable, onehot, child_order, weights = fs
    assert st.depth == 3
    assert np.array_equal(weights, st.fair_weight_milli)
    assert lendable.shape == (st.node_count, len(st.resource_names))
    assert onehot.shape == (st.n_frs, len(st.resource_names))
    team = st.cq_index["cq-0-1-2"]
    assert child_order[team] == 2      # third queue of its team


def test_the_classic_window_is_the_program_it_was():
    """A cluster without fair sharing launches the window with no
    fair-sharing argument at all, so its program keeps its static key
    and holds no tournament; the fair one is another program."""
    from kueue_tpu.ops import burst as B
    calls = []
    orig = B.burst_cycles

    def spy(*a, **kw):
        calls.append(kw)
        return orig(*a, **kw)

    B.burst_cycles = spy
    try:
        clock = FakeClock()
        d = Driver(clock=clock, use_device_solver=True)
        random_tree(0, 2)(d)
        d.schedule_burst(2, on_cycle_start=lambda _k: clock.tick())
        clock = FakeClock()
        f = Driver(clock=clock, fair_sharing=True, use_device_solver=True)
        random_tree(0, 2)(f)
        f.schedule_burst(2, on_cycle_start=lambda _k: clock.tick())
    finally:
        B.burst_cycles = orig
    classic = [kw for kw in calls if "fair" not in kw]
    fair = [kw for kw in calls if kw.get("fair")]
    assert classic and fair and len(classic) + len(fair) == len(calls)
    assert all("fs_statics" not in kw for kw in classic)
    assert all(kw["fs_statics"] is not None for kw in fair)


def test_tie_starts():
    from kueue_tpu.ops.burst import tie_starts
    p = np.array([-10, -10, -10, 0, 0, 5])
    t = np.array([1.0, 1.0, 2.0, 1.0, 1.0, 0.0])
    assert tie_starts(p, t).tolist() == [0, 0, 2, 3, 3, 5]


@pytest.mark.parametrize("seed", range(8))
def test_the_delta_pack_keeps_the_fair_cycle_rank(seed):
    """Under fair sharing ``wl_cycle_rank`` is each row's first position
    among the rows of its (priority, timestamp); the streaming pack
    keeps it over delta windows as the full pack writes it, ties on
    both keys arriving and leaving included."""
    from tests.test_delta_pack import check_step
    rng = random.Random(seed)
    clock = FakeClock()
    d = Driver(clock=clock, fair_sharing=True, use_device_solver=True)
    random_tree(seed, 3, preempt=seed % 2 == 0)(d)
    names = [q for q in d.queues.cluster_queue_names()]
    stats: dict = {}
    state = check_step(d, None, stats, 0, f"seed{seed}:init")
    for step in range(10):
        roll = rng.random()
        if roll < 0.4:
            d.create_workload(T.Workload(
                name=f"n{step}", queue_name="lq-" + rng.choice(names),
                priority=rng.choice([0, 10]),
                creation_time=float(rng.choice(range(6))),
                pod_sets=[T.PodSet(name="main", count=1, requests={
                    "cpu": 1500, "memory": 9})]))
        elif roll < 0.8:
            clock.tick()
            d.schedule_once()
        else:
            admitted = sorted(d.admitted_keys())
            if admitted:
                d.finish_workload(rng.choice(admitted))
        state = check_step(d, state, stats, 0, f"seed{seed}:step{step}")
    assert stats.get("burst_delta_packs", 0) > 0, stats


def toy_tree(seed):
    """The cell's tree at the toy's size (tests/data/toy-1kcq-fair.json:
    the tenant of rank r in team r mod teams, team t in department t mod
    departments, weight 2 on every ``weighted_team_every``-th team, the
    cell's preemption policy), a seeded backlog on it."""
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "toy-1kcq-fair.json")) as f:
        toy = json.load(f)
    dep = toy["deployment"]
    tree, fs = dep["tree"], dep["fair_sharing"]
    n_t, n_d = tree["teams"], tree["departments"]

    def build(d):
        rng = random.Random(seed)
        d.apply_resource_flavor(T.ResourceFlavor(name="default"))
        with d.bulk_apply():
            for i in range(n_d):
                d.apply_cohort(T.Cohort(name=f"dept-{i}"))
            for t in range(n_t):
                d.apply_cohort(T.Cohort(
                    name=f"team-{t}", parent_name=f"dept-{t % n_d}",
                    fair_sharing=T.FairSharing(
                        weight=(fs["weighted_team_weight"]
                                if t % fs["weighted_team_every"] == 0
                                else 1.0))))
            for r in range(1, dep["cluster_queues"] + 1):
                _cq(d, f"cq-{r}", f"team-{r % n_t}", 1.0,
                    (rng.choice([1000, 2000]), rng.choice([8, 16])),
                    True)
        for i in range(120):
            r = rng.randint(1, dep["cluster_queues"])
            d.create_workload(T.Workload(
                name=f"w{i}", queue_name=f"lq-cq-{r}",
                priority=rng.choice([50, 100]),
                creation_time=float(rng.choice(range(40))),
                pod_sets=[T.PodSet(name="main", count=1, requests={
                    "cpu": rng.choice([500, 1000, 2500]),
                    "memory": rng.choice([4, 5, 10])})]))
    return build


@pytest.mark.parametrize("seed", range(3))
def test_the_toy_tree_on_every_engine(seed):
    host, d = assert_engines_agree(toy_tree(seed), cycles=12)
    assert any(e for _, _, e in host)
    assert d._burst_solver.stats["burst_dispatches"] >= 1


@pytest.mark.parametrize("seed", range(2))
def test_the_toy_tree_puts_no_question_to_the_oracle(seed, monkeypatch):
    """The cell's group holds one flavor, so a preempting head's walk
    meets one preempt-capable flavor and no comparison reads the reclaim
    oracle's answer: its questions are met (``walk_oracle_skips``) and
    never put, and the engines still agree cycle by cycle."""
    from kueue_tpu.scheduler.preemption import PreemptionOracle
    asked = []
    real = PreemptionOracle.is_reclaim_possible

    def spy(self, *question):
        asked.append(question)
        return real(self, *question)

    monkeypatch.setattr(PreemptionOracle, "is_reclaim_possible", spy)
    host, d = assert_engines_agree(toy_tree(seed), cycles=12)
    assert any(e for _, _, e in host)
    assert asked == []
    pre = d.scheduler.preemptor.stats
    assert pre["walk_oracle_skips"] > 0, pre
    assert pre["fs_preempt_heads"] > 0, pre
