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
    WithinClusterQueue,
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
    it runs, append a dict read off the entries themselves before the
    round: ``tree`` (remaining heads in the first head's tree), ``all``
    (remaining heads of every tree), ``trees`` (trees with a remaining
    head), ``root`` (the picked tree's root cohort), ``left`` (the picked
    tree's remaining entries) and ``winner`` (the entry it yielded)."""
    real = sched._fair_sharing_iterator

    def wrapped(entries, snapshot):
        left = {e.info.cluster_queue: e for e in entries
                if e.cq_snapshot is not None}
        inner = real(entries, snapshot)
        while True:
            pending = None
            first = next(iter(left.values()), None)
            if first is not None and first.cq_snapshot.parent is not None:
                in_tree = [e for e in left.values()
                           if e.cq_snapshot.parent is not None]
                root = first.cq_snapshot.parent.root()
                picked = [e for e in in_tree
                          if e.cq_snapshot.parent.root() is root]
                pending = dict(
                    tree=len(picked), all=len(in_tree),
                    trees=len({id(e.cq_snapshot.parent.root())
                               for e in in_tree}),
                    root=root, left=picked)
            try:
                e = next(inner)
            except StopIteration:
                return
            if e.cq_snapshot is not None:
                left.pop(e.info.cluster_queue, None)
                if pending is not None:
                    rounds.append(dict(pending, winner=e))
            yield e
    sched._fair_sharing_iterator = wrapped


def _cohorts_below_root(cq):
    """The cohorts on ``cq``'s chain, its root left out."""
    out, cohort = set(), cq.parent
    while cohort is not None and cohort.parent is not None:
        out.add(cohort.name)
        cohort = cohort.parent
    return out


def kept_round_bounds(rounds):
    """(least, most) ``fs_drs_entries`` the batched tournament can count
    over ``rounds``: a tree's first round computes its remaining heads;
    a later one only those whose path crosses a node the tree's last
    winner changed, at most the heads sharing a cohort below the root
    with that winner, and none where its usage stayed in its queue."""
    least = most = 0
    last_winner = {}
    for r in rounds:
        root = id(r["root"])
        prev = last_winner.get(root)
        if prev is None:
            least += r["tree"]
            most += r["tree"]
        else:
            shared = _cohorts_below_root(prev.cq_snapshot)
            most += sum(bool(_cohorts_below_root(e.cq_snapshot) & shared)
                        for e in r["left"])
        last_winner[root] = r["winner"]
    return least, most


@pytest.mark.parametrize("batched", [True, False])
@pytest.mark.parametrize("seed", [71, 72])
def test_drs_entries_count_the_picked_tree(seed, batched):
    """``fs_drs_entries`` grows, a cycle, by less than every remaining
    head wherever a round had two trees or more to pick from.  On the
    scalar path it grows by the remaining heads of each round's picked
    tree.  On the batched path it grows by a tree's remaining heads at
    its first round of the cycle, plus, at a later round, the heads whose
    path crosses a node the tree's last pick changed: fewer than the
    scalar count wherever a tree has a second round."""
    d, clock, wls = build_fs_driver(seed, batched=batched, use_device=True,
                                    **DEPARTMENTS)
    for wl in wls:
        d.create_workload(wl)
    stats = d.scheduler.solver.stats
    rounds = []
    watch_rounds(d.scheduler, rounds)
    several = second_rounds = 0
    for _ in range(12):
        clock.t += 1.0
        rounds.clear()
        before = stats["fs_drs_entries"]
        d.schedule_once()
        counted = stats["fs_drs_entries"] - before
        whole = sum(r["tree"] for r in rounds)
        if batched:
            least, most = kept_round_bounds(rounds)
            assert least <= counted <= most
            if len(rounds) > len({id(r["root"]) for r in rounds}):
                second_rounds += 1
                assert counted < whole
        else:
            assert counted == whole
        if any(r["trees"] >= 2 for r in rounds):
            several += 1
            assert counted < sum(r["all"] for r in rounds)
    assert several >= 1
    assert stats["fs_host_cycles"] >= 1
    if batched:
        assert second_rounds >= 1
        assert stats["fs_picks_reused"] > 0
    else:
        assert stats["fs_picks_reused"] == 0


def build_tree_driver(seed, *, batched, depth, roots=2, fanout=2,
                      queues=2, beside=False, weights=(1000,),
                      lending=None, memory=False, ties=False,
                      nofit=False, reserve=False, n_wl=120):
    """``roots`` root cohorts, each a tree of ``depth`` levels counting
    its queues: every cohort above the lowest has ``fanout`` child
    cohorts (and, with ``beside``, one queue of its own beside them), the
    lowest ``queues`` queues.  Queues and cohorts below a root take
    ``weights`` in turn; ``lending`` is every other queue's lending
    limit on its 4,000 cpu (the rest is guaranteed quota, where an
    admission's carry stops in its queue), ``memory`` a second resource; ``ties`` makes every workload alike in
    size, priority and timestamp; ``nofit`` adds workloads no queue can
    hold; ``reserve`` makes queues preempt only lower priorities within
    themselves and never reclaim, so a preempt-capable head finds no
    target and reserves."""
    rng = random.Random(seed)
    clock = FakeClock()
    d = Driver(clock=clock, fair_sharing=True, use_device_solver=True)
    d.scheduler.fs_batched = batched
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    pre = (PreemptionPolicy(
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
        if reserve else
        PreemptionPolicy(reclaim_within_cohort=ReclaimWithinCohort.ANY))
    weight = iter(weights * 1000)
    lqs = []

    def add_queue(name, cohort):
        q = len(lqs)
        resources = {"cpu": ResourceQuota(
            nominal=4000, borrowing_limit=8000,
            lending_limit=lending if q % 2 else None)}
        if memory:
            resources["memory"] = ResourceQuota(nominal=8000,
                                                borrowing_limit=8000)
        d.apply_cluster_queue(ClusterQueue(
            name=name, cohort=cohort, preemption=pre,
            fair_sharing=FairSharing(weight=next(weight)),
            resource_groups=[ResourceGroup(
                covered_resources=list(resources),
                flavors=[FlavorQuotas(name="default",
                                      resources=resources)])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{name}", cluster_queue=name))
        lqs.append(f"lq-{name}")

    def grow(name, parent, level):
        d.apply_cohort(Cohort(
            name=name, parent_name=parent,
            fair_sharing=(FairSharing(weight=next(weight))
                          if parent is not None else None)))
        if level == depth - 1:
            for q in range(queues):
                add_queue(f"{name}-q{q}", name)
            return
        for c in range(fanout):
            grow(f"{name}-{c}", name, level + 1)
        if beside:
            add_queue(f"{name}-q", name)

    for r in range(roots):
        grow(f"r{r}", None, 1)
    for i in range(n_wl):
        cpu = 2000 if ties else rng.choice([1000, 2000, 4000])
        if nofit and i % 10 == 0:
            cpu = 64000
        reqs = {"cpu": cpu}
        if memory:
            reqs["memory"] = 4000 if ties else rng.choice([1000, 4000, 8000])
        d.create_workload(Workload(
            name=f"wl-{i}", queue_name=rng.choice(lqs),
            priority=10 if ties or reserve else rng.choice([10, 10, 50, 100]),
            creation_time=1.0 if ties else float(i + 1),
            pod_sets=[PodSet(name="main", count=1, requests=reqs)]))
    return d, clock


@pytest.mark.parametrize("seed", [91, 92, 93])
def test_drs_path_is_a_row_of_drs_for(seed):
    """``TournamentDRS.drs_path`` gives, from any level up, what
    ``drs_for`` gives the same entry there, bit for bit: over a depth-4
    forest whose usage, quotas, subtree masks, lendables and weights
    (zero among them) are drawn at random, so usage under guaranteed
    quota, a resource outside a node's subtree quota and every DRS
    branch are met."""
    import numpy as np
    from kueue_tpu.ops.fairsharing_kernel import TournamentDRS
    d, _ = build_tree_driver(seed, batched=True, depth=4, beside=True,
                             memory=True, n_wl=0)
    t = TournamentDRS(d.scheduler.cache.snapshot())
    rng = np.random.default_rng(seed)
    N, F = t.u.shape
    t.u = rng.integers(0, 9000, (N, F))
    t.subtree = rng.integers(0, 6000, (N, F))
    t.sq_mask = rng.random((N, F)) < 0.8
    t.guaranteed = rng.integers(0, 5000, (N, F))
    t.lendable_r = rng.integers(0, 3, t.lendable_r.shape) * rng.integers(
        0, 20000, t.lendable_r.shape)
    t.weights = rng.choice([0, 500, 1000, 2000, 3], N)
    cq_is = np.array(sorted(t.cq_index.values()) * 4)
    u_es = rng.integers(0, 7000, (len(cq_is), F))
    paths, drs = t.drs_for(cq_is, u_es)
    for j, ci in enumerate(cq_is.tolist()):
        want = [(int(t.parent[node]), int(v))
                for node, v in zip(paths[j], drs[j])
                if node >= 0 and t.parent[node] >= 0]
        for start in range(t.depth):
            got = t.drs_path(ci, u_es[j].tolist(), start)
            assert got == want[start:len(want)], (ci, start)


KEPT_CASES = {
    "depth2": dict(depth=2, queues=4),
    "depth3": dict(depth=3, fanout=3),
    "depth4": dict(depth=4),
    "queue_beside_cohorts": dict(depth=3, beside=True),
    "ties": dict(depth=3, fanout=3, ties=True),
    "weights_0_1_2_and_lending": dict(depth=3, weights=(0, 1000, 2000),
                                      lending=2000, memory=True),
    "preempt_reserve": dict(depth=3, reserve=True),
    "nofit_and_skipped": dict(depth=3, nofit=True),
    "carry_stops_below_team": dict(depth=4, lending=0),
    "stale_mid_cycle": dict(depth=3, fanout=3),
}


def record_picks(sched):
    """Wrap ``sched``'s fair-sharing iterator; returns the list that each
    cycle appends the keys it yields to, in order."""
    cycles = []
    real = sched._fair_sharing_iterator

    def wrapped(entries, snapshot):
        picks = []
        cycles.append(picks)
        for e in real(entries, snapshot):
            picks.append(e.info.key)
            yield e
    sched._fair_sharing_iterator = wrapped
    return cycles


@pytest.mark.parametrize("case", sorted(KEPT_CASES))
def test_kept_tournament_matches_full_recompute(case, monkeypatch):
    """The tournament that keeps each tree's DRS values and cohort picks
    across its rounds yields, pick for pick and cycle for cycle, what the
    scalar oracle's full recompute every round yields, and decides the
    same; each case also shows that its path was taken."""
    from kueue_tpu.ops.fairsharing_kernel import TournamentDRS
    from kueue_tpu.scheduler.scheduler import Mode
    spec = KEPT_CASES[case]
    notes = []                 # nodes each note_add changed
    real_note = TournamentDRS.note_add

    def note_add(self, cq_name, usage):
        self.notes = getattr(self, "notes", 0) + 1
        if case == "stale_mid_cycle" and self.notes == 3:
            self.stale = True   # as an unseen flavor-resource makes it
            return
        before = len(self.changed)
        real_note(self, cq_name, usage)
        notes.append(self.changed[before:])
    monkeypatch.setattr(TournamentDRS, "note_add", note_add)

    runs = []
    for batched in (False, True):
        d, clock = build_tree_driver(8100, batched=batched, **spec)
        reserved = []
        real_reserve = d.scheduler._resources_to_reserve

        def reserve(e, cq, real_reserve=real_reserve, reserved=reserved):
            if e.assignment.representative_mode() == Mode.PREEMPT:
                reserved.append(e.info.key)
            return real_reserve(e, cq)
        d.scheduler._resources_to_reserve = reserve
        picks = record_picks(d.scheduler)
        log = drive(d, clock, [], n_cycles=24)
        runs.append((picks, log, d, reserved))
    (ref_picks, ref_log, ref, _), (picks, log, d, reserved) = runs
    assert len(picks) == len(ref_picks)
    for cyc, (r, b) in enumerate(zip(ref_picks, picks)):
        assert r == b, f"{case} cycle {cyc}:\nscalar={r}\nkept={b}"
    assert log == ref_log
    assert any(c["admitted"] for c in log)
    stats = d.scheduler.solver.stats
    # a root's own pick is always made again: a tree of depth 2 has no
    # other cohort to keep one of
    assert (stats["fs_picks_reused"] > 0) == (spec["depth"] > 2)
    assert ref.scheduler.solver.stats["fs_picks_reused"] == 0
    assert stats["fs_drs_entries"] < ref.scheduler.solver.stats[
        "fs_drs_entries"]
    if case == "stale_mid_cycle":
        assert d.scheduler.fs_stats["scalar_drs_rounds"] > 0
    else:
        assert d.scheduler.fs_stats["scalar_drs_rounds"] == 0
    if case == "preempt_reserve":
        assert reserved
    if case == "nofit_and_skipped":
        assert any(c["skipped"] or c["inadmissible"] for c in log)
    if case == "carry_stops_below_team":
        # an admission whose usage its own queue's guaranteed quota held
        assert any(len(n) == 1 for n in notes)


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
