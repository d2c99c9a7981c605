"""The preemption search's host side reads columns (a candidate table a
ClusterQueue, kueue_tpu/cache/candidates.py): its candidates, their
order and the launch's planes have to equal those of the loops an
object at a time, kept in tests/candidate_reference.py, and the table
has to stay what a build from ``cq.workloads`` would give."""

import dataclasses
import inspect
import random

import numpy as np
import pytest

from kueue_tpu.api.types import (
    EVICTED_BY_PREEMPTION,
    WL_QUOTA_RESERVED,
    Admission,
    BorrowWithinCohort,
    BorrowWithinCohortPolicy,
    PodSet,
    PodSetAssignment,
    PreemptionPolicy,
    ReclaimWithinCohort,
    WithinClusterQueue,
    Workload,
)
from kueue_tpu.cache.candidates import CandidateTable
from kueue_tpu.ops import preemption_solver
from kueue_tpu.ops.packing import pack_cycle
from kueue_tpu.resources import FlavorResource, FlavorResourceQuantities
from kueue_tpu.scheduler.preemption import Preemptor, _PreemptionCtx
from kueue_tpu.workload import (
    Info,
    set_evicted_condition,
    set_quota_reservation,
    sync_admitted_condition,
)
from tests import candidate_reference as reference
from tests import test_conformance_preemption as conformance

K = 1000
FLAVORS = ("alpha", "beta")
RESOURCES = ("cpu", "memory")


# ---------------------------------------------------------------------
# clusters
# ---------------------------------------------------------------------

def restore(d, name, queue, pod_sets, priority=0, reserved_at=1.0,
            created=1.0, uid=""):
    """A workload holding quota in ``queue``; ``pod_sets`` = [{resource:
    (flavor, amount)}], a PodSet each."""
    wl = Workload(
        name=name, namespace="default", priority=priority, uid=uid,
        creation_time=created,
        pod_sets=[PodSet(name=f"ps{i}", count=1,
                         requests={r: a for r, (_, a) in ps.items()})
                  for i, ps in enumerate(pod_sets)])
    set_quota_reservation(wl, Admission(
        cluster_queue=queue, pod_set_assignments=[
            PodSetAssignment(
                name=f"ps{i}", count=1,
                flavors={r: f for r, (f, _) in ps.items()},
                resource_usage={r: a for r, (_, a) in ps.items()})
            for i, ps in enumerate(pod_sets)]), reserved_at)
    sync_admitted_condition(wl, reserved_at)
    d.restore_workload(wl)
    return wl


def random_cluster(seed, within=WithinClusterQueue.LOWER_PRIORITY,
                   reclaim=ReclaimWithinCohort.ANY):
    """Four queues of one cohort and one alone, two flavors over cpu
    and memory, some queues past their nominal quota; few priorities,
    reservation times and creation times, so that every key of the
    order ties somewhere, and a few workloads sharing one uid."""
    rng = random.Random(seed)
    queues = [f"q{i}" for i in range(5)]
    borrow = [BorrowWithinCohort()]
    if reclaim != ReclaimWithinCohort.NEVER:
        borrow += [BorrowWithinCohort(
            policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
            max_priority_threshold=t) for t in (None, 5)]
    d, clock = conformance.make_driver(True, [conformance.cq(
        name,
        [(f, {r: (4 * K, None if name == "q4" else 40 * K, None)
              for r in RESOURCES}) for f in FLAVORS],
        cohort=None if name == "q4" else "team",
        preemption=PreemptionPolicy(
            within_cluster_queue=within, reclaim_within_cohort=reclaim,
            borrow_within_cohort=rng.choice(borrow)))
        for name in queues])
    for qi, queue in enumerate(queues):
        for i in range(rng.randrange(3, 12)):
            # q2 and q4 run on one flavor: their tables have fewer columns
            pod_sets = [
                {r: (rng.choice(FLAVORS[:1] if queue in ("q2", "q4")
                                else FLAVORS),
                     rng.choice([1, 2]) * K)
                 for r in rng.sample(RESOURCES, rng.choice([1, 2]))}
                for _ in range(rng.choice([1, 1, 2]))]
            restore(d, f"w{qi}-{i}", queue, pod_sets,
                    priority=rng.choice([0, 10]),
                    reserved_at=rng.choice([1.0, 2.0]),
                    created=rng.choice([1.0, 2.0, 3.0]),
                    uid=rng.choice(["", "", "", "shared-uid"]))
    return d, clock


def head_context(snapshot, queue, n, priority, created, frs):
    """A head of ``queue`` that has to preempt in ``frs``."""
    info = Info(Workload(
        name=f"head-{queue}-{n}", priority=priority, creation_time=created,
        pod_sets=[PodSet(name="main", count=1, requests={"cpu": K})]))
    info.cluster_queue = queue
    return _PreemptionCtx(
        preemptor=info, preemptor_cq=snapshot.cq(queue), snapshot=snapshot,
        frs_need_preemption=frs,
        workload_usage=FlavorResourceQuantities({fr: K for fr in frs}))


def head_contexts(snapshot, seed):
    """Three heads a queue, priority and needed flavor-resources drawn."""
    rng = random.Random(seed)
    return [head_context(
        snapshot, name, n, rng.choice([0, 10, 20]),
        rng.choice([1.5, 2.0, 2.5]),
        {FlavorResource(rng.choice(FLAVORS), r)
         for r in rng.sample(RESOURCES, rng.choice([1, 2]))})
        for name in sorted(snapshot.cluster_queues) for n in range(3)]


def same_infos(got, want):
    got = list(got)
    return len(got) == len(want) and all(g is w for g, w in zip(got, want))


def assert_discovery_equal(preemptor, ctxs):
    """Candidates, order and the planned searches, head by head."""
    found = 0
    for ctx in ctxs:
        got = preemptor._find_candidates(ctx)
        want = reference.find_candidates(preemptor, ctx)
        assert same_infos(got, want), (
            ctx.preemptor.key, [i.key for i in got], [i.key for i in want])
        found += len(want)
        if not want:
            continue
        specs, staged = preemptor.plan_searches(ctx, got)
        ref_specs, ref_staged = reference.plan_searches(preemptor, ctx, want)
        assert staged == ref_staged and len(specs) == len(ref_specs)
        for (c, ab, thr), (rc, rab, rthr) in zip(specs, ref_specs):
            assert same_infos(c, rc) and (ab, thr) == (rab, rthr)
    return found


# ---------------------------------------------------------------------
# discovery and order
# ---------------------------------------------------------------------

@pytest.fixture
def checked_discovery(monkeypatch):
    """Every candidate discovery of the test is compared with the
    written-out one; yields the list of their candidate counts."""
    counts = []
    find = Preemptor._find_candidates

    def checking(self, ctx):
        got = find(self, ctx)
        want = reference.find_candidates(self, ctx)
        assert same_infos(got, want), (
            ctx.preemptor.key, [i.key for i in got], [i.key for i in want])
        counts.append(len(want))
        return got

    monkeypatch.setattr(Preemptor, "_find_candidates", checking)
    return counts


CONFORMANCE_CASES = sorted(
    name for name, fn in vars(conformance).items()
    if name.startswith("test_") and inspect.isfunction(fn)
    and list(inspect.signature(fn).parameters) == ["use_device"])


@pytest.mark.parametrize("use_device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("case", CONFORMANCE_CASES)
def test_discovery_equals_the_written_out_loop_on_the_conformance_tables(
        checked_discovery, case, use_device):
    """The reference's TestPreemption clusters, each through the whole
    cycle: every head's candidates and their order are those of
    findCandidates and candidatesOrdering written out."""
    getattr(conformance, case)(use_device)
    # the one table none of whose heads comes to preempt
    assert checked_discovery or case == "test_no_workloads_borrowing"


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("reclaim", list(ReclaimWithinCohort),
                         ids=lambda p: "reclaim_" + p.name)
@pytest.mark.parametrize("within", list(WithinClusterQueue),
                         ids=lambda p: "within_" + p.name)
def test_discovery_equals_the_written_out_loop_on_random_clusters(
        within, reclaim, seed):
    """Every policy pair over clusters where priorities, timestamps,
    reservation times and uids tie, with two flavors and several
    PodSets; then with conditions changed under workloads that stay in
    their queues, and with a cohort mate that starts and stops
    borrowing."""
    d, clock = random_cluster(seed, within, reclaim)
    preemptor = d.scheduler.preemptor
    snapshot = d.cache.snapshot()
    ctxs = head_contexts(snapshot, seed)
    found = assert_discovery_equal(preemptor, ctxs)
    if (within, reclaim) != (WithinClusterQueue.NEVER,
                             ReclaimWithinCohort.NEVER):
        assert found

    # conditions move under candidates that stay where they are: the
    # snapshot's clones and their tables are the same objects as before
    rng = random.Random(seed)
    running = [i for cq in snapshot.cluster_queues.values()
               for i in cq.workloads.values()]
    for info in rng.sample(running, 6):
        set_evicted_condition(info.obj, EVICTED_BY_PREEMPTION, "moved",
                              clock.t)
    for info in rng.sample(running, 6):
        cond = info.obj.conditions[WL_QUOTA_RESERVED]
        info.obj.conditions[WL_QUOTA_RESERVED] = dataclasses.replace(
            cond, last_transition_time=rng.choice([0.5, 1.0, 1.5, 7.0]))
    assert_discovery_equal(preemptor, ctxs)
    again = d.cache.snapshot()
    assert all(again.cq(n) is snapshot.cq(n) for n in snapshot.cluster_queues)
    assert_discovery_equal(preemptor, head_contexts(again, seed))

    # a mate starts borrowing, then stops
    big = restore(d, "big", "q1", [{"cpu": ("alpha", 30 * K),
                                    "memory": ("beta", 30 * K)}])
    borrowing = d.cache.snapshot()
    assert borrowing.cq("q1").borrowing(FlavorResource("alpha", "cpu"))
    assert_discovery_equal(preemptor, head_contexts(borrowing, seed + 7))
    d.cache.delete_workload(Info(big))
    for name in ("q0", "q1", "q2", "q3"):
        for info in list(d.cache.cluster_queue(name).workloads.values()):
            d.cache.delete_workload(info)
            break
    assert_discovery_equal(
        preemptor, head_contexts(d.cache.snapshot(), seed + 7))


def test_scanned_rows_are_the_tables_the_queries_read():
    """``search_table_rows_scanned`` grows by the rows of every table a
    query masks: the head's own, and its borrowing mates'."""
    d, _ = random_cluster(3)
    preemptor = d.scheduler.preemptor
    snapshot = d.cache.snapshot()
    for ctx in head_contexts(snapshot, 3):
        cq = ctx.preemptor_cq
        want = len(cq.workloads)
        if cq.has_parent():
            want += sum(
                len(mate.workloads)
                for mate in cq.parent.root().subtree_cqs()
                if mate is not cq and reference.cq_is_borrowing(
                    mate, ctx.frs_need_preemption))
        before = preemptor.stats["search_table_rows_scanned"]
        preemptor._find_candidates(ctx)
        assert preemptor.stats["search_table_rows_scanned"] - before == want


# ---------------------------------------------------------------------
# the table against a build from scratch
# ---------------------------------------------------------------------

def rows_of(table):
    """The table's rows in the queue's order, as plain values."""
    out = []
    for i in np.argsort(table.seq[:table.n]).tolist():
        out.append((
            id(table.infos[i]), table.uid[i], int(table.priority[i]),
            frozenset(fr for fr, c in table.col_of.items()
                      if table.uses[i, c]),
            {fr: int(table.raw[i, c]) for fr, c in table.col_of.items()
             if table.has[i, c]}))
    return out


def assert_table_is_the_queue(cq):
    table = cq.candidates
    scratch = CandidateTable()
    for info in cq.workloads.values():
        scratch.add(info, info.usage())
    assert rows_of(table) == rows_of(scratch)
    assert table.n == len(cq.workloads)
    assert table.row_of == {table.infos[i].key: i for i in range(table.n)}
    assert set(table.row_of) == set(cq.workloads)
    assert table.frs == tuple(sorted(table.frs)) and set(table.col_of) == set(
        table.frs)
    # rows past the end are blank: an append writes only what it has
    assert not any(table.infos[table.n:]) and not any(table.uid[table.n:])
    assert not table.uses[table.n:].any() and not table.has[table.n:].any()
    assert not table.raw[table.n:].any()


def assert_tables_are_the_queues(d, snapshot=None):
    for name in d.cache.cluster_queue_names():
        assert_table_is_the_queue(d.cache.cluster_queue(name))
    for cq in (snapshot.cluster_queues.values() if snapshot else ()):
        assert_table_is_the_queue(cq)


def _add(d):
    restore(d, "new", "q0", [{"cpu": ("alpha", K)}])
    return 1


def _remove(d):
    d.cache.delete_workload(next(iter(
        d.cache.cluster_queue("q1").workloads.values())))
    return 0


def _remove_not_the_last_row(d):
    cq = d.cache.cluster_queue("q2")
    first = cq.candidates.infos[0]
    assert cq.candidates.n > 1
    d.cache.delete_workload(first)
    return 0


def _duplicate_add(d):
    cq = d.cache.cluster_queue("q0")
    assert cq.add_workload(next(iter(cq.workloads.values()))) is False
    return 0


def _remove_absent(d):
    stranger = Info(Workload(name="stranger", pod_sets=[PodSet(
        name="main", count=1, requests={"cpu": K})]))
    d.cache.cluster_queue("q0").remove_workload(stranger)
    return 0


def _a_flavor_resource_the_table_has_not_seen(d):
    d.apply_resource_flavor(conformance.ResourceFlavor(name="gamma"))
    restore(d, "wide", "q0", [{"cpu": ("gamma", K)},
                              {"memory": ("alpha", K)}])
    return 1


def _more_rows_than_the_table_has_room_for(d):
    for i in range(40):
        restore(d, f"many-{i}", "q4", [{"cpu": ("beta", K)}])
    return 40


def _clone(d):
    cq = d.cache.cluster_queue("q0")
    clone = cq.clone(parent=None)
    assert_table_is_the_queue(clone)
    assert clone.candidates.tally is cq.candidates.tally
    # the columns are shared until a side writes, and neither side
    # writes what the other reads
    assert clone.candidates.priority is cq.candidates.priority
    clone.remove_workload(next(iter(clone.workloads.values())))
    assert_table_is_the_queue(clone)
    assert_table_is_the_queue(cq)
    second = cq.clone(parent=None)
    restore(d, "after-the-clone", "q0", [{"memory": ("beta", K)}])
    d.cache.delete_workload(next(iter(cq.workloads.values())))
    assert_table_is_the_queue(second)
    assert_table_is_the_queue(cq)
    assert len(second.workloads) == len(cq.workloads)
    return 1


def _simulated_removal_and_revert(d):
    snapshot = d.cache.snapshot()
    gone = [i for name in ("q0", "q1")
            for i in list(snapshot.cq(name).workloads.values())[:2]]
    revert = snapshot.simulate_workload_removal(gone)
    assert_tables_are_the_queues(d, snapshot)
    assert not any(i.key in snapshot.cq(i.cluster_queue).candidates.row_of
                   for i in gone)
    revert()
    assert_tables_are_the_queues(d, snapshot)
    return len(gone)


def _snapshot_reuses_a_clean_clone_and_reclones_a_dirty_one(d):
    first = d.cache.snapshot()
    restore(d, "late", "q4", [{"cpu": ("alpha", K)}])
    second = d.cache.snapshot()
    # the cohort's tree was not touched: its clones, tables and all
    assert second.cq("q0") is first.cq("q0")
    assert second.cq("q0").candidates is first.cq("q0").candidates
    assert second.cq("q4") is not first.cq("q4")
    assert "default/late" in second.cq("q4").candidates.row_of
    assert "default/late" not in first.cq("q4").candidates.row_of
    assert_tables_are_the_queues(d, second)
    # a consumer scribbles on a clone: the next snapshot clones again
    second.remove_workload(next(iter(second.cq("q1").workloads.values())))
    third = d.cache.snapshot()
    assert third.cq("q1") is not second.cq("q1")
    assert_tables_are_the_queues(d, third)
    return 1 + 0


def _structure_generation_change(d):
    before = d.cache.snapshot()
    d.apply_cluster_queue(conformance.cq(
        "q5", [("alpha", {"cpu": (4 * K, None, None)})], cohort="team"))
    after = d.cache.snapshot()
    assert after.structure_generation != before.structure_generation
    assert after.cq("q0") is not before.cq("q0")
    assert_tables_are_the_queues(d, after)
    # the columns hold unscaled quantities, so a new pack structure,
    # with other scales or another F axis, has nothing to rebuild
    return 0


TABLE_STEPS = [
    _add, _remove, _remove_not_the_last_row, _duplicate_add, _remove_absent,
    _a_flavor_resource_the_table_has_not_seen,
    _more_rows_than_the_table_has_room_for, _clone,
    _simulated_removal_and_revert,
    _snapshot_reuses_a_clean_clone_and_reclones_a_dirty_one,
    _structure_generation_change,
]


@pytest.mark.parametrize("step", TABLE_STEPS,
                         ids=lambda s: s.__name__.lstrip("_"))
def test_table_stays_what_a_build_from_the_queue_gives(step):
    """After each kind of change the table of every queue, live and
    cloned, equals one built from ``cq.workloads`` from scratch, and
    the rows written are the rows changed, not the queue's size."""
    d, _ = random_cluster(11)
    assert_tables_are_the_queues(d, d.cache.snapshot())
    tally = d.cache.table_tally
    preemptor = d.scheduler.preemptor
    preemptor._find_candidates(head_contexts(d.cache.snapshot(), 0)[0])
    assert preemptor.stats["search_table_rows_built"] == tally.built == sum(
        len(d.cache.cluster_queue(n).workloads)
        for n in d.cache.cluster_queue_names())
    before = tally.built
    written = step(d)
    snapshot = d.cache.snapshot()
    assert_tables_are_the_queues(d, snapshot)
    assert tally.built - before == written
    preemptor._find_candidates(head_contexts(snapshot, 0)[0])
    assert preemptor.stats["search_table_rows_built"] == tally.built


# ---------------------------------------------------------------------
# the launch's planes
# ---------------------------------------------------------------------

PLANES = ("usage", "subtree", "guaranteed", "borrow_cap", "has_blim",
          "parent", "pre_cq", "wl_usage", "frs_mask", "cand_cq",
          "cand_delta", "cand_other", "cand_above", "allow_b0", "thr_en")


def assert_planes_equal(got, want, names=PLANES):
    assert (got is None) == (want is None)
    if want is None:
        return
    assert len(got) == len(want) == len(names)
    for name, g, w in zip(names, got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w), name


def planned_specs(preemptor, ctxs):
    """The non-empty specs of ``ctxs`` as the preemptor plans them and
    as the reference does, side by side."""
    specs, ref_specs = [], []
    for ctx in ctxs:
        got = preemptor._find_candidates(ctx)
        if not got:
            continue
        want = reference.find_candidates(preemptor, ctx)
        for (c, ab, thr), (rc, _, _) in zip(
                preemptor.plan_searches(ctx, got)[0],
                reference.plan_searches(preemptor, ctx, want)[0]):
            if len(c):
                specs.append((ctx, c, ab, thr))
                ref_specs.append((ctx, rc, ab, thr))
    return specs, ref_specs


def _as_it_is(packed):
    pass


def _unscalable_usage(packed):
    # 1,000 m of cpu under a scale of 16: no candidate's vector is whole
    packed.structure.resource_scale = np.array(
        [16 if r == "cpu" else 1 for r in packed.resource_names])


def _unknown_flavor_resource(packed):
    del packed.structure.fr_index[FlavorResource("beta", "memory")]


def _candidate_outside_the_forest(packed):
    planes = preemption_solver._planes_for(packed)
    ci = packed.structure.cq_index["q1"]
    planes.local[ci] = (planes.local[ci][0] + 1, planes.local[ci][1])


@pytest.mark.parametrize("tamper", [
    _as_it_is, _unscalable_usage, _unknown_flavor_resource,
    _candidate_outside_the_forest], ids=lambda t: t.__name__.lstrip("_"))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_gathered_planes_equal_the_loop_a_candidate(seed, tamper,
                                                    monkeypatch):
    """``_pack_batch`` and ``device_minimal_preemptions`` gather from
    the candidates' columns what the loop a candidate wrote, array for
    array, and refuse what it refused."""
    d, _ = random_cluster(seed)
    preemptor = d.scheduler.preemptor
    restore(d, "solo-low", "q4", [{"cpu": ("alpha", K)}], priority=-1)
    snapshot = d.cache.snapshot()
    specs, ref_specs = planned_specs(
        preemptor, head_contexts(snapshot, seed) + [head_context(
            snapshot, "q4", 9, 20, 2.0, {FlavorResource("alpha", "cpu")})])
    assert len(specs) > 8
    # the launch holds candidates over several sets of columns: those
    # of q4, which runs on one flavor, and the cohort's
    assert len({c.frs for _, c, _, _ in specs}) > 1
    packed = pack_cycle(snapshot, [])
    assert packed.exact
    tamper(packed)

    stats = dict(Preemptor().stats)
    got = preemption_solver._pack_batch(specs, packed, stats)
    assert_planes_equal(got, reference.pack_batch(ref_specs, packed))
    refused = tamper is not _as_it_is
    assert (got is None) == refused
    assert stats["search_refused_unpackable"] == refused
    assert stats["search_candidate_slots"] == (
        0 if refused else sum(len(c) for _, c, _, _ in specs))

    # launched alone: the planes handed to the kernel
    launched = []
    monkeypatch.setattr(
        preemption_solver, "minimal_preemptions",
        lambda *args, **kw: launched.append(args) or (np.False_, None))
    for (ctx, c, ab, thr), (_, rc, _, _) in zip(specs, ref_specs):
        del launched[:]
        result = preemption_solver.device_minimal_preemptions(
            ctx, c, ab, thr, packed=packed)
        want = reference.single_planes(ctx, rc, thr, packed)
        assert (result is None) == (want is None) == (not launched)
        if want is not None:
            assert result == []
            assert_planes_equal(launched[0][6:13], want, PLANES[6:13])
            assert launched[0][13:] == (ab, thr is not None)


def test_a_row_the_planes_cannot_hold_sends_the_cycle_to_each_head(
        monkeypatch):
    """A candidate whose usage the pack's scale does not divide: the
    batched launch is refused as unpackable, every head is searched
    alone, there on the host, and the targets are the host's."""
    from tests.test_burst import preempting_cluster, run_host

    d, clock = preempting_cluster()
    d.scheduler.preemptor.device_search = False
    (host,) = run_host(d, clock, 1, 0)
    assert len(host.preempted_targets) == 9

    layout = preemption_solver._Layout

    class Coarse(layout):
        def __init__(self, packed):
            super().__init__(packed)
            self.scale_of["cpu"] = 3000     # the heads' 3,000 m divide

    monkeypatch.setattr(preemption_solver, "_Layout", Coarse)
    d, clock = preempting_cluster()
    (cycle,) = run_host(d, clock, 1, 0)
    stats = d.scheduler.preemptor.stats
    assert stats["search_refused_unpackable"] == 1
    assert stats["search_batch_refusals"] == 1
    assert stats["search_batch_launches"] == 0
    assert stats["search_single_launches"] == 0
    # the three heads, and again at the admit scan, whose targets the
    # same scale cannot hold either (CycleSolver.pack_targets)
    assert stats["host_searches"] >= 3 and stats["device_searches"] == 0
    assert cycle.preempted_targets == host.preempted_targets
    assert cycle.preempting == host.preempting
