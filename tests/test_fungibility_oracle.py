"""Flavor fungibility decided by the reclaim oracle, on every engine.

With several ResourceFlavors in one resource group and no policy stop,
the flavor walk keeps the first flavor of the best mode, and Reclaim
over Preempt is the preemption oracle's answer
(flavorassigner.go:308, :692; preemption_oracle.go:40): a target search
of its own, a flavor and resource, that says whether the quota can be
had from other queues' borrowers alone.  Each case runs through the host
scalar scheduler, the per-cycle device engine and ``schedule_burst`` on
identically built clusters; the three have to agree on admitted, evicted
and flavor, cycle by cycle, and the device engines have to decide it
with no host walk and no host search.
"""

from __future__ import annotations

import pytest

from kueue_tpu.api.types import (
    BorrowWithinCohort,
    BorrowWithinCohortPolicy,
    ClusterQueue,
    FlavorFungibility,
    FlavorFungibilityPolicy,
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
from kueue_tpu.resources import (
    FlavorResource,
    FlavorResourceQuantities,
    Requests,
)
from kueue_tpu.scheduler.flavorassigner import (
    Assignment,
    FlavorAssigner,
    FlavorAssignmentDecision,
    Mode,
    PodSetAssignmentResult,
)
from kueue_tpu.workload import Info
from tests.conftest import FakeClock
from tests.test_conformance_preemption import admit, incoming

K = 1000
GI = 1024
PREEMPT = FlavorFungibilityPolicy.PREEMPT
TRY_NEXT = FlavorFungibilityPolicy.TRY_NEXT_FLAVOR
FLAVORS = ("f1", "f2", "f3", "f4")


def queue(name, nominal, wcp, resources=("cpu",),
          reclaim=ReclaimWithinCohort.ANY):
    """One resource group; ``nominal`` = {flavor: quota of each
    resource, or {resource: quota}}, in walk order."""
    return ClusterQueue(
        name=name, cohort="co", preemption=PreemptionPolicy(
            within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
            reclaim_within_cohort=reclaim),
        flavor_fungibility=FlavorFungibility(when_can_preempt=wcp),
        resource_groups=[ResourceGroup(
            covered_resources=list(resources),
            flavors=[FlavorQuotas(name=f, resources={
                r: ResourceQuota(nominal=q[r] if isinstance(q, dict) else q)
                for r in resources}) for f, q in nominal.items()])])


def cluster(d, wcp, a, b, resources=("cpu",),
            reclaim=ReclaimWithinCohort.ANY):
    for f in FLAVORS:
        d.apply_resource_flavor(ResourceFlavor(name=f))
    for name, nominal in (("a", a), ("b", b)):
        d.apply_cluster_queue(queue(name, nominal, wcp, resources,
                                    reclaim))
        d.apply_local_queue(LocalQueue(name=f"lq-{name}",
                                       cluster_queue=name))


def own(d, flavor, cpu=4 * K):
    """A low-priority workload of queue a: evicting it is Preempt."""
    admit(d, f"own-{flavor}", "a", {"cpu": (flavor, cpu)}, priority=-10)


def lent(d, flavor, cpu=4 * K, priority=-10):
    """Queue b holds a's quota of the flavor: evicting it is Reclaim."""
    admit(d, f"lent-{flavor}", "b", {"cpu": (flavor, cpu)},
          priority=priority)


# Each scenario builds the cluster and returns what may happen between
# cycle 1 and 2 (or None).  ``want``: the flavor the head of queue a
# ends up on, under whenCanPreempt TryNextFlavor and under Preempt, and
# whether the TryNextFlavor walk asks the oracle at all.

def one_reclaimable(d, wcp):
    cluster(d, wcp, a={"f1": 4 * K, "f2": 4 * K}, b={"f1": 0, "f2": 0})
    own(d, "f1")
    lent(d, "f2")
    incoming(d, "head", "a", {"cpu": 4 * K}, priority=10)


def two_reclaimable(d, wcp):
    cluster(d, wcp, a={"f1": 4 * K, "f2": 4 * K, "f3": 4 * K},
            b={"f1": 0, "f2": 0, "f3": 0})
    own(d, "f1")
    lent(d, "f2")
    lent(d, "f3")
    incoming(d, "head", "a", {"cpu": 4 * K}, priority=10)


def none_reclaimable(d, wcp):
    """Half of every flavor is a's own low-priority work, half is lent
    to b at a priority the head may not reclaim (LowerPriority): the
    oracle's search finds a's own workload each time."""
    cluster(d, wcp, a={f: 8 * K for f in FLAVORS},
            b={f: 0 for f in FLAVORS},
            reclaim=ReclaimWithinCohort.LOWER_PRIORITY)
    for f in FLAVORS:
        own(d, f)
        lent(d, f, priority=100)
    incoming(d, "head", "a", {"cpu": 4 * K}, priority=10)


def over_nominal(d, wcp):
    """f1's nominal is under the request and its cohort is full: NoFit
    there (no preemption while borrowing), then Preempt and Reclaim."""
    cluster(d, wcp, a={"f1": 2 * K, "f2": 4 * K, "f3": 4 * K},
            b={"f1": 0, "f2": 0, "f3": 0})
    own(d, "f1", 2 * K)
    own(d, "f2")
    lent(d, "f3")
    incoming(d, "head", "a", {"cpu": 4 * K}, priority=10)


def memory_short(d, wcp):
    """Two resources: cpu fits everywhere, memory is what is short, so
    the oracle is asked about memory alone."""
    res = ("cpu", "memory")
    quota = {"cpu": 8 * K, "memory": 4 * GI}
    cluster(d, wcp, a={"f1": quota, "f2": quota},
            b={"f1": {"cpu": 0, "memory": 0}, "f2": {"cpu": 0, "memory": 0}},
            resources=res)
    admit(d, "own-f1", "a", {"cpu": ("f1", K), "memory": ("f1", 4 * GI)},
          priority=-10)
    admit(d, "lent-f2", "b", {"cpu": ("f2", K), "memory": ("f2", 4 * GI)},
          priority=-10)
    incoming(d, "head", "a", {"cpu": K, "memory": 4 * GI}, priority=10)


def _resume_cluster(d, wcp):
    """Cycle 1: a's head fits f1 only by borrowing b's quota (the walk
    stops there, slot 0), b's own head takes that quota first, and a's
    head is skipped with its resume slot recorded.  Cycle 2: it resumes
    at f2 (Preempt) and f3 (Reclaim)."""
    cluster(d, wcp, a={"f1": 0, "f2": 4 * K, "f3": 4 * K},
            b={"f1": 4 * K, "f2": 0, "f3": 0})
    own(d, "f2")
    lent(d, "f3")
    incoming(d, "head", "a", {"cpu": 4 * K}, priority=10, created=5.0)
    incoming(d, "first", "b", {"cpu": 4 * K}, priority=20, created=1.0)


def resume_from_slot_1(d, wcp):
    _resume_cluster(d, wcp)


def resume_dropped(d, wcp):
    """As above, but a's quota moves between the cycles (f1 grows to the
    request): the resume slot is void, the walk starts over at f1 and
    fits there."""
    _resume_cluster(d, wcp)
    return lambda: d.apply_cluster_queue(queue(
        "a", {"f1": 4 * K, "f2": 4 * K, "f3": 4 * K}, wcp))


SCENARIOS = {
    # name: (builder, flavor under TryNextFlavor, under Preempt, asks)
    "one_reclaimable": (one_reclaimable, "f2", "f1", True),
    "two_reclaimable": (two_reclaimable, "f2", "f1", True),
    "none_reclaimable": (none_reclaimable, "f1", "f1", True),
    "over_nominal": (over_nominal, "f3", "f2", True),
    "memory_short": (memory_short, "f2", "f1", True),
    "resume_from_slot_1": (resume_from_slot_1, "f3", "f2", True),
    "resume_dropped": (resume_dropped, "f1", "f1", False),
}
CYCLES = 5


def flavors_of(d, keys):
    return {k: sorted(set(
        d.workload(k).admission.pod_set_assignments[0].flavors.values()))
        for k in keys}


def run(engine, build, wcp):
    """[(admitted, evicted, {admitted key: flavors})] a cycle, and the
    driver."""
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=engine != "host")
    between = build(d, wcp)
    out = []

    def record(stats):
        out.append((sorted(stats.admitted), sorted(stats.preempted_targets),
                    flavors_of(d, stats.admitted)))

    def tick(_k=None):
        clock.t += 1.0

    if engine == "burst":
        # one burst, or one cycle, what happens between, and the rest
        for k, n in enumerate([CYCLES] if between is None
                              else [1, CYCLES - 1]):
            if k:
                between()
            d.schedule_burst(n, on_cycle_start=tick,
                             on_cycle=lambda _k, stats: record(stats))
    else:
        for c in range(CYCLES):
            if c == 1 and between is not None:
                between()
            tick()
            record(d.schedule_once())
    return out, d


@pytest.mark.parametrize("wcp", [TRY_NEXT, PREEMPT],
                         ids=["try_next", "preempt"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_oracle_picks_the_flavor_on_every_engine(scenario, wcp):
    build, want_try_next, want_preempt, asks = SCENARIOS[scenario]
    want = want_try_next if wcp == TRY_NEXT else want_preempt
    host, dh = run("host", build, wcp)
    assert dh.workload("default/head").has_quota_reservation, host
    assert flavors_of(dh, ["default/head"]) == {"default/head": [want]}
    evicted = sorted(k for _, ev, _ in host for k in ev)
    if want != "f1" or scenario == "none_reclaimable":
        # Reclaim evicts b's workload, Preempt a's own
        kind = "lent" if (wcp == TRY_NEXT
                          and scenario != "none_reclaimable") else "own"
        assert evicted == [f"default/{kind}-{want}"], host

    for engine in ("device", "burst"):
        got, d = run(engine, build, wcp)
        # the burst stops once nothing is left to decide
        assert got == host[:len(got)], (engine, got, host)
        assert all(not (a or ev) for a, ev, _ in host[len(got):])
        solver, pre = d.scheduler.solver.stats, d.scheduler.preemptor.stats
        assert solver["scalar_heads"] == 0, solver
        assert solver["host_cycles"] == 0, solver
        assert pre["host_searches"] == 0, pre
        if engine == "device":
            # (a clean fused window walks in its kernel, uncounted)
            assert 0 < solver["walk_heads"] <= solver["walk_slots"], solver
        if wcp == TRY_NEXT and asks:
            # several preempt-capable flavors and no stop: the oracle's
            # searches went out in the batched launch
            assert pre["oracle_specs"] > 0, pre
            assert pre["search_batch_launches"] >= 2, pre
            assert pre["oracle_reclaims"] <= pre["oracle_specs"], pre
            assert (pre["oracle_reclaims"] > 0) == (
                scenario != "none_reclaimable"), pre
        else:
            # a policy stop, or a fit, is final without the oracle
            assert pre["oracle_specs"] == 0, pre


def test_oracle_span_holds_its_searches_and_self_time_adds_up():
    """``cycle.nominate.oracle`` is a child of ``cycle.nominate`` and
    the parent of the searches it plans, packs, launches and decodes;
    each level's self time is its duration less its own children."""
    from kueue_tpu.obs import trace as trace_mod
    from kueue_tpu.obs.trace import HOT_PATH_PHASES, SELF_SUFFIX
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    two_reclaimable(d, TRY_NEXT)
    tracer = d.obs.enable_tracing()
    try:
        clock.t += 1.0
        d.schedule_once()
    finally:
        d.obs.disable_tracing()
        trace_mod.clear()
    assert "cycle.nominate.oracle" in HOT_PATH_PHASES
    recs = tracer.trace_spans
    (oracle,) = [r for r in recs if r.name == "cycle.nominate.oracle"]
    assert oracle.parent == "cycle.nominate"
    inside = [r for r in recs if r.parent == "cycle.nominate.oracle"]
    assert {r.name for r in inside} == {
        "cycle.nominate.candidates", "cycle.nominate.search_pack",
        "cycle.nominate.search_launch", "cycle.nominate.search_decode"}
    roster = tracer.roster()
    assert roster["cycle.nominate.oracle" + SELF_SUFFIX]["total_s"] == \
        pytest.approx(oracle.dur - sum(r.dur for r in inside))
    (nominate,) = [r for r in recs if r.name == "cycle.nominate"]
    assert roster["cycle.nominate" + SELF_SUFFIX]["total_s"] == \
        pytest.approx(nominate.dur - sum(
            r.dur for r in recs if r.parent == "cycle.nominate"))


@pytest.mark.parametrize("fit, reclaim, want", [
    # one resource short a slot: the first Reclaim beats an earlier Preempt
    ([[False], [False], [False]], [[False], [True], [True]], 1),
    ([[False], [False], [False]], [[False], [False], [False]], 0),
    # a slot is as good as its worst resource: Reclaim + Preempt = Preempt
    ([[False, False], [True, False]], [[True, False], [False, True]], 1),
    ([[False, False], [False, False]], [[True, False], [True, True]], 1),
])
def test_lattice_picks_the_first_slot_of_the_best_mode(fit, reclaim, want):
    import numpy as np
    from kueue_tpu.ops.cycle import pick_preempt_slot_np
    fit, reclaim = np.array([fit]), np.array([reclaim])
    capable = np.ones(fit.shape[:2], dtype=bool)
    assert pick_preempt_slot_np(capable, fit, reclaim)[0] == want
    # a slot that is not preempt-capable is never picked, whatever it reads
    capable[0, want] = False
    assert pick_preempt_slot_np(capable, fit, reclaim)[0] != want


# ---------------------------------------------------------------------------
# The host walk asks the oracle only where two preempt-capable flavors meet
# ---------------------------------------------------------------------------

class ScriptedOracle:
    """Answers from a table keyed (flavor, resource, quantity), every
    question written down; a question not in the table fails the case."""

    def __init__(self, answers):
        self.answers = answers
        self.asked = []

    def is_reclaim_possible(self, cq, wl, fr, quantity) -> bool:
        self.asked.append((fr.flavor, fr.resource, quantity))
        return self.answers[(fr.flavor, fr.resource, quantity)]


def walk_cluster(d, a, b, fungibility, borrow_within):
    """Queues ``a`` and ``b`` of one cohort over one resource group;
    ``a`` / ``b`` = {flavor: {resource: nominal}}, in walk order."""
    for f in FLAVORS:
        d.apply_resource_flavor(ResourceFlavor(name=f))
    for name, nominal in (("a", a), ("b", b)):
        d.apply_cluster_queue(ClusterQueue(
            name=name, cohort="co", preemption=PreemptionPolicy(
                within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
                reclaim_within_cohort=ReclaimWithinCohort.ANY,
                borrow_within_cohort=BorrowWithinCohort(policy=borrow_within)),
            flavor_fungibility=fungibility,
            resource_groups=[ResourceGroup(
                covered_resources=sorted(next(iter(a.values()))),
                flavors=[FlavorQuotas(name=f, resources={
                    r: ResourceQuota(nominal=q) for r, q in quota.items()})
                    for f, quota in nominal.items()])]))


def _walk_case(a, b, running, pod_sets, answers, want, asked, skips,
               wcp=TRY_NEXT, wcb=FlavorFungibilityPolicy.BORROW,
               borrow_within="Never"):
    return dict(a=a, b=b, running=running, pod_sets=pod_sets,
                answers=answers, want=want, asked=asked, skips=skips,
                wcp=wcp, wcb=wcb, borrow_within=borrow_within)


def _short(flavor, res, more):
    return (f"insufficient unused quota for {res} in flavor {flavor}, "
            f"{more} more needed")


CPU4 = {"cpu": 4 * K}
BOTH = {"cpu": 4 * K, "memory": 4 * GI}
# queue a holds its own quota of f1 and f2 to the full
FULL = [("a", "f1", CPU4), ("a", "f2", CPU4)]
FULL_BOTH = [("a", "f1", BOTH), ("a", "f2", BOTH)]
# ``want``: per PodSet ({resource: (flavor, borrow)}, tried, reasons), the
# eager walk's answer, worked out by hand (every pick here is Preempt
# but where the case says Fit); ``asked``: the oracle's questions
WALKS = {
    "one_flavor_asks_nothing": _walk_case(
        a={"f1": CPU4}, b={"f1": {"cpu": 0}}, running=[("a", "f1", CPU4)],
        pod_sets=[CPU4], answers={},
        want=[({"cpu": ("f1", True)}, -1, [_short("f1", "cpu", 4 * K)])],
        asked=[], skips=1),
    "reclaim_second_beats_preempt": _walk_case(
        a={"f1": CPU4, "f2": CPU4}, b={"f1": {"cpu": 0}, "f2": {"cpu": 0}},
        running=FULL, pod_sets=[CPU4],
        answers={("f1", "cpu", 4 * K): False, ("f2", "cpu", 4 * K): True},
        want=[({"cpu": ("f2", True)}, -1, [_short("f1", "cpu", 4 * K),
                                           _short("f2", "cpu", 4 * K)])],
        asked=[("f1", "cpu", 4 * K), ("f2", "cpu", 4 * K)], skips=0),
    "reclaim_first_beats_preempt": _walk_case(
        a={"f1": CPU4, "f2": CPU4}, b={"f1": {"cpu": 0}, "f2": {"cpu": 0}},
        running=FULL, pod_sets=[CPU4],
        answers={("f1", "cpu", 4 * K): True, ("f2", "cpu", 4 * K): False},
        want=[({"cpu": ("f1", True)}, -1, [_short("f1", "cpu", 4 * K),
                                           _short("f2", "cpu", 4 * K)])],
        asked=[("f1", "cpu", 4 * K)], skips=1),
    "preempt_ties_keep_the_first": _walk_case(
        a={"f1": CPU4, "f2": CPU4}, b={"f1": {"cpu": 0}, "f2": {"cpu": 0}},
        running=FULL, pod_sets=[CPU4],
        answers={("f1", "cpu", 4 * K): False, ("f2", "cpu", 4 * K): False},
        want=[({"cpu": ("f1", True)}, -1, [_short("f1", "cpu", 4 * K),
                                           _short("f2", "cpu", 4 * K)])],
        asked=[("f1", "cpu", 4 * K), ("f2", "cpu", 4 * K)], skips=0),
    "policy_stop_asks_nothing": _walk_case(
        a={"f1": CPU4, "f2": CPU4}, b={"f1": {"cpu": 0}, "f2": {"cpu": 0}},
        running=FULL, pod_sets=[CPU4], answers={}, wcp=PREEMPT,
        want=[({"cpu": ("f1", True)}, 0, [_short("f1", "cpu", 4 * K)])],
        asked=[], skips=1),
    "fit_borrowing_then_preempt": _walk_case(
        a={"f1": {"cpu": 0}, "f2": CPU4}, b={"f1": CPU4, "f2": {"cpu": 0}},
        running=[("a", "f2", CPU4)], pod_sets=[CPU4], answers={},
        wcb=TRY_NEXT, want=[({"cpu": ("f1", True)}, -1, "fit")],
        asked=[], skips=1),
    "definite_preempt_beside_undecided": _walk_case(
        a={"f1": {"cpu": 2 * K, "memory": 4 * GI}, "f2": BOTH},
        b={"f1": {"cpu": 4 * K, "memory": 0}, "f2": {"cpu": 0, "memory": 0}},
        running=[("a", "f1", {"cpu": 2 * K, "memory": 4 * GI}),
                 ("b", "f1", {"cpu": 4 * K}), ("a", "f2", BOTH)],
        pod_sets=[BOTH], borrow_within="LowerPriority",
        answers={("f2", "cpu", 4 * K): True, ("f2", "memory", 4 * GI): True},
        want=[({"cpu": ("f2", True), "memory": ("f2", True)}, -1, [
            _short("f1", "cpu", 4 * K), _short("f1", "memory", 4 * GI),
            _short("f2", "cpu", 4 * K), _short("f2", "memory", 4 * GI)])],
        asked=[("f2", "cpu", 4 * K), ("f2", "memory", 4 * GI)], skips=1),
    "first_no_leaves_the_second_unasked": _walk_case(
        a={"f1": BOTH, "f2": BOTH},
        b={"f1": {"cpu": 0, "memory": 0}, "f2": {"cpu": 0, "memory": 0}},
        running=FULL_BOTH, pod_sets=[BOTH],
        answers={("f1", "cpu", 4 * K): False, ("f2", "cpu", 4 * K): True,
                 ("f2", "memory", 4 * GI): True},
        want=[({"cpu": ("f2", True), "memory": ("f2", True)}, -1, [
            _short("f1", "cpu", 4 * K), _short("f1", "memory", 4 * GI),
            _short("f2", "cpu", 4 * K), _short("f2", "memory", 4 * GI)])],
        asked=[("f1", "cpu", 4 * K), ("f2", "cpu", 4 * K),
               ("f2", "memory", 4 * GI)], skips=1),
    "nofit_after_preempt_capable": _walk_case(
        a={"f1": BOTH, "f2": {"cpu": 4 * K, "memory": 8 * GI}},
        b={"f1": {"cpu": 0, "memory": 0}, "f2": {"cpu": 0, "memory": 0}},
        running=[("a", "f1", CPU4),
                 ("a", "f2", {"cpu": 4 * K, "memory": 8 * GI})],
        pod_sets=[{"cpu": 4 * K, "memory": 8 * GI}], answers={},
        want=[({"cpu": ("f2", True), "memory": ("f2", True)}, -1, [
            _short("f1", "cpu", 4 * K),
            "insufficient quota for memory in flavor f1, request > maximum "
            f"capacity ({8 * GI} > {4 * GI})",
            _short("f2", "cpu", 4 * K), _short("f2", "memory", 8 * GI)])],
        asked=[], skips=3),
    "gang_second_podset_at_its_charged_val": _walk_case(
        a={"f1": CPU4, "f2": CPU4}, b={"f1": {"cpu": 0}, "f2": {"cpu": 0}},
        running=FULL, pod_sets=[{"cpu": 2 * K}, {"cpu": 2 * K}],
        answers={("f1", "cpu", 2 * K): False, ("f2", "cpu", 2 * K): True,
                 ("f2", "cpu", 4 * K): True},
        want=[({"cpu": ("f2", True)}, -1, [_short("f1", "cpu", 2 * K),
                                           _short("f2", "cpu", 2 * K)]),
              ({"cpu": ("f2", True)}, -1, [_short("f1", "cpu", 2 * K),
                                           _short("f2", "cpu", 4 * K)])],
        asked=[("f1", "cpu", 2 * K), ("f2", "cpu", 2 * K),
               ("f1", "cpu", 2 * K), ("f2", "cpu", 4 * K)], skips=0),
}


@pytest.mark.parametrize("case", sorted(WALKS))
def test_the_host_walk_asks_only_where_preempt_flavors_meet(case):
    """The whole ``Assignment`` of the host walk, its reasons and resume
    state, is the eager walk's, while the oracle is asked only where a
    comparison of two preempt-capable flavors reads its answer, in the
    walk's order and no further than the first no; ``oracle_skips``
    counts the questions met and never put."""
    c = WALKS[case]
    d = Driver(clock=FakeClock())
    walk_cluster(d, c["a"], c["b"], FlavorFungibility(
        when_can_borrow=c["wcb"], when_can_preempt=c["wcp"]),
        BorrowWithinCohortPolicy(c["borrow_within"]))
    for i, (q, f, usage) in enumerate(c["running"]):
        admit(d, f"run-{i}", q, {r: (f, v) for r, v in usage.items()},
              priority=-10)
    snap = d.cache.snapshot()
    cq = snap.cq("a")
    head = Info(Workload(name="head", namespace="default", queue_name="lq-a",
                         pod_sets=[PodSet(name=f"ps{p}", count=1,
                                          requests=dict(reqs))
                                   for p, reqs in enumerate(c["pod_sets"])]))
    head.cluster_queue = "a"
    oracle = ScriptedOracle(c["answers"])
    assigner = FlavorAssigner(head, cq, snap.resource_flavors, oracle=oracle)
    got = assigner.assign()

    want = Assignment()
    want.last_state.cluster_queue_generation = cq.allocatable_generation
    for p, (reqs, (pick, tried, reasons)) in enumerate(
            zip(c["pod_sets"], c["want"], strict=True)):
        mode = Mode.FIT if reasons == "fit" else Mode.PREEMPT
        want.pod_sets.append(PodSetAssignmentResult(
            name=f"ps{p}", count=1, requests=Requests(reqs),
            reasons=[] if reasons == "fit" else reasons,
            flavors={r: FlavorAssignmentDecision(
                name=f, mode=mode, tried_flavor_idx=tried, borrow=borrow)
                for r, (f, borrow) in pick.items()}))
        want.last_state.last_tried_flavor_idx.append(
            {r: tried for r in pick})
        want.borrowing |= any(borrow for _, borrow in pick.values())
        for r, (f, _) in pick.items():
            fr = FlavorResource(f, r)
            want.usage[fr] = want.usage.get(fr, 0) + reqs[r]
    want.usage = FlavorResourceQuantities(want.usage)
    assert got == want
    assert oracle.asked == c["asked"]
    assert assigner.oracle_skips == c["skips"]
