"""The deployment kind ``fair_hierarchy``: its plain reference against the
program's host scalar scheduler and against the device path (the fused
window deciding fair-sharing cycles with the tournament, and handing
back every cycle with a preempt-capable head), its controls, its
ledger, and its sizes from the configuration's file alone.

Three witnesses on one cluster, same finishes, same clock: the plain
reference (which imports nothing of the program), the host scheduler
(the tournament of ``fair_sharing_iterator.go`` and the fair-sharing
strategies of ``preemption.go``), and the device path.  Every cycle's
admitted, evicted, skipped and preempting sets and every eviction's
reason have to agree.
"""

import ast
import json
import os

import numpy as np
import pytest

import correct
import harness
from deployment_kinds import fair_hierarchy as kind
from deployment_kinds.fair_hierarchy import cluster, program, reference
from deployment_kinds.flat_one_flavor.cluster import Queue
from traffic_kinds import burst_rounds_fair

from conftest import BENCH, HERE, ROOT

TOY = os.path.join(HERE, "data", "toy-fair.json")
CONFIG = os.path.join(BENCH, "configs", "mk8-1kcq-fair.json")
FIRST = os.path.join(BENCH, "configs", "mk8-1kcq-zipf.json")
GIB = 1 << 30
NEW_METRICS = {
    # name: (layer, moves, source)
    "fs_tournament_ms": ("scheduler", "cycle_ms", "program_span"),
    "fs_preempt_ms": ("scheduler", "cycle_ms", "program_span"),
    "fs_device_cycles_pct": ("scheduler", "cycle_ms", "program_counter"),
    "fs_preempt_heads_per_round": ("scheduler", "cycle_ms",
                                   "program_counter"),
    "fs_candidates_per_round": ("scheduler", "cycle_ms", "program_counter"),
    "fs_second_strategy_heads_per_round": ("scheduler", "cycle_ms",
                                           "program_counter"),
    "fs_tracker_fallback_cycles_per_round": ("scheduler", "cycle_ms",
                                             "program_counter"),
}


def traffic_params(**over):
    with open(os.path.join(BENCH, "traffic", "backlog-fair.json")) as f:
        return dict(json.load(f), **over)


def witness(plan, rounds, seed, use_device=False, warm=False, **over):
    """``rounds`` rounds of the cell's traffic through the host scalar
    scheduler or the device path, the first of ``warm_cycles`` cycles
    with ``warm`` as the harness's set-up runs it, and the comparison's
    verdict."""
    driver, clock = program.build_driver(plan, use_device=use_device)
    if use_device:
        program.warm_up(driver, plan)
    params = traffic_params(**over)
    traffic = burst_rounds_fair.Traffic(params, plan, seed)
    records = [traffic.round(driver, clock, max_cycles=(
        params["warm_cycles"] if warm and r == 0 else None))
        for r in range(rounds)]
    return records, correct.compare(kind, plan, records, 0), driver


@pytest.fixture(scope="module")
def toy_run():
    plan = cluster.plan_cluster(harness.load_config(TOY), 5)
    records, verdict, _ = witness(plan, 12, 5, warm=True)
    return plan, records, verdict


def hand_plan():
    """One department of two teams, a queue each, cpu in units of
    1,000 m: ``a`` (nominal 10) runs 2 and asks for 10 more, ``b``
    (nominal 10) runs two of 8.  ``a``'s head fits only by preemption;
    with its usage added its team's share is 100 against ``b``'s team's
    300, and taking either of ``b``'s workloads leaves ``b``'s at 0: the
    first strategy (100 <= 0) refuses both, the second (100 < 300)
    takes one, and that is enough."""
    cfg = harness.load_config(TOY)
    cfg["deployment"]["tree"].update(teams=2, departments=1)
    queues = [Queue(name=n, cohort=t, rank=i + 1,
                    nominal={"cpu": 10_000, "memory": 100 * GIB},
                    borrowing_limit={})
              for i, (n, t) in enumerate((("cq-a", "team-0"),
                                          ("cq-b", "team-1")))]
    rows = [(0, 2_000, False), (1, 8_000, True), (1, 8_000, True),
            (0, 10_000, False)]
    running = [True, True, True, False]
    n = len(rows)
    return cluster.FairPlan(
        config=cfg, resources=["cpu", "memory"], queues=queues,
        wl_queue=np.array([r[0] for r in rows]),
        wl_name=["a-run", "b-0", "b-1", "a-head"],
        wl_priority=np.full(n, 50),
        wl_pods=np.ones(n, dtype=np.int64),
        wl_request=np.array([[r[1], GIB] for r in rows], dtype=np.int64),
        wl_created=np.arange(1.0, n + 1),
        wl_running=np.array(running),
        wl_reserved=np.arange(1.0, n + 1) + 500_000.0,
        clock_start=1_000_000.0, cycle_s=1.0,
        teams=["team-0", "team-1"], departments=["dept-0"],
        team_parent={"team-0": "dept-0", "team-1": "dept-0"},
        weight={"cq-a": 1.0, "cq-b": 1.0, "team-0": 1.0, "team-1": 1.0,
                "dept-0": 1.0},
        strategies=["LessThanOrEqualToFinalShare", "LessThanInitialShare"])


@pytest.fixture(scope="module")
def second_strategy_run():
    plan = hand_plan()
    records, verdict, driver = witness(plan, 1, 3,
                                       finish_fraction_per_round=0.0)
    return plan, records, verdict, driver


@pytest.mark.parametrize("seed", list(range(20)) + [2_147_483_659])
def test_reference_equals_scalar_scheduler(seed):
    plan = cluster.plan_cluster(harness.load_config(TOY), seed)
    records, verdict, d = witness(plan, 3, seed)
    assert verdict["correct"], verdict
    facts = verdict["facts"]
    assert facts["cycles_compared"] == 24
    assert facts["cycles_with_evictions"] > 2
    reasons = {e.partition("=")[2] for r in records for c in r.cycles
               for e in c.evicted_for}
    assert reasons <= {reference.IN_CQ, reference.IN_COHORT_FS}
    assert reference.IN_COHORT_FS in reasons
    assert d.scheduler.preemptor.stats["fs_preempt_heads"] > 0


@pytest.mark.parametrize("seed", [1, 8, 2_147_483_700])
def test_device_path_equals_reference_and_scalar_scheduler(seed):
    """The device path cycle for cycle against both other witnesses:
    fair-sharing windows launched every round, their clean cycles
    applied with the in-kernel tournament, every cycle with a
    preempt-capable head handed to the per-cycle engine."""
    plan = cluster.plan_cluster(harness.load_config(TOY), seed)
    host, verdict_h, _ = witness(plan, 4, seed)
    dev, verdict_d, d = witness(plan, 4, seed, use_device=True)
    assert verdict_h["correct"], verdict_h
    assert verdict_d["correct"], verdict_d
    for rh, rd in zip(host, dev, strict=True):
        assert len(rh.cycles) == len(rd.cycles)
        for ch, cd in zip(rh.cycles, rd.cycles):
            for name in kind.COMPARED:
                assert sorted(getattr(ch, name)) == sorted(
                    getattr(cd, name)), name
    solver, b = d.scheduler.solver.stats, d._burst_solver.stats
    assert b["burst_dispatches"] >= 4, b
    assert b["burst_fair_dirty_cycles"] >= 1, b
    assert b["burst_dirty_preempt"] == 0, b
    assert solver["fs_window_cycles"] >= 1, solver
    assert solver["fs_host_cycles"] >= 1, solver
    assert solver["fs_tracker_unavailable_cycles"] == 0, solver


def test_the_second_strategy_decides_the_cluster_written_out(
        second_strategy_run):
    plan, records, verdict, d = second_strategy_run
    assert verdict["correct"], verdict
    (cyc,) = [c for c in records[0].cycles if c.evicted]
    assert cyc.preempting == ["default/a-head"]
    # the newer reservation goes first (candidatesOrdering)
    assert cyc.evicted_for == ["default/b-1=InCohortFairSharing"]
    assert d.scheduler.preemptor.stats["fs_second_strategy_heads"] == 1


@pytest.mark.parametrize("broken", kind.CONTROLS + reference.TOY_CONTROLS)
def test_control_fails_the_comparison(toy_run, second_strategy_run,
                                      broken):
    """Each control is the reference with one stated guarantee switched
    off, put in the program's place: it has to come out not correct.
    The second strategy's is read on the cluster written out by hand
    where it decides, the others on the toy.  The toy's controls fail
    here and not at the cell's size, so the cell's ``CONTROLS`` leave
    them out."""
    if broken == "first_strategy_only":
        plan, records, verdict, _ = second_strategy_run
    else:
        plan, records, verdict = toy_run
    assert verdict["correct"], verdict
    control = correct.compare(kind, plan, records, 0, broken=broken)
    assert not control["correct"], broken
    assert control["compared"]["mismatched_cycles"]["value"] >= 1


def test_ledger_counts_a_department_over_its_quota(toy_run):
    plan, records, _ = toy_run
    import copy
    broken = copy.deepcopy(records)
    # admit a pending workload of the fullest queue at once, twice over
    pending = [plan.key(i) for i in np.nonzero(~plan.wl_running)[0]]
    broken[0].cycles[0].admitted.extend(pending[:200])
    led = kind.ledger(plan, broken)
    assert led["quota_violations"] >= 1
    broken[0].cycles[0].admitted.append(pending[0])
    assert kind.ledger(plan, broken)["double_admissions"] >= 1


def test_a_program_without_the_fair_counter_is_turned_away(monkeypatch):
    """The commit before the deployment landed never packs a window
    under fair sharing; the kind ends its run before set-up, with an
    exit code other than 0."""
    from kueue_tpu.ops.solver import CycleSolver
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    real = CycleSolver.__init__

    def without_counter(self, *a, **kw):
        real(self, *a, **kw)
        del self.stats[program.FAIR_COUNTER]
    monkeypatch.setattr(CycleSolver, "__init__", without_counter)
    with pytest.raises(SystemExit) as stop:
        program.build_driver(plan)
    assert stop.value.code not in (0, None)
    assert program.FAIR_COUNTER in str(stop.value.code)
    monkeypatch.undo()
    driver, _ = program.build_driver(plan)
    assert driver.scheduler.solver.stats[program.FAIR_COUNTER] == 0


def test_the_reference_imports_nothing_of_the_program():
    """Neither the reference, nor the cluster plan, nor the ledger."""
    here = os.path.dirname(reference.__file__)
    for name in ("reference.py", "cluster.py", "ledger.py"):
        with open(os.path.join(here, name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            assert not any(n.split(".")[0] in ("kueue_tpu", "jax")
                           for n in names), (name, names)


def test_the_warm_up_builds_the_fair_shapes():
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    driver, _ = program.build_driver(plan)
    done = program.warm_up(driver, plan)
    assert done["ladder"] and done["window_statics"]
    assert done["depth"] == 3 and done["fs_scan_buckets"] >= 2
    st = driver.scheduler.solver._structure
    assert st._fs_window is not None


# ---- the plan ------------------------------------------------------------------

def test_the_tree_quota_and_weights_from_the_plan():
    cfg = harness.load_config(TOY)
    plan = cluster.plan_cluster(cfg, 3)
    tree = cfg["deployment"]["tree"]
    assert len(plan.teams) == tree["teams"]
    assert len(plan.departments) == tree["departments"]
    for q in plan.queues:
        assert q.cohort == f"team-{q.rank % tree['teams']}"
        assert not q.borrowing_limit
    # a department's queues share one nominal, its usage over them
    by_dept: dict = {}
    for i, q in enumerate(plan.queues):
        by_dept.setdefault(plan.team_parent[q.cohort], []).append(i)
    run = plan.wl_running
    for d, members in by_dept.items():
        noms = {tuple(plan.queues[i].nominal.values()) for i in members}
        assert len(noms) == 1
        use = plan.wl_request[run & np.isin(plan.wl_queue, members)].sum(0)
        (nom,) = noms
        assert all(n * len(members) >= u for n, u in zip(nom, use))
    every = cfg["deployment"]["fair_sharing"]["weighted_team_every"]
    assert [plan.weight[t] for t in plan.teams] == [
        2.0 if i % every == 0 else 1.0 for i in range(len(plan.teams))]


def test_the_configuration_is_the_first_ones_population_in_a_tree():
    cfg = harness.load_config(CONFIG)
    first = harness.load_config(FIRST)
    for key in ("classes", "population", "clock", "fused_path_limits"):
        assert cfg[key] == first[key], key
    dep, fdep = cfg["deployment"], first["deployment"]
    for key in ("cluster_queues", "flavor", "resources", "resource_units",
                "queueing_strategy", "preemption", "quota_round_up"):
        assert dep[key] == fdep[key], key
    assert dep["tree"]["teams"] == 200 and dep["tree"]["departments"] == 20
    assert dep["fair_sharing"]["preemptionStrategies"] == [
        "LessThanOrEqualToFinalShare", "LessThanInitialShare"]
    assert cfg["reduced"] == [] and cfg["kind"] == "fair_hierarchy"
    assert len(cfg["source"]) <= 200
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    (entry,) = [c for c in m["configs"] if c["name"] == cfg["name"]]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    (cell,) = [w for w in m["workloads"] if w["config"] == cfg["name"]]
    assert cell["name"] == "mk8-fair.backlog" and cell["chips"] == 1
    assert cell["traffic"] == "backlog-fair"
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name, (layer, moves, source) in NEW_METRICS.items():
        e = by_name[name]
        assert (e["layer"], e["moves"], e["source"]) == (layer, moves,
                                                         source)
        assert e["workloads"] == ["mk8-fair.backlog"]
    # every metric the fifth cell reports, the sixth reports too, and
    # the first cell's window pack and launches, and the two rooflines
    # of the window the sixth cell launches (no second name for them)
    assert not any(n.startswith("fair_") for n in by_name)
    for e in m["per_layer"]:
        if ("mk8-gangs.backlog" in e.get("workloads", ())
                or e["name"] in ("pack_ms", "launches_per_round",
                                 "burst_kernel_roofline",
                                 "decide_roofline")):
            assert "mk8-fair.backlog" in e["workloads"], e["name"]


def test_grid_and_the_plan_at_the_cells_size():
    """The first cell's grid (1,000 x 65,536), the tree's 220 cohort
    nodes counted as queue records, every department full at the start
    and its hot tenants borrowing."""
    cfg = harness.load_config(CONFIG)
    rows = cluster.queue_rows(cfg)
    assert rows["M"] == 65_536 and rows["slots"] == 65_536_000
    plan = cluster.plan_cluster(cfg, 2_147_483_700)
    assert int(plan.wl_running.sum()) == int(sum(rows["running"]))
    assert len(plan.teams) == 200 and len(plan.departments) == 20
    problem = kind.problem(cfg, plan)
    assert problem["queues"] == 1_220
    assert problem["real_rows"] == rows["preempting_forest_rows"]
    usage = np.zeros((1000, 2), dtype=np.int64)
    np.add.at(usage, plan.wl_queue[plan.wl_running],
              plan.wl_request[plan.wl_running])
    nominal = np.array([[q.nominal[r] for r in plan.resources]
                        for q in plan.queues])
    borrowing = (usage > nominal).any(axis=1)
    assert 20 <= int(borrowing.sum()) < 500
    rank1 = [i for i, q in enumerate(plan.queues) if q.rank == 1][0]
    assert borrowing[rank1]
