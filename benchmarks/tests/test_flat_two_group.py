"""The deployment kind ``flat_two_group``: its plain reference against
the program's host scalar scheduler and against the device solver with
windows applied and with windows dropped, its controls, its ledger, and
its sizes from the configuration's file alone.

Three witnesses on one cluster, same finishes, same clock: the plain
reference (which imports nothing of the program), the host
``FlavorAssigner`` with the ``PreemptionOracle``, and the device path
(one vector walk a resource group and their join, the batched search
over the flavor-resources of both groups, the fused window with its
per-group resume and mask planes).  Every cycle's admitted, evicted,
skipped and preempting sets and the flavors of every admission have to
agree.
"""

import ast
import copy
import json
import os

import numpy as np
import pytest

import correct
import harness
from deployment_kinds import flat_labelled_flavor
from deployment_kinds import flat_two_group as kind
from deployment_kinds.flat_one_flavor.cluster import Queue
from deployment_kinds.flat_two_group import cluster, program, reference
from traffic_kinds import burst_rounds_flavors

from conftest import BENCH, HERE, ROOT

TOY = os.path.join(HERE, "data", "toy-2group.json")
CONFIG = os.path.join(BENCH, "configs", "mk8-1kcq-2group.json")
FIRST = os.path.join(BENCH, "configs", "mk8-1kcq-zipf.json")
GIB = 1 << 30


def traffic_params(**over):
    with open(os.path.join(BENCH, "traffic", "backlog-flavors.json")) as f:
        return dict(json.load(f), **over)


def witness(plan, rounds, seed, use_device=False, **over):
    """``rounds`` rounds of the cell's traffic through the host scalar
    scheduler or the device solver, and the comparison's verdict."""
    driver, clock = program.build_driver(plan, use_device=use_device)
    traffic = burst_rounds_flavors.Traffic(traffic_params(**over), plan,
                                           seed)
    records = [traffic.round(driver, clock) for _ in range(rounds)]
    return records, correct.compare(kind, plan, records, 0), driver


@pytest.fixture(scope="module")
def toy_run():
    plan = cluster.plan_cluster(harness.load_config(TOY), 5)
    records, verdict, _ = witness(plan, 3, 5, finish_fraction_per_round=0.05)
    return plan, records, verdict


def oracle_plan():
    """Two queues of one cohort, by hand, where the oracle decides (the
    toy's traffic never brings it to: a job free to choose finds room on
    x86 whenever it comes up).  a's x86 is full of its own
    higher-priority work, its arm is lent to b, and a's head may take
    either: Reclaim on arm beats Preempt on x86, and b's borrower goes."""
    cfg = harness.load_config(TOY)
    res = ["cpu", "memory"]
    groups = cluster.plan_groups(cfg, res)

    def queue(name, rank, x86, arm):
        return Queue(
            name=name, cohort="cohort-0", rank=rank,
            nominal={"x86": {"cpu": x86}, "arm": {"cpu": arm},
                     "default-flavor": {"memory": 64 * GIB}},
            borrowing_limit={"x86": {"cpu": 8000}, "arm": {"cpu": 8000},
                             "default-flavor": {"memory": 64 * GIB}})
    # (queue, name, priority, cpu, job class, created, (cpu slot) | None)
    rows = [(0, "wl-1-0", 100, 4000, 0, 1.0, 0),     # a's own, on x86
            (1, "wl-2-0", 50, 4000, 2, 2.0, 1),      # b's, borrowing arm
            (0, "wl-1-1", 50, 2000, 1, 3.0, None)]   # a's head: either
    n = len(rows)
    return cluster.GroupPlan(
        config=cfg, resources=res,
        queues=[queue("cq-0", 1, 4000, 4000), queue("cq-1", 2, 0, 0)],
        wl_queue=np.array([r[0] for r in rows]),
        wl_name=[r[1] for r in rows],
        wl_priority=np.array([r[2] for r in rows]),
        wl_pods=np.ones(n, dtype=np.int64),
        wl_request=np.array([[r[3], 4 * GIB] for r in rows],
                            dtype=np.int64),
        wl_created=np.array([r[5] for r in rows]),
        wl_running=np.array([r[6] is not None for r in rows]),
        wl_reserved=np.array([r[5] + 100.0 for r in rows]),
        clock_start=1000.0, cycle_s=1.0, groups=groups,
        job_classes=list(cfg["job_constraints"]),
        wl_job=np.array([r[4] for r in rows]),
        wl_flavor=np.array([[-1, -1] if r[6] is None else [r[6], 0]
                            for r in rows]))


@pytest.fixture(scope="module")
def oracle_run():
    plan = oracle_plan()
    records, verdict, _ = witness(plan, 1, 1, finish_fraction_per_round=0.0)
    return plan, records, verdict


@pytest.mark.parametrize("seed", [3, 2_147_483_659])
@pytest.mark.parametrize("policy", ["TryNextFlavor", "Preempt"])
def test_reference_equals_scalar_scheduler(seed, policy):
    cfg = harness.load_config(TOY)
    cfg["deployment"]["flavor_fungibility"]["whenCanPreempt"] = policy
    plan = cluster.plan_cluster(cfg, seed)
    records, verdict, _ = witness(plan, 3, seed,
                                  finish_fraction_per_round=0.05)
    assert verdict["correct"], verdict
    assert "placed" in kind.COMPARED
    facts = verdict["facts"]
    assert facts["cycles_compared"] == 24
    assert facts["cycles_with_evictions"] > 2
    assert facts["cross_queue_evictions"] > 0
    placed = [p for r in records for c in r.cycles for p in c.placed]
    assert len(placed) > 40
    # one flavor a group, sorted and joined: cpu on either architecture,
    # memory on the one flavor of its group, and never where it may not
    assert {p.rpartition("@")[2] for p in placed} == {
        "arm+default-flavor", "default-flavor+x86"}
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    for p in placed:
        k, _, flavors = p.rpartition("@")
        for f in flavors.split("+"):
            g, s = plan.group_of(f)
            assert plan.groups[g].may_take[plan.wl_job[row[k]], s], p


@pytest.mark.parametrize("path", ["windows_applied", "windows_dropped"])
def test_device_solver_equals_reference_and_scalar_scheduler(
        monkeypatch, path):
    """The device path cycle for cycle against both other witnesses,
    with the fused window deciding (its in-kernel walks read the rows'
    resume slots and masks a group) and with every window dropped as at
    the cell's size (the per-cycle engine's walks a group, their join
    and the batched search decide)."""
    if path == "windows_dropped":
        from kueue_tpu.ops import burst
        monkeypatch.setattr(burst, "KC_CAP", 32)
    plan = cluster.plan_cluster(harness.load_config(TOY), 11)
    host, verdict_h, _ = witness(plan, 3, 11,
                                 finish_fraction_per_round=0.05)
    dev, verdict_d, d = witness(plan, 3, 11, use_device=True,
                                finish_fraction_per_round=0.05)
    assert verdict_h["correct"], verdict_h
    assert verdict_d["correct"], verdict_d
    for rh, rd in zip(host, dev, strict=True):
        assert len(rh.cycles) == len(rd.cycles)
        for ch, cd in zip(rh.cycles, rd.cycles):
            for name in kind.COMPARED:
                assert sorted(getattr(ch, name)) == sorted(
                    getattr(cd, name)), name
    solver, pre = d.scheduler.solver.stats, d.scheduler.preemptor.stats
    burst_stats = d._burst_solver.stats
    assert solver["scalar_heads"] == solver["host_cycles"] == 0, solver
    assert solver["scalar_reasons"] == {}, solver
    assert solver["cq_shape_heads"] == 0, solver
    assert pre["host_searches"] == 0, pre
    applied = (burst_stats["burst_cycles_decided"]
               - burst_stats["burst_cycles_discarded"])
    if path == "windows_dropped":
        # two walks a head, and heads whose cpu fits while their memory
        # must be reclaimed, or the reverse
        assert solver["group_walks"] == 2 * solver["walk_heads"] > 0
        assert 0 < solver["split_mode_heads"] < solver["walk_heads"]
        assert 0 < solver["constrained_heads"] < solver["walk_heads"]
        # three job classes, one declared flavor list
        assert solver["eligibility_masks_built"] <= 3
    else:
        assert applied > 0, burst_stats


@pytest.mark.parametrize("broken", kind.CONTROLS)
def test_control_fails_the_comparison(toy_run, oracle_run, broken):
    """Each control is the reference with one stated guarantee switched
    off, put in the program's place: it has to come out not correct.
    The oracle's is read on the cluster written out by hand, where the
    oracle decides."""
    plan, records, verdict = oracle_run if broken == "oracle_off" \
        else toy_run
    assert verdict["correct"], verdict
    control = correct.compare(kind, plan, records, 0, broken=broken)
    assert not control["correct"], broken
    assert control["compared"]["mismatched_cycles"]["value"] >= 1


def test_the_oracle_decides_on_the_cluster_written_out(oracle_run):
    plan, records, verdict = oracle_run
    assert verdict["correct"], verdict
    first = records[0].cycles[0]
    assert first.evicted == ["default/wl-2-0"]
    assert first.preempting == ["default/wl-1-1"]
    placed = [p for c in records[0].cycles for p in c.placed]
    assert "default/wl-1-1@arm+default-flavor" in placed
    # the device path, with its oracle asked in the cpu group alone
    dev, verdict_d, d = witness(plan, 1, 1, use_device=True,
                                finish_fraction_per_round=0.0)
    assert verdict_d["correct"], verdict_d
    assert d.scheduler.solver.stats["scalar_heads"] == 0


def test_control_readings_put_each_control_in_the_programs_place(toy_run):
    import control
    plan, records, _ = toy_run
    assert kind.CONTROLS == flat_labelled_flavor.CONTROLS + (
        "first_group_decides", "one_mask_all_groups")
    readings = control.control_readings(kind, plan, records, 0)
    assert set(readings) == set(kind.CONTROLS)
    for name, row in readings.items():
        if name != "oracle_off":       # see ``oracle_plan``
            assert row["correct"] is False, name
            assert row["mismatched_cycles"] >= 1, name
    assert readings["memory_unenforced"]["quota_violations"] > 0
    # admissions on flavors the jobs may not take are what the ledger's
    # added count is for
    assert readings["eligibility_off"]["quota_violations"] > 0


def test_ledger_counts_a_barred_flavor_and_a_group_left_out(toy_run):
    plan, records, _ = toy_run
    clean = {"quota_violations": 0, "double_admissions": 0,
             "unknown_finishes": 0}
    assert kind.ledger(plan, records) == clean
    row = {plan.key(i): i for i in range(len(plan.wl_name))}

    def first_placed(moved, want):
        return next((c, j, k) for r in moved for c in r.cycles
                    for j, p in enumerate(c.placed)
                    for k in [p.rpartition("@")[0]]
                    if want(plan.job_classes[plan.wl_job[row[k]]]))
    # an admission of a job pinned to arm, put by hand on x86
    moved = copy.deepcopy(records)
    cyc, at, key = first_placed(
        moved, lambda job: job.get("nodeSelector") == {"cpu-arch": "arm"})
    cyc.placed[at] = f"{key}@default-flavor+x86"
    assert kind.ledger(plan, moved)["quota_violations"] >= 1
    assert not correct.compare(kind, plan, moved, 0)["correct"]
    # an admission that names no flavor for its memory
    moved = copy.deepcopy(records)
    cyc, at, key = first_placed(moved, lambda job: True)
    cyc.placed[at] = cyc.placed[at].replace("default-flavor+", "").replace(
        "+default-flavor", "")
    assert kind.ledger(plan, moved)["quota_violations"] >= 1
    # two flavors of one group, or a flavor of none: no admission at all
    moved = copy.deepcopy(records)
    cyc, at, key = first_placed(moved, lambda job: True)
    cyc.placed[at] = f"{key}@arm+default-flavor+x86"
    assert kind.ledger(plan, moved)["double_admissions"] == 1


def test_a_program_without_the_group_counter_is_turned_away(monkeypatch):
    """The commit before the deployment landed walks every head of a
    two-group queue on the host; the kind ends its run before set-up,
    with an exit code other than 0, and a check then measures the cell
    on the program that supports it."""
    from kueue_tpu.ops.solver import CycleSolver
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    real = CycleSolver.__init__

    def without_counter(self, *a, **kw):
        real(self, *a, **kw)
        del self.stats[program.GROUP_COUNTER]
    monkeypatch.setattr(CycleSolver, "__init__", without_counter)
    with pytest.raises(SystemExit) as stop:
        program.build_driver(plan)
    assert stop.value.code not in (0, None)
    assert program.GROUP_COUNTER in str(stop.value.code)
    monkeypatch.undo()
    driver, _ = program.build_driver(plan)
    assert driver.scheduler.solver.stats[program.GROUP_COUNTER] == 0


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


# ---- the plan ------------------------------------------------------------------

def test_groups_job_classes_flavors_and_quota_from_the_plan():
    cfg = harness.load_config(TOY)
    plan = cluster.plan_cluster(cfg, 7)
    assert [g.flavors for g in plan.groups] == [["x86", "arm"],
                                                ["default-flavor"]]
    assert [[plan.resources[r] for r in g.resources]
            for g in plan.groups] == [["cpu"], ["memory"]]
    # the selector's key is a label of the cpu group's flavors only
    assert [g.label_keys for g in plan.groups] == [{"cpu-arch"}, set()]
    assert [j["name"] for j in plan.job_classes] == [
        "medium-x86", "small-any", "small-arm"]
    assert plan.groups[0].may_take.tolist() == [
        [True, False], [True, True], [False, True]]
    assert plan.groups[1].may_take.tolist() == [[True]] * 3
    first = np.searchsorted(plan.wl_queue, np.arange(len(plan.queues)))
    k = np.arange(len(plan.wl_queue)) - first[plan.wl_queue]
    assert np.array_equal(plan.wl_job, np.where(
        k % 3 == 2, 0, np.where(k % 3 == 0, 1, 2)))
    assert (plan.wl_priority[plan.wl_job == 0] == 100).all()
    assert (plan.wl_priority[plan.wl_job != 0] == 50).all()
    res = plan.resources
    cpu, mem = res.index("cpu"), res.index("memory")
    step = {"cpu": 1000, "memory": 8 << 30}
    for c, q in enumerate(plan.queues):
        rows = np.nonzero((plan.wl_queue == c) & plan.wl_running)[0]
        rows = rows[np.argsort(plan.wl_reserved[rows])]
        of = plan.wl_flavor[rows, 0]
        assert plan.groups[0].may_take[plan.wl_job[rows], of].all()
        assert (plan.wl_flavor[rows, 1] == 0).all()
        # replayed one by one: first flavor it may take under its target
        total = int(plan.wl_request[rows, cpu].sum())
        filled = [0, 0]
        for i, f in zip(rows, of):
            mine = np.nonzero(plan.groups[0].may_take[plan.wl_job[i]])[0]
            open_ = [g for g in mine
                     if filled[g] < total * [60, 40][g] // 100]
            assert f == (open_[0] if open_ else mine[-1])
            filled[f] += int(plan.wl_request[i, cpu])
        for s, f in enumerate(("x86", "arm")):
            used = int(plan.wl_request[rows[of == s], cpu].sum())
            assert set(q.nominal[f]) == {"cpu"}
            assert 0 <= q.nominal[f]["cpu"] - used < step["cpu"]
        used = int(plan.wl_request[rows, mem].sum())
        assert set(q.nominal["default-flavor"]) == {"memory"}
        assert 0 <= q.nominal["default-flavor"]["memory"] - used < step[
            "memory"]
    assert (plan.wl_flavor[~plan.wl_running] == -1).all()


@pytest.mark.parametrize("edit, says", [
    (lambda c: c["job_constraints"][0].update(may_take=["x86"]),
     "medium-x86"),
    (lambda c: c["deployment"]["resource_groups"][1].update(
        flavors=["arm"]), "one resource group each"),
    (lambda c: c["deployment"]["resource_groups"].pop(), "cover"),
])
def test_a_file_that_breaks_the_groups_rule_is_refused(edit, says):
    cfg = harness.load_config(TOY)
    edit(cfg)
    with pytest.raises(ValueError, match=says):
        cluster.plan_cluster(cfg, 1)


# ---- the cell's size, from the configuration's file alone ----------------

def test_the_configuration_is_the_first_one_in_two_groups():
    """Every number of ``mk8-1kcq-zipf`` kept, key by key but for the
    groups; what is added is listed under ``assumed``; nothing is
    reduced."""
    cfg, first = harness.load_config(CONFIG), harness.load_config(FIRST)
    assert cfg["kind"] == "flat_two_group" and cfg["reduced"] == []
    for key in ("classes", "population", "clock", "fused_path_limits"):
        assert cfg[key] == first[key], key
    dep, dep1 = cfg["deployment"], first["deployment"]
    added = {"resource_groups", "flavor_specs", "flavor_fungibility"}
    assert set(dep) - set(dep1) == added
    assert set(dep1) - set(dep) == {"flavor"}
    for key in set(dep) - added:
        assert dep[key] == dep1[key], key
    assert dep["resource_groups"] == [
        {"coveredResources": ["cpu"], "flavors": ["x86", "arm"],
         "flavor_target_percent": [60, 40]},
        {"coveredResources": ["memory"], "flavors": ["default-flavor"],
         "flavor_target_percent": [100]}]
    assert dep["flavor_specs"] == {
        "x86": {"nodeLabels": {"cpu-arch": "x86"}},
        "arm": {"nodeLabels": {"cpu-arch": "arm"}}, "default-flavor": {}}
    assert dep["flavor_fungibility"] == {
        "whenCanBorrow": "Borrow", "whenCanPreempt": "TryNextFlavor"}
    assert set(first["assumed"]) <= set(cfg["assumed"])
    for name in ("flavor_labels", "job_constraints", "pods_not_covered",
                 "flavor_of_a_running_workload", "quota_rule",
                 "flavor_fungibility", "scale_from_the_manager"):
        assert name in cfg["assumed"], name
    for name in ("two_resource_groups", "one_flavor_a_group",
                 "walk_a_group", "node_labels"):
        assert name in cfg["documented"], name
    toy = harness.load_config(TOY)
    for key in ("resource_groups", "flavor_specs", "resources",
                "flavor_fungibility", "preemption", "borrowing_limit"):
        assert toy["deployment"][key] == dep[key], key
    assert toy["job_constraints"] == cfg["job_constraints"]
    assert toy["classes"] == cfg["classes"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == []
    cell = next(w for w in manifest["workloads"]
                if w["config"] == cfg["name"])
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "mk8-2group.backlog", "backlog-flavors", 1)
    new = ["group_walk_ms", "group_walks_per_round",
           "split_mode_heads_per_round", "cq_shape_heads_per_round",
           "grouped_decide_roofline"]
    assert [m["name"] for m in manifest["per_layer"][-5:]] == new
    for m in manifest["per_layer"][-5:]:
        assert m["workloads"] == [w["name"] for w in manifest["workloads"]]


def test_grid_and_the_plan_at_the_cells_size():
    """M and the slots are the first cell's; the plan at the cell's size
    gives every job class work on the flavors it may take, both groups
    of every queue full, and a decided cycle's bytes counted a (flavor,
    resource) pair."""
    cfg = harness.load_config(CONFIG)
    rows = cluster.queue_rows(cfg)
    assert rows["M"] == 65_536 and rows["slots"] == 65_536_000
    plan = cluster.plan_cluster(cfg, 2_147_483_700)
    run = plan.wl_running
    assert int(run.sum()) == int(sum(rows["running"]))
    cpu_of = plan.wl_flavor[run, 0]
    assert plan.groups[0].may_take[plan.wl_job[run], cpu_of].all()
    assert (plan.wl_flavor[run, 1] == 0).all()
    per_job = np.bincount(plan.wl_job, minlength=3) / len(plan.wl_job)
    assert (abs(per_job - 1 / 3) < 0.01).all()
    # two of three classes are pinned to an architecture
    pinned = ~plan.groups[0].may_take.all(axis=1)
    assert pinned.tolist() == [True, False, True]
    held = np.bincount(cpu_of, minlength=2)
    assert (held > 50_000).all()
    assert all(q.nominal["x86"]["cpu"] > 0
               and q.nominal["default-flavor"]["memory"] > 0
               for q in plan.queues)
    problem = kind.problem(cfg, plan)
    import peaks
    assert problem["real_rows"] == rows["preempting_forest_rows"]
    assert peaks.row_bytes(problem["resources"]) == 25
    # three (flavor, resource) pairs a queue, 12 B each
    assert (problem["queues"] * peaks.queue_bytes(problem["resources"])
            == 1000 * 3 * 12)
