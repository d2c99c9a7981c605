"""The deployment kind ``flat_labelled_flavor``: its plain reference
against the program's host scalar scheduler and against the device
solver with windows applied and with windows dropped, its controls, its
ledger, and its sizes from the configuration's file alone.

Three witnesses on one cluster, same finishes, same clock: the plain
reference (which imports nothing of the program), the host
``FlavorAssigner`` with the ``PreemptionOracle``, and the device path
(the masked vector classify, the batched search, the fused window with
its row masks).  Every cycle's admitted, evicted, skipped and preempting
sets and the flavor of every admission have to agree.
"""

import ast
import copy
import json
import os

import numpy as np
import pytest

import correct
import harness
from deployment_kinds import flat_labelled_flavor as kind
from deployment_kinds import flat_multi_flavor
from deployment_kinds.flat_labelled_flavor import cluster, program, reference
from traffic_kinds import burst_rounds_flavors

from conftest import BENCH, HERE, ROOT

TOY = os.path.join(HERE, "data", "toy-labelled.json")
CONFIG = os.path.join(BENCH, "configs", "mk8-1kcq-labelled.json")
SECOND = os.path.join(BENCH, "configs", "mk8-1kcq-4flavor.json")


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
    # the walk places work on every flavor, and never where it may not
    assert {p.rpartition("@")[2] for p in placed} == set(plan.flavors)
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    for p in placed:
        k, _, flavor = p.rpartition("@")
        assert plan.may_take[plan.wl_job[row[k]],
                             plan.flavors.index(flavor)], p


@pytest.mark.parametrize("path", ["windows_applied", "windows_dropped"])
def test_device_solver_equals_reference_and_scalar_scheduler(
        monkeypatch, path):
    """The device path cycle for cycle against both other witnesses,
    with the fused window deciding (its in-kernel walk reads the rows'
    masks) and with every window dropped as at the cell's size (the
    per-cycle engine's masked classify and batched search decide)."""
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
    assert pre["host_searches"] == 0, pre
    applied = (burst_stats["burst_cycles_decided"]
               - burst_stats["burst_cycles_discarded"])
    if path == "windows_dropped":
        assert solver["walk_heads"] > 0
        assert 0 < solver["walk_ineligible_slots"] < solver["walk_slots"]
        assert 0 < solver["constrained_heads"] < solver["walk_heads"]
        # four job classes, one flavor list
        assert solver["eligibility_masks_built"] <= 4
    else:
        assert applied > 0, burst_stats


@pytest.mark.parametrize("broken", kind.CONTROLS)
def test_control_fails_the_comparison(toy_run, broken):
    """Each control is the reference with one stated guarantee switched
    off, put in the program's place: it has to come out not correct."""
    plan, records, verdict = toy_run
    assert verdict["correct"], verdict
    control = correct.compare(kind, plan, records, 0, broken=broken)
    assert not control["correct"], broken
    assert control["compared"]["mismatched_cycles"]["value"] >= 1


def test_control_readings_put_each_control_in_the_programs_place(toy_run):
    import control
    plan, records, _ = toy_run
    assert kind.CONTROLS == flat_multi_flavor.CONTROLS + (
        "eligibility_off",)
    readings = control.control_readings(kind, plan, records, 0)
    assert set(readings) == set(kind.CONTROLS)
    for name, row in readings.items():
        assert row["correct"] is False and row["mismatched_cycles"] >= 1, name
    assert readings["memory_unenforced"]["quota_violations"] > 0
    # admissions on flavors the jobs may not take are what the ledger's
    # added count is for
    assert readings["eligibility_off"]["quota_violations"] > 0


def test_ledger_counts_an_admission_on_a_barred_flavor(toy_run):
    plan, records, _ = toy_run
    assert kind.ledger(plan, records) == {
        "quota_violations": 0, "double_admissions": 0,
        "unknown_finishes": 0}
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    moved = copy.deepcopy(records)
    # the first admission of a job that may not take every flavor, put
    # by hand on one it may not take
    cyc, at, key, barred = next(
        (c, j, k, plan.flavors[int(np.argmin(may))])
        for r in moved for c in r.cycles for j, p in enumerate(c.placed)
        for k in [p.rpartition("@")[0]]
        for may in [plan.may_take[plan.wl_job[row[k]]]] if not may.all())
    cyc.placed[at] = f"{key}@{barred}"
    second = flat_multi_flavor.ledger(plan, moved)["quota_violations"]
    assert kind.ledger(plan, moved)["quota_violations"] == second + 1
    # ... and the comparison's own field fails it too
    assert not correct.compare(kind, plan, moved, 0)["correct"]


def test_a_program_without_the_eligibility_counter_is_turned_away(
        monkeypatch):
    """The commit before the deployment landed walks every head of a
    labelled queue on the host; the kind ends its run before set-up,
    with an exit code other than 0, and a check then measures the cell
    on the program that supports it."""
    from kueue_tpu.ops.solver import CycleSolver
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    real = CycleSolver.__init__

    def without_counter(self, *a, **kw):
        real(self, *a, **kw)
        del self.stats[program.ELIGIBILITY_COUNTER]
    monkeypatch.setattr(CycleSolver, "__init__", without_counter)
    with pytest.raises(SystemExit) as stop:
        program.build_driver(plan)
    assert stop.value.code not in (0, None)
    assert program.ELIGIBILITY_COUNTER in str(stop.value.code)
    monkeypatch.undo()
    driver, _ = program.build_driver(plan)
    assert driver.scheduler.solver.stats[program.ELIGIBILITY_COUNTER] == 0


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


# ---- the rule, and the plan it gives ------------------------------------------

SPOT = {"key": "spot", "value": "true", "effect": "NoSchedule"}


@pytest.mark.parametrize("job, flavor, keys, want", [
    ({}, {"nodeTaints": [SPOT]}, set(), False),
    ({"tolerations": [{"key": "spot", "operator": "Exists"}]},
     {"nodeTaints": [SPOT]}, set(), True),
    ({"tolerations": [{"key": "spot", "value": "false"}]},
     {"nodeTaints": [SPOT]}, set(), False),
    ({"tolerations": [{"operator": "Exists", "effect": "NoExecute"}]},
     {"nodeTaints": [SPOT]}, set(), False),
    ({}, {"nodeTaints": [dict(SPOT, effect="PreferNoSchedule")]}, set(),
     True),
    ({}, {"nodeTaints": [SPOT], "tolerations": [
        {"key": "spot", "value": "true"}]}, set(), True),
    ({"nodeSelector": {"tier": "spot"}}, {"nodeLabels": {"tier": "spot"}},
     {"tier"}, True),
    ({"nodeSelector": {"tier": "spot"}}, {"nodeLabels": {"tier": "od"}},
     {"tier"}, False),
    ({"nodeSelector": {"tier": "spot"}}, {"nodeLabels": {}}, {"tier"},
     False),
    ({"nodeSelector": {"arch": "amd64"}}, {"nodeLabels": {"tier": "od"}},
     {"tier"}, True),
])
def test_the_rule_written_out(job, flavor, keys, want):
    assert reference.eligible(job, flavor, keys) is want


def test_the_rule_is_the_programs_on_the_toys_classes():
    """The plan's ``may_take`` (the reference's rule on the file's data)
    against the program's mask on the objects ``program.py`` builds."""
    from kueue_tpu.ops.eligibility import FlavorList
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    driver, _ = program.build_driver(plan, use_device=False)
    fl = FlavorList([driver.cache.resource_flavors[f]
                     for f in plan.flavors])
    seen = set()
    for i, name in enumerate(plan.wl_name):
        j = int(plan.wl_job[i])
        if j in seen:
            continue
        seen.add(j)
        mask = fl.skip_mask(driver.workload(plan.key(i)).pod_sets[0])
        assert [not mask >> s & 1 for s in range(len(plan.flavors))] \
            == plan.may_take[j].tolist(), plan.job_classes[j]["name"]
    assert len(seen) == 4


def test_job_classes_flavors_and_quota_from_the_plan():
    cfg = harness.load_config(TOY)
    plan = cluster.plan_cluster(cfg, 7)
    assert plan.flavors == ["reserved", "on-demand", "spot-a", "spot-b"]
    assert [j["name"] for j in plan.job_classes] == [
        "small-spot-zone-b", "small-spot", "small-any", "medium"]
    assert plan.may_take.tolist() == [
        [False, False, False, True], [False, False, True, True],
        [True, True, True, True], [True, True, False, False]]
    # a job's class from its index k within its queue
    first = np.searchsorted(plan.wl_queue, np.arange(len(plan.queues)))
    k = np.arange(len(plan.wl_queue)) - first[plan.wl_queue]
    want = np.where(k % 3 == 2, 3, np.where(
        k % 3 == 0, 2, np.where((k // 3) % 4 == 3, 0, 1)))
    assert np.array_equal(plan.wl_job, want)
    assert [n.rsplit("-", 1)[1] for n in plan.wl_name] == [
        str(x) for x in k]
    # the class is the population's: medium where k mod 3 = 2
    assert (plan.wl_priority[plan.wl_job == 3] == 100).all()
    assert (plan.wl_priority[plan.wl_job != 3] == 50).all()
    res = plan.resources
    cpu = res.index("cpu")
    step = {"cpu": 1000, "memory": 8 << 30}
    share = cfg["deployment"]["flavor_target_percent"]
    for c, q in enumerate(plan.queues):
        rows = np.nonzero((plan.wl_queue == c) & plan.wl_running)[0]
        rows = rows[np.argsort(plan.wl_reserved[rows])]
        of = plan.wl_flavor[rows]
        assert plan.may_take[plan.wl_job[rows], of].all()
        # replayed one by one: first flavor it may take under its target
        total = int(plan.wl_request[rows, cpu].sum())
        filled = [0] * 4
        for i, f in zip(rows, of):
            mine = np.nonzero(plan.may_take[plan.wl_job[i]])[0]
            open_ = [g for g in mine if filled[g] < total * share[g] // 100]
            assert f == (open_[0] if open_ else mine[-1])
            filled[f] += int(plan.wl_request[i, cpu])
        for fi, f in enumerate(plan.flavors):
            used = plan.wl_request[rows[of == fi]].sum(axis=0)
            for ri, r in enumerate(res):
                assert 0 <= q.nominal[f][r] - used[ri] < step[r]
    assert (plan.wl_flavor[~plan.wl_running] == -1).all()


def test_a_file_whose_may_take_disagrees_with_the_rule_is_refused():
    cfg = harness.load_config(TOY)
    cfg["job_constraints"][3]["may_take"] = ["reserved"]
    with pytest.raises(ValueError, match="medium"):
        cluster.plan_cluster(cfg, 1)


# ---- the cell's size, from the configuration's file alone ----------------

def test_the_configuration_is_the_second_one_declared():
    """Every number of ``mk8-1kcq-4flavor`` kept; what is added is
    listed under ``assumed``; nothing is reduced."""
    cfg, second = harness.load_config(CONFIG), harness.load_config(SECOND)
    assert cfg["kind"] == "flat_labelled_flavor" and cfg["reduced"] == []
    for key in ("classes", "population", "clock", "fused_path_limits"):
        assert cfg[key] == second[key], key
    dep, dep2 = cfg["deployment"], second["deployment"]
    added = {"flavor_specs", "flavor_target_percent"}
    assert set(dep) - set(dep2) == added
    assert set(dep2) - set(dep) == {"flavor_fill_percent"}
    for key in set(dep) - added:
        assert dep[key] == dep2[key], key
    assert dep["flavor_target_percent"] == [40, 30, 15, 15]
    assert dep["flavor_fungibility"] == {
        "whenCanBorrow": "Borrow", "whenCanPreempt": "TryNextFlavor"}
    taint = {"key": "spot", "value": "true", "effect": "NoSchedule"}
    zone = "topology.kubernetes.io/zone"
    assert dep["flavor_specs"] == {
        "reserved": {"nodeLabels": {"instance-type": "reserved"}},
        "on-demand": {"nodeLabels": {"instance-type": "on-demand"}},
        "spot-a": {"nodeLabels": {"instance-type": "spot", zone: "zone-a"},
                   "nodeTaints": [taint]},
        "spot-b": {"nodeLabels": {"instance-type": "spot", zone: "zone-b"},
                   "nodeTaints": [taint]}}
    for name in ("flavor_labels_and_taints", "job_constraints",
                 "flavor_of_a_running_workload", "quota_rule"):
        assert name in cfg["assumed"], name
    assert "plain_flavors" not in cfg["assumed"]
    toy = harness.load_config(TOY)
    for key in ("flavors", "flavor_specs", "flavor_target_percent",
                "resources", "flavor_fungibility", "preemption"):
        assert toy["deployment"][key] == dep[key], key
    assert toy["job_constraints"] == cfg["job_constraints"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == []
    cell = next(w for w in manifest["workloads"]
                if w["config"] == cfg["name"])
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "mk8-labelled.backlog", "backlog-flavors", 1)


def test_grid_and_the_plan_at_the_cells_size():
    """M and the slots are the second cell's; the plan at the cell's
    size gives every job class work on the flavors it may take, every
    queue full in every flavor, and a decided cycle's bytes counted with
    the eligibility byte a row."""
    cfg = harness.load_config(CONFIG)
    rows = cluster.queue_rows(cfg)
    assert rows["M"] == 65_536 and rows["slots"] == 65_536_000
    plan = cluster.plan_cluster(cfg, 2_147_483_700)
    run = plan.wl_running
    assert int(run.sum()) == int(sum(rows["running"]))
    assert plan.may_take[plan.wl_job[run], plan.wl_flavor[run]].all()
    per_job = np.bincount(plan.wl_job, minlength=4)
    n = len(plan.wl_job)
    # k mod 3: a third each; of k mod 3 = 1 a quarter pinned to spot-b
    assert abs(per_job[3] / n - 1 / 3) < 0.01
    assert abs(per_job[2] / n - 1 / 3) < 0.01
    assert abs(per_job[0] / n - 1 / 12) < 0.01
    assert abs(per_job[1] / n - 1 / 4) < 0.01
    # three of four classes are constrained: about two thirds of all jobs
    constrained = ~plan.may_take.all(axis=1)
    assert constrained.tolist() == [True, True, False, True]
    assert (per_job[constrained].sum() / n) > 0.6
    held = np.bincount(plan.wl_flavor[run], minlength=4)
    assert (held > 10_000).all()
    assert all(q.nominal[f]["cpu"] > 0 for q in plan.queues[:50]
               for f in ("reserved", "on-demand"))
    problem = kind.problem(cfg, plan)
    second = flat_multi_flavor.problem(cfg, plan)
    assert problem["real_rows"] == second["real_rows"]
    import peaks
    assert (peaks.burst_launch_bytes(**{
        "real_rows": problem["real_rows"], "queues": problem["queues"],
        "n_resources": problem["resources"]})
        - peaks.burst_launch_bytes(
            second["real_rows"], second["queues"], second["resources"])
        ) // 24 == -(-problem["real_rows"] // 24)
