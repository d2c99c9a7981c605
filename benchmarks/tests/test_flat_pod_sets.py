"""The deployment kind ``flat_pod_sets``: its plain reference against the
program's host scalar scheduler and against the device solver with
windows applied and with windows dropped, its controls, its ledger, and
its sizes from the configuration's file alone.

Three witnesses on one cluster, same finishes, same clock: the plain
reference (which imports nothing of the program), the host
``FlavorAssigner`` with the ``PreemptionOracle``, and the device path
(one vector pass a PodSet, each charged with the earlier ones' choices,
the batched search over the pairs short of quota in any PodSet, the
fused window with its per-PodSet request, resume and mask planes).
Every cycle's admitted, evicted, skipped and preempting sets and the
flavor of every resource of every PodSet of every admission have to
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
from deployment_kinds import flat_pod_sets as kind
from deployment_kinds import flat_two_group
from deployment_kinds.flat_one_flavor.cluster import Queue
from deployment_kinds.flat_pod_sets import cluster, program, reference
from traffic_kinds import burst_rounds_podsets

from conftest import BENCH, HERE, ROOT

TOY = os.path.join(HERE, "data", "toy-gangs.json")
CONFIG = os.path.join(BENCH, "configs", "mk8-1kcq-gangs.json")
FOURTH = os.path.join(BENCH, "configs", "mk8-1kcq-2group.json")
GIB = 1 << 30
NEW_METRICS = {
    # name: (layer, moves, source)
    "podset_walk_ms": ("scheduler", "cycle_ms", "program_span"),
    "podset_walks_per_round": ("scheduler", "cycle_ms", "program_counter"),
    "gang_heads_per_round": ("scheduler", "cycle_ms", "program_counter"),
    "charged_walks_per_round": ("scheduler", "cycle_ms", "program_counter"),
    "split_flavor_gangs_per_round": ("scheduler", "cycle_ms",
                                     "program_counter"),
    "podset_scalar_heads_per_round": ("scheduler", "cycle_ms",
                                      "program_counter"),
    "gang_decide_roofline": ("device", "admissions_per_s", "device_trace"),
}


def traffic_params(**over):
    with open(os.path.join(BENCH, "traffic", "backlog-podsets.json")) as f:
        return dict(json.load(f), **over)


def witness(plan, rounds, seed, use_device=False, **over):
    """``rounds`` rounds of the cell's traffic through the host scalar
    scheduler or the device solver, and the comparison's verdict."""
    driver, clock = program.build_driver(plan, use_device=use_device)
    traffic = burst_rounds_podsets.Traffic(traffic_params(**over), plan,
                                           seed)
    records = [traffic.round(driver, clock) for _ in range(rounds)]
    return records, correct.compare(kind, plan, records, 0), driver


@pytest.fixture(scope="module")
def toy_run():
    plan = cluster.plan_cluster(harness.load_config(TOY), 5)
    records, verdict, _ = witness(plan, 3, 5, finish_fraction_per_round=0.05)
    return plan, records, verdict


def hand_plan(rows, x86=4000, arm=4000, memory=64):
    """Two queues of one cohort, by hand.  ``rows``: (queue, name,
    priority, created, [(PodSet name, pods, cpu a pod, constraint class,
    cpu slot held or None)]); every pod asks 4 GiB."""
    cfg = harness.load_config(TOY)
    res = ["cpu", "memory"]
    groups = cluster.plan_groups(cfg, res)

    def queue(name, rank, x86, arm):
        return Queue(
            name=name, cohort="cohort-0", rank=rank,
            nominal={"x86": {"cpu": x86}, "arm": {"cpu": arm},
                     "default-flavor": {"memory": memory * GIB}},
            borrowing_limit={"x86": {"cpu": 8000}, "arm": {"cpu": 8000},
                             "default-flavor": {"memory": 64 * GIB}})
    ps = [(i, p) for i, r in enumerate(rows) for p in r[4]]
    n = len(rows)
    first = np.concatenate(([0], np.cumsum([len(r[4]) for r in rows])))
    pods = np.array([p[1] for _, p in ps], dtype=np.int64)
    request = np.array([[p[1] * p[2], p[1] * 4 * GIB] for _, p in ps],
                       dtype=np.int64)
    wl_request = np.array([request[first[i]:first[i + 1]].sum(axis=0)
                           for i in range(n)])
    return cluster.PodSetPlan(
        config=cfg, resources=res,
        queues=[queue("cq-0", 1, x86, arm), queue("cq-1", 2, 0, 0)],
        wl_queue=np.array([r[0] for r in rows]),
        wl_name=[r[1] for r in rows],
        wl_priority=np.array([r[2] for r in rows]),
        wl_pods=np.array([pods[first[i]:first[i + 1]].sum()
                          for i in range(n)]),
        wl_request=wl_request,
        wl_created=np.array([r[3] for r in rows]),
        wl_running=np.array([r[4][0][4] is not None for r in rows]),
        wl_reserved=np.array([r[3] + 100.0 for r in rows]),
        clock_start=1000.0, cycle_s=1.0, groups=groups,
        job_classes=list(cfg["job_constraints"]),
        wl_first=first, ps_name=[p[0] for _, p in ps], ps_pods=pods,
        ps_request=request, ps_job=np.array([p[3] for _, p in ps]),
        ps_flavor=np.array([[-1, -1] if p[4] is None else [p[4], 0]
                            for _, p in ps]))


def oracle_plan():
    """Where the oracle decides, at a charged quantity.  a's x86 is full
    of its own higher-priority work, its arm is lent to b; a's gang has
    its launcher on arm (class small-arm stands in: one flavor, Preempt)
    and workers that may take either: arm is asked about at the
    workers' cpu plus the launcher's, Reclaim beats Preempt on x86, and
    b's borrower goes."""
    return hand_plan([
        (0, "wl-1-0", 100, 1.0, [("main", 1, 4000, 0, 0)]),
        (1, "wl-2-0", 50, 2.0, [("main", 1, 4000, 2, 1)]),
        (0, "wl-1-1", 50, 3.0, [("launcher", 1, 1000, 2, None),
                                ("workers", 1, 1000, 1, None)])])


def charged_plan():
    """Where only the charge decides.  x86 has 3 of its 4 cpu free and
    arm none for a; the launcher takes 1 of x86, and the workers, free
    to take either, ask 3: alone they fit x86, charged they do not and
    must preempt a's own lower-priority work."""
    return hand_plan([
        (0, "wl-1-0", 10, 1.0, [("main", 1, 1000, 0, 0)]),
        (0, "wl-1-1", 10, 2.0, [("main", 1, 4000, 2, 1)]),
        (0, "wl-1-2", 50, 3.0, [("launcher", 1, 1000, 3, None),
                                ("workers", 3, 1000, 0, None)])])


@pytest.fixture(scope="module")
def oracle_run():
    plan = oracle_plan()
    records, verdict, _ = witness(plan, 1, 1, finish_fraction_per_round=0.0)
    return plan, records, verdict


@pytest.fixture(scope="module")
def charged_run():
    plan = charged_plan()
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
    placed = [p for r in records for c in r.cycles for p in c.placed]
    assert len(placed) > 80
    # one entry a (PodSet, resource): cpu on either architecture,
    # memory on the one flavor of its group, the launcher on x86 alone
    what = {p.rpartition("@")[2] for p in placed}
    assert what == {
        "main:cpu=x86", "main:cpu=arm", "main:memory=default-flavor",
        "launcher:cpu=x86", "launcher:memory=default-flavor",
        "workers:cpu=x86", "workers:cpu=arm",
        "workers:memory=default-flavor"}
    # gangs whose PodSets stand on two flavors of one group
    by_key: dict = {}
    for p in placed:
        k, _, rest = p.rpartition("@")
        by_key.setdefault(k, set()).add(rest)
    assert any({"launcher:cpu=x86", "workers:cpu=arm"} <= v
               for v in by_key.values())


@pytest.mark.parametrize("path", ["windows_applied", "windows_dropped"])
def test_device_solver_equals_reference_and_scalar_scheduler(
        monkeypatch, path):
    """The device path cycle for cycle against both other witnesses,
    with the fused window deciding (its in-kernel passes read the rows'
    requests, resume slots and masks a PodSet) and with every window
    dropped as at the cell's size (the per-cycle engine's passes and the
    batched search decide)."""
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
    assert solver["host_cycles"] == 0, solver
    assert solver["cq_shape_heads"] == 0, solver
    assert solver["scalar_reasons"].get("multi_podset", 0) == 0, solver
    assert pre["host_searches"] == 0, pre
    assert d.scheduler.solver._structure.pod_sets == 2
    applied = (burst_stats["burst_cycles_decided"]
               - burst_stats["burst_cycles_discarded"])
    if path == "windows_dropped":
        # about half the heads are gangs, every gang's second pass is
        # charged (its memory at the least), and some stand on two
        # flavors of the cpu group
        assert solver["walk_heads"] < solver["podset_walks"] <= (
            2 * solver["walk_heads"])
        assert 0.3 * solver["walk_heads"] < solver["gang_heads"] < (
            solver["walk_heads"])
        assert 0 < solver["charged_walks"] <= solver["gang_heads"]
        assert solver["split_flavor_gangs"] > 0
        assert solver["group_walks"] == 2 * solver["podset_walks"]
        # a head given to the host walk is one whose earlier PodSet's
        # pick was the oracle's, and nothing else
        assert solver["scalar_heads"] == solver["podset_scalar_heads"]
        assert solver["scalar_heads"] <= 0.02 * solver["walk_heads"]
    else:
        assert applied > 0, burst_stats


@pytest.mark.parametrize("broken", kind.CONTROLS)
def test_control_fails_the_comparison(toy_run, oracle_run, charged_run,
                                      broken):
    """Each control is the reference with one stated guarantee switched
    off, put in the program's place: it has to come out not correct.
    The oracle's is read on the cluster written out by hand where the
    oracle decides, and the two PodSet controls on the toy and on the
    one where only the charge decides."""
    plan, records, verdict = oracle_run if broken == "oracle_off" \
        else toy_run
    assert verdict["correct"], verdict
    control = correct.compare(kind, plan, records, 0, broken=broken)
    assert not control["correct"], broken
    assert control["compared"]["mismatched_cycles"]["value"] >= 1
    if broken in ("podsets_uncharged", "podsets_summed"):
        plan, records, verdict = charged_run
        assert verdict["correct"], verdict
        control = correct.compare(kind, plan, records, 0, broken=broken)
        assert not control["correct"], broken


def test_the_charge_decides_on_the_cluster_written_out(charged_run):
    plan, records, verdict = charged_run
    assert verdict["correct"], verdict
    first = records[0].cycles[0]
    # charged, the workers' 3 + 1 cpu do not fit x86's free 3: the gang
    # preempts a's own lower-priority workload on x86
    assert first.preempting == ["default/wl-1-2"]
    assert first.evicted == ["default/wl-1-0"]
    placed = [p for c in records[0].cycles for p in c.placed]
    assert {p for p in placed if p.startswith("default/wl-1-2@")} == {
        "default/wl-1-2@launcher:cpu=x86",
        "default/wl-1-2@launcher:memory=default-flavor",
        "default/wl-1-2@workers:cpu=x86",
        "default/wl-1-2@workers:memory=default-flavor"}
    dev, verdict_d, d = witness(plan, 1, 1, use_device=True,
                                finish_fraction_per_round=0.0)
    assert verdict_d["correct"], verdict_d
    # (decided inside the fused window here: one cohort, in the
    # kernel's envelope)
    assert d.scheduler.solver.stats["scalar_heads"] == 0


def test_the_oracle_decides_at_the_charged_quantity(oracle_run):
    plan, records, verdict = oracle_run
    assert verdict["correct"], verdict
    first = records[0].cycles[0]
    assert first.evicted == ["default/wl-2-0"]
    assert first.preempting == ["default/wl-1-1"]
    placed = [p for c in records[0].cycles for p in c.placed]
    assert "default/wl-1-1@workers:cpu=arm" in placed
    assert "default/wl-1-1@launcher:cpu=arm" in placed
    # the device path, its oracle asked for the last PodSet at 2,000 m
    dev, verdict_d, d = witness(plan, 1, 1, use_device=True,
                                finish_fraction_per_round=0.0)
    assert verdict_d["correct"], verdict_d
    assert d.scheduler.solver.stats["scalar_heads"] == 0
    assert d.scheduler.preemptor.stats["oracle_specs"] >= 1


def test_control_readings_put_each_control_in_the_programs_place(
        toy_run, charged_run):
    import control
    plan, records, _ = toy_run
    assert kind.CONTROLS == flat_two_group.CONTROLS + (
        "podsets_uncharged", "podsets_summed")
    readings = control.control_readings(kind, plan, records, 0)
    assert set(readings) == set(kind.CONTROLS)
    for name, row in readings.items():
        if name != "oracle_off":       # see ``oracle_plan``
            assert row["correct"] is False, name
            assert row["mismatched_cycles"] >= 1, name
    assert readings["memory_unenforced"]["quota_violations"] > 0
    assert readings["eligibility_off"]["quota_violations"] > 0
    # a gang decided as one PodSet leaves its other PodSets' resources
    # on no flavor.  PodSets walked uncharged do not end over quota (the
    # admit step fits the summed usage again): they are skipped there,
    # where the program preempts or takes the next flavor
    assert readings["podsets_summed"]["quota_violations"] > 0
    plan, records, _ = charged_run
    readings = control.control_readings(kind, plan, records, 0)
    assert readings["podsets_uncharged"]["mismatched_cycles"] >= 1
    assert readings["podsets_uncharged"]["quota_violations"] == 0


def test_ledger_counts_a_podset_left_out_and_a_barred_flavor(toy_run):
    plan, records, _ = toy_run
    clean = {"quota_violations": 0, "double_admissions": 0,
             "unknown_finishes": 0}
    assert kind.ledger(plan, records) == clean

    def first_gang(moved):
        return next((c, k) for r in moved for c in r.cycles
                    for p in c.placed
                    for k in [p.rpartition("@")[0]]
                    if "@workers:" in p)
    # a launcher, pinned to x86, put by hand on arm
    moved = copy.deepcopy(records)
    cyc, key = first_gang(moved)
    cyc.placed = [p.replace("launcher:cpu=x86", "launcher:cpu=arm")
                  if p.startswith(key + "@") else p for p in cyc.placed]
    assert kind.ledger(plan, moved)["quota_violations"] >= 1
    assert not correct.compare(kind, plan, moved, 0)["correct"]
    # a gang admitted in part: its workers on no flavor
    moved = copy.deepcopy(records)
    cyc, key = first_gang(moved)
    cyc.placed = [p for p in cyc.placed
                  if not p.startswith(key + "@workers:")]
    assert kind.ledger(plan, moved)["quota_violations"] >= 2
    # a PodSet the workload has not, and one (PodSet, resource) twice:
    # no admission at all
    moved = copy.deepcopy(records)
    cyc, key = first_gang(moved)
    cyc.placed.append(f"{key}@sidecar:cpu=x86")
    assert kind.ledger(plan, moved)["double_admissions"] == 1
    moved = copy.deepcopy(records)
    cyc, key = first_gang(moved)
    cyc.placed.append(f"{key}@workers:cpu=x86")
    assert kind.ledger(plan, moved)["double_admissions"] == 1
    # memory on a cpu flavor
    moved = copy.deepcopy(records)
    cyc, key = first_gang(moved)
    cyc.placed = [p.replace("workers:memory=default-flavor",
                            "workers:memory=x86")
                  if p.startswith(key + "@") else p for p in cyc.placed]
    led = kind.ledger(plan, moved)
    # (a second flavor of the cpu group for that PodSet where its cpu
    # stands on arm: no admission; else a resource on another group's)
    assert led["quota_violations"] + led["double_admissions"] >= 1


def test_a_program_without_the_podset_counter_is_turned_away(monkeypatch):
    """The commit before the deployment landed walks every gang on the
    host; the kind ends its run before set-up, with an exit code other
    than 0, and a check then measures the cell on the program that
    supports it."""
    from kueue_tpu.ops.solver import CycleSolver
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    real = CycleSolver.__init__

    def without_counter(self, *a, **kw):
        real(self, *a, **kw)
        del self.stats[program.PODSET_COUNTER]
    monkeypatch.setattr(CycleSolver, "__init__", without_counter)
    with pytest.raises(SystemExit) as stop:
        program.build_driver(plan)
    assert stop.value.code not in (0, None)
    assert program.PODSET_COUNTER in str(stop.value.code)
    monkeypatch.undo()
    driver, _ = program.build_driver(plan)
    assert driver.scheduler.solver.stats[program.PODSET_COUNTER] == 0


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


def test_the_warm_up_builds_the_ladder_at_the_populations_podsets():
    """``warm_up`` raises the structure's PodSet extent before the
    program's ladder is built, so the scans a gang's cycle launches
    (decision pairs a (PodSet, resource) wide) are compiled in set-up:
    a traced toy run builds nothing in its window
    (test_rehearsal.py holds that for every cell)."""
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    driver, _ = program.build_driver(plan)
    done = program.warm_up(driver, plan)
    assert done["pod_sets"] == 2 and done["ladder"]
    solver = driver.scheduler.solver
    st = solver._structure
    assert st.pod_sets == 2
    R = len(st.resource_names)
    assert solver._pair_width(st) == 8 > 2 * R


# ---- the plan ------------------------------------------------------------------

def test_podsets_constraints_flavors_and_quota_from_the_plan():
    cfg = harness.load_config(TOY)
    plan = cluster.plan_cluster(cfg, 7)
    fourth = flat_two_group.plan_cluster(
        harness.load_config(os.path.join(HERE, "data", "toy-2group.json")),
        7)
    # the fourth kind's population, job for job
    for name in ("wl_queue", "wl_priority", "wl_pods", "wl_request",
                 "wl_created", "wl_running", "wl_reserved"):
        assert np.array_equal(getattr(plan, name), getattr(fourth, name))
    assert plan.wl_name == fourth.wl_name
    assert [j["name"] for j in plan.job_classes] == [
        "medium-x86", "small-any", "small-arm", "launcher-x86"]
    assert plan.groups[0].may_take.tolist() == [
        [True, False], [True, True], [False, True], [True, False]]
    n = len(plan.wl_name)
    counts = np.diff(plan.wl_first)
    assert np.array_equal(counts, np.where(plan.wl_pods >= 2, 2, 1))
    assert 0.5 < (counts == 2).mean() < 0.7          # 11 of 18 jobs
    for i in range(n):
        rows = list(plan.pod_sets(i))
        names = [plan.ps_name[j] for j in rows]
        assert names == (["launcher", "workers"] if len(rows) == 2
                         else ["main"])
        assert int(plan.ps_pods[rows].sum()) == plan.wl_pods[i]
        assert np.array_equal(plan.ps_request[rows].sum(axis=0),
                              plan.wl_request[i])
        if len(rows) == 2:
            assert plan.ps_pods[rows[0]] == 1
            assert plan.ps_job[rows[0]] == 3
            # launcher and workers ask the same a pod
            assert np.array_equal(
                plan.ps_request[rows[0]] * plan.ps_pods[rows[1]],
                plan.ps_request[rows[1]])
        assert plan.ps_job[rows[-1]] == fourth.wl_job[i]
    res = plan.resources
    cpu, mem = res.index("cpu"), res.index("memory")
    step = {"cpu": 1000, "memory": 8 << 30}
    for c, q in enumerate(plan.queues):
        wls = np.nonzero((plan.wl_queue == c) & plan.wl_running)[0]
        wls = wls[np.argsort(plan.wl_reserved[wls])]
        rows = np.array([j for i in wls for j in plan.pod_sets(i)],
                        dtype=np.int64)
        of = plan.ps_flavor[rows, 0]
        assert plan.groups[0].may_take[plan.ps_job[rows], of].all()
        assert (plan.ps_flavor[rows, 1] == 0).all()
        # replayed a PodSet after another, the launcher first
        total = int(plan.ps_request[rows, cpu].sum())
        filled = [0, 0]
        for j, f in zip(rows, of):
            mine = np.nonzero(plan.groups[0].may_take[plan.ps_job[j]])[0]
            open_ = [g for g in mine
                     if filled[g] < total * [60, 40][g] // 100]
            assert f == (open_[0] if open_ else mine[-1])
            filled[f] += int(plan.ps_request[j, cpu])
        for s, f in enumerate(("x86", "arm")):
            used = int(plan.ps_request[rows[of == s], cpu].sum())
            assert 0 <= q.nominal[f]["cpu"] - used < step["cpu"]
        used = int(plan.ps_request[rows, mem].sum())
        assert 0 <= q.nominal["default-flavor"]["memory"] - used < step[
            "memory"]
        # the fill, every PodSet counted, is the fourth kind's
        assert sum(q.nominal[f]["cpu"] for f in ("x86", "arm")) - sum(
            fourth.queues[c].nominal[f]["cpu"] for f in ("x86", "arm")
        ) in (-1000, 0, 1000)
        assert (q.nominal["default-flavor"]
                == fourth.queues[c].nominal["default-flavor"])
    pending = np.array([j for i in np.nonzero(~plan.wl_running)[0]
                        for j in plan.pod_sets(i)])
    assert (plan.ps_flavor[pending] == -1).all()


@pytest.mark.parametrize("edit, says", [
    (lambda c: c["job_constraints"][3].update(may_take=["x86", "arm"]),
     "launcher-x86"),
    (lambda c: c["pod_sets"]["launcher"].update(pods=2), "one pod"),
    (lambda c: c["pod_sets"]["launcher"].update(constraint="nobody"),
     "nobody"),
])
def test_a_file_that_breaks_the_podsets_rule_is_refused(edit, says):
    cfg = harness.load_config(TOY)
    edit(cfg)
    with pytest.raises(ValueError, match=says):
        cluster.plan_cluster(cfg, 1)


# ---- the cell's size, from the configuration's file alone ----------------

def test_the_configuration_is_the_fourth_one_with_gangs():
    """Every number of ``mk8-1kcq-2group`` kept, key by key but for the
    jobs' shape; what is added is listed under ``assumed``; nothing is
    reduced."""
    cfg, fourth = harness.load_config(CONFIG), harness.load_config(FOURTH)
    assert cfg["kind"] == "flat_pod_sets" and cfg["reduced"] == []
    for key in ("deployment", "classes", "population", "clock",
                "fused_path_limits"):
        assert cfg[key] == fourth[key], key
    assert cfg["job_constraints"][:3] == fourth["job_constraints"]
    assert cfg["job_constraints"][3] == {
        "name": "launcher-x86", "k_mod_3": [],
        "nodeSelector": {"cpu-arch": "x86"},
        "may_take": ["x86", "default-flavor"]}
    assert cfg["pod_sets"] == {
        "gang_from_pods": 2, "plain": {"name": "main"},
        "launcher": {"name": "launcher", "pods": 1,
                     "constraint": "launcher-x86"},
        "workers": {"name": "workers"}}
    assert set(fourth["assumed"]) <= set(cfg["assumed"])
    for name in ("split_of_a_job", "launcher_size", "launcher_selector",
                 "podset_order", "flavor_of_a_running_workload",
                 "no_network_no_reference"):
        assert name in cfg["assumed"], name
    assert set(fourth["documented"]) <= set(cfg["documented"])
    for name in ("pod_sets", "gangs_are_what_jobs_submit",
                 "usage_accumulates_over_podsets", "worst_podset_decides",
                 "targets_over_the_union"):
        assert name in cfg["documented"], name
    assert "1 to 8" in cfg["documented"]["pod_sets"]
    assert len(cfg["guarantees"]) == len(fourth["guarantees"]) + 2
    assert any("whole or not at all" in g for g in cfg["guarantees"])
    assert any("counts every PodSet" in g for g in cfg["guarantees"])
    toy = harness.load_config(TOY)
    for key in ("pod_sets", "job_constraints", "classes"):
        assert toy[key] == cfg[key], key
    for key in ("resource_groups", "flavor_specs", "resources",
                "flavor_fungibility", "preemption", "borrowing_limit"):
        assert toy["deployment"][key] == cfg["deployment"][key], key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == []
    assert entry["file"] == "benchmarks/configs/mk8-1kcq-gangs.json"
    cell = next(w for w in manifest["workloads"]
                if w["config"] == cfg["name"])
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "mk8-gangs.backlog", "backlog-podsets", 1)
    # the entries this deployment added, looked up by name: a later PR
    # appends after them
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells.index("mk8-gangs.backlog") == 4
    for name, (layer, moves, source) in NEW_METRICS.items():
        m = by_name[name]
        assert (m["layer"], m["moves"], m["source"]) == (
            layer, moves, source), name
        assert m["workloads"][:5] == cells[:5], name
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           name + ".json")), name
    assert by_name["gang_decide_roofline"]["unit"] == "%"


def test_grid_and_the_plan_at_the_cells_size():
    """M and the slots are the first cell's; the plan at the cell's size
    gives 11 of 18 jobs two PodSets, every launcher x86, both groups of
    every queue full, and a decided cycle's bytes counted a real row, a
    further request a gang and a (flavor, resource) pair."""
    cfg = harness.load_config(CONFIG)
    rows = cluster.queue_rows(cfg)
    assert rows["M"] == 65_536 and rows["slots"] == 65_536_000
    plan = cluster.plan_cluster(cfg, 2_147_483_700)
    run = plan.wl_running
    assert int(run.sum()) == int(sum(rows["running"])) == 287_994
    counts = np.diff(plan.wl_first)
    assert abs((counts == 2).mean() - 11 / 18) < 0.01
    assert counts.max() == 2
    launcher = np.zeros(len(plan.ps_name), dtype=bool)
    launcher[plan.wl_first[:-1][counts == 2]] = True
    held = plan.ps_flavor[:, 0] >= 0
    assert (plan.ps_flavor[launcher & held, 0] == 0).all()
    assert plan.groups[0].may_take[plan.ps_job[held],
                                   plan.ps_flavor[held, 0]].all()
    by_flavor = np.bincount(plan.ps_flavor[held, 0], minlength=2)
    assert (by_flavor > 50_000).all()
    assert all(q.nominal["x86"]["cpu"] > 0
               and q.nominal["default-flavor"]["memory"] > 0
               for q in plan.queues)
    problem = kind.problem(cfg, plan)
    import peaks
    assert problem["real_rows"] == rows["preempting_forest_rows"]
    assert peaks.row_bytes(problem["resources"]) == 25
    # three (flavor, resource) pairs a queue at 12 B, and 8 B more for
    # each real row that is a gang: between a third and two thirds of
    # the real rows' further requests, never the planes' width
    per_unit = peaks.queue_bytes(problem["resources"])
    further = problem["queues"] * per_unit - 1000 * 3 * 12
    assert 0.5 * 8 * problem["real_rows"] < further < (
        0.7 * 8 * problem["real_rows"] + per_unit)
    total = peaks.burst_launch_bytes(problem["real_rows"],
                                     problem["queues"],
                                     problem["resources"])
    assert total < problem["real_rows"] * (25 + 8) + 1000 * 3 * 12 + 24
