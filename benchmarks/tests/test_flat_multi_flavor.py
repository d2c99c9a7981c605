"""The deployment kind ``flat_multi_flavor``: its plain reference
against the program's host scalar scheduler, its controls, its ledger,
and its sizes from the configuration's file alone.

The scalar scheduler (``build_driver(use_device=False)``: the host
``FlavorAssigner`` with the ``PreemptionOracle``) is the second witness:
same cluster, same finishes, same clock, and every cycle's admitted,
evicted, skipped and preempting sets and the flavor of every admission
have to agree.
"""

import copy
import json
import os

import numpy as np
import pytest

import correct
import harness
from deployment_kinds import flat_multi_flavor as kind
from deployment_kinds.flat_multi_flavor import cluster, program
from traffic_kinds import burst_rounds_flavors

from conftest import BENCH, HERE, ROOT

TOY = os.path.join(HERE, "data", "toy-4flavor.json")
CONFIG = os.path.join(BENCH, "configs", "mk8-1kcq-4flavor.json")


def traffic_params(**over):
    with open(os.path.join(BENCH, "traffic", "backlog-flavors.json")) as f:
        return dict(json.load(f), **over)


def witness(plan, rounds, seed, **over):
    """``rounds`` rounds of the cell's traffic through the host scalar
    scheduler, and the comparison's verdict on them."""
    driver, clock = program.build_driver(plan, use_device=False)
    traffic = burst_rounds_flavors.Traffic(traffic_params(**over), plan,
                                           seed)
    records = [traffic.round(driver, clock) for _ in range(rounds)]
    return records, correct.compare(kind, plan, records, 0)


@pytest.fixture(scope="module")
def toy_run():
    plan = cluster.plan_cluster(harness.load_config(TOY), 5)
    records, verdict = witness(plan, 3, 5, finish_fraction_per_round=0.05)
    return plan, records, verdict


@pytest.mark.parametrize("seed", [3, 2_147_483_659])
@pytest.mark.parametrize("policy", ["TryNextFlavor", "Preempt"])
def test_reference_equals_scalar_scheduler(seed, policy):
    cfg = harness.load_config(TOY)
    cfg["deployment"]["flavor_fungibility"]["whenCanPreempt"] = policy
    plan = cluster.plan_cluster(cfg, seed)
    records, verdict = witness(plan, 3, seed,
                               finish_fraction_per_round=0.05)
    assert verdict["correct"], verdict
    assert "placed" in kind.COMPARED
    facts = verdict["facts"]
    assert facts["cycles_compared"] == 24
    assert facts["cycles_with_evictions"] > 2
    assert facts["cross_queue_evictions"] > 0
    placed = [p for r in records for c in r.cycles for p in c.placed]
    assert len(placed) > 40
    # the walk places work on every flavor, not on the first alone
    assert {p.rpartition("@")[2] for p in placed} == set(plan.flavors)
    for r in records:
        for c in r.cycles:
            assert sorted(p.rpartition("@")[0] for p in c.placed) \
                == sorted(c.admitted)


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
    readings = control.control_readings(kind, plan, records, 0)
    assert set(readings) == set(kind.CONTROLS)
    for name, row in readings.items():
        assert row["correct"] is False and row["mismatched_cycles"] >= 1, name
    # a queue that overruns memory's quota is what the ledger is for
    assert readings["memory_unenforced"]["quota_violations"] > 0


def test_ledger_counts_an_admission_moved_to_a_full_flavor(toy_run):
    plan, records, _ = toy_run
    assert kind.ledger(plan, records) == {
        "quota_violations": 0, "double_admissions": 0,
        "unknown_finishes": 0}
    moved = copy.deepcopy(records)
    # the first admission of the run, put by hand on every other flavor
    # in turn: each is full but for what the boundary freed, so at least
    # one of them overruns its queue's or its cohort's quota
    cyc = next(c for r in moved for c in r.cycles if c.placed)
    key, _, flavor = cyc.placed[0].rpartition("@")
    violations = 0
    for other in plan.flavors:
        if other != flavor:
            cyc.placed[0] = f"{key}@{other}"
            violations += kind.ledger(plan, moved)["quota_violations"]
    assert violations > 0
    cyc.placed[0] = f"{key}@no-such-flavor"
    assert kind.ledger(plan, moved)["double_admissions"] == 1
    # ... and the comparison's own field fails it too
    assert not correct.compare(kind, plan, moved, 0)["correct"]


def test_a_program_without_the_oracle_counter_is_turned_away(monkeypatch):
    """The commit before the deployment landed answers the oracle on the
    host, a launch a question; the kind ends its run before set-up, with
    an exit code other than 0, and a check then measures the cell on the
    program that supports it."""
    from kueue_tpu.scheduler.preemption import Preemptor
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    real = Preemptor.__init__

    def without_counter(self, *a, **kw):
        real(self, *a, **kw)
        del self.stats[program.ORACLE_COUNTER]
    monkeypatch.setattr(Preemptor, "__init__", without_counter)
    with pytest.raises(SystemExit) as stop:
        program.build_driver(plan)
    assert stop.value.code not in (0, None)
    assert program.ORACLE_COUNTER in str(stop.value.code)
    monkeypatch.undo()
    driver, _ = program.build_driver(plan, use_device=False)
    assert driver.scheduler.preemptor.stats[program.ORACLE_COUNTER] == 0


def test_flavor_stretches_and_quota_from_the_plan():
    plan = cluster.plan_cluster(harness.load_config(TOY), 7)
    assert plan.flavors == ["reserved", "on-demand", "spot-a", "spot-b"]
    res = plan.resources
    cpu = res.index("cpu")
    step = {"cpu": 1000, "memory": 8 << 30}
    for c, q in enumerate(plan.queues):
        rows = np.nonzero((plan.wl_queue == c) & plan.wl_running)[0]
        rows = rows[np.argsort(plan.wl_reserved[rows])]
        of = plan.wl_flavor[rows]
        assert (np.diff(of) >= 0).all()          # consecutive stretches
        total = plan.wl_request[rows, cpu].sum()
        before = np.cumsum(plan.wl_request[rows, cpu]) \
            - plan.wl_request[rows, cpu]
        for f, pct in enumerate((40, 70, 85)):
            assert (before[of <= f] < total * pct // 100 + 1).all()
            assert (before[of > f] >= total * pct // 100).all()
        for fi, f in enumerate(plan.flavors):
            used = plan.wl_request[rows[of == fi]].sum(axis=0)
            for ri, r in enumerate(res):
                assert 0 <= q.nominal[f][r] - used[ri] < step[r]
    assert (plan.wl_flavor[~plan.wl_running] == -1).all()


# ---- the cell's size, from the configuration's file alone ----------------

def grid_bytes_a_slot(plan, monkeypatch):
    """What the fused window holds a slot of its [C, M] grid, from the
    dtypes of the planes the program packs for this cluster: the
    workload planes as the launch tightens them (``grid_size`` decides
    the width of the rank planes) and the scan state, each counted
    once."""
    from kueue_tpu.ops import burst
    seen = {}
    launch = burst.BurstSolver._launch

    def spy(self, window, K, runtime, ext_release, ext_unpark, state,
            *rest, **kw):
        grid = window.arrays["wl_cycle_rank"].shape
        held = {id(a): a for a in list(window.arrays.values()) + list(state)
                if isinstance(a, np.ndarray) and a.shape[:2] == grid}
        in_state = {id(a) for a in state}
        width = {k: a.dtype.itemsize * int(np.prod(a.shape[2:]))
                 for k, a in held.items()}
        seen["state"] = sum(w for k, w in width.items() if k in in_state)
        seen["planes"] = sum(w for k, w in width.items()
                             if k not in in_state)
        seen["state_f"] = state[5].shape[2], state[5].dtype, state[6].dtype
        return launch(self, window, K, runtime, ext_release, ext_unpark,
                      state, *rest, **kw)

    monkeypatch.setattr(burst.BurstSolver, "_launch", spy)
    driver, clock = program.build_driver(plan)
    clock.t += 1.0
    driver.schedule_burst(1, on_cycle_start=lambda k: None)
    return seen


def test_grid_and_reckoned_device_bytes_from_the_file_alone(monkeypatch):
    """M, the slots and the bytes a slot: between the 4 GiB floor the
    driver holds a cell to and 14 GiB of the chip's 16, before the chip
    is touched.  The bytes a slot come from a toy of the same flavors
    and resources (a slot's width does not depend on C or M; the
    untightened int32 planes are what the toy's are counted as)."""
    cfg = harness.load_config(CONFIG)
    rows = cluster.queue_rows(cfg)
    assert rows["M"] == 65_536 and rows["slots"] == 65_536_000
    dep = cfg["deployment"]
    toy = harness.load_config(TOY)
    for key in ("flavors", "resources", "flavor_fill_percent",
                "flavor_fungibility", "preemption"):
        assert toy["deployment"][key] == dep[key], key
    seen = grid_bytes_a_slot(cluster.plan_cluster(toy, 1), monkeypatch)
    f_wide = len(dep["flavors"]) * len(dep["resources"])
    assert seen["state_f"] == (f_wide, np.int32, np.bool_) and f_wide == 8
    # workload planes: requests 2 x int32, three rank planes, priority,
    # the vector flag; scan state: 15 B and the [C, M, F] admitted usage
    # (int32) with its flag, 5 B a flavor-resource (10 B in the first
    # cell, 40 B here)
    assert seen["planes"] == 25 and seen["state"] == 15 + 5 * f_wide == 55
    gib = rows["slots"] / 2**30
    sent = (seen["planes"] + seen["state"]) * gib
    # the device holds at least what one launch is sent, and at most the
    # planes and three copies of the state (its input, the scan's carry
    # and its output); the first cell, with 25 + 25 B a slot, peaks at
    # 6.268 GiB = 103 B a slot (ledger, PR 30)
    worst = (seen["planes"] + 3 * seen["state"]) * gib
    assert 4.0 < sent < worst < 14.0
    assert 4.8 < sent < 4.9 and 11.5 < worst < 11.7
    assert cfg["reduced"] == [] and cfg["kind"] == "flat_multi_flavor"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == []
