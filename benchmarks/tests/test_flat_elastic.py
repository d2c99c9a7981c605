"""The deployment kind ``flat_elastic``: its plain reference against the
program's host scalar scheduler and against its device-decided cycle,
its controls, its ledger, and its sizes from the configuration's file
alone.

The scalar scheduler (``build_driver(use_device=False)``: the host
``FlavorAssigner`` and ``PodSetReducer``, one probe at a time) is the
second witness and the device-decided cycle (the count search's
lockstep passes on the vector path) the third: same cluster, same
finishes, same clock, and every cycle's admitted, evicted, skipped and
preempting sets and the pods of every admission have to agree.
"""

import copy
import json
import os

import numpy as np
import pytest

import correct
import harness
from deployment_kinds import flat_elastic as kind
from deployment_kinds.flat_elastic import cluster, program
from deployment_kinds.flat_one_flavor import cluster as one_flavor
from traffic_kinds import burst_rounds_elastic

from conftest import BENCH, HERE

TOY = os.path.join(HERE, "data", "toy-elastic.json")
CONFIG = os.path.join(BENCH, "configs", "mk8-1kcq-elastic.json")
FIRST = os.path.join(BENCH, "configs", "mk8-1kcq-zipf.json")


def traffic_params(**over):
    with open(os.path.join(BENCH, "traffic", "backlog-elastic.json")) as f:
        return dict(json.load(f), **over)


def witness(plan, rounds, seed, use_device=False, **over):
    """``rounds`` rounds of the cell's traffic through the program, and
    the comparison's verdict on them."""
    driver, clock = program.build_driver(plan, use_device=use_device)
    traffic = burst_rounds_elastic.Traffic(traffic_params(**over), plan,
                                           seed)
    records = [traffic.round(driver, clock) for _ in range(rounds)]
    return records, correct.compare(kind, plan, records, 0), driver


def reduced(plan, records) -> int:
    pods = {plan.key(i): n for i, n in enumerate(plan.wl_pods.tolist())}
    return sum(int(e.rpartition("=")[2]) < pods[e.partition("@")[0]]
               for r in records for c in r.cycles for e in c.counts)


@pytest.fixture(scope="module")
def toy_run():
    plan = cluster.plan_cluster(harness.load_config(TOY), 5)
    records, verdict, _ = witness(plan, 3, 5, finish_fraction_per_round=0.05)
    return plan, records, verdict


@pytest.mark.parametrize("seed", [3, 2_147_483_659])
@pytest.mark.parametrize("use_device", [False, True],
                         ids=["host", "device"])
def test_reference_equals_the_program(seed, use_device):
    plan = cluster.plan_cluster(harness.load_config(TOY), seed)
    records, verdict, driver = witness(plan, 3, seed, use_device,
                                       finish_fraction_per_round=0.05)
    assert verdict["correct"], verdict
    assert "counts" in kind.COMPARED
    facts = verdict["facts"]
    assert facts["cycles_compared"] == 24
    assert facts["cycles_with_evictions"] > 0
    for r in records:
        for c in r.cycles:
            assert sorted(e.partition("@")[0] for e in c.counts) \
                == sorted(c.admitted)
    # Jobs were admitted on fewer pods than they ask for
    assert reduced(plan, records) > 0
    if use_device:
        solver = driver.scheduler.solver.stats
        assert solver["partial_heads"] > 0, solver
        assert solver["partial_admitted"] == reduced(plan, records)
        assert solver["scalar_heads"] == 0, solver
        assert driver.scheduler.preemptor.stats["host_searches"] == 0


@pytest.mark.parametrize("broken", kind.CONTROLS)
def test_control_fails_the_comparison(toy_run, broken):
    """Each control is the reference with one stated guarantee switched
    off, put in the program's place: it has to come out not correct."""
    plan, records, verdict = toy_run
    assert verdict["correct"], verdict
    control = correct.compare(kind, plan, records, 0, broken=broken)
    assert not control["correct"], broken
    assert control["compared"]["mismatched_cycles"]["value"] >= 1


def test_the_ledger_charges_the_admitted_pods(toy_run):
    plan, records, _ = toy_run
    assert kind.ledger(plan, records) == {
        "quota_violations": 0, "double_admissions": 0,
        "unknown_finishes": 0}
    pods = plan.wl_pods.tolist()
    least = plan.wl_min_pods.tolist()
    row = {plan.key(i): i for i in range(len(pods))}
    cyc = next(c for r in records for c in r.cycles
               if any(least[row[e.partition("@")[0]]]
                      < pods[row[e.partition("@")[0]]] for e in c.counts))
    at = next(j for j, e in enumerate(cyc.counts)
              if least[row[e.partition("@")[0]]]
              < pods[row[e.partition("@")[0]]])
    key = cyc.counts[at].partition("@")[0]

    def tampered(entry):
        moved = copy.deepcopy(records)
        c = next(c for r in moved for c in r.cycles if c.clock == cyc.clock)
        if entry is None:
            del c.counts[at]
        else:
            c.counts[at] = entry
        return kind.ledger(plan, moved), moved

    # below the Job's job-min-parallelism, past its parallelism, or not
    # named: quota nobody accounted for
    for n in (least[row[key]] - 1, pods[row[key]] + 1):
        assert tampered(f"{key}@main={n}")[0]["quota_violations"] >= 1
    assert tampered(None)[0]["quota_violations"] >= 1
    # a PodSet the Job has not
    assert tampered(f"{key}@workers=1")[0]["double_admissions"] >= 1
    # and the comparison's own field fails a count changed within range
    n = int(cyc.counts[at].rpartition("=")[2])
    other = pods[row[key]] if n < pods[row[key]] else least[row[key]]
    led, moved = tampered(f"{key}@main={other}")
    assert not correct.compare(kind, plan, moved, 0)["correct"]


def test_a_program_without_the_count_search_is_turned_away(monkeypatch):
    from kueue_tpu.ops.solver import CycleSolver
    real = CycleSolver.__init__

    def older(self, *a, **kw):
        real(self, *a, **kw)
        del self.stats[program.PARTIAL_COUNTER]
    monkeypatch.setattr(CycleSolver, "__init__", older)
    plan = cluster.plan_cluster(harness.load_config(TOY), 1)
    with pytest.raises(SystemExit, match="partial_heads"):
        program.build_driver(plan)


def test_the_plan_holds_the_first_cells_cluster_in_elastic_jobs():
    """The cell's configuration is the first cell's number for number
    but for the small class: twice the pods at half the request, so
    every Job's whole request, the quotas, the fill and the grid are
    the first cell's; job-min-parallelism is half the parallelism."""
    cfg = harness.load_config(CONFIG)
    first = harness.load_config(FIRST)
    for key in ("population", "clock", "fused_path_limits"):
        assert cfg[key] == first[key], key
    dep = dict(cfg["deployment"], what=first["deployment"]["what"])
    assert dep == first["deployment"]
    assert cfg["reduced"] == []
    assert one_flavor.queue_rows(cfg) == one_flavor.queue_rows(first)
    plan = cluster.plan_cluster(cfg, 3_000_000_019)
    base = one_flavor.plan_cluster(first, 3_000_000_019)
    assert np.array_equal(plan.wl_request, base.wl_request)
    assert np.array_equal(plan.wl_running, base.wl_running)
    assert [q.nominal for q in plan.queues] == [q.nominal
                                                for q in base.queues]
    small = plan.wl_priority == 50
    assert np.array_equal(plan.wl_pods[small], 2 * base.wl_pods[small])
    assert np.array_equal(plan.wl_min_pods[small], base.wl_pods[small])
    assert np.array_equal(plan.wl_min_pods[~small], plan.wl_pods[~small])
    assert (plan.per_pod()[small] == [250, 2 << 30]).all()
    # every pending workload is an elastic Job; 100,001 of them
    assert small[~plan.wl_running].all()
    assert int((~plan.wl_running).sum()) == 100_001
    # the pods the restored Jobs run, at full count: within the
    # documented 8 workers x 150,000 pods
    running_pods = int(plan.wl_pods[plan.wl_running].sum())
    assert 925_000 < running_pods < 940_000 < 8 * 150_000
    assert "elastic" in cluster.summary(plan)
