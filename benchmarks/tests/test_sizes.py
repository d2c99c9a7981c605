"""The cells' sizes, from the configuration files alone.

ISSUE 27 reckons them: the hottest tenant runs 38,475 workloads, the
fused window's row bucket M is 65,536 (so the [C, M] grid has 65.5 M
slots and the device holds over 4 GiB), and the rows in forests that
can preempt stay under the kernel's 2^19.  A later edit that quietly
shrinks a cell fails here, not in a chip check.
"""

import json
import os

import numpy as np
import pytest

import harness
from deployment_kinds.flat_one_flavor import cluster

from conftest import ROOT

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIGS = [c["file"] for c in MANIFEST["configs"]]


@pytest.mark.parametrize("path", CONFIGS)
def test_grid_from_the_file_alone(path):
    cfg = harness.load_config(os.path.join(ROOT, path))
    rows = cluster.queue_rows(cfg)
    assert abs(rows["hottest_running"] - 38_475) <= 1
    assert rows["M"] == 65_536 == cfg["fused_path_limits"]["grid_rows_M"]
    assert rows["deepest_rows"] > 32_768 * 1.15      # 17% above the edge
    assert rows["slots"] == 1000 * 65_536
    limits = cfg["fused_path_limits"]
    assert rows["preempting_forest_rows"] == 287_994 + 34 * 1000 - sum(
        max(0, 34 - p) for p in rows["pending"])
    assert rows["preempting_forest_rows"] < limits[
        "preempting_forest_rows_max"] == 1 << 19
    assert abs(sum(rows["running"]) - 288_000) < 50
    assert abs(sum(rows["pending"]) - 100_000) < 50
    assert cfg["reduced"] == [] and cfg["deployment"]["resources"] == [
        "cpu", "memory"]


@pytest.mark.parametrize("path", CONFIGS)
@pytest.mark.parametrize("seed", [0, 17, 3_000_000_019])
def test_plan_holds_the_sizes_for_any_seed(path, seed):
    cfg = harness.load_config(os.path.join(ROOT, path))
    plan = cluster.plan_cluster(cfg, seed)
    per_queue = np.bincount(plan.wl_queue[plan.wl_running], minlength=1000)
    assert per_queue.max() == cluster.queue_rows(cfg)["hottest_running"]
    assert sorted(per_queue) == sorted(cluster.queue_rows(cfg)["running"])
    assert int(plan.wl_running.sum()) + 34 * 1000 < 1 << 19
    # reservation sequence: restored rows plus a window's admissions
    assert int(plan.wl_running.sum()) + 32 * 1000 < 1 << 20
    pods = int(plan.wl_pods[plan.wl_running].sum())
    assert 560_000 < pods < 570_000            # ISSUE 27: about 565,000
    # two resources that both bind: every queue starts full in both
    res = plan.resources
    usage = np.zeros((1000, 2), dtype=np.int64)
    np.add.at(usage, plan.wl_queue[plan.wl_running],
              plan.wl_request[plan.wl_running])
    cohorts = {}
    for i, q in enumerate(plan.queues):
        cohorts.setdefault(q.cohort, []).append(i)
    assert len(cohorts) == 200 and all(len(m) == 5 for m in cohorts.values())
    step = [1000, 8 << 30]
    for members in cohorts.values():
        for ri, r in enumerate(res):
            quota = sum(plan.queues[i].nominal[r] for i in members)
            used = int(usage[members, ri].sum())
            assert used <= quota < used + 5 * step[ri] + 1
    for i, q in enumerate(plan.queues):
        for ri, r in enumerate(res):
            assert 0 <= q.nominal[r] - usage[i, ri] < step[ri]


def test_seeds_share_sizes_and_differ_in_order():
    cfg = harness.load_config(os.path.join(ROOT, CONFIGS[0]))
    a, b = cluster.plan_cluster(cfg, 1), cluster.plan_cluster(cfg, 2)
    assert sorted(q.running for q in a.queues) == sorted(
        q.running for q in b.queues)
    assert [q.rank for q in a.queues] != [q.rank for q in b.queues]
    assert sorted(a.wl_created) == sorted(b.wl_created)
    # ... and only in order: a rank's tenant is the same under any label
    for rank in (1, 500, 1000):
        qa = next(i for i, q in enumerate(a.queues) if q.rank == rank)
        qb = next(i for i, q in enumerate(b.queues) if q.rank == rank)
        assert np.array_equal(a.wl_created[a.wl_queue == qa],
                              b.wl_created[b.wl_queue == qb])
        assert ([n for n, q in zip(a.wl_name, a.wl_queue) if q == qa]
                == [n for n, q in zip(b.wl_name, b.wl_queue) if q == qb])
    again = cluster.plan_cluster(cfg, 1)
    assert np.array_equal(a.wl_created, again.wl_created)
    assert [q.rank for q in a.queues] == [q.rank for q in again.queues]
