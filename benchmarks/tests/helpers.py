"""Small clusters written out by hand, and the loop the tests share."""

import json
import os

import numpy as np

from deployment_kinds.flat_one_flavor import cluster
from traffic_kinds import burst_rounds

from conftest import BENCH

GIB = cluster.GIB


def hand_plan(queues, workloads, preemption=None):
    """queues: (name, cohort, {res: nominal}, {res: borrowing limit});
    workloads: (queue name, name, priority, {res: total}, created,
    reserved-or-None).  cpu in m, memory in GiB."""
    scale = {"cpu": 1, "memory": GIB}
    res = ["cpu", "memory"]
    qs = [cluster.Queue(
        name=n, cohort=c, rank=i + 1,
        nominal={r: nom[r] * scale[r] for r in res},
        borrowing_limit={r: bl[r] * scale[r] for r in res})
        for i, (n, c, nom, bl) in enumerate(queues)]
    index = {q.name: i for i, q in enumerate(qs)}
    cfg = {"deployment": {
        "flavor": "default", "resources": res,
        "queueing_strategy": "BestEffortFIFO",
        "preemption": preemption or {
            "reclaimWithinCohort": "Any",
            "withinClusterQueue": "LowerPriority",
            "borrowWithinCohort": "Never"}}}
    n = len(workloads)
    return cluster.ClusterPlan(
        config=cfg, resources=res, queues=qs,
        wl_queue=np.array([index[w[0]] for w in workloads]),
        wl_name=[w[1] for w in workloads],
        wl_priority=np.array([w[2] for w in workloads]),
        wl_pods=np.ones(n, dtype=np.int64),
        wl_request=np.array([[w[3][r] * scale[r] for r in res]
                             for w in workloads], dtype=np.int64),
        wl_created=np.array([float(w[4]) for w in workloads]),
        wl_running=np.array([w[5] is not None for w in workloads]),
        wl_reserved=np.array([float(w[5] or 0) for w in workloads]),
        clock_start=1000.0, cycle_s=1.0)


def backlog_params(**over):
    with open(os.path.join(BENCH, "traffic", "backlog.json")) as f:
        return dict(json.load(f), **over)


def drive(driver, clock, plan, rounds, seed=0, **over):
    """``rounds`` rounds of the backlog traffic through a driver;
    returns the records."""
    traffic = burst_rounds.Traffic(backlog_params(**over), plan, seed)
    return [traffic.round(driver, clock) for _ in range(rounds)]


def short(key):
    return key.split("/", 1)[1]


def shadow_root(tmp_path):
    """A root beside the repository's: ``<tmp>/benchmarks`` holds a link
    to everything ``benchmarks/`` has, and the directories a later PR
    adds files to are real, with a link to each of their entries, so
    that a test can put a new file beside the benchmark's and edit none
    of them.  Returns the root."""
    bench = tmp_path / "benchmarks"
    bench.mkdir()
    for x in os.listdir(BENCH):
        if x == "__pycache__":
            continue
        if x not in ("configs", "traffic", "deployment_kinds",
                     "traffic_kinds"):
            os.symlink(os.path.join(BENCH, x), bench / x)
            continue
        (bench / x).mkdir()
        for y in os.listdir(os.path.join(BENCH, x)):
            if y != "__pycache__":
                os.symlink(os.path.join(BENCH, x, y), bench / x / y)
    return str(tmp_path)
