"""Small clusters written out by hand, and the loop the tests share."""

import json
import os

import numpy as np

import cluster
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
