"""A configuration file -> the cluster it describes, as plain data.

Nothing here imports the program.  The population (queues, classes,
which workloads run, every timestamp, what the seed draws) is
``flat_one_flavor``'s, planned by its module from the same keys.  This
kind adds the cohort tree and the quota rule: the tenant of Zipf rank r
sits in team cohort ``r mod teams``, team t in department cohort
``t mod departments`` (a root), so every department holds one tenant of
each block of ``departments`` consecutive ranks; each ClusterQueue's
nominalQuota is its department's restored usage over the department's
queues, rounded up, with no borrowingLimit and no lendingLimit; the
fair-sharing weight of every ``weighted_team_every``-th team is
``weighted_team_weight``, of every other node 1.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass, field

import numpy as np

from ..flat_one_flavor import cluster as one_flavor
from ..flat_one_flavor.cluster import queue_rows, summary, unit_scale

__all__ = ["plan_cluster", "problem", "summary", "queue_rows"]


def _ceil_to(x: int, step: int) -> int:
    return -(-x // step) * step


@dataclass
class FairPlan(one_flavor.ClusterPlan):
    """``flat_one_flavor``'s plan; a queue's ``cohort`` is its team, a
    queue has no borrowing limit (``borrowing_limit`` empty)."""
    teams: list[str] = field(default_factory=list)
    departments: list[str] = field(default_factory=list)
    team_parent: dict = field(default_factory=dict)   # team -> department
    # fair-sharing weight of every node that names one: queues by name,
    # teams and departments by name
    weight: dict = field(default_factory=dict)
    strategies: list[str] = field(default_factory=list)


def tree_of(cfg: dict) -> dict:
    """The tree's keys, checked."""
    dep = cfg["deployment"]
    tree = dep["tree"]
    n_q, n_t, n_d = (dep["cluster_queues"], tree["teams"],
                     tree["departments"])
    if n_q % n_t or n_t % n_d:
        raise ValueError("teams must divide the queues and departments "
                         "the teams")
    return tree


def plan_cluster(cfg: dict, seed: int) -> FairPlan:
    dep = cfg["deployment"]
    tree = tree_of(cfg)
    n_t, n_d = tree["teams"], tree["departments"]
    # the first kind plans the population; its cohorts are the teams
    flat_cfg = copy.deepcopy(cfg)
    flat_cfg["deployment"].update(
        cohorts=n_t, quota_rule="queue_usage",
        borrowing_limit={r: 0 for r in dep["resources"]})
    base = one_flavor.plan_cluster(flat_cfg, seed)
    if dep["quota_rule"] != "department_usage_share":
        raise ValueError(f"quota_rule {dep['quota_rule']!r}")
    res = base.resources
    scale = unit_scale(cfg)
    step = [dep["quota_round_up"][r] * scale[r] for r in res]

    teams = [f"team-{t}" for t in range(n_t)]
    departments = [f"dept-{d}" for d in range(n_d)]
    team_parent = {f"team-{t}": f"dept-{t % n_d}" for t in range(n_t)}
    dept_of_q = [(q.rank % n_t) % n_d for q in base.queues]
    usage = np.zeros((len(base.queues), len(res)), dtype=np.int64)
    np.add.at(usage, base.wl_queue[base.wl_running],
              base.wl_request[base.wl_running])
    d_usage = np.zeros((n_d, len(res)), dtype=np.int64)
    np.add.at(d_usage, dept_of_q, usage)
    d_count = np.bincount(dept_of_q, minlength=n_d)
    fs = dep["fair_sharing"]
    weight = {}
    for i, q in enumerate(base.queues):
        q.cohort = f"team-{q.rank % n_t}"
        d = dept_of_q[i]
        q.nominal = {r: _ceil_to(-(-int(d_usage[d, ri]) // int(d_count[d])),
                                 step[ri]) for ri, r in enumerate(res)}
        q.borrowing_limit = {}
        weight[q.name] = 1.0
    for t, name in enumerate(teams):
        weight[name] = (float(fs["weighted_team_weight"])
                        if t % fs["weighted_team_every"] == 0 else 1.0)
    for name in departments:
        weight[name] = 1.0
    fields = {k: getattr(base, k) for k in base.__dataclass_fields__}
    return FairPlan(**fields, teams=teams, departments=departments,
                    team_parent=team_parent, weight=weight,
                    strategies=list(fs["preemptionStrategies"]))


def problem(cfg: dict, plan: FairPlan) -> dict:
    """What ``benchmarks/peaks.py`` counts a decided cycle's bytes from:
    the rows a cycle can decide about, the queues, and the tree's cohort
    nodes, whose usage and quota a tournament reads as a queue's (one
    queue record each), and the resources."""
    return {"real_rows": queue_rows(cfg)["preempting_forest_rows"],
            "queues": (len(plan.queues) + len(plan.teams)
                       + len(plan.departments)),
            "resources": len(plan.resources)}
