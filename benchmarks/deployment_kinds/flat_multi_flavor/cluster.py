"""A configuration file -> the cluster it describes, as plain data.

Nothing here imports the program.  The population (queues, cohorts,
classes, which workloads run, every timestamp, what the seed draws) is
``flat_one_flavor``'s, planned by its module from the same keys.  This
kind adds the flavors: which flavor each running workload holds, and the
quota of every (queue, flavor, resource).

A queue's running workloads, ordered by reservation time, oldest first,
are cut into consecutive stretches at the configuration's
``flavor_fill_percent`` of the queue's summed cpu (a workload belongs to
the stretch its first millicore falls in); stretch f holds flavor f, as
a cluster that filled up in flavor order while it grew.  nominalQuota of
(queue, flavor, resource) is that stretch's usage rounded up: every
flavor of every queue starts full and nobody borrows.  An empty stretch
gives nominal 0.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..flat_one_flavor import cluster as one_flavor
from ..flat_one_flavor.cluster import queue_rows, summary, unit_scale

__all__ = ["plan_cluster", "problem", "summary", "queue_rows"]


@dataclass
class FlavorPlan(one_flavor.ClusterPlan):
    """``flat_one_flavor``'s plan; a queue's ``nominal`` and
    ``borrowing_limit`` are keyed by flavor, then resource."""
    flavors: list[str] = field(default_factory=list)
    # [N] index into flavors of a running row, -1 of a pending one
    wl_flavor: np.ndarray = None


def problem(cfg: dict, plan: FlavorPlan) -> dict:
    """What ``benchmarks/peaks.py`` counts a decided cycle's bytes from:
    the rows as the first kind counts them, and a queue's quota state
    once a flavor."""
    return {"real_rows": queue_rows(cfg)["preempting_forest_rows"],
            "queues": len(plan.queues) * len(plan.flavors),
            "resources": len(plan.resources)}


def plan_cluster(cfg: dict, seed: int) -> FlavorPlan:
    base = one_flavor.plan_cluster(cfg, seed)
    dep = cfg["deployment"]
    flavors = list(dep["flavors"])
    cuts = list(dep["flavor_fill_percent"])
    if len(cuts) != len(flavors) or cuts[-1] != 100 or sorted(cuts) != cuts:
        raise ValueError(f"flavor_fill_percent {cuts!r} for {flavors!r}")
    res = base.resources
    scale = unit_scale(cfg)
    step = [dep["quota_round_up"][r] * scale[r] for r in res]
    cpu = res.index("cpu")

    wl_flavor = np.full(len(base.wl_queue), -1, dtype=np.int64)
    running = np.nonzero(base.wl_running)[0]
    # a queue's running rows, oldest reservation first
    order = running[np.lexsort((base.wl_reserved[running],
                                base.wl_queue[running]))]
    bounds = np.searchsorted(base.wl_queue[order],
                             np.arange(len(base.queues) + 1))
    for c, q in enumerate(base.queues):
        rows = order[bounds[c]:bounds[c + 1]]
        req = base.wl_request[rows]
        before = np.cumsum(req[:, cpu]) - req[:, cpu]
        total = int(req[:, cpu].sum())
        edges = [total * p // 100 for p in cuts[:-1]]
        of = np.searchsorted(edges, before, side="right")
        wl_flavor[rows] = of
        usage = np.zeros((len(flavors), len(res)), dtype=np.int64)
        np.add.at(usage, of, req)
        limit = q.borrowing_limit
        q.nominal = {f: {r: int(-(-usage[fi, ri] // step[ri]) * step[ri])
                         for ri, r in enumerate(res)}
                     for fi, f in enumerate(flavors)}
        q.borrowing_limit = {f: dict(limit) for f in flavors}
    return FlavorPlan(**vars(base), flavors=flavors, wl_flavor=wl_flavor)
