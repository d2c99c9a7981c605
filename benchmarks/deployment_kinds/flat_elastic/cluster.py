"""A configuration file -> the cluster it describes, as plain data:
``flat_one_flavor``'s plan, with the least pods each workload's Job
runs on beside the pods it asks for.  Nothing here imports the program.

A class with ``min_pods`` is elastic: its row at k asks ``pods[(k div
3) mod len]`` pods and may run on ``min_pods`` at the same index.  A
class without it asks for its pods whole.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..flat_one_flavor import cluster as one_flavor
from ..flat_one_flavor.cluster import problem

__all__ = ["ElasticPlan", "plan_cluster", "problem", "summary"]


@dataclass
class ElasticPlan(one_flavor.ClusterPlan):
    # [N] the least pods a row's Job runs on (wl_pods where not elastic)
    wl_min_pods: np.ndarray = None

    def per_pod(self) -> np.ndarray:
        """[N, R] one pod's request, canonical units."""
        return self.wl_request // self.wl_pods[:, None]


def plan_cluster(cfg: dict, seed: int) -> ElasticPlan:
    base = one_flavor.plan_cluster(cfg, seed)
    # rows are laid out queue after queue, k = 0.. within each
    q = base.wl_queue
    first = np.searchsorted(q, np.arange(len(base.queues)))
    k = np.arange(len(q)) - first[q]
    min_pods = base.wl_pods.copy()
    for c in cfg["classes"]:
        if "min_pods" not in c:
            continue
        mine = np.isin(k % 3, c["k_mod_3"])
        cyc = np.array(c["min_pods"])[(k[mine] // 3) % len(c["min_pods"])]
        min_pods[mine] = cyc
    fields = {f: getattr(base, f) for f in base.__dataclass_fields__}
    plan = ElasticPlan(**fields, wl_min_pods=min_pods)
    assert (plan.wl_min_pods <= plan.wl_pods).all()
    assert (plan.wl_request % plan.wl_pods[:, None] == 0).all()
    return plan


def summary(plan: ElasticPlan) -> str:
    elastic = plan.wl_min_pods < plan.wl_pods
    return (one_flavor.summary(plan) + f", {int(elastic.sum())} elastic Jobs"
            f" ({int((elastic & ~plan.wl_running).sum())} pending), "
            f"{int(plan.wl_pods[plan.wl_running].sum())} pods running")
