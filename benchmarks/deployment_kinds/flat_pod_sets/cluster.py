"""A configuration file -> the cluster it describes, as plain data.

Nothing here imports the program.  The population (queues, cohorts,
classes, which workloads run, every timestamp, what the seed draws) is
``flat_one_flavor``'s, planned by its module from the same keys, and
the resource groups, the flavors' labels and the jobs' constraints are
``flat_two_group``'s.  This kind adds the jobs' shape
(``pod_sets``): a job of fewer pods than ``gang_from_pods`` is one
PodSet (``plain``) of all its pods under its class's constraint; a
larger one is a gang of two PodSets in this order: ``launcher``, one
pod under the launcher's own constraint, and ``workers``, the other
pods under the class's.  Every pod asks its class's per-pod request,
so a job's totals are what they are in ``flat_two_group``.

Group by group, a queue's running workloads, ordered by reservation
time, oldest first, are placed one PodSet after another, a workload's
PodSets in their order, as the fourth kind places a workload: each
flavor of the group has a target, its share
(``flavor_target_percent``) of the queue's summed use of the group's
first resource, and a PodSet takes the first flavor of the group's
order that it may take and whose target is not yet reached, else the
last it may take.  nominalQuota of (queue, flavor, resource) is that
flavor's usage, every PodSet counted, rounded up: every flavor of every
group of every queue starts full and nobody borrows.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..flat_labelled_flavor.cluster import job_class_of
from ..flat_one_flavor import cluster as one_flavor
from ..flat_one_flavor.cluster import queue_rows, summary, unit_scale
from ..flat_two_group.cluster import GroupPlan, plan_groups

__all__ = ["plan_cluster", "problem", "summary", "queue_rows"]


@dataclass
class PodSetPlan(GroupPlan):
    """``flat_two_group``'s plan, a row a workload, and beside it a row
    a PodSet: workload ``i``'s PodSets are the rows
    ``wl_first[i]:wl_first[i + 1]``, in the Workload's order.
    ``wl_job`` and ``wl_flavor`` (the workload-grade columns of the
    fourth kind) are not kept: a constraint and a flavor are a
    PodSet's."""
    wl_first: np.ndarray = None      # [N + 1] first PodSet row
    ps_name: list = None             # [M]
    ps_pods: np.ndarray = None       # [M] pods of the PodSet
    ps_request: np.ndarray = None    # [M, R] its total request
    ps_job: np.ndarray = None        # [M] index into job_classes
    # [M, G] slot in each group's flavor list that a running PodSet
    # holds, -1 of a pending one
    ps_flavor: np.ndarray = None

    def pod_sets(self, i: int) -> range:
        return range(int(self.wl_first[i]), int(self.wl_first[i + 1]))


def problem(cfg: dict, plan: PodSetPlan) -> dict:
    """What ``benchmarks/peaks.py`` counts a decided cycle's bytes from:
    the rows as the first kind counts them (a row's requests, the four
    words of its place in the orders, a byte of state: 25 B with two
    resources), a request (4 B a resource) more for each PodSet a real
    row has after its first, and a queue's quota state once a (flavor,
    resource) of each group, 12 B a pair.  The real rows are the running
    workloads and the pending ones a queue packs (its best
    ``pending_rows_per_queue``); of those only the gangs count a second
    request: the width of the program's planes is its padding.  The
    count in peaks.py has no term a row beyond the first kind's, and
    takes a queue's state in units of 12 B a resource, so the pairs and
    the further requests are given in that unit."""
    R = len(plan.resources)
    pairs = len(plan.queues) * sum(
        len(g.flavors) * len(g.resources) for g in plan.groups)
    per_queue = cfg["fused_path_limits"]["pending_rows_per_queue"]
    extra = np.diff(plan.wl_first) - 1          # PodSets after the first
    packed = plan.wl_running.copy()
    pending = np.nonzero(~plan.wl_running)[0]
    order = pending[np.lexsort((plan.wl_created[pending],
                                -plan.wl_priority[pending],
                                plan.wl_queue[pending]))]
    first = np.searchsorted(plan.wl_queue[order],
                            np.arange(len(plan.queues)))
    rank = np.arange(len(order)) - first[plan.wl_queue[order]]
    packed[order[rank < per_queue]] = True
    further = int(extra[packed].sum()) * 4 * R   # bytes
    return {"real_rows": queue_rows(cfg)["preempting_forest_rows"],
            "queues": -(-pairs // R) + -(-further // (12 * R)),
            "resources": R}


def plan_cluster(cfg: dict, seed: int) -> PodSetPlan:
    base = one_flavor.plan_cluster(cfg, seed)
    dep, shape = cfg["deployment"], cfg["pod_sets"]
    res = base.resources
    groups = plan_groups(cfg, res)
    jobs = list(cfg["job_constraints"])
    names = [j["name"] for j in jobs]
    launcher_job = names.index(shape["launcher"]["constraint"])
    if shape["launcher"]["pods"] != 1:
        raise ValueError("the launcher is one pod")
    scale = unit_scale(cfg)
    step = [dep["quota_round_up"][r] * scale[r] for r in res]

    # rows are laid out queue by queue, then k
    n = len(base.wl_queue)
    first_row = np.searchsorted(base.wl_queue, np.arange(len(base.queues)))
    wl_job = job_class_of(cfg, np.arange(n) - first_row[base.wl_queue])

    # the PodSets: a gang's launcher, then its workers
    gang = base.wl_pods >= shape["gang_from_pods"]
    count = np.where(gang, 2, 1)
    wl_first = np.concatenate(([0], np.cumsum(count)))
    m = int(wl_first[-1])
    ps_wl = np.repeat(np.arange(n), count)
    is_launcher = np.zeros(m, dtype=bool)
    is_launcher[wl_first[:-1][gang]] = True
    is_workers = np.zeros(m, dtype=bool)
    is_workers[wl_first[:-1][gang] + 1] = True
    ps_pods = np.where(is_launcher, 1,
                       base.wl_pods[ps_wl] - is_workers.astype(np.int64))
    per_pod = base.wl_request // base.wl_pods[:, None]
    if (per_pod * base.wl_pods[:, None] != base.wl_request).any():
        raise ValueError("a job's request is not pods x a per-pod request")
    ps_request = per_pod[ps_wl] * ps_pods[:, None]
    ps_job = np.where(is_launcher, launcher_job, wl_job[ps_wl])
    ps_name = [shape["launcher"]["name"] if a else
               shape["workers"]["name"] if b else shape["plain"]["name"]
               for a, b in zip(is_launcher.tolist(), is_workers.tolist())]

    ps_flavor = np.full((m, len(groups)), -1, dtype=np.int64)
    running = np.nonzero(base.wl_running)[0]
    # a queue's running workloads, oldest reservation first
    order = running[np.lexsort((base.wl_reserved[running],
                                base.wl_queue[running]))]
    bounds = np.searchsorted(base.wl_queue[order],
                             np.arange(len(base.queues) + 1))
    for c, q in enumerate(base.queues):
        wls = order[bounds[c]:bounds[c + 1]]
        rows = np.concatenate([np.arange(wl_first[i], wl_first[i + 1])
                               for i in wls.tolist()]
                              or [np.empty(0, np.int64)]).astype(np.int64)
        req = ps_request[rows]
        limit = q.borrowing_limit
        q.nominal, q.borrowing_limit = {}, {}
        for g, grp in enumerate(groups):
            share = dep["resource_groups"][g]["flavor_target_percent"]
            lead = req[:, grp.resources[0]]
            total = int(lead.sum())
            target = [total * p // 100 for p in share]
            filled = [0] * len(grp.flavors)
            options = [np.nonzero(row)[0].tolist() for row in grp.may_take]
            of = []
            for job, v in zip(ps_job[rows].tolist(), lead.tolist()):
                mine = options[job]
                f = next((f for f in mine if filled[f] < target[f]),
                         mine[-1])
                filled[f] += v
                of.append(f)
            ps_flavor[rows, g] = of
            usage = np.zeros((len(grp.flavors), len(res)), dtype=np.int64)
            np.add.at(usage, np.array(of, dtype=np.int64), req)
            for fi, f in enumerate(grp.flavors):
                q.nominal[f] = {
                    res[ri]: int(-(-usage[fi, ri] // step[ri]) * step[ri])
                    for ri in grp.resources}
                q.borrowing_limit[f] = {res[ri]: limit[res[ri]]
                                        for ri in grp.resources}
    return PodSetPlan(**vars(base), groups=groups, job_classes=jobs,
                      wl_first=wl_first, ps_name=ps_name, ps_pods=ps_pods,
                      ps_request=ps_request, ps_job=ps_job,
                      ps_flavor=ps_flavor)
