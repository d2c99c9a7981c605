"""A configuration file -> the cluster it describes, as plain data.

Nothing here imports the program.  The population (queues, cohorts,
classes, which workloads run, every timestamp, what the seed draws) is
``flat_one_flavor``'s, planned by its module from the same keys, and the
plan's shape is ``flat_multi_flavor``'s.  This kind adds what the
flavors declare (``deployment.flavor_specs``: node labels, taints),
what each job carries (``job_constraints``: a node selector and
tolerations, by the job's index k within its queue), and with them
which flavor each running workload holds.

A queue's running workloads, ordered by reservation time, oldest first,
are placed one by one: each flavor has a target, its share
(``flavor_target_percent``) of the queue's summed cpu, and a workload
takes the first flavor of the queue's order that it may take (the plain
reference's ``eligible``) and whose target is not yet reached, else the
last flavor it may take.  nominalQuota of (queue, flavor, resource) is
that flavor's usage rounded up: every flavor of every queue starts full
and nobody borrows.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..flat_multi_flavor.cluster import FlavorPlan
from ..flat_one_flavor import cluster as one_flavor
from ..flat_one_flavor.cluster import queue_rows, summary, unit_scale
from .reference import eligible

__all__ = ["plan_cluster", "problem", "summary", "queue_rows"]


@dataclass
class LabelledPlan(FlavorPlan):
    """``flat_multi_flavor``'s plan, and: what each flavor declares (in
    ``flavors``' order), the jobs' constraint classes, the class of each
    workload, and which flavors each class may take."""
    flavor_specs: list[dict] = field(default_factory=list)
    job_classes: list[dict] = field(default_factory=list)
    wl_job: np.ndarray = None        # [N] index into job_classes
    may_take: np.ndarray = None      # [len(job_classes), S] bool


def problem(cfg: dict, plan: LabelledPlan) -> dict:
    """What ``benchmarks/peaks.py`` counts a decided cycle's bytes from:
    the rows as the first kind counts them, a queue's quota state once a
    flavor, and one byte a row for the flavors it may take.  The count
    in peaks.py has no term a row beyond its own, so that byte is given
    in its unit for a queue's state, 12 bytes a resource."""
    rows = queue_rows(cfg)["preempting_forest_rows"]
    queue_state = 12 * len(plan.resources)
    return {"real_rows": rows,
            "queues": (len(plan.queues) * len(plan.flavors)
                       + -(-rows // queue_state)),
            "resources": len(plan.resources)}


def job_class_of(cfg: dict, k: np.ndarray) -> np.ndarray:
    """The constraint class of the job with index ``k`` in its queue:
    the first entry of ``job_constraints`` whose ``k_mod_3`` (and
    ``k_div_3_mod_4``, where it names one) holds ``k``."""
    out = np.full(len(k), -1, dtype=np.int64)
    for j, job in enumerate(cfg["job_constraints"]):
        hit = np.isin(k % 3, job["k_mod_3"])
        if "k_div_3_mod_4" in job:
            hit &= np.isin((k // 3) % 4, job["k_div_3_mod_4"])
        out[hit & (out < 0)] = j
    if (out < 0).any():
        raise ValueError("job_constraints leave a job without a class")
    return out


def plan_cluster(cfg: dict, seed: int) -> LabelledPlan:
    base = one_flavor.plan_cluster(cfg, seed)
    dep = cfg["deployment"]
    flavors = list(dep["flavors"])
    specs = [dep["flavor_specs"][f] for f in flavors]
    share = list(dep["flavor_target_percent"])
    if len(share) != len(flavors) or sum(share) != 100:
        raise ValueError(f"flavor_target_percent {share!r} for {flavors!r}")
    jobs = list(cfg["job_constraints"])
    keys = {k for f in specs for k in f.get("nodeLabels", {})}
    may_take = np.array([[eligible(job, f, keys) for f in specs]
                         for job in jobs])
    for job, row in zip(jobs, may_take):
        # the file says which flavors a class may take; the rule decides
        if [f for f, ok in zip(flavors, row) if ok] != job["may_take"]:
            raise ValueError(f"job class {job['name']!r}: may_take "
                             f"{job['may_take']!r} is not what its selector "
                             "and tolerations give")
    if not may_take.any(axis=1).all():
        raise ValueError("a job class may take no flavor")
    res = base.resources
    scale = unit_scale(cfg)
    step = [dep["quota_round_up"][r] * scale[r] for r in res]
    cpu = res.index("cpu")
    S = len(flavors)

    # rows are laid out queue by queue, then k
    n = len(base.wl_queue)
    first = np.searchsorted(base.wl_queue, np.arange(len(base.queues)))
    wl_job = job_class_of(cfg, np.arange(n) - first[base.wl_queue])

    wl_flavor = np.full(n, -1, dtype=np.int64)
    running = np.nonzero(base.wl_running)[0]
    # a queue's running rows, oldest reservation first
    order = running[np.lexsort((base.wl_reserved[running],
                                base.wl_queue[running]))]
    bounds = np.searchsorted(base.wl_queue[order],
                             np.arange(len(base.queues) + 1))
    options = [np.nonzero(row)[0].tolist() for row in may_take]
    for c, q in enumerate(base.queues):
        rows = order[bounds[c]:bounds[c + 1]]
        req = base.wl_request[rows]
        total = int(req[:, cpu].sum())
        target = [total * p // 100 for p in share]
        filled = [0] * S
        of = []
        for job, v in zip(wl_job[rows].tolist(), req[:, cpu].tolist()):
            mine = options[job]
            f = next((f for f in mine if filled[f] < target[f]), mine[-1])
            filled[f] += v
            of.append(f)
        wl_flavor[rows] = of
        usage = np.zeros((S, len(res)), dtype=np.int64)
        np.add.at(usage, np.array(of, dtype=np.int64), req)
        limit = q.borrowing_limit
        q.nominal = {f: {r: int(-(-usage[fi, ri] // step[ri]) * step[ri])
                         for ri, r in enumerate(res)}
                     for fi, f in enumerate(flavors)}
        q.borrowing_limit = {f: dict(limit) for f in flavors}
    return LabelledPlan(**vars(base), flavors=flavors, wl_flavor=wl_flavor,
                        flavor_specs=specs, job_classes=jobs,
                        wl_job=wl_job, may_take=may_take)
