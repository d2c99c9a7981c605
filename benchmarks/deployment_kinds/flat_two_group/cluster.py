"""A configuration file -> the cluster it describes, as plain data.

Nothing here imports the program.  The population (queues, cohorts,
classes, which workloads run, every timestamp, what the seed draws) is
``flat_one_flavor``'s, planned by its module from the same keys.  This
kind adds the resource groups (``deployment.resource_groups``: the
resources each covers and its flavors in order), what the flavors
declare (``deployment.flavor_specs``), what each job carries
(``job_constraints``, the third kind's, by the job's index k within
its queue), and with them which flavor of each group a running
workload holds.

Group by group, a queue's running workloads, ordered by reservation
time, oldest first, are placed one by one as the third kind places
them: each flavor of the group has a target, its share
(``flavor_target_percent``) of the queue's summed use of the group's
first resource, and a workload takes the first flavor of the group's
order that it may take (the plain reference's ``eligible``, on the
label keys of *that group's* flavors) and whose target is not yet
reached, else the last it may take.  nominalQuota of (queue, flavor,
resource) is that flavor's usage rounded up: every flavor of every
group of every queue starts full and nobody borrows.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..flat_labelled_flavor.cluster import job_class_of
from ..flat_labelled_flavor.reference import eligible
from ..flat_one_flavor import cluster as one_flavor
from ..flat_one_flavor.cluster import queue_rows, summary, unit_scale

__all__ = ["plan_cluster", "problem", "summary", "queue_rows"]


@dataclass
class Group:
    """One resource group as every queue declares it."""
    resources: list[int]            # indices into the plan's resources
    flavors: list[str]              # in the group's order
    specs: list[dict]               # what each flavor declares
    label_keys: set                 # the keys a selector is matched on
    may_take: np.ndarray = None     # [job class, slot] bool


@dataclass
class GroupPlan(one_flavor.ClusterPlan):
    """``flat_one_flavor``'s plan; a queue's ``nominal`` and
    ``borrowing_limit`` are keyed by flavor, then by the resources of
    the flavor's group."""
    groups: list[Group] = field(default_factory=list)
    job_classes: list[dict] = field(default_factory=list)
    wl_job: np.ndarray = None        # [N] index into job_classes
    # [N, G] slot in each group's flavor list that a running row holds,
    # -1 of a pending one
    wl_flavor: np.ndarray = None

    def group_of(self, flavor: str):
        """(group index, slot) of a flavor name, or None."""
        for g, grp in enumerate(self.groups):
            if flavor in grp.flavors:
                return g, grp.flavors.index(flavor)
        return None


def problem(cfg: dict, plan: GroupPlan) -> dict:
    """What ``benchmarks/peaks.py`` counts a decided cycle's bytes from:
    the rows as the first kind counts them (a row's requests, the four
    words of its place in the orders, a byte of state: 25 B with two
    resources), and a queue's quota state once a (flavor, resource) of
    each group: nominal, borrowing limit and usage, 12 B a pair.  The
    count in peaks.py takes a queue's state in units of 12 B a
    resource, so the pairs are given in that unit."""
    pairs = len(plan.queues) * sum(
        len(g.flavors) * len(g.resources) for g in plan.groups)
    return {"real_rows": queue_rows(cfg)["preempting_forest_rows"],
            "queues": -(-pairs // len(plan.resources)),
            "resources": len(plan.resources)}


def plan_groups(cfg: dict, resources: list[str]) -> list[Group]:
    dep = cfg["deployment"]
    jobs = list(cfg["job_constraints"])
    groups, seen_r, seen_f = [], set(), set()
    for rg in dep["resource_groups"]:
        flavors = list(rg["flavors"])
        covered = list(rg["coveredResources"])
        if seen_r & set(covered) or seen_f & set(flavors):
            raise ValueError("a resource and a flavor belong to one "
                             "resource group each")
        seen_r |= set(covered)
        seen_f |= set(flavors)
        specs = [dep["flavor_specs"][f] for f in flavors]
        keys = {k for f in specs for k in f.get("nodeLabels", {})}
        may = np.array([[eligible(job, f, keys) for f in specs]
                        for job in jobs])
        if not may.any(axis=1).all():
            raise ValueError("a job class may take no flavor of a group")
        share = list(rg["flavor_target_percent"])
        if len(share) != len(flavors) or sum(share) != 100:
            raise ValueError(f"flavor_target_percent {share!r} for "
                             f"{flavors!r}")
        groups.append(Group(resources=[resources.index(r) for r in covered],
                            flavors=flavors, specs=specs, label_keys=keys,
                            may_take=may))
    if seen_r != set(resources):
        raise ValueError("the resource groups cover "
                         f"{sorted(seen_r)}, the population requests "
                         f"{resources}")
    for j, job in enumerate(jobs):
        # the file says which flavors a class may take; the rule decides
        mine = [f for g in groups for f, ok in zip(g.flavors, g.may_take[j])
                if ok]
        if mine != job["may_take"]:
            raise ValueError(f"job class {job['name']!r}: may_take "
                             f"{job['may_take']!r} is not what its selector "
                             f"and tolerations give ({mine!r})")
    return groups


def plan_cluster(cfg: dict, seed: int) -> GroupPlan:
    base = one_flavor.plan_cluster(cfg, seed)
    dep = cfg["deployment"]
    res = base.resources
    groups = plan_groups(cfg, res)
    scale = unit_scale(cfg)
    step = [dep["quota_round_up"][r] * scale[r] for r in res]

    # rows are laid out queue by queue, then k
    n = len(base.wl_queue)
    first = np.searchsorted(base.wl_queue, np.arange(len(base.queues)))
    wl_job = job_class_of(cfg, np.arange(n) - first[base.wl_queue])

    wl_flavor = np.full((n, len(groups)), -1, dtype=np.int64)
    running = np.nonzero(base.wl_running)[0]
    # a queue's running rows, oldest reservation first
    order = running[np.lexsort((base.wl_reserved[running],
                                base.wl_queue[running]))]
    bounds = np.searchsorted(base.wl_queue[order],
                             np.arange(len(base.queues) + 1))
    for c, q in enumerate(base.queues):
        rows = order[bounds[c]:bounds[c + 1]]
        req = base.wl_request[rows]
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
            for job, v in zip(wl_job[rows].tolist(), lead.tolist()):
                mine = options[job]
                f = next((f for f in mine if filled[f] < target[f]),
                         mine[-1])
                filled[f] += v
                of.append(f)
            wl_flavor[rows, g] = of
            usage = np.zeros((len(grp.flavors), len(res)), dtype=np.int64)
            np.add.at(usage, np.array(of, dtype=np.int64), req)
            for fi, f in enumerate(grp.flavors):
                q.nominal[f] = {
                    res[ri]: int(-(-usage[fi, ri] // step[ri]) * step[ri])
                    for ri in grp.resources}
                q.borrowing_limit[f] = {res[ri]: limit[res[ri]]
                                        for ri in grp.resources}
    return GroupPlan(**vars(base), groups=groups,
                     job_classes=list(cfg["job_constraints"]),
                     wl_job=wl_job, wl_flavor=wl_flavor)
