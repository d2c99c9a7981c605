"""The system under test, built from a ``GroupPlan``.

The only file of this deployment kind that imports the program's
cluster objects.  The ResourceFlavors are declared with their node
labels, every ClusterQueue with its resource groups as the plan lists
them, every Workload's PodSet carries its job's node selector and
tolerations, and set-up is the first kind's: ``Driver.restore_workload``
for the workloads that hold quota, each resource on the flavor its
group's plan gives it, ``Driver.ingest_workloads`` for the backlog.
"""

from __future__ import annotations

import gc

from ..flat_one_flavor import program as one_flavor
from ..flat_one_flavor.program import VirtualClock

# What a program that supports this deployment counts: the (head, group)
# walks its vector classify did.  A program without it (the commit
# before the deployment landed) gives every head of a queue with two
# resource groups to the host walk, a thousand a cycle with a search
# launch each; it is turned away before set-up, with an exit code of its
# own, so that a check measures the cell on the program that supports it
# and does not wait for the other to be stopped.
GROUP_COUNTER = "group_walks"


def build_driver(plan, use_device: bool = True):
    """Returns (driver, clock).  ``use_device=False`` gives the host
    scalar scheduler, the CPU tests' second witness."""
    from kueue_tpu.api import types as T
    from kueue_tpu.controller.driver import Driver
    from kueue_tpu.workload import (set_quota_reservation,
                                    sync_admitted_condition)

    dep = plan.config["deployment"]
    if dep["queueing_strategy"] != "BestEffortFIFO":
        raise ValueError("the plain reference covers BestEffortFIFO only")
    pre = dep["preemption"]
    if pre["borrowWithinCohort"] != "Never":
        raise ValueError("the plain reference covers borrowWithinCohort "
                         "Never only")
    policy = T.PreemptionPolicy(
        reclaim_within_cohort=T.ReclaimWithinCohort(
            pre["reclaimWithinCohort"]),
        within_cluster_queue=T.WithinClusterQueue(
            pre["withinClusterQueue"]))
    ff = dep["flavor_fungibility"]
    fungibility = T.FlavorFungibility(
        when_can_borrow=T.FlavorFungibilityPolicy(ff["whenCanBorrow"]),
        when_can_preempt=T.FlavorFungibilityPolicy(ff["whenCanPreempt"]))

    clock = VirtualClock(plan.clock_start)
    d = Driver(clock=clock, use_device_solver=use_device)
    if use_device and GROUP_COUNTER not in d.scheduler.solver.stats:
        raise SystemExit(
            "benchmark: deployment kind flat_two_group needs a program "
            "whose vector classify walks a flavor list a resource group "
            f"(its solver has no counter {GROUP_COUNTER!r}); this one "
            "would walk every head of every cycle on the host, a search "
            "launch a head")

    def toleration(t):
        return T.Toleration(key=t.get("key", ""),
                            operator=t.get("operator", "Equal"),
                            value=t.get("value", ""),
                            effect=t.get("effect", ""))

    res = plan.resources
    for grp in plan.groups:
        for flavor, spec in zip(grp.flavors, grp.specs):
            d.apply_resource_flavor(T.ResourceFlavor(
                name=flavor, node_labels=dict(spec.get("nodeLabels", {})),
                node_taints=[T.Taint(key=t["key"], value=t.get("value", ""),
                                     effect=t["effect"])
                             for t in spec.get("nodeTaints", ())],
                tolerations=[toleration(t)
                             for t in spec.get("tolerations", ())]))
    with d.bulk_apply():
        for q in plan.queues:
            d.apply_cluster_queue(T.ClusterQueue(
                name=q.name, cohort=q.cohort,
                queueing_strategy=T.QueueingStrategy.BEST_EFFORT_FIFO,
                preemption=policy, flavor_fungibility=fungibility,
                resource_groups=[T.ResourceGroup(
                    covered_resources=[res[r] for r in grp.resources],
                    flavors=[T.FlavorQuotas(name=f, resources={
                        r: T.ResourceQuota(
                            nominal=q.nominal[f][r],
                            borrowing_limit=q.borrowing_limit[f][r])
                        for r in q.nominal[f]}) for f in grp.flavors])
                    for grp in plan.groups]))
            d.apply_local_queue(T.LocalQueue(
                name="lq-" + q.name[3:], cluster_queue=q.name))

    # a job class's selector and tolerations, parsed once
    selectors = [dict(j.get("nodeSelector", {})) for j in plan.job_classes]
    tolerations = [[toleration(t) for t in j.get("tolerations", ())]
                   for j in plan.job_classes]
    queue = plan.wl_queue.tolist()
    prio = plan.wl_priority.tolist()
    pods = plan.wl_pods.tolist()
    req = plan.wl_request.tolist()
    created = plan.wl_created.tolist()
    reserved = plan.wl_reserved.tolist()
    flavor_of = plan.wl_flavor.tolist()
    job = plan.wl_job.tolist()
    backlog = []
    for i, name in enumerate(plan.wl_name):
        qname = plan.queues[queue[i]].name
        n = pods[i]
        per_pod = {r: req[i][ri] // n for ri, r in enumerate(res)}
        wl = T.Workload(
            name=name, namespace=plan.namespace,
            queue_name="lq-" + qname[3:], priority=prio[i],
            creation_time=created[i],
            pod_sets=[T.PodSet(name="main", count=n, requests=per_pod,
                               node_selector=dict(selectors[job[i]]),
                               tolerations=list(tolerations[job[i]]))])
        if flavor_of[i][0] >= 0:
            total = {r: req[i][ri] for ri, r in enumerate(res)}
            adm = T.Admission(cluster_queue=qname, pod_set_assignments=[
                T.PodSetAssignment(
                    name="main",
                    flavors={res[r]: grp.flavors[s]
                             for grp, s in zip(plan.groups, flavor_of[i])
                             for r in grp.resources},
                    resource_usage=total, count=n)])
            set_quota_reservation(wl, adm, reserved[i])
            sync_admitted_condition(wl, reserved[i])
            d.restore_workload(wl)
        else:
            backlog.append(wl)
    d.ingest_workloads(backlog)
    # the workload graph lives as long as the run: keep the collector
    # from walking it in the middle of a cycle
    gc.collect()
    gc.freeze()
    return d, clock


def warm_up(driver, plan) -> dict:
    """Every shape the cell's cycles can reach, compiled or loaded
    before the window: the first kind's warm-up, which builds the
    program's own ladder from the cluster's structure (the admit scans,
    the fused window's planes and the searches are F-wide: 3 here, the
    window's resume and mask planes two groups deep) and the search
    shapes that ladder leaves to first use."""
    return one_flavor.warm_up(driver, plan)
