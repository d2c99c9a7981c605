"""The system under test, built from a ``PodSetPlan``.

The only file of this deployment kind that imports the program's
cluster objects.  The ResourceFlavors and ClusterQueues are declared as
the fourth kind declares them; every Workload is declared with its
PodSets in the plan's order, each with its own node selector and
tolerations; set-up is the first kind's: ``Driver.restore_workload``
for the workloads that hold quota, each PodSet's resources on the
flavors the plan gives that PodSet, ``Driver.ingest_workloads`` for the
backlog.
"""

from __future__ import annotations

import gc

from ..flat_one_flavor import program as one_flavor
from ..flat_one_flavor.program import VirtualClock

# What a program that supports this deployment counts: the (head,
# PodSet) passes its vector classify did.  A program without it (the
# commit before the deployment landed) gives every gang to the host
# walk, six hundred a cycle with a search launch each, and drops every
# window as dirty on its first gang; it is turned away before set-up,
# with an exit code of its own, so that a check measures the cell on
# the program that supports it and does not wait for the other to be
# stopped.
PODSET_COUNTER = "podset_walks"


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
    if use_device and PODSET_COUNTER not in d.scheduler.solver.stats:
        raise SystemExit(
            "benchmark: deployment kind flat_pod_sets needs a program "
            "whose vector classify passes over a Workload's PodSets "
            f"(its solver has no counter {PODSET_COUNTER!r}); this one "
            "would walk every gang of every cycle on the host, a search "
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

    # a constraint class's selector and tolerations, parsed once
    selectors = [dict(j.get("nodeSelector", {})) for j in plan.job_classes]
    tolerations = [[toleration(t) for t in j.get("tolerations", ())]
                   for j in plan.job_classes]
    queue = plan.wl_queue.tolist()
    prio = plan.wl_priority.tolist()
    created = plan.wl_created.tolist()
    reserved = plan.wl_reserved.tolist()
    first = plan.wl_first.tolist()
    ps_pods = plan.ps_pods.tolist()
    ps_req = plan.ps_request.tolist()
    ps_job = plan.ps_job.tolist()
    ps_flavor = plan.ps_flavor.tolist()
    backlog = []
    for i, name in enumerate(plan.wl_name):
        qname = plan.queues[queue[i]].name
        rows = range(first[i], first[i + 1])
        wl = T.Workload(
            name=name, namespace=plan.namespace,
            queue_name="lq-" + qname[3:], priority=prio[i],
            creation_time=created[i],
            pod_sets=[T.PodSet(
                name=plan.ps_name[j], count=ps_pods[j],
                requests={r: ps_req[j][ri] // ps_pods[j]
                          for ri, r in enumerate(res)},
                node_selector=dict(selectors[ps_job[j]]),
                tolerations=list(tolerations[ps_job[j]])) for j in rows])
        if ps_flavor[first[i]][0] >= 0:
            adm = T.Admission(cluster_queue=qname, pod_set_assignments=[
                T.PodSetAssignment(
                    name=plan.ps_name[j],
                    flavors={res[r]: grp.flavors[s]
                             for grp, s in zip(plan.groups, ps_flavor[j])
                             for r in grp.resources},
                    resource_usage={r: ps_req[j][ri]
                                    for ri, r in enumerate(res)},
                    count=ps_pods[j]) for j in rows])
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
    before the window: the program's own ladder at the population's
    PodSets (``CycleSolver.warmup(..., pod_sets=)``: the admit scans'
    decision pairs are a (PodSet, resource) wide, the fused window's
    request, resume and mask planes a PodSet deeper), then the first
    kind's warm-up, which finds that ladder built and adds the search
    shapes it leaves to first use."""
    most = int((plan.wl_first[1:] - plan.wl_first[:-1]).max())
    driver.scheduler.solver.warmup(driver.cache.snapshot(),
                                   len(plan.queues), pod_sets=most)
    return dict(one_flavor.warm_up(driver, plan), pod_sets=most)
