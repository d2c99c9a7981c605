"""The system under test, built from a ``FlavorPlan``.

The only file of this deployment kind that imports the program's
cluster objects.  Set-up drives ``Driver.restore_workload`` for the
workloads that hold quota, each on the flavor the plan gives it, and
``Driver.ingest_workloads`` for the backlog, as a manager does when it
restarts on a full cluster.
"""

from __future__ import annotations

import gc

from ..flat_one_flavor import program as one_flavor
from ..flat_one_flavor.program import VirtualClock

# What a program that supports this deployment counts: the searches the
# reclaim oracle asked of the cycle's batched launch.  A program without
# it (the commit before the deployment landed) is turned away before
# set-up, with an exit code of its own, so that a check measures the
# cell on the program that supports it and does not wait for the other
# to be stopped.
ORACLE_COUNTER = "oracle_specs"


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
    if ORACLE_COUNTER not in d.scheduler.preemptor.stats:
        raise SystemExit(
            "benchmark: deployment kind flat_multi_flavor needs a program "
            "that makes the reclaim oracle's pick on the batched search "
            f"(its preemptor has no counter {ORACLE_COUNTER!r}); this one "
            "would answer every several-flavor preempting head by the "
            "host walk, one launch a question, and a traced run of that "
            "at the cell's size does not fit the machine's memory")
    for flavor in plan.flavors:
        d.apply_resource_flavor(T.ResourceFlavor(name=flavor))
    res = plan.resources
    with d.bulk_apply():
        for q in plan.queues:
            d.apply_cluster_queue(T.ClusterQueue(
                name=q.name, cohort=q.cohort,
                queueing_strategy=T.QueueingStrategy.BEST_EFFORT_FIFO,
                preemption=policy, flavor_fungibility=fungibility,
                resource_groups=[T.ResourceGroup(
                    covered_resources=list(res),
                    flavors=[T.FlavorQuotas(name=f, resources={
                        r: T.ResourceQuota(
                            nominal=q.nominal[f][r],
                            borrowing_limit=q.borrowing_limit[f][r])
                        for r in res}) for f in plan.flavors])]))
            d.apply_local_queue(T.LocalQueue(
                name="lq-" + q.name[3:], cluster_queue=q.name))

    queue = plan.wl_queue.tolist()
    prio = plan.wl_priority.tolist()
    pods = plan.wl_pods.tolist()
    req = plan.wl_request.tolist()
    created = plan.wl_created.tolist()
    reserved = plan.wl_reserved.tolist()
    flavor_of = plan.wl_flavor.tolist()
    backlog = []
    for i, name in enumerate(plan.wl_name):
        qname = plan.queues[queue[i]].name
        n = pods[i]
        per_pod = {r: req[i][ri] // n for ri, r in enumerate(res)}
        wl = T.Workload(
            name=name, namespace=plan.namespace,
            queue_name="lq-" + qname[3:], priority=prio[i],
            creation_time=created[i],
            pod_sets=[T.PodSet(name="main", count=n, requests=per_pod)])
        if flavor_of[i] >= 0:
            flavor = plan.flavors[flavor_of[i]]
            total = {r: req[i][ri] for ri, r in enumerate(res)}
            adm = T.Admission(cluster_queue=qname, pod_set_assignments=[
                T.PodSetAssignment(name="main",
                                   flavors={r: flavor for r in res},
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
    before the window: the first kind's warm-up.  It builds the
    program's own ladder from the cluster's structure (the admit scans,
    the fused window's planes and the searches are F-wide: 8 here;
    ``CycleSolver.warmup`` counts the reclaim oracle's specs, up to two
    a head, flavor and resource, into its S rungs) and the search shapes
    that ladder leaves to first use, up to the S rung of two specs a
    head, which at this cluster's size is the ladder's top rung."""
    return one_flavor.warm_up(driver, plan)
