"""The system under test, built from an ``ElasticPlan``.

The only file of this deployment kind that imports the program's
cluster objects.  The ResourceFlavor and ClusterQueues are declared as
the first kind declares them; every Workload is declared with its one
PodSet, ``minCount`` set where its Job is elastic (what the batch/v1
Job integration writes from ``kueue.x-k8s.io/job-min-parallelism``);
set-up is the first kind's: ``Driver.restore_workload`` at the full
count for the workloads that hold quota, ``Driver.ingest_workloads``
for the backlog.
"""

from __future__ import annotations

import gc

from ..flat_one_flavor import program as one_flavor
from ..flat_one_flavor.program import VirtualClock

# What a program that supports this deployment counts: the heads whose
# count search its vector path ran.  A program without it walks every
# elastic head that does not fit whole on the host, with a search
# launch a probe, thousands a round; it is turned away before set-up,
# with an exit code of its own, so that a check measures the cell on
# the program that supports it and does not wait for the other to be
# stopped.
PARTIAL_COUNTER = "partial_heads"


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

    clock = VirtualClock(plan.clock_start)
    d = Driver(clock=clock, use_device_solver=use_device)
    if use_device and PARTIAL_COUNTER not in d.scheduler.solver.stats:
        raise SystemExit(
            "benchmark: deployment kind flat_elastic needs a program "
            f"whose solver counts {PARTIAL_COUNTER!r} (partial admission's "
            "count search on the vector path); this one does not")
    flavor = dep["flavor"]
    d.apply_resource_flavor(T.ResourceFlavor(name=flavor))
    res = plan.resources
    with d.bulk_apply():
        for q in plan.queues:
            d.apply_cluster_queue(T.ClusterQueue(
                name=q.name, cohort=q.cohort,
                queueing_strategy=T.QueueingStrategy.BEST_EFFORT_FIFO,
                preemption=policy,
                resource_groups=[T.ResourceGroup(
                    covered_resources=list(res),
                    flavors=[T.FlavorQuotas(name=flavor, resources={
                        r: T.ResourceQuota(
                            nominal=q.nominal[r],
                            borrowing_limit=q.borrowing_limit[r])
                        for r in res})])]))
            d.apply_local_queue(T.LocalQueue(
                name="lq-" + q.name[3:], cluster_queue=q.name))

    queue = plan.wl_queue.tolist()
    prio = plan.wl_priority.tolist()
    pods = plan.wl_pods.tolist()
    least = plan.wl_min_pods.tolist()
    req = plan.wl_request.tolist()
    created = plan.wl_created.tolist()
    running = plan.wl_running.tolist()
    reserved = plan.wl_reserved.tolist()
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
                               min_count=least[i] if least[i] < n
                               else None)])
        if running[i]:
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
    """The first kind's shapes: the count search adds no program of its
    own (its probes are walked on the host and searched in the batched
    launches' rungs, which a probe pass fills no wider than the cycle's
    heads)."""
    return one_flavor.warm_up(driver, plan)
