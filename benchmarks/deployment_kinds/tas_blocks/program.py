"""The system under test, built from a ``TASPlan``.

The only file of this deployment kind that imports the program's
cluster objects.  The cluster is declared as upstream declares it: a
``Topology`` with the configuration's levels, one ``ResourceFlavor``
naming it in ``topologyName``, a node a ``NodeInfo`` with its labels and
allocatable, the ``TopologyAwareScheduling`` feature gate on; the
queues and the workloads are the first kind's, each PodSet with its
class's topology request, each restored workload's admission with its
placement (a ``TopologyAssignment`` at the host level).  Set-up drives
``Driver.restore_workload`` for the running workloads and
``Driver.ingest_workloads`` for the backlog.
"""

from __future__ import annotations

import gc

from ..flat_one_flavor.program import VirtualClock
from ..flat_one_flavor.program import warm_up as flat_warm_up
from .cluster import NONE, REQUIRED

# What a program that supports this deployment counts: the queries its
# per-cycle engine's batched topology stage answered.  A program without
# it (the commit before the deployment landed) refuses a topology-aware
# queue on the vector path and walks each of a thousand heads a cycle
# on the host against a tree it rebuilds from 49,152 nodes a cycle, far
# past a run's time; it is turned away before set-up, with an exit code
# of its own, so that a check measures the cell on the program that
# supports it and does not wait for the other to be stopped.
TAS_COUNTER = "tas_queries"


def build_driver(plan, use_device: bool = True):
    """Returns (driver, clock).  ``use_device=False`` gives the host
    scalar scheduler, the CPU tests' second witness."""
    from kueue_tpu import features
    from kueue_tpu.api import types as T
    from kueue_tpu.cache.tas_cache import NodeInfo
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

    features.set_feature_gates({"TopologyAwareScheduling": True})
    clock = VirtualClock(plan.clock_start)
    d = Driver(clock=clock, use_device_solver=use_device)
    if use_device and TAS_COUNTER not in d.scheduler.solver.stats:
        raise SystemExit(
            "benchmark: deployment kind tas_blocks needs a program whose "
            "per-cycle engine places topology-aware heads in a batched "
            f"stage (its solver has no counter {TAS_COUNTER!r}); this one "
            "would walk every head of the cell on the host")
    topo = dep["topology"]
    flavor = dep["flavor"]
    levels = list(plan.levels)
    d.apply_topology(T.Topology(name=topo["name"], levels=levels))
    d.apply_resource_flavor(T.ResourceFlavor(name=flavor,
                                             topology_name=topo["name"]))
    cap = dict(zip(("cpu", "memory", "pods"), plan.node_cap.tolist()))
    for h in range(plan.n_hosts):
        values = plan.host_values(h)
        d.cache.tas.add_or_update_node(NodeInfo(
            name=values[-1], labels=dict(zip(levels, values)),
            capacity=dict(cap)))
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

    requests = {}            # (kind, level) -> one shared request object
    queue = plan.wl_queue.tolist()
    prio = plan.wl_priority.tolist()
    pods = plan.wl_pods.tolist()
    req = plan.wl_request.tolist()
    created = plan.wl_created.tolist()
    running = plan.wl_running.tolist()
    reserved = plan.wl_reserved.tolist()
    kinds = plan.wl_topology.tolist()
    lvl = plan.wl_level.tolist()
    starts = plan.place_start.tolist()
    hosts = plan.place_host.tolist()
    counts = plan.place_pods.tolist()
    backlog = []
    for i, name in enumerate(plan.wl_name):
        qname = plan.queues[queue[i]].name
        n = pods[i]
        per_pod = {r: req[i][ri] // n for ri, r in enumerate(res)}
        key = (kinds[i], lvl[i])
        tr = requests.get(key)
        if tr is None and kinds[i] != NONE:
            tr = requests[key] = (
                T.PodSetTopologyRequest(required=levels[lvl[i]])
                if kinds[i] == REQUIRED
                else T.PodSetTopologyRequest(preferred=levels[lvl[i]]))
        wl = T.Workload(
            name=name, namespace=plan.namespace,
            queue_name="lq-" + qname[3:], priority=prio[i],
            creation_time=created[i],
            pod_sets=[T.PodSet(name="main", count=n, requests=per_pod,
                               topology_request=tr)])
        if running[i]:
            total = {r: req[i][ri] for ri, r in enumerate(res)}
            where = T.TopologyAssignment(levels=levels, domains=[
                T.TopologyDomainAssignment(
                    values=list(plan.host_values(h)), count=k)
                for h, k in zip(hosts[starts[i]:starts[i + 1]],
                                counts[starts[i]:starts[i + 1]])])
            adm = T.Admission(cluster_queue=qname, pod_set_assignments=[
                T.PodSetAssignment(name="main",
                                   flavors={r: flavor for r in res},
                                   resource_usage=total, count=n,
                                   topology_assignment=where)])
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
    before the window: the first kind's (``CycleSolver.warmup`` and the
    search shapes it leaves to first use), and the topology stage's
    launches for the distinct PodSets a workload of the cell has, any of
    which can be a head (``CycleSolver.warm_topology``)."""
    from kueue_tpu.api import types as T
    done = flat_warm_up(driver, plan)
    res = plan.resources
    levels = list(plan.levels)
    shapes = set(zip(map(tuple, plan.wl_request.tolist()),
                     plan.wl_pods.tolist(), plan.wl_topology.tolist(),
                     plan.wl_level.tolist()))
    pod_sets = []
    for req, n, kind, lvl in sorted(shapes):
        tr = (None if kind == NONE else
              T.PodSetTopologyRequest(required=levels[lvl])
              if kind == REQUIRED else
              T.PodSetTopologyRequest(preferred=levels[lvl]))
        pod_sets.append(T.PodSet(
            name="main", count=n, topology_request=tr,
            requests={r: req[ri] // n for ri, r in enumerate(res)}))
    done["topology_launches"] = driver.scheduler.solver.warm_topology(
        driver.cache.snapshot(), pod_sets)
    return done
