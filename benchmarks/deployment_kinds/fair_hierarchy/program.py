"""The system under test, built from a ``FairPlan``.

The only file of this deployment kind that imports the program's
cluster objects.  The tree is declared as upstream declares it: a
``Cohort`` a department (a root), a ``Cohort`` a team with ``parent``
its department, each with its ``fairSharing.weight``; a ClusterQueue a
tenant in its team, with its weight, its nominal quota and no
borrowing limit; the Driver runs with fair sharing on and the
configuration's preemption strategies.  Set-up is the first kind's:
``Driver.restore_workload`` for the workloads that hold quota,
``Driver.ingest_workloads`` for the backlog.
"""

from __future__ import annotations

import gc

from ..flat_one_flavor.program import VirtualClock

# What a program that supports this deployment counts: the fair-sharing
# cycles its fused window decided.  A program without it (the commit
# before the deployment landed) never packs a window under fair sharing
# and decides every cycle of a round of a thousand preempt-capable
# heads on the host; it is turned away before set-up, with an exit code
# of its own, so that a check measures the cell on the program that
# supports it and does not wait for the other to be stopped.
FAIR_COUNTER = "fs_window_cycles"


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
    d = Driver(clock=clock, use_device_solver=use_device,
               fair_sharing=True,
               fs_preemption_strategies=list(plan.strategies))
    if use_device and FAIR_COUNTER not in d.scheduler.solver.stats:
        raise SystemExit(
            "benchmark: deployment kind fair_hierarchy needs a program "
            "whose fused window decides fair-sharing cycles (its solver "
            f"has no counter {FAIR_COUNTER!r}); this one would decide "
            "every cycle of the cell on the host")
    flavor = dep["flavor"]
    d.apply_resource_flavor(T.ResourceFlavor(name=flavor))
    res = plan.resources
    with d.bulk_apply():
        # roots first, then the teams under them, then the queues: the
        # order each node's children are listed in, which breaks the
        # tournament's last ties
        for name in plan.departments:
            d.apply_cohort(T.Cohort(
                name=name, fair_sharing=T.FairSharing(
                    weight=plan.weight[name])))
        for name in plan.teams:
            d.apply_cohort(T.Cohort(
                name=name, parent_name=plan.team_parent[name],
                fair_sharing=T.FairSharing(weight=plan.weight[name])))
        for q in plan.queues:
            d.apply_cluster_queue(T.ClusterQueue(
                name=q.name, cohort=q.cohort,
                queueing_strategy=T.QueueingStrategy.BEST_EFFORT_FIFO,
                preemption=policy,
                fair_sharing=T.FairSharing(weight=plan.weight[q.name]),
                resource_groups=[T.ResourceGroup(
                    covered_resources=list(res),
                    flavors=[T.FlavorQuotas(name=flavor, resources={
                        r: T.ResourceQuota(nominal=q.nominal[r])
                        for r in res})])]))
            d.apply_local_queue(T.LocalQueue(
                name="lq-" + q.name[3:], cluster_queue=q.name))

    queue = plan.wl_queue.tolist()
    prio = plan.wl_priority.tolist()
    pods = plan.wl_pods.tolist()
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
            pod_sets=[T.PodSet(name="main", count=n, requests=per_pod)])
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
    """Every shape the cell's cycles can reach, compiled or loaded
    before the window: the program's own ladder (``CycleSolver.warmup``:
    the classify and admit scans, a head a queue), the per-cycle
    engine's fair-sharing scan (``fs_admit_scan``) at every head bucket
    up to a head a queue, on the structure's tournament statics, and the
    statics the fused window's tournament reads.  The fused window
    itself is built by the warm round's launch, as in every cell.
    Fair-sharing target searches are the host's: no search shape is
    reachable."""
    import jax
    import numpy as np
    from kueue_tpu.ops.burst import fs_window_statics
    from kueue_tpu.ops.fs_scan import build_fs_statics, fs_admit_scan
    from kueue_tpu.ops.packing import _bucket
    n_heads = len(plan.queues)
    solver = driver.scheduler.solver
    snapshot = driver.cache.snapshot()
    solver.warmup(snapshot, n_heads)
    st = solver._structure_for(snapshot, [])
    statics = build_fs_statics(snapshot, st)
    st._fs_statics = statics
    window = fs_window_statics(st, driver.cache.snapshot)
    N, F = st.subtree_quota.shape
    buckets = []
    W = 8
    while True:
        buckets.append(W)
        if W >= _bucket(n_heads):
            break
        W *= 2
    for W in buckets:
        jax.device_get(fs_admit_scan(
            np.zeros((N, F), np.int32), st.subtree_quota, statics.sq_mask,
            st.guaranteed, st.borrow_cap, st.has_borrow_limit, st.parent,
            statics.node_level, st.fair_weight_milli, statics.lendable_r,
            statics.onehot, statics.child_order,
            np.full(W, -1, np.int32), np.zeros((W, F), np.int32),
            np.zeros(W, bool), np.zeros(W, np.int32),
            np.zeros(W, np.int32), np.zeros(W, bool),
            depth=st.depth, n_levels=statics.n_levels))
    return {"ladder": True, "fs_scan_buckets": len(buckets),
            "depth": int(st.depth), "window_statics": window is not None}
