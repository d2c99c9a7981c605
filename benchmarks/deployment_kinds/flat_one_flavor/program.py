"""The system under test, built from a ``ClusterPlan``.

The only file of this deployment kind that imports the program's
cluster objects.  Set-up drives ``Driver.restore_workload`` for the workloads
that hold quota and ``Driver.ingest_workloads`` for the backlog, as a
manager does when it restarts on a full cluster.
"""

from __future__ import annotations

import gc


class VirtualClock:
    def __init__(self, t: float):
        self.t = t

    def __call__(self) -> float:
        return self.t


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
    before the window: a head a queue, and candidate sets up to the
    largest cohort's running rows.  ``CycleSolver.warmup`` is the program's own
    ladder: the admit scans and the batched preemption search up to
    128 candidates a head.  The search shapes it leaves to first use
    are warmed here, with the program's kernels and its own structure
    arrays: the batched search's 1,024-candidate rungs, and the
    one-head search (the path a cycle takes when a head has more than
    1,024 candidates) at every power-of-two bucket up to the largest
    candidate set the cluster can form.  These are the program's
    internals, and nothing is caught: where a later program no longer
    has them this fails, and does not quietly move their compilation
    into the window (PERF.md, Open questions: S6 should take these
    shapes into ``CycleSolver.warmup``)."""
    import jax
    import numpy as np
    from kueue_tpu.ops.packing import coarse_bucket
    from kueue_tpu.ops.preemption_kernel import (
        minimal_preemptions, minimal_preemptions_batch)
    from kueue_tpu.ops.preemption_solver import K_LADDER, S_LADDER
    n_heads = len(plan.queues)
    cohort_rows: dict[str, int] = {}
    for q in plan.queues:
        cohort_rows[q.cohort] = cohort_rows.get(q.cohort, 0) + q.running
    max_candidates = max(cohort_rows.values())
    solver = driver.scheduler.solver
    solver.warmup(driver.cache.snapshot(), n_heads)
    done = {"ladder": True, "batch_rungs": 0, "one_head_buckets": 0}
    st = solver._structure_for(driver.cache.snapshot(), [])
    N, F = st.subtree_quota.shape
    NL = st._preempt_planes.NL
    s_top = coarse_bucket(2 * n_heads, S_LADDER)
    for S in [s for s in S_LADDER if s <= s_top]:
        for K in K_LADDER[2:]:
            jax.device_get(minimal_preemptions_batch(
                np.zeros((S, NL, F), np.int32),
                np.zeros((S, NL, F), np.int32),
                np.zeros((S, NL, F), np.int32),
                np.full((S, NL, F), 2**30, np.int32),
                np.zeros((S, NL, F), bool),
                np.full((S, NL), -1, np.int32),
                np.full(S, -1, np.int32),
                np.zeros((S, F), np.int32), np.zeros((S, F), bool),
                np.full((S, K), -1, np.int32),
                np.zeros((S, K, F), np.int32),
                np.zeros((S, K), bool), np.zeros((S, K), bool),
                np.zeros(S, bool), np.zeros(S, bool), depth=st.depth))
            done["batch_rungs"] += 1
    K = 8
    while True:
        jax.device_get(minimal_preemptions(
            np.zeros((N, F), np.int32), st.subtree_quota, st.guaranteed,
            st.borrow_cap, st.has_borrow_limit, st.parent,
            0, np.zeros(F, np.int32), np.zeros(F, bool),
            np.full(K, -1, np.int32), np.zeros((K, F), np.int32),
            np.zeros(K, bool), np.zeros(K, bool), True, False,
            depth=st.depth))
        done["one_head_buckets"] += 1
        if K >= max_candidates:
            break
        K *= 2
    return done
