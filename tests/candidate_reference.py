"""The preemption search's host side written out an object at a time:
what ``Preemptor._find_candidates``, ``_pack_batch`` and
``device_minimal_preemptions`` did before they read the candidate
tables (kueue_tpu/cache/candidates.py).  Kept as the reference the
columnar path has to equal, order and ties and refusals included
(tests/test_candidate_table.py)."""

from typing import Optional

import numpy as np

from kueue_tpu.api.types import (
    ConditionStatus,
    ReclaimWithinCohort,
    WithinClusterQueue,
    WL_EVICTED,
    WL_QUOTA_RESERVED,
)
from kueue_tpu.ops import preemption_solver
from kueue_tpu.ops.packing import coarse_bucket
from kueue_tpu.resources import FlavorResource


def quota_reservation_time(info, now: float) -> float:
    c = info.obj.conditions.get(WL_QUOTA_RESERVED)
    if c is None or c.status != ConditionStatus.TRUE:
        return now
    return c.last_transition_time


def candidates_ordering_key(cq_name: str, now: float):
    """reference preemption.go:591 candidatesOrdering: evicted first, then
    other-CQ borrowers, then lower priority, then later admission."""
    def key(info):
        evicted = 0 if info.obj.condition_true(WL_EVICTED) else 1
        in_cq = 1 if info.cluster_queue == cq_name else 0
        return (evicted, in_cq, info.obj.priority,
                -quota_reservation_time(info, now), info.obj.uid)
    return key


def workload_uses_resources(info, frs) -> bool:
    for psr in info.total_requests:
        for res, flavor in psr.flavors.items():
            if FlavorResource(flavor, res) in frs:
                return True
    return False


def cq_is_borrowing(cq, frs) -> bool:
    if not cq.has_parent():
        return False
    return any(cq.borrowing(fr) for fr in frs)


def find_candidates(preemptor, ctx) -> list:
    """reference preemption.go:480 findCandidates over ``cq.workloads``,
    then the candidatesOrdering sort."""
    cq = ctx.preemptor_cq
    wl = ctx.preemptor
    candidates = []
    wl_priority = wl.obj.priority

    if cq.preemption.within_cluster_queue != WithinClusterQueue.NEVER:
        consider_same_prio = (
            cq.preemption.within_cluster_queue
            == WithinClusterQueue.LOWER_OR_NEWER_EQUAL_PRIORITY)
        preemptor_ts = preemptor.ordering.queue_order_timestamp(wl.obj)
        for cand in cq.workloads.values():
            if cand.obj.priority > wl_priority:
                continue
            if cand.obj.priority == wl_priority and not (
                    consider_same_prio and preemptor_ts
                    < preemptor.ordering.queue_order_timestamp(cand.obj)):
                continue
            if not workload_uses_resources(cand, ctx.frs_need_preemption):
                continue
            candidates.append(cand)

    if (cq.has_parent() and cq.preemption.reclaim_within_cohort
            != ReclaimWithinCohort.NEVER):
        only_lower = (cq.preemption.reclaim_within_cohort
                      != ReclaimWithinCohort.ANY)
        for cohort_cq in cq.parent.root().subtree_cqs():
            if cohort_cq is cq or not cq_is_borrowing(
                    cohort_cq, ctx.frs_need_preemption):
                continue
            for cand in cohort_cq.workloads.values():
                if only_lower and cand.obj.priority >= wl_priority:
                    continue
                if not workload_uses_resources(cand,
                                               ctx.frs_need_preemption):
                    continue
                candidates.append(cand)
    candidates.sort(key=candidates_ordering_key(cq.name, preemptor.clock()))
    return candidates


def plan_searches(preemptor, ctx, candidates: list):
    """``Preemptor.plan_searches`` over a list of ``Info``."""
    same_queue = [c for c in candidates
                  if c.cluster_queue == ctx.preemptor_cq.name]
    if len(same_queue) == len(candidates):
        return [(candidates, True, None)], False
    borrow_ok, threshold = preemptor._can_borrow_within_cohort(ctx)
    if borrow_ok:
        if not preemptor._queue_under_nominal(ctx):
            candidates = [c for c in candidates
                          if c.cluster_queue == ctx.preemptor_cq.name
                          or c.obj.priority < threshold]
        return [(candidates, True, threshold)], False
    if preemptor._queue_under_nominal(ctx):
        return [(candidates, False, None), (same_queue, True, None)], True
    return [(same_queue, True, None)], False


def _to_f_vec(packed, frq) -> Optional[np.ndarray]:
    F = packed.usage0.shape[1]
    scale_of = {r: int(packed.resource_scale[i])
                for i, r in enumerate(packed.resource_names)}
    vec = np.zeros(F, dtype=np.int64)
    for fr, v in frq.items():
        fi = packed.fr_index.get(fr)
        if fi is None:
            return None
        s = scale_of[fr.resource]
        if v % s:
            return None
        vec[fi] += v // s
    if vec.max(initial=0) > 2**31 - 1:
        return None
    return vec.astype(np.int32)


def pack_batch(specs, packed):
    """One batched launch's planes, a candidate at a time: the kernel's
    positional arguments, or None where a spec cannot be packed.
    ``specs`` = [(ctx, [Info], allow_borrowing, threshold)]."""
    if packed is None or not packed.exact or not specs:
        return None
    planes = preemption_solver._planes_for(packed)
    if planes is None:
        return None
    cq_idx = {n: i for i, n in enumerate(packed.cq_names)}
    F = packed.usage0.shape[1]
    max_cands = max(1, max(len(c) for _, c, _, _ in specs))
    if len(specs) > preemption_solver.S_LADDER[-1]:
        return None
    S = coarse_bucket(len(specs), preemption_solver.S_LADDER)
    K = coarse_bucket(max_cands, preemption_solver.K_LADDER)
    usage_planes = planes.usage_planes(packed.usage0)
    forest_of = np.zeros(S, dtype=np.int32)
    pre_cq = np.full(S, -1, dtype=np.int32)
    wl_usage = np.zeros((S, F), dtype=np.int32)
    frs_mask = np.zeros((S, F), dtype=bool)
    cand_cq = np.full((S, K), -1, dtype=np.int32)
    cand_delta = np.zeros((S, K, F), dtype=np.int32)
    cand_other = np.zeros((S, K), dtype=bool)
    cand_above = np.zeros((S, K), dtype=bool)
    allow_b0 = np.zeros(S, dtype=bool)
    thr_en = np.zeros(S, dtype=bool)
    for si, (ctx, candidates, allow_borrowing, threshold) in enumerate(specs):
        ci = cq_idx.get(ctx.preemptor_cq.name)
        if ci is None or ci not in planes.local:
            return None
        f, ci_local = planes.local[ci]
        wu = _to_f_vec(packed, ctx.workload_usage)
        if wu is None:
            return None
        forest_of[si] = f
        pre_cq[si] = ci_local
        wl_usage[si] = wu
        for fr in ctx.frs_need_preemption:
            fi = packed.fr_index.get(fr)
            if fi is None:
                return None
            frs_mask[si, fi] = True
        allow_b0[si] = allow_borrowing
        thr_en[si] = threshold is not None
        for k, cand in enumerate(candidates):
            cci = cq_idx.get(cand.cluster_queue)
            if cci is None:
                return None
            cf_local = planes.local.get(cci)
            if cf_local is None or cf_local[0] != f:
                return None     # candidate outside the preemptor's forest
            delta = _to_f_vec(packed, cand.usage())
            if delta is None:
                return None
            cand_cq[si, k] = cf_local[1]
            cand_delta[si, k] = delta
            cand_other[si, k] = cand.cluster_queue != ctx.preemptor_cq.name
            cand_above[si, k] = (threshold is not None
                                 and cand.obj.priority >= threshold)
    return (usage_planes[forest_of], planes.subtree[forest_of],
            planes.guaranteed[forest_of], planes.borrow_cap[forest_of],
            planes.has_blim[forest_of], planes.parent[forest_of],
            pre_cq, wl_usage, frs_mask, cand_cq, cand_delta, cand_other,
            cand_above, allow_b0, thr_en)


def single_planes(ctx, candidates: list, threshold, packed):
    """The planes of the search launched alone, a candidate at a time:
    (pre_cq, wl_usage, frs_mask, cand_cq, cand_delta, cand_other,
    cand_above), or None where it cannot be packed."""
    if packed is None or not packed.exact:
        return None
    cq_idx = {n: i for i, n in enumerate(packed.cq_names)}
    pre_cq = cq_idx.get(ctx.preemptor_cq.name)
    if pre_cq is None:
        return None
    F = packed.usage0.shape[1]
    wl_usage = _to_f_vec(packed, ctx.workload_usage)
    if wl_usage is None:
        return None
    frs_mask = np.zeros(F, dtype=bool)
    for fr in ctx.frs_need_preemption:
        fi = packed.fr_index.get(fr)
        if fi is None:
            return None
        frs_mask[fi] = True
    K = preemption_solver._bucket(len(candidates))
    cand_cq = np.full(K, -1, dtype=np.int32)
    cand_delta = np.zeros((K, F), dtype=np.int32)
    cand_other = np.zeros(K, dtype=bool)
    cand_above = np.zeros(K, dtype=bool)
    for i, cand in enumerate(candidates):
        ci = cq_idx.get(cand.cluster_queue)
        if ci is None:
            return None
        delta = _to_f_vec(packed, cand.usage())
        if delta is None:
            return None
        cand_cq[i] = ci
        cand_delta[i] = delta
        cand_other[i] = cand.cluster_queue != ctx.preemptor_cq.name
        cand_above[i] = (threshold is not None
                         and cand.obj.priority >= threshold)
    return (pre_cq, wl_usage, frs_mask, cand_cq, cand_delta, cand_other,
            cand_above)
