"""Host wrapper for the device preemption search.

Packs the snapshot + candidate list and runs
ops.preemption_kernel.minimal_preemptions; returns the Target list in
host semantics, or None when the scenario needs the host path (inexact
scaling, unknown flavor-resources).  Decision parity with the host
greedy+fillback search is enforced by tests/test_preemption_kernel.py.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..api.types import (
    IN_CLUSTER_QUEUE_REASON,
    IN_COHORT_RECLAIM_WHILE_BORROWING_REASON,
    IN_COHORT_RECLAMATION_REASON,
)
from ..obs.trace import span as _span
from .device import on_accelerator, output_devices
from .packing import coarse_bucket, pack_cycle
from .preemption_kernel import minimal_preemptions

# shape ladders for the batched search (see coarse_bucket)
S_LADDER = (32, 256, 1024, 4096)
K_LADDER = (16, 128, 1024)
# The floor of one scan step of the batched search, in rows: a step over
# S rows takes about as long as STEP_FLOOR_ROWS + S rows' worth, so a
# launch costs about K * (STEP_FLOOR_ROWS + S) (plan_launches).  Read
# off the chip; PERF.md §5 has the twelve (S, K) timings it rests on.
STEP_FLOOR_ROWS = 50


def _bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


class _ForestPlanes:
    """Per-forest compact quota planes, cached per PackedStructure.

    Each cohort forest's nodes are remapped to a dense local index space
    (bucketed to NL) so a preemption search carries [NL, F] instead of
    the whole [N, F] cluster."""

    def __init__(self, st):
        forest = np.asarray(st.forest_of_node)
        N, F = st.subtree_quota.shape
        per_forest: list[list[int]] = [[] for _ in range(st.n_forests)]
        for ni in range(N):
            per_forest[int(forest[ni])].append(ni)
        self.NL = _bucket(max(1, max(len(v) for v in per_forest)),
                          minimum=4)
        G = st.n_forests
        self.glob_idx = np.full((G, self.NL), -1, dtype=np.int32)
        self.parent = np.full((G, self.NL), -1, dtype=np.int32)
        self.subtree = np.zeros((G, self.NL, F), dtype=np.int32)
        self.guaranteed = np.zeros((G, self.NL, F), dtype=np.int32)
        self.borrow_cap = np.full((G, self.NL, F), 2**30, dtype=np.int32)
        self.has_blim = np.zeros((G, self.NL, F), dtype=bool)
        self.local: dict[int, tuple[int, int]] = {}   # global → (f, local)
        for f, nodes in enumerate(per_forest):
            if len(nodes) > self.NL:
                raise ValueError("forest exceeds bucket")
            loc = {g: i for i, g in enumerate(nodes)}
            for i, g in enumerate(nodes):
                self.glob_idx[f, i] = g
                p = int(st.parent[g])
                self.parent[f, i] = loc.get(p, -1) if p >= 0 else -1
                self.subtree[f, i] = st.subtree_quota[g]
                self.guaranteed[f, i] = st.guaranteed[g]
                self.borrow_cap[f, i] = st.borrow_cap[g]
                self.has_blim[f, i] = st.has_borrow_limit[g]
                self.local[g] = (f, i)

    def usage_planes(self, usage0: np.ndarray) -> np.ndarray:
        """[G, NL, F] usage slices from the cycle's [N, F] usage."""
        safe = np.maximum(self.glob_idx, 0)
        return usage0[safe] * (self.glob_idx >= 0)[:, :, None]


def _planes_for(packed) -> Optional[_ForestPlanes]:
    st = getattr(packed, "structure", None)
    if st is None:
        return None
    planes = getattr(st, "_preempt_planes", None)
    if planes is None:
        try:
            planes = _ForestPlanes(st)
        except ValueError:
            return None
        st._preempt_planes = planes
    return planes


def _refused(stats: Optional[dict], reason: str) -> None:
    """Count one refusal of the batched search under its one reason
    (``over_s`` or ``unpackable``); the caller then runs a launch a
    head."""
    if stats is not None:
        stats["search_batch_refusals"] += 1
        stats["search_refused_" + reason] += 1
    return None


def plan_launches(counts: list[int]) -> list[tuple[int, list[int]]]:
    """The launches of one cycle's batched searches, from each spec's
    candidate count (every one of 1 to ``K_LADDER``'s top rung):
    [(K rung, positions in ``counts``)], the longest scan first.

    The kernel is two scans of K steps over S rows, so a launch costs
    its own largest search's K whatever the others hold.  Specs are
    grouped by the K rung of their own count and neighbouring rungs are
    merged where that is cheaper: of the ways to cut the occupied rungs
    into runs of neighbours, the one with the least
    ``sum(K * (STEP_FLOOR_ROWS + S))`` over its launches wins, the
    fewest launches on a tie.  A group goes out at its highest rung in
    launches of at most ``S_LADDER``'s top rung.  Counts that share a
    rung plan the one launch there was before the plan looked at
    sizes."""
    by_rung: dict[int, list[int]] = {}
    for i, n in enumerate(counts):
        by_rung.setdefault(coarse_bucket(n, K_LADDER), []).append(i)
    rungs = sorted(by_rung)
    if not rungs:
        return []
    top = S_LADDER[-1]

    def cost(group: list[int]) -> int:
        full, rest = divmod(sum(len(by_rung[r]) for r in group), top)
        rows = full * (STEP_FLOOR_ROWS + top)
        if rest:
            rows += STEP_FLOOR_ROWS + coarse_bucket(rest, S_LADDER)
        return group[-1] * rows

    best = None
    joints = len(rungs) - 1
    # bit j of ``cuts`` set: rungs j and j + 1 go out apart
    for cuts in sorted(range(1 << joints), key=int.bit_count):
        groups, start = [], 0
        for j in range(len(rungs)):
            if j == joints or cuts >> j & 1:
                groups.append(rungs[start:j + 1])
                start = j + 1
        total = sum(cost(g) for g in groups)
        if best is None or total < best[0]:
            best = (total, groups)
    launches = []
    for group in reversed(best[1]):
        members = [i for r in group for i in by_rung[r]]
        launches += [(group[-1], members[at:at + top])
                     for at in range(0, len(members), top)]
    return launches


def device_minimal_preemptions_batch(launches, packed,
                                     stats: Optional[dict] = None):
    """A cycle's preemption searches in the vmapped dispatches the
    preemptor planned (``plan_launches``), every one dispatched before
    the first result is fetched: launch n + 1 is packed while launch n
    is on the device, and the host blocks once for the lot.

    ``launches``: [[(ctx, candidates, allow_borrowing, threshold)]] —
    a launch holds the specs that share a scan length: it is padded to
    the S rung of its own count and the K rung of its own longest
    candidate list, not the cycle's.  Every search is against the same
    nominate-time snapshot, so they are independent, in a launch and
    across launches.  No spec is empty (the preemptor answers ``[]``
    itself) and none has more candidates than ``K_LADDER``'s top rung
    (it launches such a search alone, ``search_alone_over_k``).
    Returns a list a launch of per-spec Target lists ([] = search
    failed), or None when any launch is refused: a spec can't be
    packed, or a launch has more specs than ``S_LADDER``'s top rung
    (the caller runs a launch a head; ``stats`` counts the refusal
    under its one reason; what was dispatched is dropped unread).
    ``stats["accel_searches"]`` counts the searches whose output landed
    on an accelerator, ``search_batch_launches`` the launches, and
    ``search_candidate_slots`` / ``search_padded_slots`` the real
    candidates against the S x K slots of the buckets launched."""
    from .preemption_kernel import minimal_preemptions_batch
    flying = []
    for specs in launches:
        with _span("cycle.nominate.search_pack"):
            args = _pack_batch(specs, packed, stats)
        if args is None:
            return None
        with _span("cycle.nominate.search_launch"):
            fitted, mask = minimal_preemptions_batch(*args,
                                                     depth=packed.depth)
            if stats is not None:
                stats["search_batch_launches"] += 1
                if on_accelerator(output_devices(fitted)):
                    stats["accel_searches"] += len(specs)
            flying.append((fitted, mask))
    out = []
    for specs, (fitted, mask) in zip(launches, flying):
        with _span("cycle.nominate.search_launch"):
            fitted = np.asarray(fitted)
            mask = np.asarray(mask)
        with _span("cycle.nominate.search_decode"):
            out.append(_decode_batch(specs, fitted, mask))
    return out


def _pack_batch(specs, packed, stats: Optional[dict]):
    """The numpy planes of one batched launch (the kernel's positional
    arguments, their real and padded candidate slots counted), or None
    with the refusal counted.  It packs what it is given: S and K are
    the rungs of this launch's own spec count and longest candidate
    list, so the planes are as large as the plan's grouping made
    them."""
    if packed is None or not packed.exact or not specs:
        return _refused(stats, "unpackable")
    planes = _planes_for(packed)
    if planes is None:
        return _refused(stats, "unpackable")
    cq_idx = {n: i for i, n in enumerate(packed.cq_names)}
    F = packed.usage0.shape[1]
    scale_of = {r: int(packed.resource_scale[i])
                for i, r in enumerate(packed.resource_names)}

    def to_f_vec(frq) -> Optional[np.ndarray]:
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

    # coarse shape ladders: each distinct (S, K) combination is one XLA
    # compilation — a handful of rungs covers every cycle, and warmup
    # pre-compiles them (CycleSolver.warmup).  Beyond S's top rung the
    # caller runs a launch a head (None); a spec beyond K's never comes
    # here (Preemptor._search_batch searches it alone).
    max_cands = max(1, max(len(c) for _, c, _, _ in specs))
    if len(specs) > S_LADDER[-1]:
        return _refused(stats, "over_s")
    S = coarse_bucket(len(specs), S_LADDER)
    K = coarse_bucket(max_cands, K_LADDER)
    NL = planes.NL
    usage_planes = planes.usage_planes(packed.usage0)     # [G, NL, F]
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
    # target-usage vectors dedupe across specs (the same admitted
    # workload is a candidate for many preemptors)
    vec_cache: dict[str, Optional[np.ndarray]] = {}

    for si, (ctx, candidates, allow_borrowing, threshold) in enumerate(specs):
        ci = cq_idx.get(ctx.preemptor_cq.name)
        if ci is None or ci not in planes.local:
            return _refused(stats, "unpackable")
        f, ci_local = planes.local[ci]
        wu = to_f_vec(ctx.workload_usage)
        if wu is None:
            return _refused(stats, "unpackable")
        forest_of[si] = f
        pre_cq[si] = ci_local
        wl_usage[si] = wu
        for fr in ctx.frs_need_preemption:
            fi = packed.fr_index.get(fr)
            if fi is None:
                return _refused(stats, "unpackable")
            frs_mask[si, fi] = True
        allow_b0[si] = allow_borrowing
        thr_en[si] = threshold is not None
        for k, cand in enumerate(candidates):
            cci = cq_idx.get(cand.cluster_queue)
            if cci is None:
                return _refused(stats, "unpackable")
            cf_local = planes.local.get(cci)
            if cf_local is None or cf_local[0] != f:
                # candidate outside the preemptor's forest
                return _refused(stats, "unpackable")
            delta = vec_cache.get(cand.key)
            if delta is None and cand.key not in vec_cache:
                delta = to_f_vec(cand.usage())
                vec_cache[cand.key] = delta
            if delta is None:
                return _refused(stats, "unpackable")
            cand_cq[si, k] = cf_local[1]
            cand_delta[si, k] = delta
            cand_other[si, k] = cand.cluster_queue != ctx.preemptor_cq.name
            cand_above[si, k] = (threshold is not None
                                 and cand.obj.priority >= threshold)

    if stats is not None:
        stats["search_candidate_slots"] += sum(
            len(c) for _, c, _, _ in specs)
        stats["search_padded_slots"] += S * K
    return (usage_planes[forest_of], planes.subtree[forest_of],
            planes.guaranteed[forest_of], planes.borrow_cap[forest_of],
            planes.has_blim[forest_of], planes.parent[forest_of],
            pre_cq, wl_usage, frs_mask, cand_cq, cand_delta, cand_other,
            cand_above, allow_b0, thr_en)


def _decode_batch(specs, fitted, mask):
    """The launch's masks as per-spec Target lists ([] = no fit)."""
    from ..scheduler.preemption import Target  # circular-safe import
    out = []
    for si, (ctx, candidates, _, threshold) in enumerate(specs):
        if not fitted[si]:
            out.append([])
            continue
        targets = []
        for k, cand in enumerate(candidates):
            if not mask[si, k]:
                continue
            if cand.cluster_queue == ctx.preemptor_cq.name:
                reason = IN_CLUSTER_QUEUE_REASON
            elif threshold is not None and cand.obj.priority < threshold:
                reason = IN_COHORT_RECLAIM_WHILE_BORROWING_REASON
            else:
                reason = IN_COHORT_RECLAMATION_REASON
            targets.append(Target(info=cand, reason=reason))
        out.append(targets)
    return out


def device_minimal_preemptions(ctx, candidates, allow_borrowing: bool,
                               threshold: Optional[int], packed=None,
                               stats: Optional[dict] = None):
    """Device twin of Preemptor._minimal_preemptions.

    ``packed`` (a PackedCycle for the SAME snapshot at nominate time, e.g.
    the admission solver's cached-structure pack) avoids re-packing per
    search.  Returns a list of Targets, [] (search failed), or None
    (unsupported — run the host path)."""
    from ..scheduler.preemption import Target  # circular-safe import

    if not candidates:
        return []
    if packed is None:
        packed = pack_cycle(ctx.snapshot, [])
    if packed is None or not packed.exact:
        return None
    cq_idx = {n: i for i, n in enumerate(packed.cq_names)}
    pre_cq = cq_idx.get(ctx.preemptor_cq.name)
    if pre_cq is None:
        return None
    F = packed.usage0.shape[1]
    scale_of = {r: int(packed.resource_scale[i])
                for i, r in enumerate(packed.resource_names)}

    def to_f_vec(frq) -> Optional[np.ndarray]:
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

    wl_usage = to_f_vec(ctx.workload_usage)
    if wl_usage is None:
        return None
    frs_mask = np.zeros(F, dtype=bool)
    for fr in ctx.frs_need_preemption:
        fi = packed.fr_index.get(fr)
        if fi is None:
            return None
        frs_mask[fi] = True

    K = _bucket(len(candidates))
    cand_cq = np.full(K, -1, dtype=np.int32)
    cand_delta = np.zeros((K, F), dtype=np.int32)
    cand_other = np.zeros(K, dtype=bool)
    cand_above = np.zeros(K, dtype=bool)
    for i, cand in enumerate(candidates):
        ci = cq_idx.get(cand.cluster_queue)
        if ci is None:
            return None
        delta = to_f_vec(cand.usage())
        if delta is None:
            return None
        cand_cq[i] = ci
        cand_delta[i] = delta
        cand_other[i] = cand.cluster_queue != ctx.preemptor_cq.name
        cand_above[i] = (threshold is not None
                         and cand.obj.priority >= threshold)

    fitted, target_mask = minimal_preemptions(
        packed.usage0, packed.subtree_quota, packed.guaranteed,
        packed.borrow_cap, packed.has_borrow_limit, packed.parent,
        pre_cq, wl_usage, frs_mask, cand_cq, cand_delta, cand_other,
        cand_above, allow_borrowing, threshold is not None,
        depth=packed.depth)
    if stats is not None:
        stats["search_single_launches"] += 1
        if on_accelerator(output_devices(fitted)):
            stats["accel_searches"] += 1
    if not bool(fitted):
        return []
    mask = np.asarray(target_mask)
    targets = []
    for i, cand in enumerate(candidates):
        if not mask[i]:
            continue
        if not cand_other[i]:
            reason = IN_CLUSTER_QUEUE_REASON
        elif threshold is not None and cand.obj.priority < threshold:
            reason = IN_COHORT_RECLAIM_WHILE_BORROWING_REASON
        else:
            reason = IN_COHORT_RECLAMATION_REASON
        targets.append(Target(info=cand, reason=reason))
    return targets
