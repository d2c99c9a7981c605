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


class _Layout:
    """The F axis and the scales of one pack's structure: what turns
    unscaled usage, a column a flavor-resource, into the kernel's
    [.., F] int32 planes."""

    def __init__(self, packed):
        self.fr_index = packed.fr_index
        self.F = packed.usage0.shape[1]
        self.scale_of = {r: int(packed.resource_scale[i])
                         for i, r in enumerate(packed.resource_names)}

    def scaled(self, frs, raw: np.ndarray, has: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
        """Rows of usage over the columns ``frs`` (``raw`` [m, len(frs)]
        int64, ``has`` the keys each row holds) on the F axis: ([m, F]
        int32, [m] bool).  A row is False, and its vector not to be
        used, where the planes cannot hold it: a key the F axis lacks,
        a quantity its resource's scale does not divide, or one past
        int32."""
        at = [self.fr_index.get(fr) for fr in frs]
        known = np.array([fi is not None for fi in at], dtype=bool)
        ok = ~has[:, ~known].any(axis=1)
        scale = np.array([self.scale_of[fr.resource] if fi is not None else 1
                          for fr, fi in zip(frs, at)], dtype=np.int64)
        quot, rem = np.divmod(raw, scale)
        ok &= ~(rem != 0)[:, known].any(axis=1)
        ok &= ~(quot > 2**31 - 1)[:, known].any(axis=1)
        vec = np.zeros((len(raw), self.F), dtype=np.int32)
        # fr_index is one to one, so no two columns land on one plane
        vec[:, [fi for fi in at if fi is not None]] = np.where(
            ok[:, None], quot[:, known], 0)
        return vec, ok

    def scaled_usages(self, usages) -> tuple[np.ndarray, np.ndarray]:
        """``scaled`` for usages given as dicts, a row each."""
        col_of: dict = {}
        for usage in usages:
            for fr in usage:
                col_of.setdefault(fr, len(col_of))
        raw = np.zeros((len(usages), len(col_of)), dtype=np.int64)
        has = np.zeros(raw.shape, dtype=bool)
        for i, usage in enumerate(usages):
            for fr, v in usage.items():
                raw[i, col_of[fr]] = v
                has[i, col_of[fr]] = True
        return self.scaled(tuple(col_of), raw, has)


def layout_for(packed) -> _Layout:
    st = packed.structure
    layout = getattr(st, "_candidate_layout", None)
    if layout is None:
        layout = st._candidate_layout = _Layout(packed)
    return layout


def _candidate_planes(searches, K: int, S: int, layout: _Layout, locate):
    """The candidate planes of ``searches`` = [(candidates, threshold)],
    a search a row: (cand_cq [S, K], cand_delta [S, K, F], cand_other
    [S, K], cand_above [S, K]), gathered from the candidates' columns;
    None where the planes cannot hold a candidate (its usage, or its
    queue: ``locate(si, queue name)`` is the queue's index for search
    ``si``, or None)."""
    counts = np.array([len(c) for c, _ in searches], dtype=np.intp)
    total = int(counts.sum())
    # where search si's candidate k lands in the planes taken flat
    flat = (np.arange(total) + np.repeat(
        np.arange(len(searches)) * K - (np.cumsum(counts) - counts), counts))
    cand_cq = np.full(S * K, -1, dtype=np.int32)
    cand_delta = np.zeros((S * K, layout.F), dtype=np.int32)
    cand_other = np.zeros(S * K, dtype=bool)
    cand_above = np.zeros(S * K, dtype=bool)

    queue_at, others, priorities = [], [], []
    by_frs: dict[tuple, list[int]] = {}
    for si, (cands, _) in enumerate(searches):
        at = [locate(si, name) for name in cands.queues]
        queue_at.append(np.array([-1 if q is None else q for q in at],
                                 dtype=np.int32)[cands.queue])
        others.append(cands.own)
        priorities.append(cands.priority)
        by_frs.setdefault(cands.frs, []).append(si)
    cand_cq[flat] = np.concatenate(queue_at)
    if (cand_cq[flat] < 0).any():
        return None
    cand_other[flat] = ~np.concatenate(others)
    # a search without a threshold has none of its candidates above it
    cand_above[flat] = np.concatenate(priorities) >= np.repeat(
        np.array([np.iinfo(np.int64).max if thr is None else thr
                  for _, thr in searches], dtype=np.int64), counts)
    # one scaling a set of columns: the searches of a launch are of
    # queues over the same flavors, so as a rule the launch has one
    ends = np.cumsum(counts)
    for frs, members in by_frs.items():
        vec, ok = layout.scaled(
            frs, np.concatenate([searches[si][0].raw for si in members]),
            np.concatenate([searches[si][0].has for si in members]))
        if not ok.all():
            return None
        cand_delta[flat if len(members) == len(searches) else np.concatenate(
            [flat[ends[si] - counts[si]:ends[si]] for si in members])] = vec
    return (cand_cq.reshape(S, K), cand_delta.reshape(S, K, layout.F),
            cand_other.reshape(S, K), cand_above.reshape(S, K))


def _pack_batch(specs, packed, stats: Optional[dict]):
    """The numpy planes of one batched launch (the kernel's positional
    arguments, their real and padded candidate slots counted), or None
    with the refusal counted.  It packs what it is given: S and K are
    the rungs of this launch's own spec count and longest candidate
    list, so the planes are as large as the plan's grouping made
    them.  The candidates' planes are gathers from the columns the
    specs' ``Candidates`` hold, the whole launch at once."""
    if packed is None or not packed.exact or not specs:
        return _refused(stats, "unpackable")
    planes = _planes_for(packed)
    if planes is None:
        return _refused(stats, "unpackable")
    cq_idx = packed.structure.cq_index
    layout = layout_for(packed)
    F = layout.F

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
    n = len(specs)
    usage_planes = planes.usage_planes(packed.usage0)     # [G, NL, F]
    forest_of = np.zeros(S, dtype=np.int32)
    pre_cq = np.full(S, -1, dtype=np.int32)
    wl_usage = np.zeros((S, F), dtype=np.int32)
    frs_mask = np.zeros((S, F), dtype=bool)
    allow_b0 = np.zeros(S, dtype=bool)
    thr_en = np.zeros(S, dtype=bool)

    for si, (ctx, _, allow_borrowing, threshold) in enumerate(specs):
        ci = cq_idx.get(ctx.preemptor_cq.name)
        if ci is None or ci not in planes.local:
            return _refused(stats, "unpackable")
        forest_of[si], pre_cq[si] = planes.local[ci]
        for fr in ctx.frs_need_preemption:
            fi = packed.fr_index.get(fr)
            if fi is None:
                return _refused(stats, "unpackable")
            frs_mask[si, fi] = True
        allow_b0[si] = allow_borrowing
        thr_en[si] = threshold is not None
    wl_usage[:n], ok = layout.scaled_usages(
        [ctx.workload_usage for ctx, _, _, _ in specs])
    if not ok.all():
        return _refused(stats, "unpackable")

    def locate(si: int, name: str) -> Optional[int]:
        """The queue's place in the head's forest; outside it, None."""
        f, local = planes.local.get(cq_idx.get(name), (-1, None))
        return local if f == forest_of[si] else None

    cand = _candidate_planes([(c, thr) for _, c, _, thr in specs],
                             K, S, layout, locate)
    if cand is None:
        return _refused(stats, "unpackable")
    cand_cq, cand_delta, cand_other, cand_above = cand

    if stats is not None:
        stats["search_candidate_slots"] += sum(
            len(c) for _, c, _, _ in specs)
        stats["search_padded_slots"] += S * K
    return (usage_planes[forest_of], planes.subtree[forest_of],
            planes.guaranteed[forest_of], planes.borrow_cap[forest_of],
            planes.has_blim[forest_of], planes.parent[forest_of],
            pre_cq, wl_usage, frs_mask, cand_cq, cand_delta, cand_other,
            cand_above, allow_b0, thr_en)


def _targets(candidates, mask: np.ndarray, threshold: Optional[int]):
    """The Targets a search's mask picks of its candidates."""
    from ..scheduler.preemption import Target  # circular-safe import
    targets = []
    for k in np.flatnonzero(mask[:len(candidates)]).tolist():
        if candidates.own[k]:
            reason = IN_CLUSTER_QUEUE_REASON
        elif threshold is not None and candidates.priority[k] < threshold:
            reason = IN_COHORT_RECLAIM_WHILE_BORROWING_REASON
        else:
            reason = IN_COHORT_RECLAMATION_REASON
        targets.append(Target(info=candidates[k], reason=reason))
    return targets


def _decode_batch(specs, fitted, mask):
    """The launch's masks as per-spec Target lists ([] = no fit)."""
    return [_targets(candidates, mask[si], threshold) if fitted[si] else []
            for si, (_, candidates, _, threshold) in enumerate(specs)]


def device_minimal_preemptions(ctx, candidates, allow_borrowing: bool,
                               threshold: Optional[int], packed=None,
                               stats: Optional[dict] = None):
    """Device twin of Preemptor._minimal_preemptions.

    ``packed`` (a PackedCycle for the SAME snapshot at nominate time, e.g.
    the admission solver's cached-structure pack) avoids re-packing per
    search.  Returns a list of Targets, [] (search failed), or None
    (unsupported — run the host path)."""
    if not len(candidates):
        return []
    if packed is None:
        packed = pack_cycle(ctx.snapshot, [])
    if packed is None or not packed.exact:
        return None
    cq_idx = packed.structure.cq_index
    pre_cq = cq_idx.get(ctx.preemptor_cq.name)
    if pre_cq is None:
        return None
    layout = layout_for(packed)
    (wl_usage,), ok = layout.scaled_usages([ctx.workload_usage])
    if not ok.all():
        return None
    frs_mask = np.zeros(layout.F, dtype=bool)
    for fr in ctx.frs_need_preemption:
        fi = packed.fr_index.get(fr)
        if fi is None:
            return None
        frs_mask[fi] = True

    cand = _candidate_planes([(candidates, threshold)],
                             _bucket(len(candidates)), 1, layout,
                             lambda si, name: cq_idx.get(name))
    if cand is None:
        return None
    cand_cq, cand_delta, cand_other, cand_above = (p[0] for p in cand)

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
    return _targets(candidates, np.asarray(target_mask), threshold)
