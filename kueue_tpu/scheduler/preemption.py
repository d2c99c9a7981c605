"""Preemption target selection and eviction issuance.

Capability parity with reference pkg/scheduler/preemption/preemption.go:
candidate discovery honoring withinClusterQueue / reclaimWithinCohort /
borrowWithinCohort policies (findCandidates :480), candidate ordering
(:591), greedy minimal-preemption simulation with fill-back (:275-342),
fair-sharing preemption with S2-a/S2-b strategies (:372-442), and the
reclaim oracle used by the flavor assigner (preemption_oracle.go:40).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, Optional

import numpy as np

from ..api.types import (
    BorrowWithinCohortPolicy,
    ConditionStatus,
    ReclaimWithinCohort,
    WithinClusterQueue,
    IN_CLUSTER_QUEUE_REASON,
    IN_COHORT_FAIR_SHARING_REASON,
    IN_COHORT_RECLAIM_WHILE_BORROWING_REASON,
    IN_COHORT_RECLAMATION_REASON,
    WL_EVICTED,
    WL_QUOTA_RESERVED,
)
from ..cache.candidates import TableTally
from ..cache.snapshot import Snapshot
from ..cache.state import CQState
from ..obs.trace import span as _span
from ..resources import FlavorResource, FlavorResourceQuantities
from ..workload import Info, Ordering
from . import fairsharing
from .flavorassigner import Assignment, Mode


@dataclass
class Target:
    info: Info
    reason: str


@dataclass
class _PreemptionCtx:
    preemptor: Info
    preemptor_cq: CQState
    snapshot: Snapshot
    frs_need_preemption: set[FlavorResource]
    workload_usage: FlavorResourceQuantities
    tas_requests: object = None


HUMAN_READABLE_REASONS = {
    IN_CLUSTER_QUEUE_REASON: "prioritization in the ClusterQueue",
    IN_COHORT_RECLAMATION_REASON: "reclamation within the cohort",
    IN_COHORT_FAIR_SHARING_REASON: "Fair Sharing within the cohort",
    IN_COHORT_RECLAIM_WHILE_BORROWING_REASON:
        "reclamation within the cohort while borrowing",
}


class Candidates:
    """A head's preemption candidates in candidatesOrdering's order, as
    columns gathered from the candidate tables of its own queue and of
    its cohort's borrowing queues (cache/candidates.py).  A sequence of
    ``Info``: ``len`` is what the launch plan reads, iteration what the
    host search and fair sharing walk; the device search takes its
    planes from the columns."""

    __slots__ = ("infos", "priority", "own", "queue", "queues", "frs",
                 "raw", "has")

    def __init__(self, infos, priority, own, queue, queues, frs, raw, has):
        self.infos = infos          # [k] object, the rows' Infos
        self.priority = priority    # [k] int64
        self.own = own              # [k] bool: of the head's own queue
        self.queue = queue          # [k] index into ``queues``
        self.queues = queues        # names of the queues the rows are of
        self.frs = frs              # the columns of raw and has
        self.raw = raw              # [k, len(frs)] int64 usage, unscaled
        self.has = has              # [k, len(frs)] bool: usage() has the key

    def __len__(self) -> int:
        return self.infos.shape[0]

    def __iter__(self) -> Iterator[Info]:
        return iter(self.infos.tolist())

    def __getitem__(self, k: int) -> Info:
        return self.infos[k]

    def take(self, keep: np.ndarray) -> "Candidates":
        """The candidates ``keep`` ([k] bool) picks, in their order."""
        return Candidates(self.infos[keep], self.priority[keep],
                          self.own[keep], self.queue[keep], self.queues,
                          self.frs, self.raw[keep], self.has[keep])


NO_CANDIDATES = Candidates(
    np.zeros(0, dtype=object), np.zeros(0, dtype=np.int64),
    np.zeros(0, dtype=bool), np.zeros(0, dtype=np.intp), [], (),
    np.zeros((0, 0), dtype=np.int64), np.zeros((0, 0), dtype=bool))


class _Part:
    """The rows one queue's candidate table gives a search, in
    candidatesOrdering's order among themselves, with the two keys of
    that order that are read off the ``Info``s."""

    __slots__ = ("queue", "frs", "infos", "uid", "priority", "seq", "raw",
                 "has", "not_evicted", "reserved")

    def __init__(self, cq: CQState, rows: np.ndarray, now: float):
        """``rows`` of ``cq``'s table.  The Evicted condition and the
        quota reservation time are read here, off the chosen rows'
        ``Info``s: both change under a workload while it stays in its
        queue, so no column could hold them."""
        table = cq.candidates
        infos = table.infos[rows]
        conditions = [info.obj.conditions for info in infos.tolist()]
        reserved = np.array([
            now if (c := conds.get(WL_QUOTA_RESERVED)) is None
            or c.status != ConditionStatus.TRUE else c.last_transition_time
            for conds in conditions], dtype=np.float64)
        not_evicted = np.array([
            (c := conds.get(WL_EVICTED)) is None
            or c.status != ConditionStatus.TRUE for conds in conditions],
            dtype=bool)
        order = np.lexsort((table.seq[rows], table.uid[rows], -reserved,
                            table.priority[rows], not_evicted))
        rows = rows[order]
        self.queue = cq.name
        self.frs = table.frs
        self.infos = infos[order]
        self.not_evicted = not_evicted[order]
        self.reserved = reserved[order]
        for name in ("uid", "priority", "seq", "raw", "has"):
            setattr(self, name, getattr(table, name)[rows])

    def over(self, frs: tuple, name: str) -> np.ndarray:
        """``raw`` or ``has`` over the columns ``frs``, a superset."""
        plane = getattr(self, name)
        if self.frs == frs:
            return plane
        wide = np.zeros((len(plane), len(frs)), dtype=plane.dtype)
        wide[:, [frs.index(fr) for fr in self.frs]] = plane
        return wide


def _ordered(parts: list[_Part], own: Optional[_Part]) -> Candidates:
    """The candidates of ``parts``, a part a queue and ``own`` the one
    of the head's own, in the order of reference preemption.go:591
    candidatesOrdering: evicted first, then other queues' before the
    head's own, then lower priority, then later quota reservation, then
    uid; what is left equal stays in the order the queues and their
    dicts gave it.  A part is in that order already, so one part alone
    is the answer as it stands."""
    queues = [part.queue for part in parts]
    if len(parts) == 1:
        (part,) = parts
        k = len(part.infos)
        return Candidates(part.infos, part.priority,
                          np.full(k, part is own, dtype=bool),
                          np.zeros(k, dtype=np.intp), queues, part.frs,
                          part.raw, part.has)
    frs = parts[0].frs
    if any(part.frs != frs for part in parts):
        frs = tuple(sorted({fr for part in parts for fr in part.frs}))
    infos, uid, priority, seq, not_evicted, reserved = (
        np.concatenate([getattr(part, name) for part in parts])
        for name in ("infos", "uid", "priority", "seq", "not_evicted",
                     "reserved"))
    queue = np.repeat(np.arange(len(parts)),
                      [len(part.infos) for part in parts])
    is_own = queue == next(
        (qi for qi, part in enumerate(parts) if part is own), -1)
    order = np.lexsort((seq, queue, uid, -reserved, priority, is_own,
                        not_evicted))
    return Candidates(
        infos[order], priority[order], is_own[order], queue[order], queues,
        frs, np.concatenate([p.over(frs, "raw") for p in parts])[order],
        np.concatenate([p.over(frs, "has") for p in parts])[order])


def flavor_resources_need_preemption(assignment: Assignment) -> set[FlavorResource]:
    """reference preemption.go:466."""
    out = set()
    for ps in assignment.pod_sets:
        for res, fa in ps.flavors.items():
            if fa.mode == Mode.PREEMPT:
                out.add(FlavorResource(fa.name, res))
    return out


def _cq_is_borrowing(cq: CQState, frs: set[FlavorResource]) -> bool:
    if not cq.has_parent():
        return False
    return any(cq.borrowing(fr) for fr in frs)


class Preemptor:
    """reference preemption.go Preemptor."""

    def __init__(self, enable_fair_sharing: bool = False,
                 fs_strategies: list[str] | None = None,
                 ordering: Ordering | None = None,
                 clock: Callable[[], float] = time.time):
        self.enable_fair_sharing = enable_fair_sharing
        self.fs_strategies = fairsharing.parse_strategies(fs_strategies)
        self.ordering = ordering or Ordering()
        self.clock = clock
        # Pluggable apply hook (reference OverrideApply, preemption.go:96):
        # called with (target Info, reason, message) when issuing evictions.
        self.apply_preemption: Optional[Callable[[Info, str, str], None]] = None
        # Run the minimal-preemptions search on device.  "auto" (default):
        # device whenever the scheduler threaded the cycle's cached pack
        # for the current snapshot (O(candidates) per search, no re-pack);
        # True: always (re-packs the snapshot when no pack is cached);
        # False: host greedy+fillback only.  All three are
        # decision-identical (tests/test_preemption_kernel.py).
        self.device_search: object = "auto"
        self._cycle_pack = None   # (weakref to snapshot, PackedCycle)
        # accel_searches: the device searches whose output landed on an
        # accelerator (a subset of device_searches; all of them on a
        # chip host, none under JAX_PLATFORMS=cpu)
        self.stats = {"device_searches": 0, "host_searches": 0,
                      "accel_searches": 0,
                      # the two device routes apart: one batched launch
                      # for a cycle's heads, or one launch a search
                      "search_batch_launches": 0,
                      "search_single_launches": 0,
                      # a launch the batched route turned away, and why
                      # (the sum of the two reasons): more specs than
                      # the S ladder's top rung, or a spec the planes
                      # cannot hold.  Size never refuses a cycle: its
                      # specs go out in launches of at most that rung,
                      # and a spec with more candidates than the K
                      # ladder's top rung is searched alone, counted in
                      # search_alone_over_k, and the rest stay batched
                      "search_batch_refusals": 0,
                      "search_refused_over_s": 0,
                      "search_refused_unpackable": 0,
                      "search_alone_over_k": 0,
                      # what the launch plan saw in the specs' sizes:
                      # specs with no candidate, answered [] unpacked,
                      # and calls whose launches held several K rungs
                      "search_empty_specs": 0,
                      "search_split_plans": 0,
                      # the reclaim oracle on the batched route: the
                      # searches it asked for, and those answered Reclaim
                      "oracle_specs": 0,
                      "oracle_reclaims": 0,
                      # real candidates in the batched launches, and the
                      # S x K slots of the buckets they were padded to
                      "search_candidate_slots": 0,
                      "search_padded_slots": 0,
                      # the candidate tables (cache/candidates.py): rows
                      # the candidate queries read, and rows written
                      # into the tables since (appends; a queue's rows
                      # are built once, as its workloads are added)
                      "search_table_rows_scanned": 0,
                      "search_table_rows_built": 0}
        # the tally of the tables queried, their cache's, and the rows
        # it had counted when search_table_rows_built last took them
        self._tally: Optional[TableTally] = None
        self._tally_seen = 0
        # while a batch's candidates are found: (queue, flavor-resources,
        # priority bound) -> the part it gave (_queue_part)
        self._parts_memo: Optional[dict] = None

    def set_cycle_pack(self, snapshot: Snapshot, packed) -> None:
        """Thread the admission solver's cached pack for this cycle's
        snapshot so nominate-time searches skip the O(cluster) re-pack.
        Only valid for searches against the same (unmutated) snapshot —
        nominate runs before any admit-loop usage mutation."""
        import weakref
        self._cycle_pack = (weakref.ref(snapshot), packed)

    def _pack_for(self, snapshot: Snapshot):
        if self._cycle_pack is not None and self._cycle_pack[0]() is snapshot:
            return self._cycle_pack[1]
        return None

    # ------------------------------------------------------------------
    # Target selection — reference preemption.go:127-191
    # ------------------------------------------------------------------

    def get_targets(self, wl: Info, assignment: Assignment,
                    snapshot: Snapshot) -> list[Target]:
        cq = snapshot.cq(wl.cluster_queue)
        ctx = _PreemptionCtx(
            preemptor=wl,
            preemptor_cq=cq,
            snapshot=snapshot,
            frs_need_preemption=flavor_resources_need_preemption(assignment),
            workload_usage=assignment.total_requests_for(wl),
        )
        return self._get_targets(ctx)

    def _get_targets(self, ctx: _PreemptionCtx) -> list[Target]:
        candidates = self._find_candidates(ctx)
        if not candidates:
            return []
        if self.enable_fair_sharing:
            return self._fair_preemptions(ctx, candidates)

        specs, staged = self.plan_searches(ctx, candidates)
        cands, ab, thr = specs[0]
        first = self._minimal_preemptions(ctx, cands, ab, thr)
        if not staged or first:
            return first
        cands, ab, thr = specs[1]  # queue-under-nominal retry
        return self._minimal_preemptions(ctx, cands, ab, thr)

    def plan_searches(self, ctx: _PreemptionCtx, candidates: Candidates
                      ) -> tuple[list[tuple[Candidates, bool, Optional[int]]],
                                 bool]:
        """The minimalPreemptions calls _get_targets will issue, computed
        UPFRONT (every branch condition is snapshot-state only) so a
        cycle's searches can run as one batched dispatch.

        Returns (specs, staged): specs = [(candidates, allow_borrowing,
        threshold)]; staged=True → use spec 0's result if it fitted,
        else spec 1's (the queue-under-nominal retry,
        preemption.go:144-191)."""
        if candidates.own.all():
            # no cross-queue candidates: try borrowing
            return [(candidates, True, None)], False
        same_queue = candidates.take(candidates.own)

        borrow_ok, threshold = self._can_borrow_within_cohort(ctx)
        if borrow_ok:
            if not self._queue_under_nominal(ctx):
                candidates = candidates.take(
                    candidates.own | (candidates.priority < threshold))
            return [(candidates, True, threshold)], False

        if self._queue_under_nominal(ctx):
            return [(candidates, False, None),
                    (same_queue, True, None)], True

        return [(same_queue, True, None)], False

    def get_targets_batch(self, requests: list[tuple[Info, Assignment]],
                          snapshot: Snapshot) -> list[list[Target]]:
        """Target searches for ALL of a cycle's preempt heads in the
        batched device dispatch (``_search_batch``)."""
        return self._search_batch([_PreemptionCtx(
            preemptor=wl,
            preemptor_cq=snapshot.cq(wl.cluster_queue),
            snapshot=snapshot,
            frs_need_preemption=flavor_resources_need_preemption(
                assignment),
            workload_usage=assignment.total_requests_for(wl))
            for wl, assignment in requests], snapshot)

    def reclaim_possible_batch(self, queries: list[tuple],
                               snapshot: Snapshot) -> list[bool]:
        """The reclaim oracle (preemption_oracle.go:40) for a cycle's
        questions at once: ``queries`` = [(head Info, FlavorResource,
        quantity)], each a target search of its own for that one
        flavor-resource, all through the batched device dispatch.  The
        quantity is the flavor walk's ``val``: of a gang's later PodSet,
        its request and what the head's earlier PodSets chose on that
        flavor-resource (a head may ask about one flavor-resource twice,
        at two quantities).
        Reclaim is possible when the search evicts nobody of the head's
        own queue."""
        with _span("cycle.nominate.oracle"):
            found = self._search_batch(
                [_oracle_ctx(snapshot.cq(wl.cluster_queue), wl, fr, qty,
                             snapshot) for wl, fr, qty in queries],
                snapshot)
            answers = [
                all(t.info.cluster_queue != wl.cluster_queue
                    for t in targets)
                for (wl, _, _), targets in zip(queries, found)]
        self.stats["oracle_specs"] += len(queries)
        self.stats["oracle_reclaims"] += sum(answers)
        return answers

    def _search_batch(self, ctxs: list[_PreemptionCtx],
                      snapshot: Snapshot) -> list[list[Target]]:
        """One target search a context, all in batched device dispatches
        (ops/preemption_kernel minimal_preemptions_batch) — candidate
        discovery and ordering stay host-side, the greedy+fillback
        searches vmap.  The launches follow the planned specs' sizes,
        which is all the plan looks at: a spec with no candidate is
        answered ``[]`` here and never packed (``search_empty_specs``);
        a search with more candidates than the K ladder holds is
        launched alone, over the candidates found and sorted here; the
        others are grouped by the K rung of their own candidate count,
        neighbouring rungs merged where that scans less
        (``preemption_solver.plan_launches``), and a group is one
        launch, or several of at most ``S_LADDER``'s top rung.  So a
        launch scans as long as its own largest search, not the
        cycle's, and specs that share a rung still make the one launch
        (``search_split_plans`` counts the calls that made more).  All
        launches are dispatched before the first is fetched.  Falls
        back to a search a context for fair sharing, a missing cycle
        pack, or an unpackable spec in any launch (decision-identical
        either way)."""
        packed = self._pack_for(snapshot)

        def each_head():
            """One search (and one candidate discovery) a context."""
            with _span("cycle.nominate.search_fallback"):
                return [self._get_targets(ctx) for ctx in ctxs]

        if (self.enable_fair_sharing or packed is None
                or self.device_search is False or not ctxs):
            return each_head()

        flat_specs: list[tuple] = []
        plans: list[tuple[list[int], bool]] = []
        with _span("cycle.nominate.candidates"):
            self._parts_memo = {}
            try:
                for ctx in ctxs:
                    candidates = self._find_candidates(ctx)
                    if not candidates:
                        plans.append(([], False))
                        continue
                    specs, staged = self.plan_searches(ctx, candidates)
                    idxs = []
                    for cands, ab, thr in specs:
                        idxs.append(len(flat_specs))
                        flat_specs.append((ctx, cands, ab, thr))
                    plans.append((idxs, staged))
            finally:
                self._parts_memo = None

        # the launch plan follows each spec's size: one with no
        # candidate is answered here, one over the K ladder's top rung
        # gets a launch of its own, and the others go out grouped by
        # the K rung of their own candidate count (plan_launches)
        from ..ops import preemption_solver
        top = preemption_solver.K_LADDER[-1]
        results: list[Optional[list[Target]]] = [None] * len(flat_specs)
        batch = []
        for i, spec in enumerate(flat_specs):
            if not spec[1]:
                results[i] = []     # nobody to evict: the search fails
                self.stats["search_empty_specs"] += 1
            elif len(spec[1]) <= top:
                batch.append(i)
        plan = preemption_solver.plan_launches(
            [len(flat_specs[i][1]) for i in batch])
        if len({k_rung for k_rung, _ in plan}) > 1:
            self.stats["search_split_plans"] += 1
        launches = [[batch[j] for j in members] for _, members in plan]
        found = preemption_solver.device_minimal_preemptions_batch(
            [[flat_specs[i] for i in launch] for launch in launches],
            packed, stats=self.stats)
        if found is None:
            # refused (stats say why): one launch a context, each
            # finding and sorting its candidates again
            return each_head()
        self.stats["device_searches"] += len(batch)
        for launch, targets in zip(launches, found):
            for i, t in zip(launch, targets):
                results[i] = t
        if any(r is None for r in results):
            with _span("cycle.nominate.search_fallback"):
                for idxs, _ in plans:
                    for i in idxs:
                        if results[i] is not None:
                            continue
                        # a staged plan's retry is a subset of its first
                        # spec, so the first was searched just above:
                        # the retry runs only if that found no fit
                        if i != idxs[0] and results[idxs[0]]:
                            continue
                        self.stats["search_alone_over_k"] += 1
                        results[i] = self._minimal_preemptions(
                            *flat_specs[i])

        out: list[list[Target]] = []
        for idxs, staged in plans:
            if not idxs:
                out.append([])
            elif staged and results[idxs[0]]:
                out.append(results[idxs[0]])
            else:
                out.append(results[idxs[-1]])
        return out

    def _can_borrow_within_cohort(self, ctx: _PreemptionCtx
                                  ) -> tuple[bool, Optional[int]]:
        """reference preemption.go:194 canBorrowWithinCohort."""
        bwc = ctx.preemptor_cq.preemption.borrow_within_cohort
        if bwc.policy == BorrowWithinCohortPolicy.NEVER:
            return False, None
        threshold = ctx.preemptor.obj.priority
        if (bwc.max_priority_threshold is not None
                and bwc.max_priority_threshold < threshold):
            threshold = bwc.max_priority_threshold + 1
        return True, threshold

    def _queue_under_nominal(self, ctx: _PreemptionCtx) -> bool:
        """reference preemption.go queueUnderNominalInResourcesNeedingPreemption."""
        cq = ctx.preemptor_cq
        for fr in ctx.frs_need_preemption:
            quota = cq.resource_node.quotas.get(fr)
            nominal = quota.nominal if quota else 0
            if cq.resource_node.usage.get(fr, 0) >= nominal:
                return False
        return True

    # ------------------------------------------------------------------
    # Candidates — reference preemption.go:480 findCandidates
    # ------------------------------------------------------------------

    def _find_candidates(self, ctx: _PreemptionCtx) -> Candidates:
        """The head's candidates, in order: a mask over its own queue's
        candidate table and over those of its cohort's borrowing
        queues, a part each (``_queue_part``), then one sort
        (``_ordered``)."""
        cq = ctx.preemptor_cq
        wl = ctx.preemptor
        frs = ctx.frs_need_preemption
        wl_priority = wl.obj.priority
        parts: list[_Part] = []
        own = None

        within = cq.preemption.within_cluster_queue
        if within != WithinClusterQueue.NEVER:
            own = self._queue_part(
                cq, frs, wl_priority, equal_if_older=wl.obj if within
                == WithinClusterQueue.LOWER_OR_NEWER_EQUAL_PRIORITY else None)
            if own is not None:
                parts.append(own)

        if cq.has_parent() and cq.preemption.reclaim_within_cohort != ReclaimWithinCohort.NEVER:
            below = (None if cq.preemption.reclaim_within_cohort
                     == ReclaimWithinCohort.ANY else wl_priority)
            for cohort_cq in cq.parent.root().subtree_cqs():
                if cohort_cq is cq or not _cq_is_borrowing(cohort_cq, frs):
                    continue
                part = self._queue_part(cohort_cq, frs, below)
                if part is not None:
                    parts.append(part)

        self._count_rows_built(cq.candidates.tally)
        if not parts:
            return NO_CANDIDATES
        return _ordered(parts, own)

    def _queue_part(self, cq: CQState, frs: set[FlavorResource],
                    below: Optional[int], equal_if_older=None
                    ) -> Optional[_Part]:
        """The rows of ``cq``'s table that use one of ``frs`` and, with
        ``below``, have a lower priority; None where there is none.
        With ``equal_if_older``, the head's Workload under
        LowerOrNewerEqualPriority, an equal priority falls to the head
        too if the head queued first: the queue-order timestamp is a
        condition's to change, so it is read off the ``Info``s.

        A batch of searches is against one snapshot at one time, so
        there the answer is kept for the next head that asks the same:
        the heads of a cohort ask it of each borrowing queue."""
        memo = self._parts_memo if equal_if_older is None else None
        if memo is not None:
            key = (cq.name, frozenset(frs), below)
            if key in memo:
                return memo[key]
        table = cq.candidates
        self.stats["search_table_rows_scanned"] += table.n
        part = None
        keep = table.using(frs)
        if keep is not None:
            if below is not None:
                priority = table.priority[:table.n]
                same = (np.flatnonzero(keep & (priority == below))
                        if equal_if_older is not None else ())
                keep = keep & (priority < below)
                if len(same):
                    ts = self.ordering.queue_order_timestamp
                    head_ts = ts(equal_if_older)
                    keep[same[[head_ts < ts(info.obj) for info
                               in table.infos[same].tolist()]]] = True
            rows = np.flatnonzero(keep)
            if rows.size:
                part = _Part(cq, rows, self.clock())
        if memo is not None:
            memo[key] = part
        return part

    def _count_rows_built(self, tally: TableTally) -> None:
        """Bring ``search_table_rows_built`` up to what the tables'
        tally, their cache's, has counted."""
        if tally is not self._tally:
            self._tally, self._tally_seen = tally, 0
        self.stats["search_table_rows_built"] += tally.built - self._tally_seen
        self._tally_seen = tally.built

    # ------------------------------------------------------------------
    # Minimal preemptions — reference preemption.go:275-342
    # ------------------------------------------------------------------

    def _workload_fits(self, ctx: _PreemptionCtx, allow_borrowing: bool) -> bool:
        """reference preemption.go:552 workloadFits."""
        for fr, v in ctx.workload_usage.items():
            if not allow_borrowing and ctx.preemptor_cq.borrowing_with(fr, v):
                return False
            if v > ctx.preemptor_cq.available(fr):
                return False
        return True

    def _workload_fits_for_fair_sharing(self, ctx: _PreemptionCtx) -> bool:
        revert = ctx.preemptor_cq.simulate_usage_removal(ctx.workload_usage)
        res = self._workload_fits(ctx, True)
        revert()
        return res

    def _minimal_preemptions(self, ctx: _PreemptionCtx, candidates: Candidates,
                             allow_borrowing: bool,
                             allow_borrowing_below_priority: Optional[int]
                             ) -> list[Target]:
        packed = self._pack_for(ctx.snapshot)
        if self.device_search is True or (
                self.device_search == "auto" and packed is not None):
            from ..ops.preemption_solver import device_minimal_preemptions
            result = device_minimal_preemptions(
                ctx, candidates, allow_borrowing,
                allow_borrowing_below_priority, packed=packed,
                stats=self.stats)
            if result is not None:
                self.stats["device_searches"] += 1
                return result
        self.stats["host_searches"] += 1
        targets: list[Target] = []
        fits = False
        for cand in candidates:
            cand_cq = ctx.snapshot.cq(cand.cluster_queue)
            reason = IN_CLUSTER_QUEUE_REASON
            if cand_cq is not ctx.preemptor_cq:
                if not _cq_is_borrowing(cand_cq, ctx.frs_need_preemption):
                    continue
                reason = IN_COHORT_RECLAMATION_REASON
                if allow_borrowing_below_priority is not None:
                    if cand.obj.priority >= allow_borrowing_below_priority:
                        # a target above the threshold disables borrowing;
                        # safe because candidates are priority-ordered and
                        # the last-added target survives fill-back
                        allow_borrowing = False
                    else:
                        reason = IN_COHORT_RECLAIM_WHILE_BORROWING_REASON
            ctx.snapshot.remove_workload(cand)
            targets.append(Target(info=cand, reason=reason))
            if self._workload_fits(ctx, allow_borrowing):
                fits = True
                break
        if not fits:
            self._restore(ctx.snapshot, targets)
            return []
        targets = self._fill_back(ctx, targets, allow_borrowing)
        self._restore(ctx.snapshot, targets)
        return targets

    def _fill_back(self, ctx: _PreemptionCtx, targets: list[Target],
                   allow_borrowing: bool) -> list[Target]:
        """reference preemption.go:329 fillBackWorkloads."""
        i = len(targets) - 2
        while i >= 0:
            ctx.snapshot.add_workload(targets[i].info)
            if self._workload_fits(ctx, allow_borrowing):
                targets[i] = targets[-1]
                targets.pop()
            else:
                ctx.snapshot.remove_workload(targets[i].info)
            i -= 1
        return targets

    @staticmethod
    def _restore(snapshot: Snapshot, targets: list[Target]) -> None:
        for t in targets:
            snapshot.add_workload(t.info)

    # ------------------------------------------------------------------
    # Fair-sharing preemptions — reference preemption.go:372-460
    # ------------------------------------------------------------------

    def _fair_preemptions(self, ctx: _PreemptionCtx,
                          candidates: list[Info]) -> list[Target]:
        revert = ctx.preemptor_cq.simulate_usage_addition(ctx.workload_usage)
        fits, targets, retry = self._run_first_fs_strategy(
            ctx, candidates, self.fs_strategies[0])
        if not fits and len(self.fs_strategies) > 1:
            fits, targets = self._run_second_fs_strategy(retry, ctx, targets)
        revert()
        if not fits:
            self._restore(ctx.snapshot, targets)
            return []
        targets = self._fill_back(ctx, targets, True)
        self._restore(ctx.snapshot, targets)
        return targets

    def _run_first_fs_strategy(self, ctx: _PreemptionCtx, candidates: list[Info],
                               strategy) -> tuple[bool, list[Target], list[Info]]:
        ordering = fairsharing.TargetClusterQueueOrdering(
            ctx.preemptor_cq, candidates, ctx.snapshot.cluster_queues)
        targets: list[Target] = []
        retry_candidates: list[Info] = []
        for tcq in ordering.iterate():
            if tcq.in_cluster_queue_preemption():
                cand = tcq.pop_workload()
                ctx.snapshot.remove_workload(cand)
                targets.append(Target(info=cand, reason=IN_CLUSTER_QUEUE_REASON))
                if self._workload_fits_for_fair_sharing(ctx):
                    return True, targets, []
                continue
            preemptor_new, target_old = tcq.compute_shares()
            while tcq.has_workload():
                cand = tcq.pop_workload()
                target_new = tcq.compute_target_share_after_removal(cand)
                if strategy(preemptor_new, target_old, target_new):
                    ctx.snapshot.remove_workload(cand)
                    targets.append(Target(info=cand,
                                          reason=IN_COHORT_FAIR_SHARING_REASON))
                    if self._workload_fits_for_fair_sharing(ctx):
                        return True, targets, []
                    break  # re-pick CQ: shares changed
                retry_candidates.append(cand)
        return False, targets, retry_candidates

    def _run_second_fs_strategy(self, retry_candidates: list[Info],
                                ctx: _PreemptionCtx, targets: list[Target]
                                ) -> tuple[bool, list[Target]]:
        ordering = fairsharing.TargetClusterQueueOrdering(
            ctx.preemptor_cq, retry_candidates, ctx.snapshot.cluster_queues)
        for tcq in ordering.iterate():
            preemptor_new, target_old = tcq.compute_shares()
            if fairsharing.less_than_initial_share(preemptor_new, target_old, 0):
                cand = tcq.pop_workload()
                ctx.snapshot.remove_workload(cand)
                targets.append(Target(info=cand,
                                      reason=IN_COHORT_FAIR_SHARING_REASON))
                if self._workload_fits_for_fair_sharing(ctx):
                    return True, targets
            ordering.drop_queue(tcq)
        return False, targets

    # ------------------------------------------------------------------
    # Issuance — reference preemption.go:232-257
    # ------------------------------------------------------------------

    def issue_preemptions(self, preemptor: Info, targets: list[Target]) -> int:
        from ..workload import set_evicted_condition, set_preempted_condition
        from ..api.types import EVICTED_BY_PREEMPTION
        count = 0
        now = self.clock()
        for t in targets:
            if not t.info.obj.condition_true(WL_EVICTED):
                message = (f"Preempted to accommodate a workload (UID: "
                           f"{preemptor.obj.uid}) due to "
                           f"{HUMAN_READABLE_REASONS.get(t.reason, 'UNKNOWN')}")
                if self.apply_preemption is not None:
                    self.apply_preemption(t.info, t.reason, message)
                else:
                    set_evicted_condition(t.info.obj, EVICTED_BY_PREEMPTION,
                                          message, now)
                    set_preempted_condition(t.info.obj, t.reason, message, now)
            count += 1
        return count


def _oracle_ctx(cq: CQState, wl: Info, fr: FlavorResource, quantity: int,
                snapshot: Snapshot) -> _PreemptionCtx:
    """The oracle's search (preemption_oracle.go:40): the head asks for
    ``quantity`` of the one flavor-resource and nothing else."""
    return _PreemptionCtx(
        preemptor=wl, preemptor_cq=cq, snapshot=snapshot,
        frs_need_preemption={fr},
        workload_usage=FlavorResourceQuantities({fr: quantity}))


class PreemptionOracle:
    """reference preemption_oracle.go:40."""

    def __init__(self, preemptor: Preemptor, snapshot: Snapshot):
        self.preemptor = preemptor
        self.snapshot = snapshot

    def is_reclaim_possible(self, cq: CQState, wl: Info,
                            fr: FlavorResource, quantity: int) -> bool:
        if cq.borrowing_with(fr, quantity):
            return False
        ctx = _oracle_ctx(self.snapshot.cq(wl.cluster_queue) or cq, wl, fr,
                          quantity, self.snapshot)
        for target in self.preemptor._get_targets(ctx):
            if target.info.cluster_queue == cq.name:
                return False
        return True
