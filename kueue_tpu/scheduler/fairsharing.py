"""Fair-sharing preemption ordering over the cohort tree.

Capability parity with reference pkg/scheduler/preemption/fairsharing/
(ordering.go, target.go, strategy.go, least_common_ancestor.go): a
tournament that repeatedly descends from the root cohort into the child
with the highest DominantResourceShare to pick the next preemption-target
ClusterQueue, with almost-LCA share comparisons for the S2 rules.
"""

from __future__ import annotations

from typing import Iterator, Optional

from ..cache.state import CohortState, CQState, dominant_resource_share
from ..workload import Info

# Strategy signature: (preemptor_new_share, target_old_share, target_new_share) -> bool


def less_than_or_equal_to_final_share(preemptor_new: int, _old: int, target_new: int) -> bool:
    """Rule S2-a (reference strategy.go)."""
    return preemptor_new <= target_new


def less_than_initial_share(preemptor_new: int, target_old: int, _new: int) -> bool:
    """Rule S2-b (reference strategy.go)."""
    return preemptor_new < target_old


DEFAULT_STRATEGIES = (less_than_or_equal_to_final_share, less_than_initial_share)


def parse_strategies(names: list[str] | None):
    """reference preemption.go:353 parseStrategies."""
    if not names:
        return list(DEFAULT_STRATEGIES)
    mapping = {
        "LessThanOrEqualToFinalShare": less_than_or_equal_to_final_share,
        "LessThanInitialShare": less_than_initial_share,
    }
    return [mapping[n] for n in names]


def _drs(node) -> int:
    return dominant_resource_share(node)[0]


class TargetClusterQueue:
    """reference fairsharing/target.go."""

    def __init__(self, ordering: "TargetClusterQueueOrdering", cq: CQState):
        self.ordering = ordering
        self.target_cq = cq

    def in_cluster_queue_preemption(self) -> bool:
        return self.target_cq is self.ordering.preemptor_cq

    def has_workload(self) -> bool:
        return bool(self.ordering.cq_to_targets.get(self.target_cq.name))

    def pop_workload(self) -> Info:
        lst = self.ordering.cq_to_targets[self.target_cq.name]
        head = lst.pop(0)
        return head

    # -- almost-LCA shares (reference least_common_ancestor.go) --

    def _lca(self) -> Optional[CohortState]:
        cohort = self.target_cq.parent
        while cohort is not None:
            if cohort in self.ordering.preemptor_ancestors:
                return cohort
            cohort = cohort.parent
        return None

    @staticmethod
    def _almost_lca(cq: CQState, lca: CohortState):
        if cq.parent is lca:
            return cq
        cohort = cq.parent
        while cohort is not None and cohort.parent is not lca:
            cohort = cohort.parent
        return cohort

    def compute_shares(self) -> tuple[int, int]:
        """(preemptor almost-LCA DRS, target almost-LCA DRS)."""
        lca = self._lca()
        pre = self._almost_lca(self.ordering.preemptor_cq, lca)
        tgt = self._almost_lca(self.target_cq, lca)
        return _drs(pre), _drs(tgt)

    def compute_target_share_after_removal(self, wl: Info) -> int:
        lca = self._lca()
        tgt = self._almost_lca(self.target_cq, lca)
        revert = self.target_cq.simulate_usage_removal(wl.usage())
        drs = _drs(tgt)
        revert()
        return drs


class TargetClusterQueueOrdering:
    """reference fairsharing/ordering.go:43."""

    def __init__(self, preemptor_cq: CQState, candidates: list[Info],
                 snapshot_cqs: dict[str, CQState],
                 groups: Optional[dict[str, list[Info]]] = None):
        """``candidates`` in order, grouped here by queue; or ``groups``,
        each queue's in order, already grouped (taken over, and popped)."""
        self.preemptor_cq = preemptor_cq
        self.snapshot_cqs = snapshot_cqs
        self.preemptor_ancestors: set = set()
        cohort = preemptor_cq.parent
        while cohort is not None:
            self.preemptor_ancestors.add(cohort)
            cohort = cohort.parent
        self.cq_to_targets: dict[str, list[Info]] = (
            groups if groups is not None else {})
        for cand in candidates:
            self.cq_to_targets.setdefault(cand.cluster_queue, []).append(cand)
        self.pruned_cqs: set[int] = set()
        self.pruned_cohorts: set[int] = set()

    def drop_queue(self, tcq: TargetClusterQueue) -> None:
        self.pruned_cqs.add(id(tcq.target_cq))

    def _has_workload(self, cq: CQState) -> bool:
        return bool(self.cq_to_targets.get(cq.name))

    def iterate(self) -> Iterator[TargetClusterQueue]:
        if self.preemptor_cq.parent is None:
            tcq = TargetClusterQueue(self, self.preemptor_cq)
            while tcq.has_workload():
                yield tcq
            return
        root = self.preemptor_cq.parent.root()
        while id(root) not in self.pruned_cohorts:
            tcq = self._next_target(root)
            if tcq is None:
                continue
            yield tcq

    def _next_target(self, cohort: CohortState) -> Optional[TargetClusterQueue]:
        highest_cq, highest_cq_drs = None, -1
        for cq in cohort.child_cqs:
            if id(cq) in self.pruned_cqs:
                continue
            drs = _drs(cq)
            if (drs == 0 and cq is not self.preemptor_cq) or not self._has_workload(cq):
                self.pruned_cqs.add(id(cq))
            elif drs >= highest_cq_drs:
                highest_cq_drs = drs
                highest_cq = cq
        highest_cohort, highest_cohort_drs = None, -1
        for child in cohort.child_cohorts:
            if id(child) in self.pruned_cohorts:
                continue
            drs = _drs(child)
            if drs == 0 and child not in self.preemptor_ancestors:
                self.pruned_cohorts.add(id(child))
            elif drs >= highest_cohort_drs:
                highest_cohort_drs = drs
                highest_cohort = child
        if highest_cohort is None and highest_cq is None:
            self.pruned_cohorts.add(id(cohort))
            return None
        if highest_cohort is not None and highest_cohort_drs >= highest_cq_drs:
            return self._next_target(highest_cohort)
        return TargetClusterQueue(self, highest_cq)
