"""ClusterQueue / Cohort in-memory state shared by the live cache and snapshots.

Capability parity with reference pkg/cache/clusterqueue.go + cohort.go +
fair_sharing.go.  A ``CQState``/``CohortState`` pair forms the hierarchical
resource tree; the same classes back per-cycle snapshots (cloned usage).
"""

from __future__ import annotations

import sys
from dataclasses import dataclass, field
from typing import Optional

from ..api.types import (
    ClusterQueue,
    Cohort,
    FlavorFungibility,
    PreemptionPolicy,
    QueueingStrategy,
    ResourceQuota,
    StopPolicy,
)
from ..resources import FlavorResource, FlavorResourceQuantities, Requests
from ..workload import Info
from . import resource_node as rn
from .candidates import CandidateTable, TableTally

MAX_DRS = sys.maxsize  # weight-zero sentinel (reference fair_sharing.go:52)


class SnapTag:
    """Per-root-tree mutation flag attached to *snapshot clones*.

    The incremental snapshot builder (cache.Cache.snapshot) hands out
    cached clone trees across cycles; a cached tree is only reusable if
    the scheduler didn't scribble on it (preemption simulation,
    in-cycle capacity reservation).  Every CQ clone in a cached tree
    shares one tag; the usage mutators flip it, and the builder
    re-clones flipped trees from the live cache.  Live CQStates carry
    ``_snap_tag = None`` so the hot-path cost on the live side is one
    attribute test."""

    __slots__ = ("mutated",)

    def __init__(self):
        self.mutated = False


def build_quotas(resource_groups) -> dict[FlavorResource, ResourceQuota]:
    """Flatten resource groups into the (flavor, resource) → quota map.

    lendingLimit is dropped at build when its gate is off — the
    reference does the same at cache build (scheduler_test.go:748
    disableLendingLimit), keeping the per-cycle hot paths gate-free."""
    import dataclasses
    from .. import features
    lending_on = features.enabled("LendingLimit")
    quotas: dict[FlavorResource, ResourceQuota] = {}
    for rg in resource_groups:
        for fq in rg.flavors:
            for rname, q in fq.resources.items():
                if q.lending_limit is not None and not lending_on:
                    q = dataclasses.replace(q, lending_limit=None)
                quotas[FlavorResource(fq.name, rname)] = q
    return quotas


class CohortState:
    """Cohort node payload (reference pkg/cache/cohort.go)."""

    def __init__(self, name: str):
        self.name = name
        self.spec: Optional[Cohort] = None
        self.resource_node = rn.ResourceNode()
        self.fair_weight_milli: int = 1000
        self.parent: Optional["CohortState"] = None
        self.child_cohorts: list["CohortState"] = []
        self.child_cqs: list["CQState"] = []

    def parent_node(self) -> Optional["CohortState"]:
        return self.parent

    def has_parent(self) -> bool:
        return self.parent is not None

    def root(self) -> "CohortState":
        node = self
        while node.parent is not None:
            node = node.parent
        return node

    def subtree_cqs(self) -> list["CQState"]:
        out = list(self.child_cqs)
        for c in self.child_cohorts:
            out.extend(c.subtree_cqs())
        return out

    def clone_subtree(self, parent: Optional["CohortState"],
                      cq_map: dict[str, "CQState"]) -> "CohortState":
        c = CohortState(self.name)
        c.spec = self.spec
        c.resource_node = self.resource_node.clone()
        c.fair_weight_milli = self.fair_weight_milli
        c.parent = parent
        c.child_cohorts = [ch.clone_subtree(c, cq_map) for ch in self.child_cohorts]
        for cq in self.child_cqs:
            cq_clone = cq.clone(parent=c)
            c.child_cqs.append(cq_clone)
            cq_map[cq_clone.name] = cq_clone
        return c


class CQState:
    """ClusterQueue cache entry (reference pkg/cache/clusterqueue.go)."""

    def __init__(self, spec: ClusterQueue,
                 table_tally: Optional[TableTally] = None):
        self.spec = spec
        self.resource_node = rn.ResourceNode()
        self.parent: Optional[CohortState] = None
        self.workloads: dict[str, Info] = {}
        # the same workloads as columns, a row each, for the preemptor
        # (cache/candidates.py); add_workload, remove_workload and
        # clone are all that write either
        self.candidates = CandidateTable(table_tally)
        self.allocatable_generation = 0
        self.active = True
        self.inactive_reasons: list[str] = []
        self.fair_weight_milli = int((spec.fair_sharing.weight if spec.fair_sharing else 1.0) * 1000)
        self.admitted_usage = FlavorResourceQuantities()  # Admitted (vs merely reserving)
        self._snap_tag: Optional[SnapTag] = None
        self.update_quotas(spec)

    # -- identity / config passthroughs --

    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def preemption(self) -> PreemptionPolicy:
        return self.spec.preemption

    @property
    def flavor_fungibility(self) -> FlavorFungibility:
        return self.spec.flavor_fungibility

    @property
    def queueing_strategy(self) -> QueueingStrategy:
        return self.spec.queueing_strategy

    def update_quotas(self, spec: ClusterQueue) -> None:
        self.spec = spec
        self.resource_node.quotas = build_quotas(spec.resource_groups)
        self.fair_weight_milli = int((spec.fair_sharing.weight if spec.fair_sharing else 1.0) * 1000)

    # -- tree navigation --

    def parent_node(self) -> Optional[CohortState]:
        return self.parent

    def has_parent(self) -> bool:
        return self.parent is not None

    # -- usage --

    def add_workload(self, info: Info) -> bool:
        """Add and account a workload; refuses duplicates (reference
        clusterqueue.go addWorkload errors on an already-present key)."""
        if info.key in self.workloads:
            return False
        tag = self._snap_tag
        if tag is not None:
            tag.mutated = True
        self.workloads[info.key] = info
        usage = info.usage()
        self.candidates.add(info, usage)
        rn.apply_usage(self, usage, +1)
        if info.obj.is_admitted:
            self.admitted_usage.add(usage)
        return True

    def remove_workload(self, info: Info) -> None:
        if self.workloads.pop(info.key, None) is None:
            return
        tag = self._snap_tag
        if tag is not None:
            tag.mutated = True
        self.candidates.remove(info.key)
        rn.apply_usage(self, info.usage(), -1)
        if info.obj.is_admitted:
            self.admitted_usage.sub(info.usage())

    def available(self, fr: FlavorResource) -> int:
        return rn.available(self, fr)

    def potential_available(self, fr: FlavorResource) -> int:
        return rn.potential_available(self, fr)

    def fits(self, usage: FlavorResourceQuantities) -> bool:
        """reference clusterqueue_snapshot.go:133 Fits."""
        return all(qty <= self.available(fr) for fr, qty in usage.items())

    def borrowing(self, fr: FlavorResource) -> bool:
        """Usage above this node's own subtree quota for fr."""
        return self.borrowing_with(fr, 0)

    def simulate_usage_addition(self, usage: FlavorResourceQuantities):
        """Apply usage, returning a revert closure (reference
        clusterqueue_snapshot.go SimulateUsageAddition)."""
        tag = self._snap_tag
        if tag is not None:
            tag.mutated = True
        rn.apply_usage(self, usage, +1)
        return lambda: rn.apply_usage(self, usage, -1)

    def simulate_usage_removal(self, usage: FlavorResourceQuantities):
        tag = self._snap_tag
        if tag is not None:
            tag.mutated = True
        rn.apply_usage(self, usage, -1)
        return lambda: rn.apply_usage(self, usage, +1)

    def borrowing_with(self, fr: FlavorResource, val: int) -> bool:
        """Would usage+val exceed this CQ's own subtree quota
        (reference clusterqueue_snapshot.go BorrowingWith)."""
        return self.resource_node.usage.get(fr, 0) + val > self.resource_node.subtree_quota.get(fr, 0)

    def is_borrowing(self) -> bool:
        return any(self.resource_node.usage.get(fr, 0) > self.resource_node.subtree_quota.get(fr, 0)
                   for fr in self.resource_node.usage)

    def clone(self, parent: Optional[CohortState]) -> "CQState":
        c = CQState.__new__(CQState)
        c.spec = self.spec
        c.resource_node = self.resource_node.clone()
        c.parent = parent
        c.workloads = dict(self.workloads)
        c.candidates = self.candidates.clone()
        c.allocatable_generation = self.allocatable_generation
        c.active = self.active
        c.inactive_reasons = list(self.inactive_reasons)
        c.fair_weight_milli = self.fair_weight_milli
        c.admitted_usage = self.admitted_usage.clone()
        c._snap_tag = None
        return c

    # -- fair sharing (reference pkg/cache/fair_sharing.go:47) --

    def dominant_resource_share(self, wl_req: FlavorResourceQuantities | None = None
                                ) -> tuple[int, str]:
        return dominant_resource_share(self, wl_req)


def dominant_resource_share(node, wl_req: FlavorResourceQuantities | None = None
                            ) -> tuple[int, str]:
    """DRS in [0, 1e6]: max over resources of (usage above subtree quota)
    ·1000 / lendable-in-cohort, ÷ fair weight (reference fair_sharing.go:47)."""
    if not node.has_parent():
        return 0, ""
    if node.fair_weight_milli == 0:
        return MAX_DRS, ""
    r = node.resource_node
    borrowing: dict[str, int] = {}
    for fr in r.subtree_quota:
        borrowed = ((wl_req.get(fr, 0) if wl_req else 0)
                    + r.usage.get(fr, 0) - r.subtree_quota.get(fr, 0))
        if borrowed > 0:
            borrowing[fr.resource] = borrowing.get(fr.resource, 0) + borrowed
    if not borrowing:
        return 0, ""
    lendable = calculate_lendable(node.parent_node())
    drs, d_res = -1, ""
    for rname in borrowing:
        lr = lendable.get(rname, 0)
        if lr > 0:
            ratio = borrowing[rname] * 1000 // lr
            if ratio > drs or (ratio == drs and rname < d_res):
                drs, d_res = ratio, rname
    dws = drs * 1000 // node.fair_weight_milli
    return dws, d_res


def calculate_lendable(node) -> dict[str, int]:
    """Aggregate potential capacity per resource name at the root
    (reference fair_sharing.go:86)."""
    root = node
    while root.has_parent():
        root = root.parent_node()
    lendable: dict[str, int] = {}
    for fr in root.resource_node.subtree_quota:
        lendable[fr.resource] = lendable.get(fr.resource, 0) + rn.potential_available(node, fr)
    return lendable


def update_cluster_queue_resource_node(cq: CQState) -> None:
    """reference resource_node.go:146."""
    cq.allocatable_generation += 1
    sq = FlavorResourceQuantities()
    for fr, quota in cq.resource_node.quotas.items():
        sq[fr] = quota.nominal
    cq.resource_node.subtree_quota = sq


def update_cohort_resource_node(cohort: CohortState) -> None:
    """Accumulate subtree quota/usage root-down (reference resource_node.go:169)."""
    sq = FlavorResourceQuantities()
    usage = FlavorResourceQuantities()
    for fr, quota in cohort.resource_node.quotas.items():
        sq[fr] = quota.nominal
    cohort.resource_node.subtree_quota = sq
    cohort.resource_node.usage = usage
    for child in cohort.child_cohorts:
        update_cohort_resource_node(child)
        _accumulate_from_child(cohort, child.resource_node)
    for child in cohort.child_cqs:
        update_cluster_queue_resource_node(child)
        _accumulate_from_child(cohort, child.resource_node)


def _accumulate_from_child(parent: CohortState, child: rn.ResourceNode) -> None:
    """reference resource_node.go:186."""
    for fr, child_quota in child.subtree_quota.items():
        parent.resource_node.subtree_quota[fr] = (
            parent.resource_node.subtree_quota.get(fr, 0)
            + child_quota - child.guaranteed_quota(fr))
    for fr, child_usage in child.usage.items():
        parent.resource_node.usage[fr] = (
            parent.resource_node.usage.get(fr, 0)
            + max(0, child_usage - child.guaranteed_quota(fr)))
