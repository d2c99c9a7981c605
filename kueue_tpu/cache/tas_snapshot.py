"""Topology flavor snapshot: the domain tree + assignment algorithm.

Capability parity with reference pkg/cache/tas_flavor_snapshot.go:91: a tree
of topology domains (e.g. block → rack → hostname) built from node labels,
with per-leaf free capacity.  ``find_topology_assignment`` mirrors the
two-phase algorithm (tas_flavor_snapshot.go:406-613): phase 1 fills pod
counts bottom-up; phase 2 picks the lowest level whose best domain fits all
pods (falling back upward for `preferred`), then walks down level by level
minimizing the number of domains (BestFit).

The batched/TPU formulation of the same algorithm lives in
kueue_tpu.ops.tas_kernel (segment reductions over a level-indexed CSR tree).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..api.types import (
    PodSetTopologyRequest,
    TopologyAssignment,
    TopologyDomainAssignment,
)
from .tas_cache import NodeInfo, TASLayout


@dataclass(eq=False)  # identity hash: domains are keyed in chosen-maps
class Domain:
    """One topology domain (reference tas_flavor_snapshot.go `domain`)."""
    id: tuple                      # label values from root level to this level
    level: int
    parent: Optional["Domain"] = None
    children: list["Domain"] = field(default_factory=list)
    # leaf-only: free capacity and allocatable (canonical ints)
    free: dict[str, int] = field(default_factory=dict)
    capacity: dict[str, int] = field(default_factory=dict)
    # per-query state
    state: int = 0                 # how many pods fit in this subtree


class TASFlavorSnapshot:
    """One flavor's topology as a cycle sees it: the flavor's layout
    (``TASLayout``: the domains, level-sorted) and its leaves' free
    capacity ``free`` [N_leaf, R] (allocatable less usage) as the cycle
    started.  The ``Domain`` tree the host walk reads is built from them
    on first use; the batched topology stage (``ops/tas_kernel.py``)
    reads the planes.  ``charge``/``holds`` keep what the cycle's
    admissions placed (``cycle_used``), apart from ``free``."""

    def __init__(self, flavor: str, levels: list[str],
                 layout: Optional[TASLayout] = None,
                 free: Optional[np.ndarray] = None):
        self.flavor = flavor
        self.levels = levels
        if layout is None:
            layout = TASLayout.build(levels, [])
            free = layout.capacity.copy()
        self.layout = layout
        self.free = free
        self.cycle_used: Optional[np.ndarray] = None
        self.host_queries = 0          # find_topology_assignment calls
        self._tree = None

    @staticmethod
    def build(flavor: str, levels: list[str], nodes: list[NodeInfo],
              usage: dict[tuple, dict[str, int]]) -> "TASFlavorSnapshot":
        layout = TASLayout.build(
            levels, nodes,
            extra_resources={r for per in usage.values() for r in per})
        return TASFlavorSnapshot(flavor, levels, layout=layout,
                                 free=layout.capacity
                                 - layout.usage_plane(usage))

    # -- the host walk's tree, built on first use -----------------------

    def _build_tree(self):
        lay = self.layout
        names = lay.resource_names
        doms = [[Domain(id=did, level=lvl) for did in ids]
                for lvl, ids in enumerate(lay.domain_ids)]
        for lvl, par in enumerate(lay.parents):
            upper = doms[lvl]
            for child, p in zip(doms[lvl + 1], par.tolist()):
                child.parent = upper[p]
                upper[p].children.append(child)
        free = self.free.tolist()
        cap = lay.capacity.tolist()
        leaves = {}
        for leaf, f, c in zip(doms[-1], free, cap):
            leaf.free = dict(zip(names, f))
            leaf.capacity = dict(zip(names, c))
            leaves[leaf.id] = leaf
        self._tree = (leaves, doms[0] if doms else [], doms)
        return self._tree

    @property
    def leaves(self) -> dict[tuple, Domain]:
        return (self._tree or self._build_tree())[0]

    @property
    def roots(self) -> list[Domain]:
        return (self._tree or self._build_tree())[1]

    @property
    def domains_per_level(self) -> list[list[Domain]]:
        return (self._tree or self._build_tree())[2]

    # -- in-cycle usage (scheduler admit loop) ---------------------------

    def demand(self, placements) -> Optional[tuple]:
        """``placements`` = [(TopologyAssignment, per-pod requests)] as
        (leaf indices, [k, R] demand); None where one names a leaf or a
        resource the layout does not know."""
        lay = self.layout
        r_index = lay.r_index
        idx, need = [], []
        for assignment, per_pod in placements:
            vec = np.zeros(len(lay.resource_names), dtype=np.int64)
            for r, v in per_pod.items():
                if v <= 0:
                    continue
                ri = r_index.get(r)
                if ri is None:
                    return None
                vec[ri] = v
            for d in assignment.domains:
                i = lay.leaf_index.get(tuple(d.values))
                if i is None:
                    return None
                idx.append(i)
                need.append(d.count * vec)
        if not idx:
            return (np.zeros(0, dtype=np.int64),
                    np.zeros((0, len(lay.resource_names)), dtype=np.int64))
        return np.asarray(idx, dtype=np.int64), np.stack(need)

    def holds(self, demand) -> bool:
        """Do the demand's leaves still hold it after what the cycle's
        earlier admissions placed (``charge``)?"""
        if demand is None:
            return False
        idx, need = demand
        uniq, inv = np.unique(idx, return_inverse=True)
        total = np.zeros((len(uniq), need.shape[1]), dtype=np.int64)
        np.add.at(total, inv, need)
        left = self.free[uniq]
        if self.cycle_used is not None:
            left = left - self.cycle_used[uniq]
        return bool((total <= left).all())

    def charge(self, demand) -> None:
        if demand is None:
            return
        if self.cycle_used is None:
            self.cycle_used = np.zeros_like(self.free)
        idx, need = demand
        np.add.at(self.cycle_used, idx, need)

    # ------------------------------------------------------------------

    def _fill_in_counts(self, per_pod: dict[str, int],
                        assumed: dict[tuple, dict[str, int]] | None = None,
                        simulate_empty: bool = False) -> None:
        """Phase 1 (reference tas_flavor_snapshot.go fillInCounts): compute
        how many pods fit in each domain, bottom-up.  ``simulate_empty``
        counts against the leaves' allocatable, as if no workload placed
        by topology held any; ``assumed`` is charged either way."""
        for leaf in self.leaves.values():
            fits = None
            have = leaf.capacity if simulate_empty else leaf.free
            for rname, need in per_pod.items():
                if need <= 0:
                    continue
                free = have.get(rname, 0)
                if assumed:
                    free -= assumed.get(leaf.id, {}).get(rname, 0)
                n = max(0, free) // need
                fits = n if fits is None else min(fits, n)
            leaf.state = fits if fits is not None else 0
        for lvl in range(len(self.levels) - 2, -1, -1):
            for dom in self.domains_per_level[lvl]:
                dom.state = sum(c.state for c in dom.children)

    def _level_index(self, label: Optional[str]) -> Optional[int]:
        if label is None:
            return None
        try:
            return self.levels.index(label)
        except ValueError:
            return None

    def find_topology_assignment(
            self, count: int, per_pod: dict[str, int],
            request: PodSetTopologyRequest,
            assumed: dict[tuple, dict[str, int]] | None = None,
            simulate_empty: bool = False,
    ) -> tuple[Optional[TopologyAssignment], str]:
        """Phase 1 + 2 (reference tas_flavor_snapshot.go:406-613).

        Returns (assignment at the leaf level, reason-on-failure).
        """
        self.host_queries += 1
        if not self.levels:
            return None, "no topology levels"
        required_idx = self._level_index(request.required)
        preferred_idx = self._level_index(request.preferred)
        if request.required and required_idx is None:
            return None, f"level {request.required} not in topology"
        if request.preferred and preferred_idx is None:
            return None, f"level {request.preferred} not in topology"
        if self._device_kernel_eligible(request):
            found = self._find_device(count, per_pod, request, assumed,
                                      simulate_empty)
            if found is not None:
                return found
        self._fill_in_counts(per_pod, assumed, simulate_empty)

        if request.unconstrained:
            # any set of leaves; minimize domain count from the top
            total = sum(r.state for r in self.roots)
            if total < count:
                return None, self._fit_message(count, total)
            chosen = self._select_from(
                self._sorted_domains(self.roots, unconstrained=True),
                count, unconstrained=True)
        else:
            if required_idx is not None:
                fit_idx, domain = self._find_fit_at(required_idx, count)
                if domain is None:
                    return None, self._fit_message_level(count, required_idx)
            else:
                start = preferred_idx if preferred_idx is not None else len(self.levels) - 1
                fit_idx, domain = None, None
                for lvl in range(start, -1, -1):
                    fit_idx, domain = self._find_fit_at(lvl, count)
                    if domain is not None:
                        break
                if domain is None:
                    # final fallback: split across root domains
                    total = sum(r.state for r in self.roots)
                    if total < count:
                        return None, self._fit_message(count, total)
                    chosen = self._select_from(
                        self._sorted_domains(self.roots), count)
                    return self._assignment_from(chosen), ""
            chosen = {domain: count}
        return self._assignment_from(chosen), ""

    # -- device kernel path (ops/tas_kernel, TASDeviceKernel gate) -----

    def _device_kernel_eligible(self, request: PodSetTopologyRequest) -> bool:
        """The kernel implements all three TAS profiles
        (BestFit default, TASProfileMostFreeCapacity,
        TASProfileLeastFreeCapacity incl. Mixed's unconstrained
        variant — tas_flavor_snapshot.go:551-568)."""
        from .. import features
        return (features.enabled("TASDeviceKernel")
                and bool(self.layout.leaf_ids))

    def _device_profile(self, unconstrained: bool) -> str:
        if self._use_best_fit(unconstrained):
            return "bestfit"
        if self._use_least_free(unconstrained):
            return "leastfree"
        return "mostfree"

    def question(self, count: int, per_pod: dict[str, int],
                 request: PodSetTopologyRequest, empty: bool = False,
                 charges: tuple = ()):
        """The placement question of ``count`` pods of ``per_pod`` under
        ``request`` as the kernel asks it (``ops/tas_kernel.TASQuery``),
        against the free nodes or the empty tree, less ``charges``
        ((leaf index, [R] demand), ...); None where a requested resource
        is on no node (nothing holds a pod)."""
        from ..ops import tas_kernel as tk
        lay = self.layout
        vec = np.zeros(len(lay.resource_names), dtype=np.int64)
        for r, v in per_pod.items():
            if v > 0:
                ri = lay.r_index.get(r)
                if ri is None:
                    return None
                vec[ri] = v
        if request.unconstrained:
            kind, level = tk.QUERY_UNCONSTRAINED, -1
        elif request.required is not None:
            kind, level = tk.QUERY_REQUIRED, self._level_index(
                request.required)
        else:
            kind = tk.QUERY_PREFERRED
            level = (self._level_index(request.preferred)
                     if request.preferred is not None
                     else len(self.levels) - 1)
        return tk.TASQuery(vec, count, kind, level, empty, charges)

    def assumed(self, charges) -> dict[tuple, dict[str, int]]:
        """``question``'s charges as the walk's ``assumed``: a leaf id's
        demand by resource name."""
        lay = self.layout
        out: dict[tuple, dict[str, int]] = {}
        for leaf, vec in charges:
            slot = out.setdefault(lay.leaf_ids[leaf], {})
            for r, v in zip(lay.resource_names, np.asarray(vec).tolist()):
                if v:
                    slot[r] = slot.get(r, 0) + v
        return out

    def answered(self, query, answer) -> tuple[Optional[TopologyAssignment],
                                               str]:
        """A kernel answer as ``find_topology_assignment`` returns it:
        the placement at the leaf level, or the reason none was found."""
        from ..ops import tas_kernel as tk
        if not answer.ok:
            return None, self._no_fit(
                query.count, answer.best,
                query.level if query.kind == tk.QUERY_REQUIRED else None)
        leaf_ids = self.layout.leaf_ids
        return TopologyAssignment(levels=list(self.levels), domains=[
            TopologyDomainAssignment(values=list(leaf_ids[leaf]), count=c)
            for leaf, c in answer.placement]), ""

    def _find_device(self, count: int, per_pod: dict[str, int],
                     request: PodSetTopologyRequest,
                     assumed: dict[tuple, dict[str, int]] | None,
                     simulate_empty: bool = False):
        """find_topology_assignment as one question of the batched
        kernel (``ops/tas_kernel.answer_queries``), decision-identical
        to the scalar walk (tests/test_tas_kernel.py,
        tests/test_tas_device_path.py); None where the int32 kernel
        cannot answer it exactly, for the walk to answer."""
        from ..ops import tas_kernel as tk
        lay = self.layout
        charges = []
        for did, a in (assumed or {}).items():
            i = lay.leaf_index.get(did)
            if i is None:
                continue
            vec = np.zeros(len(lay.resource_names), dtype=np.int64)
            for r, v in a.items():
                ri = lay.r_index.get(r)
                if ri is not None:
                    vec[ri] = v
            charges.append((i, vec))
        q = self.question(count, per_pod, request, simulate_empty,
                          tuple(charges))
        if q is None:
            return None, self._no_fit(
                count, 0, self._level_index(request.required))
        ans, = tk.answer_queries(lay, self.free, [q],
                                 self._device_profile(False),
                                 self._device_profile(True))
        return None if ans is None else self.answered(q, ans)

    # -- helpers --

    @staticmethod
    def _domain_order(dom: Domain):
        # default sortedDomains order: state descending, ties by id
        # (reference tas_flavor_snapshot.go:631)
        return (-dom.state, dom.id)

    @staticmethod
    def _use_best_fit(unconstrained: bool = False) -> bool:
        """reference tas_flavor_snapshot.go:551 useBestFitAlgorithm."""
        from .. import features
        if (features.enabled("TASProfileMostFreeCapacity")
                or features.enabled("TASProfileLeastFreeCapacity")
                or (unconstrained and features.enabled("TASProfileMixed"))):
            return False
        return True

    @staticmethod
    def _use_least_free(unconstrained: bool = False) -> bool:
        """reference tas_flavor_snapshot.go:561."""
        from .. import features
        return (features.enabled("TASProfileLeastFreeCapacity")
                or (unconstrained and features.enabled("TASProfileMixed")))

    def _sorted_domains(self, domains: list[Domain],
                        unconstrained: bool = False) -> list[Domain]:
        """reference sortedDomains: state desc, ties by id; the
        least-free profiles reverse the order."""
        out = sorted(domains, key=self._domain_order)
        if self._use_least_free(unconstrained):
            out.reverse()
        return out

    def _find_fit_at(self, level: int, count: int) -> tuple[int, Optional[Domain]]:
        """Best single domain at `level` that fits all pods.

        Default BestFit picks the least spare capacity (reference
        findBestFitDomainIdx); TASProfileMostFreeCapacity picks the most
        free (the top of sortedDomains)."""
        fitting = [d for d in self.domains_per_level[level]
                   if d.state >= count]
        if not fitting:
            return level, None
        if self._use_best_fit() or self._use_least_free():
            return level, min(fitting, key=lambda d: (d.state, d.id))
        return level, min(fitting, key=self._domain_order)

    def _select_from(self, ordered: list[Domain], count: int,
                     unconstrained: bool = False) -> dict[Domain, int]:
        """Multi-domain split over a sortedDomains list (reference
        updateCountsToMinimum, tas_flavor_snapshot.go:571): walk the
        order taking whole domains; under BestFit, once the remainder
        fits a single domain, pick the tightest such domain for it."""
        chosen: dict[Domain, int] = {}
        remaining = count
        best_fit = self._use_best_fit(unconstrained)
        for i, dom in enumerate(ordered):
            if remaining <= 0:
                break
            if best_fit and dom.state >= remaining:
                # optimize the last domain (findBestFitDomainIdx)
                dom = min((d for d in ordered[i:] if d.state >= remaining),
                          key=lambda d: (d.state, d.id))
            if dom.state >= remaining:
                chosen[dom] = chosen.get(dom, 0) + remaining
                return chosen
            if dom.state > 0:
                chosen[dom] = chosen.get(dom, 0) + dom.state
                remaining -= dom.state
        return chosen

    def _assignment_from(self, chosen: dict[Domain, int]) -> TopologyAssignment:
        """Walk chosen domains down to leaves, minimizing leaf-domain count."""
        leaf_counts: dict[tuple, int] = {}
        for dom, cnt in chosen.items():
            self._descend(dom, cnt, leaf_counts)
        domains = [TopologyDomainAssignment(values=list(dom_id), count=cnt)
                   for dom_id, cnt in sorted(leaf_counts.items())]
        return TopologyAssignment(levels=list(self.levels), domains=domains)

    def _descend(self, dom: Domain, cnt: int, out: dict[tuple, int]) -> None:
        if not dom.children:  # leaf
            out[dom.id] = out.get(dom.id, 0) + cnt
            return
        # updateCountsToMinimum over the children at each level
        for child, take in self._select_from(
                self._sorted_domains(dom.children), cnt).items():
            self._descend(child, take, out)

    def _fit_message(self, count: int, total: int) -> str:
        return self._no_fit(count, total)

    def _fit_message_level(self, count: int, level: int) -> str:
        best = max((d.state for d in self.domains_per_level[level]), default=0)
        return self._no_fit(count, best, level)

    def _no_fit(self, count: int, best: int,
                level: Optional[int] = None) -> str:
        """Why no placement was found: ``best`` pods fit, in all the
        roots, or in a single domain of a required ``level``."""
        msg = (f"topology {self.flavor!r} allows to fit only {best} "
               f"out of {count} pod(s)")
        if level is not None:
            msg += f" in a single {self.levels[level]!r}"
        return msg
