"""Flavor assignment: map each PodSet resource to a flavor + mode.

Capability parity with reference pkg/scheduler/flavorassigner/flavorassigner.go:
walks each resource group's flavor list from the fungibility resume index,
filters by taints/tolerations and node-affinity against flavor node labels,
then classifies quota fit as Fit / Preempt(reclaim) / NoFit
(fitsResourceQuota, flavorassigner.go:692) under the FlavorFungibility
policy (shouldTryNextFlavor, :620).  Partial admission binary-searches pod
counts (podset_reducer.go).

This is the *scalar oracle* implementation; the batched TPU kernel with the
same semantics lives in kueue_tpu.ops.flavor_kernel and is verified against
this module.
"""

from __future__ import annotations

import copy
import enum
from dataclasses import dataclass, field
from typing import Callable, Optional, Protocol

from ..api.types import (
    BorrowWithinCohortPolicy,
    FlavorFungibilityPolicy,
    PodSet,
    PodSetAssignment,
    PodSetTopologyRequest,
    ReclaimWithinCohort,
    ResourceFlavor,
    TopologyAssignment,
    taints_tolerated,
)
from ..cache.state import CQState
from ..resources import FlavorResource, FlavorResourceQuantities, Requests
from ..workload import Info, PodSetResources


class Mode(enum.IntEnum):
    """Public assignment mode, ordered worst→best (flavorassigner.go:277)."""
    NO_FIT = 0
    PREEMPT = 1
    FIT = 2


class GranularMode(enum.IntEnum):
    """Internal lattice distinguishing reclaim (flavorassigner.go:308)."""
    NO_FIT = 0
    PREEMPT = 1
    RECLAIM = 2
    FIT = 3

    def public(self) -> Mode:
        if self == GranularMode.FIT:
            return Mode.FIT
        if self in (GranularMode.PREEMPT, GranularMode.RECLAIM):
            return Mode.PREEMPT
        return Mode.NO_FIT

    @property
    def is_preempt(self) -> bool:
        return self in (GranularMode.PREEMPT, GranularMode.RECLAIM)


@dataclass
class FlavorAssignmentDecision:
    name: str                      # flavor
    mode: Mode
    tried_flavor_idx: int = -1
    borrow: bool = False


@dataclass
class AssignmentClusterQueueState:
    """Fungibility resume state (reference workload.go:82)."""
    last_tried_flavor_idx: list[dict[str, int]] = field(default_factory=list)
    cluster_queue_generation: int = -1

    def next_flavor_to_try(self, ps_idx: int, res: str) -> int:
        if ps_idx < len(self.last_tried_flavor_idx):
            return self.last_tried_flavor_idx[ps_idx].get(res, -1) + 1
        return 0

    @property
    def pending_flavors(self) -> bool:
        """True when some resource still has untried flavors."""
        return any(idx != -1 for per_ps in self.last_tried_flavor_idx
                   for idx in per_ps.values())


@dataclass
class PodSetAssignmentResult:
    name: str
    flavors: dict[str, FlavorAssignmentDecision] = field(default_factory=dict)
    requests: Requests = field(default_factory=Requests)
    count: int = 0
    reasons: list[str] = field(default_factory=list)
    error: Optional[str] = None
    topology_assignment: Optional[TopologyAssignment] = None

    def representative_mode(self) -> Mode:
        if self.error is not None:
            return Mode.NO_FIT
        if not self.flavors:
            return Mode.NO_FIT if self.requests else Mode.FIT
        return Mode(min(f.mode for f in self.flavors.values()))

    def update_mode(self, mode: Mode) -> None:
        for f in self.flavors.values():
            f.mode = mode


@dataclass
class Assignment:
    pod_sets: list[PodSetAssignmentResult] = field(default_factory=list)
    borrowing: bool = False
    usage: FlavorResourceQuantities = field(default_factory=FlavorResourceQuantities)
    last_state: AssignmentClusterQueueState = field(
        default_factory=AssignmentClusterQueueState)
    _representative: Optional[Mode] = None

    def representative_mode(self) -> Mode:
        if not self.pod_sets:
            return Mode.NO_FIT
        if self._representative is not None:
            return self._representative
        return Mode(min(ps.representative_mode() for ps in self.pod_sets))

    def set_representative_mode(self, mode: Mode) -> None:
        self._representative = mode

    def borrows(self) -> bool:
        return self.borrowing

    def message(self) -> str:
        parts = []
        for ps in self.pod_sets:
            if ps.error:
                return f"failed to assign flavors to pod set {ps.name}: {ps.error}"
            if ps.reasons:
                parts.append(
                    f"couldn't assign flavors to pod set {ps.name}: "
                    + ", ".join(ps.reasons))
        return "; ".join(parts)

    def to_api(self) -> list[PodSetAssignment]:
        out = []
        for ps in self.pod_sets:
            out.append(PodSetAssignment(
                name=ps.name,
                flavors={res: fa.name for res, fa in ps.flavors.items()},
                resource_usage=dict(ps.requests),
                count=ps.count,
                topology_assignment=ps.topology_assignment))
        return out

    def total_requests_for(self, wl: Info) -> FlavorResourceQuantities:
        usage = FlavorResourceQuantities()
        for psr, aps in zip(wl.total_requests, self.pod_sets):
            if aps.count != psr.count:
                psr = psr.scaled_to(aps.count)
            for res, qty in psr.requests.items():
                fa = aps.flavors.get(res)
                if fa is None:
                    continue
                fr = FlavorResource(fa.name, res)
                usage[fr] = usage.get(fr, 0) + qty
        return usage


class PreemptionOracle(Protocol):
    def is_reclaim_possible(self, cq: CQState, wl: Info,
                            fr: FlavorResource, quantity: int) -> bool: ...


class _NeverReclaimOracle:
    def is_reclaim_possible(self, cq, wl, fr, quantity) -> bool:
        return False


@dataclass
class _FlavorMode:
    """A flavor's mode in one walk, the worst of its resources'.
    RECLAIM stands first for "Preempt, and Reclaim if the reclaim oracle
    says yes to each of ``questions``" ((FlavorResource, val), in the
    walk's order): ``FlavorAssigner._settle`` puts them to it only where
    a comparison reads the answer."""
    mode: GranularMode
    questions: list[tuple[FlavorResource, int]] = field(default_factory=list)


def rg_by_resource(cq: CQState, resource: str):
    for rg in cq.spec.resource_groups:
        if resource in rg.covered_resources:
            return rg
    return None


class FlavorAssigner:
    """reference flavorassigner.go:345."""

    def __init__(self, wl: Info, cq: CQState,
                 resource_flavors: dict[str, ResourceFlavor],
                 enable_fair_sharing: bool = False,
                 oracle: Optional[PreemptionOracle] = None,
                 tas_flavors: Optional[dict] = None,
                 flavor_fungibility_enabled: bool = True,
                 tas_enabled: bool = True):
        self.wl = wl
        self.cq = cq
        self.resource_flavors = resource_flavors
        self.enable_fair_sharing = enable_fair_sharing
        self.oracle = oracle or _NeverReclaimOracle()
        self.tas_flavors = tas_flavors or {}
        self.flavor_fungibility_enabled = flavor_fungibility_enabled
        self.tas_enabled = tas_enabled
        self._tas_only: Optional[bool] = None
        # the oracle questions the walks met and never put to it
        self.oracle_skips = 0

    def _is_tas_only(self) -> bool:
        """Every flavor of the CQ is a TAS flavor (reference
        clusterqueue_snapshot.go:221 IsTASOnly): pod sets without a
        topology request then get TAS implied (unconstrained)."""
        if self._tas_only is None:
            names = [fq.name for rg in self.cq.spec.resource_groups
                     for fq in rg.flavors] if self.cq is not None else []
            # every flavor must be a TAS flavor AND have topology data
            # loaded — without snapshots, implying TAS would drive the
            # whole CQ to NO_FIT (gate off / topology not yet cached)
            self._tas_only = (self.tas_enabled and bool(names) and all(
                (f := self.resource_flavors.get(n)) is not None
                and f.topology_name and n in self.tas_flavors
                for n in names))
        return self._tas_only

    # ------------------------------------------------------------------

    def assign(self, counts: Optional[list[int]] = None) -> Assignment:
        """reference flavorassigner.go:367 Assign."""
        last = self.wl.last_assignment
        if last is not None and self.cq.allocatable_generation > last.cluster_queue_generation:
            self.wl.last_assignment = None  # outdated resume state
        return self._assign_flavors(counts)

    def _assign_flavors(self, counts: Optional[list[int]]) -> Assignment:
        if counts is None:
            requests = self.wl.total_requests
        else:
            requests = [psr.scaled_to(c)
                        for psr, c in zip(self.wl.total_requests, counts)]

        assignment = Assignment()
        assignment.last_state.cluster_queue_generation = self.cq.allocatable_generation

        for ps_idx, psr in enumerate(requests):
            reqs = Requests(psr.requests)
            if rg_by_resource(self.cq, "pods") is not None:
                reqs["pods"] = psr.count
            else:
                # implicit pods resource only participates when the CQ
                # covers it (reference flavorassigner.go:226)
                reqs.pop("pods", None)
            ps_result = PodSetAssignmentResult(
                name=psr.name, requests=reqs, count=psr.count)
            for res in sorted(reqs):
                if res in ps_result.flavors:
                    continue  # same resource group already assigned
                flavors, reasons, error = self._find_flavor_for_podset_resource(
                    ps_idx, reqs, res, assignment.usage)
                ps_result.reasons.extend(reasons)
                if error is not None or not flavors:
                    ps_result.flavors = {}
                    ps_result.error = error
                    break
                ps_result.flavors.update(flavors)
            self._append(assignment, reqs, ps_result)
            if ps_result.error is not None or (reqs and not ps_result.flavors):
                return assignment

        if assignment.representative_mode() == Mode.NO_FIT:
            return assignment

        if self.tas_enabled:
            self._apply_tas(assignment, requests)
        return assignment

    def _append(self, assignment: Assignment, reqs: Requests,
                ps_result: PodSetAssignmentResult) -> None:
        """reference flavorassigner.go:480 Assignment.append."""
        flavor_idx: dict[str, int] = {}
        assignment.pod_sets.append(ps_result)
        for res, fa in ps_result.flavors.items():
            if fa.borrow:
                assignment.borrowing = True
            fr = FlavorResource(fa.name, res)
            assignment.usage[fr] = assignment.usage.get(fr, 0) + reqs.get(res, 0)
            flavor_idx[res] = fa.tried_flavor_idx
        assignment.last_state.last_tried_flavor_idx.append(flavor_idx)

    # ------------------------------------------------------------------

    def _find_flavor_for_podset_resource(
            self, ps_idx: int, requests: Requests, res_name: str,
            assignment_usage: FlavorResourceQuantities,
    ) -> tuple[dict[str, FlavorAssignmentDecision], list[str], Optional[str]]:
        """reference flavorassigner.go:499."""
        rg = rg_by_resource(self.cq, res_name)
        if rg is None:
            return {}, [f"resource {res_name} unavailable in ClusterQueue"], None

        reqs = Requests({r: v for r, v in requests.items()
                         if r in rg.covered_resources})
        pod_set = self.wl.obj.pod_sets[ps_idx] if ps_idx < len(self.wl.obj.pod_sets) else PodSet()
        reasons: list[str] = []

        allowed_keys = {k for fq in rg.flavors
                        for k in self.resource_flavors.get(fq.name, ResourceFlavor(fq.name)).node_labels}

        best: dict[str, FlavorAssignmentDecision] = {}
        best_mode = _FlavorMode(GranularMode.NO_FIT)
        attempted_idx = -1
        last = self.wl.last_assignment
        idx = last.next_flavor_to_try(ps_idx, res_name) if last is not None else 0

        flavor_names = [fq.name for fq in rg.flavors]
        while idx < len(flavor_names):
            attempted_idx = idx
            f_name = flavor_names[idx]
            flavor = self.resource_flavors.get(f_name)
            if flavor is None:
                reasons.append(f"flavor {f_name} not found")
                idx += 1
                continue
            if self.tas_enabled:
                msg = self._check_tas_match(pod_set, flavor)
                if msg is not None:
                    reasons.append(msg)
                    idx += 1
                    continue
            tolerations = list(pod_set.tolerations) + list(flavor.tolerations)
            if not taints_tolerated(flavor.node_taints, tolerations):
                reasons.append(f"untolerated taint in flavor {f_name}")
                idx += 1
                continue
            if not self._flavor_matches_affinity(pod_set, flavor, allowed_keys):
                reasons.append(f"flavor {f_name} doesn't match node affinity")
                idx += 1
                continue

            needs_borrowing = False
            assignments: dict[str, FlavorAssignmentDecision] = {}
            representative = _FlavorMode(GranularMode.FIT)
            for r_name in sorted(reqs):
                fr = FlavorResource(f_name, r_name)
                val = reqs[r_name] + assignment_usage.get(fr, 0)
                mode, borrow, reason = self._fits_resource_quota(fr, val)
                if reason:
                    reasons.append(reason)
                if mode == GranularMode.RECLAIM:
                    representative.questions.append((fr, val))
                    self.oracle_skips += 1
                representative.mode = min(representative.mode, mode)
                needs_borrowing = needs_borrowing or borrow
                if representative.mode == GranularMode.NO_FIT:
                    break
                assignments[r_name] = FlavorAssignmentDecision(
                    name=f_name, mode=mode.public(), borrow=borrow)

            if self.flavor_fungibility_enabled:
                if not self._should_try_next_flavor(representative.mode,
                                                    needs_borrowing):
                    best = assignments
                    best_mode = representative
                    break
                if self._beats(representative, best_mode):
                    best = assignments
                    best_mode = representative
            else:
                if self._beats(representative, best_mode):
                    best = assignments
                    best_mode = representative
                    if best_mode.mode == GranularMode.FIT:
                        return best, [], None
            idx += 1

        if self.flavor_fungibility_enabled:
            for fa in best.values():
                fa.tried_flavor_idx = (-1 if attempted_idx == len(flavor_names) - 1
                                       else attempted_idx)
            if best_mode.mode == GranularMode.FIT:
                return best, [], None
        return best, reasons, None

    def _beats(self, cand: _FlavorMode, best: _FlavorMode) -> bool:
        """``cand`` > ``best`` in the granular lattice.  Reclaim over
        Preempt is read only between two preempt-capable flavors, so
        only there are their questions put to the oracle, ``best``'s
        first as the walk met them; a policy stop, a lone
        preempt-capable flavor or one that lost to a Fit asks nothing,
        as on the device path (ops/cycle.py ``oracle_groups``)."""
        if cand.mode == GranularMode.RECLAIM and best.mode.is_preempt:
            self._settle(best)
            if best.mode == GranularMode.PREEMPT:
                self._settle(cand)
        return cand.mode > best.mode

    def _settle(self, fm: _FlavorMode) -> None:
        """Put ``fm``'s questions to the oracle in order; the first no
        makes it PREEMPT and leaves the rest unasked.  The oracle is
        pure (each search puts the snapshot back), so when it is asked
        changes no answer."""
        while fm.questions and fm.mode == GranularMode.RECLAIM:
            fr, val = fm.questions.pop(0)
            self.oracle_skips -= 1
            if not self.oracle.is_reclaim_possible(self.cq, self.wl, fr, val):
                fm.mode = GranularMode.PREEMPT

    def _should_try_next_flavor(self, mode: GranularMode,
                                needs_borrowing: bool) -> bool:
        """reference flavorassigner.go:620 shouldTryNextFlavor."""
        ff = self.cq.flavor_fungibility
        if mode.is_preempt and ff.when_can_preempt == FlavorFungibilityPolicy.PREEMPT:
            if not needs_borrowing or ff.when_can_borrow == FlavorFungibilityPolicy.BORROW:
                return False
        if mode == GranularMode.FIT and needs_borrowing \
                and ff.when_can_borrow == FlavorFungibilityPolicy.BORROW:
            return False
        if mode == GranularMode.FIT and not needs_borrowing:
            return False
        return True

    def _flavor_matches_affinity(self, pod_set: PodSet, flavor: ResourceFlavor,
                                 allowed_keys: set[str]) -> bool:
        """reference flavorSelector (flavorassigner.go:640): only selector
        keys present on flavors in the group are enforced."""
        for key, want in pod_set.node_selector.items():
            if key in allowed_keys and flavor.node_labels.get(key) != want:
                return False
        for key, values in pod_set.required_node_affinity.items():
            if key in allowed_keys and flavor.node_labels.get(key) not in values:
                return False
        return True

    def _check_tas_match(self, pod_set: PodSet,
                         flavor: ResourceFlavor) -> Optional[str]:
        """reference tas_flavorassigner.go:95
        checkPodSetAndFlavorMatchForTAS."""
        req = pod_set.topology_request
        if req is not None:
            if not flavor.topology_name:
                return (f"Flavor {flavor.name} does not support "
                        f"TopologyAwareScheduling")
            snap = self.tas_flavors.get(flavor.name)
            if snap is None:
                return (f"Flavor {flavor.name} information missing in "
                        f"TAS cache")
            for level in (req.required, req.preferred):
                if level is not None and level not in snap.levels:
                    return (f"Flavor {flavor.name} does not contain the "
                            f"requested level")
            return None
        if self._is_tas_only():
            return None   # TAS implied (unconstrained) on a TAS-only CQ
        if flavor.topology_name:
            return (f"Flavor {flavor.name} supports only "
                    f"TopologyAwareScheduling")
        return None

    def _fits_resource_quota(self, fr: FlavorResource, val: int
                             ) -> tuple[GranularMode, bool, Optional[str]]:
        """reference flavorassigner.go:692 fitsResourceQuota, with the
        reclaim oracle's question left to the walk: RECLAIM here is
        "within nominal, Reclaim if the oracle says so" (``_settle``)."""
        cq = self.cq
        borrow = cq.borrowing_with(fr, val) and cq.has_parent()
        available = cq.available(fr)
        max_capacity = cq.potential_available(fr)

        if val > max_capacity:
            return (GranularMode.NO_FIT, False,
                    f"insufficient quota for {fr.resource} in flavor {fr.flavor}, "
                    f"request > maximum capacity ({val} > {max_capacity})")
        if val <= available:
            return GranularMode.FIT, borrow, None

        quota = cq.resource_node.quotas.get(fr)
        nominal = quota.nominal if quota else 0
        mode = GranularMode.NO_FIT
        if val <= nominal:
            mode = GranularMode.RECLAIM
        elif self._can_preempt_while_borrowing():
            mode = GranularMode.PREEMPT
        return (mode, borrow,
                f"insufficient unused quota for {fr.resource} in flavor "
                f"{fr.flavor}, {val - available} more needed")

    def _can_preempt_while_borrowing(self) -> bool:
        """reference flavorassigner.go:744."""
        p = self.cq.preemption
        return (p.borrow_within_cohort.policy != BorrowWithinCohortPolicy.NEVER
                or (self.enable_fair_sharing
                    and p.reclaim_within_cohort != ReclaimWithinCohort.NEVER))

    # ------------------------------------------------------------------
    # TAS hook — reference flavorassigner.go:438-465
    # ------------------------------------------------------------------

    def _apply_tas(self, assignment: Assignment,
                   requests: list[PodSetResources]) -> None:
        if (not any(psr.topology_request is not None for psr in requests)
                and not self._is_tas_only()):
            return
        if assignment.representative_mode() == Mode.FIT:
            ok = self._find_tas(assignment, requests, simulate_empty=False)
            if not ok:
                assignment.set_representative_mode(Mode.PREEMPT)
        if assignment.representative_mode() == Mode.PREEMPT:
            if not self._find_tas(assignment, requests, simulate_empty=True,
                                  record=False):
                assignment.set_representative_mode(Mode.NO_FIT)

    def _find_tas(self, assignment: Assignment,
                  requests: list[PodSetResources],
                  simulate_empty: bool, record: bool = True) -> bool:
        """Each PodSet's placement in order (reference
        FindTopologyAssignmentsForWorkload): a later PodSet's on the
        same flavor is charged with the earlier ones'; ``simulate_empty``
        places against the nodes' allocatable, as if no workload placed
        by topology held any; ``record`` keeps the placements on the
        assignment."""
        implied = self._is_tas_only()
        assumed: dict[str, dict[tuple, dict[str, int]]] = {}
        for psr, ps_result in zip(requests, assignment.pod_sets):
            request = psr.topology_request
            if request is None:
                if not implied:
                    continue
                # TAS-only CQ: implied unconstrained placement
                # (tas_flavorassigner.go:126 isTASImplied)
                request = PodSetTopologyRequest(unconstrained=True)
            flavor_names = {fa.name for fa in ps_result.flavors.values()}
            if not flavor_names:
                continue
            f_name = sorted(flavor_names)[0]
            snap = self.tas_flavors.get(f_name)
            if snap is None:
                ps_result.reasons.append(
                    f"no topology information for flavor {f_name}")
                return False
            per_pod = ({r: v // max(1, psr.count)
                        for r, v in psr.requests.items()})
            tas_assignment, reason = snap.find_topology_assignment(
                psr.count, per_pod, request, assumed=assumed.get(f_name),
                simulate_empty=simulate_empty)
            if tas_assignment is None:
                ps_result.reasons.append(reason)
                return False
            if record:
                ps_result.topology_assignment = tas_assignment
            per_flavor = assumed.setdefault(f_name, {})
            for dom in tas_assignment.domains:
                dom_id = tuple(dom.values)
                slot = per_flavor.setdefault(dom_id, {})
                for r, v in per_pod.items():
                    slot[r] = slot.get(r, 0) + v * dom.count
        return True


# ---------------------------------------------------------------------------
# Partial admission (reference podset_reducer.go, KEP 420)
# ---------------------------------------------------------------------------

def can_be_partially_admitted(wl: Info) -> bool:
    """A PodSet of the workload may run on fewer pods than it asks
    (``min_count`` under ``count``: KEP 420)."""
    return any(ps.min_count is not None and ps.min_count < ps.count
               for ps in wl.obj.pod_sets)


def scaled_info(wl: Info, counts: list[int]) -> Info:
    """``wl`` at ``counts`` pods a PodSet: a shallow copy whose requests
    are the ones ``FlavorAssigner.assign(counts)`` walks."""
    probe = copy.copy(wl)
    probe.total_requests = [psr.scaled_to(c)
                            for psr, c in zip(wl.total_requests, counts)]
    return probe


class PodSetReducer:
    """Binary search over reduced pod counts (reference podset_reducer.go:37).

    ``search`` runs it with ``fits`` to the end; a caller that answers
    many workloads' probes together steps it instead: ``probe`` gives
    the next reduction index and its counts, ``record`` takes the
    answer, while ``searching``."""

    def __init__(self, pod_sets: list[PodSet],
                 fits: Optional[Callable[[list[int]],
                                         tuple[object, bool]]] = None):
        self.pod_sets = pod_sets
        self.fits = fits
        self.full_counts = [ps.count for ps in pod_sets]
        self.deltas = [ps.count - (ps.min_count if ps.min_count is not None else ps.count)
                       for ps in pod_sets]
        self.total_delta = sum(self.deltas)
        # the smallest index in [0, totalDelta] that fits
        self.lo, self.hi = 0, self.total_delta
        self.last_good = None
        self.last_good_idx = -1

    def _counts_for(self, up: int) -> list[int]:
        return [full - (d * up) // self.total_delta
                for full, d in zip(self.full_counts, self.deltas)]

    @property
    def searching(self) -> bool:
        return self.total_delta > 0 and self.lo <= self.hi

    def probe(self) -> tuple[int, list[int]]:
        mid = (self.lo + self.hi) // 2
        return mid, self._counts_for(mid)

    def record(self, mid: int, result, ok: bool) -> None:
        if ok:
            self.last_good, self.last_good_idx = result, mid
            self.hi = mid - 1
        else:
            self.lo = mid + 1

    def found(self) -> bool:
        return self.last_good_idx == self.lo and self.last_good is not None

    def search(self) -> tuple[object, bool]:
        """Find the largest counts that fit (smallest reduction index)."""
        while self.searching:
            mid, counts = self.probe()
            self.record(mid, *self.fits(counts))
        return self.last_good, self.found()
