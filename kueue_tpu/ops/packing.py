"""Snapshot → packed device arrays.

The deterministic codec from a cache Snapshot + cycle heads into static-
shaped integer tensors (SURVEY §7 stage 1).  Axes:

- N: quota nodes = ClusterQueues then Cohorts (parent-pointer forest)
- F: distinct (flavor, resource) pairs appearing in any quota
- W: cycle heads, padded to a bucket size (power of two) to bound
  recompilation
- S: flavor slots per resource group (max flavor-list length)
- G: resource groups a ClusterQueue (max over the queues)
- R: distinct resource names
- P: PodSets a Workload (``PackedStructure.pod_sets``: the most a head
  or a packed row has had so far, rounded up to a power of two, at most
  ``MAX_POD_SETS``)

F and G are rounded up to a power of two (``_plane_extent``): they are
the minor extent of the fused window's row planes (``adm_usage0
[C, M, F]``, ``resume0 [C, M, G]``), which the runtime lays out plane by
plane on the device and so transposes on the host at every launch, and
an extent of 3 takes it 2.1 s a plane where 2 takes 0.3 s and none at
all 0.03 s (one TPU v5e, a 1,000 x 65,536 int32 plane; PERF.md §6, PR
37).  The device tiles an extent of 3 as 4 already; a padded column
holds no quota and a padded group no flavor, and no head names either.

A resource belongs to one group of its queue, so the slot axis of
``slot_fr`` is read a resource: ``slot_fr[c, s, r]`` is flavor ``s`` of
the group that covers ``r`` (``res_group[c, r]``), and a head's
assignment is one slot a group (ops/cycle.py ``classify_np``).

The codec is split in two so the per-cycle cost is O(usage + heads), not
O(cluster):

- ``PackedStructure`` — everything derived from specs (quota tensors,
  flavor slots, the cohort forest, int32 scaling).  Rebuilt only when the
  cache's structure generation changes (a CQ/cohort/flavor apply), and
  cached by the solver across cycles.
- ``pack_cycle`` — fills the per-cycle usage [N, F] and workload
  [W, P, R] tensors (a request a PodSet, in the Workload's order)
  against a cached structure.

Quantities are canonical integers scaled per-resource so that everything
fits int32 (TPU-native); per-cycle values that don't divide the cached
scale mark the pack inexact and the solver defers to the host (int64
milli-quanta on TPU is hard part (e) in SURVEY §7).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..cache.snapshot import Snapshot
from ..cache.state import CohortState, CQState
from ..resources import FlavorResource
from ..workload import Info

INT_INF = np.int64(2**62)  # "no limit" sentinel before scaling
I32_MAX = 2**31 - 1
_LIMIT = I32_MAX // 64     # ×64 headroom for sums across the tree
# PodSets a Workload may have (upstream workload_types.go: 1 to 8); a
# head with more is the host walk's
MAX_POD_SETS = 8


@dataclass
class PackedStructure:
    """Static cluster structure: valid while the cache structure
    generation is unchanged (no CQ/cohort/flavor spec edits)."""
    generation: int
    cq_names: list[str]
    cohort_names: list[str]
    node_count: int                      # N = len(cq_names) + cohorts
    parent: np.ndarray                   # [N] int32, -1 for roots
    depth: int
    fr_index: dict[FlavorResource, int]  # (flavor, resource) -> F
    resource_names: list[str]            # R axis
    r_index: dict[str, int]
    resource_scale: np.ndarray           # [R] int64 divisor per resource
    scale_is_one: bool
    exact_static: bool                   # static tensors scaled losslessly

    subtree_quota: np.ndarray            # [N, F] int32 (scaled)
    guaranteed: np.ndarray               # [N, F] int32
    borrow_cap: np.ndarray               # [N, F] int32
    has_borrow_limit: np.ndarray         # [N, F] bool
    nominal_cq: np.ndarray               # [C, F] int32
    nominal_plus_blimit_cq: np.ndarray   # [C, F] int32 (INT "inf" when unlimited)
    slot_fr: np.ndarray                  # [C, S, R] int32 F-index or -1:
                                         # flavor s of r's own group
    res_group: np.ndarray                # [C, R] int32 group of r, -1 none
    slot_valid: np.ndarray               # [C, G, S] bool
    slot_count_cq: np.ndarray            # [C, G] int32: len(rg.flavors)
    cq_can_preempt_borrow: np.ndarray    # [C] bool
    cq_wcb_borrow: np.ndarray            # [C] bool: whenCanBorrow == Borrow
    cq_wcp_preempt: np.ndarray           # [C] bool: whenCanPreempt == Preempt
    fair_weight_milli: np.ndarray        # [N] int32
    forest_of_node: np.ndarray           # [N] int32
    n_forests: int
    cq_index: dict[str, int] = field(default_factory=dict)
    cq_covers_pods: set = field(default_factory=set)
    # P: the PodSet extent of the per-head planes (``wl_requests
    # [W, P, R]``, the fused window's ``wl_req [C, M, P*R]``).  Not the
    # specs': it follows the workloads seen, only ever grows, and a
    # change of it is a change of plane shapes (``note_pod_sets``)
    pod_sets: int = 1

    def note_pod_sets(self, n: int) -> None:
        """A head or a packed row has ``n`` PodSets: the planes hold at
        least as many from now on, up to ``MAX_POD_SETS``."""
        if n > self.pod_sets:
            self.pod_sets = _plane_extent(min(n, MAX_POD_SETS))

    @property
    def n_groups(self) -> int:
        """G: the most resource groups a ClusterQueue declares, rounded
        up to a power of two."""
        return self.slot_count_cq.shape[1]

    @property
    def n_frs(self) -> int:
        """F: the flavor-resource axis, ``len(fr_index)`` rounded up to
        a power of two."""
        return self.subtree_quota.shape[1]


@dataclass
class PackedCycle:
    """A cycle = structure + per-cycle usage and workload tensors."""
    structure: PackedStructure

    usage0: np.ndarray                   # [N, F] int32: usage at snapshot time
    wl_count: int                        # true number of heads (<= W)
    wl_cq: np.ndarray                    # [W] int32 CQ index (-1 pad)
    wl_requests: np.ndarray              # [W, P, R] int32 a PodSet's total
                                         # requests (scaled), in the
                                         # Workload's order; a head with
                                         # more PodSets than the plane
                                         # holds has their sum in the first
    wl_priority: np.ndarray              # [W] int32
    wl_timestamp: np.ndarray             # [W] float64 queue-order timestamp
    wl_keys: list[str] = field(default_factory=list)
    exact: bool = True                   # scaled comparisons are lossless
    wl_pod_sets: np.ndarray = None       # [W] int32 PodSets of the head

    # --- structure passthroughs (stable codec surface) ---
    @property
    def cq_names(self): return self.structure.cq_names
    @property
    def node_count(self): return self.structure.node_count
    @property
    def parent(self): return self.structure.parent
    @property
    def depth(self): return self.structure.depth
    @property
    def fr_index(self): return self.structure.fr_index
    @property
    def resource_names(self): return self.structure.resource_names
    @property
    def resource_scale(self): return self.structure.resource_scale
    @property
    def subtree_quota(self): return self.structure.subtree_quota
    @property
    def guaranteed(self): return self.structure.guaranteed
    @property
    def borrow_cap(self): return self.structure.borrow_cap
    @property
    def has_borrow_limit(self): return self.structure.has_borrow_limit
    @property
    def nominal_cq(self): return self.structure.nominal_cq
    @property
    def slot_fr(self): return self.structure.slot_fr
    @property
    def slot_valid(self): return self.structure.slot_valid
    @property
    def res_group(self): return self.structure.res_group
    @property
    def cq_can_preempt_borrow(self): return self.structure.cq_can_preempt_borrow
    @property
    def cq_wcb_borrow(self): return self.structure.cq_wcb_borrow
    @property
    def cq_wcp_preempt(self): return self.structure.cq_wcp_preempt
    @property
    def fair_weight_milli(self): return self.structure.fair_weight_milli
    @property
    def forest_of_node(self): return self.structure.forest_of_node
    @property
    def n_forests(self): return self.structure.n_forests


def _plane_extent(n: int) -> int:
    """The minor extent of a row plane that holds ``n`` columns: the
    next power of two (module docstring)."""
    return 1 << max(0, n - 1).bit_length()


def _bucket(n: int, minimum: int = 8) -> int:
    return max(minimum, 1 << math.ceil(math.log2(max(1, n))))


def scatter_pad(n: int, minimum: int = 8) -> int:
    """Padded row count for a dirty-row scatter onto device-resident
    state: every distinct update size is an XLA compilation, so the
    count is bucketed to powers of two and the tail padded with
    repeated last-row writes (idempotent — same index, same value)."""
    return _bucket(n, minimum=minimum)


def scaled_usage_row(st: PackedStructure, cq_live) -> Optional[np.ndarray]:
    """One CQ's live usage scaled onto the packed flavor-resource axis:
    [F] int32, or None when not exactly representable (unknown
    flavor-resource, a remainder under the scale, or int32 overflow) —
    any None fails the whole burst pack, matching the host path."""
    row = np.zeros(st.n_frs, dtype=np.int32)
    scale = st.resource_scale
    for fr, v in cq_live.resource_node.usage.items():
        fi = st.fr_index.get(fr)
        if fi is None:
            return None
        if st.scale_is_one:
            q_ = int(v)
        else:
            s = int(scale[st.r_index[fr.resource]])
            q_, rem = divmod(int(v), s)
            if rem:
                return None
        if q_ > I32_MAX:
            return None
        row[fi] = q_
    return row


def coarse_bucket(n: int, ladder: tuple[int, ...]) -> int:
    """Smallest ladder rung >= n (last rung if none).  Coarse ladders
    keep the number of DISTINCT compiled shapes small — each new shape
    is a full XLA compilation (~1s on this CPU) that would otherwise
    land inside a scheduling cycle."""
    for rung in ladder:
        if n <= rung:
            return rung
    return ladder[-1]


def _iter_nodes(snapshot: Snapshot):
    """CQs first, then cohorts (stable order)."""
    cq_names = sorted(snapshot.cluster_queues)
    cohorts: list[CohortState] = []
    seen = set()

    def walk(c: CohortState):
        if id(c) in seen:
            return
        seen.add(id(c))
        cohorts.append(c)
        for ch in c.child_cohorts:
            walk(ch)

    for root in snapshot.roots:
        walk(root)
    # cohorts reachable only via CQ parents (defensive)
    for name in cq_names:
        c = snapshot.cluster_queues[name].parent
        while c is not None and id(c) not in seen:
            walk(c)
            c = c.parent
    return cq_names, cohorts


def snapshot_fair_sharing(snapshot: Snapshot) -> bool:
    return bool(getattr(snapshot, "fair_sharing_enabled", False))


def _snapshot_nodes(snapshot: Snapshot, structure: PackedStructure):
    """Resolve the structure's node order against a fresh snapshot, or
    None if the topology changed under us (caller rebuilds)."""
    by_name: dict[str, CohortState] = {}

    def walk(c: CohortState):
        by_name[c.name] = c
        for ch in c.child_cohorts:
            walk(ch)

    for root in snapshot.roots:
        walk(root)
    nodes = []
    for name in structure.cq_names:
        cq = snapshot.cluster_queues.get(name)
        if cq is None:
            return None
        nodes.append(cq)
    for name in structure.cohort_names:
        c = by_name.get(name)
        if c is None:
            return None
        nodes.append(c)
    return nodes


def _choose_scale(max_val: int, gcd_val: int) -> tuple[int, bool]:
    """Pick a per-resource divisor so max_val/scale fits int32 with
    headroom.  Prefer a scale dividing every observed static value (exact);
    fall back to a power of two marked inexact (hard part (e))."""
    if max_val <= _LIMIT:
        return 1, True
    need = -(-max_val // _LIMIT)          # ceil
    p2 = 1
    while p2 < need:
        p2 *= 2
    cand = math.gcd(int(gcd_val), p2 * (1 << 20))  # pow2 component of gcd
    if cand >= need and max_val // cand <= _LIMIT:
        return cand, True
    if gcd_val >= need and max_val // gcd_val <= _LIMIT:
        return int(gcd_val), True
    scale = p2
    while max_val // scale > _LIMIT:
        scale *= 2
    return scale, gcd_val % scale == 0


def pack_structure(snapshot: Snapshot, heads: list[Info] = (),
                   generation: int = -1) -> PackedStructure:
    """Build the static structure tensors from a snapshot.  ``heads``
    (optional) contributes request quantities to the scale choice so a
    one-shot pack stays exact."""
    cq_names, cohorts = _iter_nodes(snapshot)
    cohort_names = [c.name for c in cohorts]
    cq_idx = {n: i for i, n in enumerate(cq_names)}
    C = len(cq_names)
    N = C + len(cohorts)

    nodes: list = [snapshot.cluster_queues[n] for n in cq_names] + cohorts

    # F axis: quota frs ∪ current usage frs
    frs: set[FlavorResource] = set()
    for node in nodes:
        frs.update(node.resource_node.quotas)
        frs.update(node.resource_node.usage)
    fr_list = sorted(frs)
    fr_index = {fr: i for i, fr in enumerate(fr_list)}
    F = _plane_extent(len(fr_list))

    cq_covers_pods = {
        name for name in cq_names
        if any("pods" in rg.covered_resources
               for rg in snapshot.cluster_queues[name].spec.resource_groups)}

    resource_names = sorted({fr.resource for fr in fr_list}
                            | {r for h in heads for psr in h.total_requests
                               for r in psr.requests}
                            | ({"pods"} if cq_covers_pods else set()))
    r_index = {r: i for i, r in enumerate(resource_names)}
    R = max(1, len(resource_names))

    # resource scaling to int32
    max_per_resource = np.zeros(R, dtype=np.int64)
    gcd_per_resource = np.zeros(R, dtype=np.int64)

    def note(r: str, v: int):
        if r in r_index and v < INT_INF:
            i = r_index[r]
            av = abs(int(v))
            max_per_resource[i] = max(max_per_resource[i], av)
            gcd_per_resource[i] = math.gcd(int(gcd_per_resource[i]), av)

    for node in nodes:
        for fr, q in node.resource_node.quotas.items():
            note(fr.resource, q.nominal)
            if q.borrowing_limit is not None:
                note(fr.resource, q.borrowing_limit)
        for fr, v in node.resource_node.subtree_quota.items():
            note(fr.resource, v)
        for fr, v in node.resource_node.usage.items():
            note(fr.resource, v)
    for h in heads:
        for psr in h.total_requests:
            for r, v in psr.requests.items():
                note(r, v)

    scale = np.ones(R, dtype=np.int64)
    exact_static = True
    for i in range(R):
        s, ok = _choose_scale(int(max_per_resource[i]),
                              int(gcd_per_resource[i]))
        scale[i] = s
        exact_static = exact_static and ok
    scale_is_one = bool((scale == 1).all())

    def scaled(r: str, v) -> int:
        if v >= INT_INF:
            return int(_LIMIT)
        s = int(scale[r_index[r]])
        return int(v) // s if v >= 0 else -((-int(v)) // s)

    # node tensors
    subtree = np.zeros((N, F), dtype=np.int32)
    guaranteed = np.zeros((N, F), dtype=np.int32)
    borrow_cap = np.full((N, F), int(_LIMIT), dtype=np.int32)
    has_blim = np.zeros((N, F), dtype=bool)
    parent = np.full(N, -1, dtype=np.int32)
    nominal_cq = np.zeros((C, F), dtype=np.int32)
    nominal_plus_blimit = np.full((C, F), int(_LIMIT), dtype=np.int32)
    fair_weight = np.full(N, 1000, dtype=np.int32)

    cohort_idx = {id(c): C + i for i, c in enumerate(cohorts)}
    for ni, node in enumerate(nodes):
        p = node.parent
        parent[ni] = cohort_idx[id(p)] if p is not None else -1
        fair_weight[ni] = getattr(node, "fair_weight_milli", 1000)
        rn = node.resource_node
        for fr, fi in fr_index.items():
            sq = rn.subtree_quota.get(fr, 0)
            subtree[ni, fi] = scaled(fr.resource, sq)
            guaranteed[ni, fi] = scaled(fr.resource, rn.guaranteed_quota(fr))
            q = rn.quotas.get(fr)
            if ni < C and q is not None:
                nominal_cq[ni, fi] = scaled(fr.resource, q.nominal)
                if q.borrowing_limit is not None:
                    nominal_plus_blimit[ni, fi] = scaled(
                        fr.resource, q.nominal + q.borrowing_limit)
            if q is not None and q.borrowing_limit is not None:
                has_blim[ni, fi] = True
                stored = sq - rn.guaranteed_quota(fr)
                borrow_cap[ni, fi] = scaled(fr.resource,
                                            stored + q.borrowing_limit)

    # depth + forest partition (each parent-pointer root is independent)
    depth = 1
    forest_of_node = np.zeros(N, dtype=np.int32)
    root_forest: dict[int, int] = {}
    for ni in range(N):
        d, p, cur = 1, parent[ni], ni
        while p >= 0:
            d += 1
            cur = p
            p = parent[p]
        depth = max(depth, d)
        forest_of_node[ni] = root_forest.setdefault(cur, len(root_forest))
    n_forests = max(1, len(root_forest))

    # flavor slots per CQ and resource group
    S = G = 1
    for name in cq_names:
        groups = snapshot.cluster_queues[name].spec.resource_groups
        G = max(G, len(groups))
        for rg in groups:
            S = max(S, len(rg.flavors))
    G = _plane_extent(G)
    slot_fr = np.full((C, S, R), -1, dtype=np.int32)
    res_group = np.full((C, R), -1, dtype=np.int32)
    slot_valid = np.zeros((C, G, S), dtype=bool)
    slot_count = np.zeros((C, G), dtype=np.int32)
    cq_can_preempt_borrow = np.zeros(C, dtype=bool)
    cq_wcb_borrow = np.zeros(C, dtype=bool)
    cq_wcp_preempt = np.zeros(C, dtype=bool)
    from ..api.types import (BorrowWithinCohortPolicy,
                             FlavorFungibilityPolicy, ReclaimWithinCohort)
    for ci, name in enumerate(cq_names):
        spec = snapshot.cluster_queues[name].spec
        p = spec.preemption
        cq_can_preempt_borrow[ci] = (
            p.borrow_within_cohort.policy != BorrowWithinCohortPolicy.NEVER
            or (snapshot_fair_sharing(snapshot)
                and p.reclaim_within_cohort != ReclaimWithinCohort.NEVER))
        ff = spec.flavor_fungibility
        cq_wcb_borrow[ci] = (
            ff.when_can_borrow == FlavorFungibilityPolicy.BORROW)
        cq_wcp_preempt[ci] = (
            ff.when_can_preempt == FlavorFungibilityPolicy.PREEMPT)
    for ci, name in enumerate(cq_names):
        cq = snapshot.cluster_queues[name]
        for gi, rg in enumerate(cq.spec.resource_groups):
            slot_count[ci, gi] = len(rg.flavors)
            for rname in rg.covered_resources:
                if rname in r_index:
                    res_group[ci, r_index[rname]] = gi
            for si, fq in enumerate(rg.flavors):
                exists = fq.name in snapshot.resource_flavors
                slot_valid[ci, gi, si] = exists
                for rname in rg.covered_resources:
                    if rname in r_index:
                        fr = FlavorResource(fq.name, rname)
                        if fr in fr_index and exists:
                            slot_fr[ci, si, r_index[rname]] = fr_index[fr]

    return PackedStructure(
        generation=generation, cq_names=cq_names, cohort_names=cohort_names,
        node_count=N, parent=parent, depth=depth, fr_index=fr_index,
        resource_names=resource_names, r_index=r_index,
        resource_scale=scale, scale_is_one=scale_is_one,
        exact_static=exact_static,
        subtree_quota=subtree, guaranteed=guaranteed, borrow_cap=borrow_cap,
        has_borrow_limit=has_blim, nominal_cq=nominal_cq,
        nominal_plus_blimit_cq=nominal_plus_blimit,
        slot_fr=slot_fr, res_group=res_group, slot_valid=slot_valid,
        slot_count_cq=slot_count,
        cq_can_preempt_borrow=cq_can_preempt_borrow,
        cq_wcb_borrow=cq_wcb_borrow, cq_wcp_preempt=cq_wcp_preempt,
        fair_weight_milli=fair_weight, forest_of_node=forest_of_node,
        n_forests=n_forests, cq_index=cq_idx, cq_covers_pods=cq_covers_pods,
    )


def pack_cycle(snapshot: Snapshot, heads: list[Info], ordering=None,
               structure: Optional[PackedStructure] = None
               ) -> Optional[PackedCycle]:
    """Fill the per-cycle tensors.  With a cached ``structure`` this is
    O(usage entries + heads); without one the structure is built fresh
    (one-shot codec, used by tests/probes).

    Returns None when the cached structure no longer describes the
    snapshot (new flavor-resource or node appeared) — the caller rebuilds
    and retries."""
    fresh = structure is None
    if fresh:
        structure = pack_structure(snapshot, heads)
    st = structure
    nodes = _snapshot_nodes(snapshot, st)
    if nodes is None:
        return None

    N, F = st.node_count, st.n_frs
    R = len(st.resource_names)
    scale = st.resource_scale
    exact = st.exact_static

    usage0 = np.zeros((N, F), dtype=np.int32)
    if st.scale_is_one:
        for ni, node in enumerate(nodes):
            for fr, v in node.resource_node.usage.items():
                fi = st.fr_index.get(fr)
                if fi is None:
                    return None
                usage0[ni, fi] = v
    else:
        for ni, node in enumerate(nodes):
            for fr, v in node.resource_node.usage.items():
                fi = st.fr_index.get(fr)
                if fi is None:
                    return None
                s = int(scale[st.r_index[fr.resource]])
                q, rem = divmod(int(v), s)
                if rem:
                    exact = False
                    q += 1  # conservative ceil
                usage0[ni, fi] = q

    W = _bucket(len(heads))
    wl_cq = np.full(W, -1, dtype=np.int32)
    for h in heads:
        st.note_pod_sets(len(h.total_requests))
    P = st.pod_sets
    wl_pod_sets = np.zeros(W, dtype=np.int32)
    # accumulate in int64: a cached structure's scale was chosen without
    # this cycle's requests, so scaled sums may exceed int32 — that marks
    # the pack inexact (host fallback) instead of wrapping
    wl_requests64 = np.zeros((W, P, R), dtype=np.int64)
    wl_priority = np.zeros(W, dtype=np.int32)
    wl_timestamp = np.zeros(W, dtype=np.float64)
    wl_keys = []
    for wi, h in enumerate(heads):
        wl_keys.append(h.key)
        wl_cq[wi] = st.cq_index.get(h.cluster_queue, -1)
        covers_pods = h.cluster_queue in st.cq_covers_pods
        wl_pod_sets[wi] = len(h.total_requests)
        # more PodSets than the planes hold: the host walk's
        # (CycleSolver._scalar_mask), summed into the first
        summed = len(h.total_requests) > P
        for pi, psr in enumerate(h.total_requests):
            pi = 0 if summed else pi
            for r, v in psr.requests.items():
                # the implicit "pods" request only participates when the
                # head's CQ covers it (flavorassigner.go:226)
                if r == "pods" and not covers_pods:
                    continue
                ri = st.r_index.get(r)
                if ri is None:
                    return None
                if st.scale_is_one:
                    wl_requests64[wi, pi, ri] += int(v)
                else:
                    s = int(scale[ri])
                    q, rem = divmod(int(v), s)
                    if rem:
                        exact = False
                        q += 1
                    wl_requests64[wi, pi, ri] += q
        wl_priority[wi] = h.obj.priority
        wl_timestamp[wi] = (ordering.queue_order_timestamp(h.obj)
                            if ordering is not None else h.obj.creation_time)
    if wl_requests64.sum(axis=1).max(initial=0) > _LIMIT:
        exact = False
        np.clip(wl_requests64, None, _LIMIT, out=wl_requests64)
    wl_requests = wl_requests64.astype(np.int32)

    return PackedCycle(
        structure=st, usage0=usage0,
        wl_count=len(heads), wl_cq=wl_cq, wl_requests=wl_requests,
        wl_pod_sets=wl_pod_sets,
        wl_priority=wl_priority, wl_timestamp=wl_timestamp, wl_keys=wl_keys,
        exact=exact,
    )


# ---------------------------------------------------------------------------
# Dtype tightening of packed planes (host→device transfer compression)
# ---------------------------------------------------------------------------

# Planes the serial burst launch may narrow below int32.  A plane's
# width is part of the fused kernel's jit signature, so it must not move
# while a cluster runs: a widened plane recompiles the whole kernel in
# the middle of a run (seconds on XLA:CPU, longer on a chip).  Two kinds
# of plane qualify:
# - GRID_PLANES hold dense ranks and row ids bounded by the packed grid
#   (< C*M): their width comes from that bound, a function of the shapes
#   the kernel is compiled for anyway;
# - the rest hold cluster structure, whose values move only with the
#   structure generation.
# Workload-valued planes (wl_req, wl_prio) are excluded for the same
# reason: their range moves with what arrives (one priority-200 wave
# takes wl_prio from int8 to int16).  So are sentinel planes (wl_rank's
# INF_I32, death0's I32_MAX), the chained scan-state 9-tuple (a chained
# window receives the previous window's device outputs, so alternating
# their dtypes would recompile every boundary) and quota planes holding
# _LIMIT-scaled sums.
TIGHTEN_PLANES = ("wl_cycle_rank", "wl_uidrank",
                  "parent", "node_level", "nominal_cq", "slot_fr",
                  "forest_of_cq", "members", "cand_rows", "cand_lmem",
                  "self_lmem")
GRID_PLANES = ("wl_cycle_rank", "wl_uidrank", "cand_rows")

_WIDTH_DT = {1: np.int8, 2: np.int16, 4: np.int32}


class TightenState:
    """Sticky per-plane narrow widths.  Widths only ever widen: a plane
    that once overflowed int16 stays int32 for the solver's lifetime,
    so the jit cache sees at most a couple of dtype signatures per
    plane instead of oscillating (every signature is a compilation)."""
    __slots__ = ("width", "widen_events")

    def __init__(self):
        self.width: dict[str, int] = {}
        self.widen_events = 0


def _range_width(lo: int, hi: int) -> int:
    if -128 <= lo and hi <= 127:
        return 1
    if -32768 <= lo and hi <= 32767:
        return 2
    return 4


def _needed_width(arr: np.ndarray) -> int:
    if arr.size == 0:
        return 1
    return _range_width(int(arr.min()), int(arr.max()))


def narrow_values(vals: np.ndarray, dtype) -> Optional[np.ndarray]:
    """``vals`` in ``dtype``, the dtype a device-resident plane was
    narrowed to when it went up, or None when one of them does not fit
    it: the caller sends the plane again, which widens; never a
    truncation."""
    dtype = np.dtype(dtype)
    if vals.dtype == dtype:
        return vals
    if dtype.kind != "i" or _needed_width(vals) > dtype.itemsize:
        return None
    return vals.astype(dtype)


def tighten_arrays(arrays: dict, state: TightenState,
                   stats: dict = None) -> dict:
    """Return a shallow copy of ``arrays`` with the TIGHTEN_PLANES
    narrowed to the smallest sticky width their values fit (range
    measured per call — the assert is the measurement; overflow never
    truncates, it widens).  The input dict is never mutated: plan
    arrays keep their reference int32 dtypes for parity checks and the
    resident scatter path."""
    out = dict(arrays)
    saved = 0
    grid = arrays.get("wl_cycle_rank")
    for name in TIGHTEN_PLANES:
        a = out.get(name)
        if a is None or a.dtype != np.int32:
            continue
        need = _needed_width(a)
        if name in GRID_PLANES and grid is not None:
            # width from the grid bound (pad cells hold -1 or 0), not
            # from this window's values; the measurement stays as the
            # guard that widens should a value ever exceed the bound
            need = max(need, _range_width(-1, grid.size - 1))
        prev = state.width.get(name)
        if prev is not None and need > prev:
            state.widen_events += 1
            if stats is not None:
                stats["pack_tighten_widened"] = (
                    stats.get("pack_tighten_widened", 0) + 1)
        width = max(need, prev or 1)
        state.width[name] = width
        if width < 4:
            out[name] = a.astype(_WIDTH_DT[width])
            saved += a.nbytes - out[name].nbytes
    if stats is not None and saved:
        stats["pack_tighten_bytes_saved"] = (
            stats.get("pack_tighten_bytes_saved", 0) + saved)
    return out
