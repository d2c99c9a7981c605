"""Host wrapper for the device cycle solver.

Per cycle the solver:

1. packs (snapshot, heads) against a CACHED ``PackedStructure`` — the
   static cluster tensors are rebuilt only when the cache structure
   generation changes, so the per-cycle cost is O(usage + heads);
2. runs the vectorized nominate (``ops.cycle.classify_np``) on the host
   for heads whose shape the batched math covers (any number of
   resource groups, one flavor walk a group; up to
   ``packing.MAX_POD_SETS`` PodSets, walked in order, each charged with
   what the earlier ones chose; flavors without topology; node labels,
   taints, selectors and tolerations ride in as each PodSet's
   eligibility masks, ``ops.eligibility``, and any fungibility policy
   and the resume state run in the vector walk); the remaining heads
   are marked SCALAR — the scheduler runs the real host FlavorAssigner
   walk for those few and attaches the resulting assignment, so TAS and
   a gang whose earlier PodSet's pick is the reclaim oracle's stay
   inside a device-decided cycle; a partial-admission head that does
   not fit at its full counts searches them on the vector path, the
   cycle's searching heads in lockstep passes (``classify_probes``,
   ``Scheduler._search_counts``);
3. dispatches the sequential admit scan (``ops.cycle.admit_scan``) as ONE
   jitted program on the solver device (``ops.device.solver_device``: the
   default JAX backend's device, so the TPU on a chip host).  The scan
   consumes per-head (flavor-resource, amount) decision pairs — the
   assignment.Usage map the reference admit loop re-checks
   (scheduler.go:372) — so HOW a head was classified (vector or scalar)
   is invisible to the kernel.

Fair-sharing cycles use ``classify`` for nominate but keep the host
admit loop (the tournament's within-cycle ordering is data-dependent on
DRS — see Scheduler._fair_sharing_iterator).  The solver falls back
entirely (returns None) for inexact int32 scaling, unrepresentable packs
(a flavor-resource or node unknown to the cached structure after one
rebuild), and scalar assignments whose usage can't be encoded exactly —
the host path then runs, keeping decisions bit-identical.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..api.types import FlavorFungibility, FlavorFungibilityPolicy
from ..cache.snapshot import Snapshot
from ..obs.trace import span as _span
from ..workload import Info, Ordering
from ..scheduler.flavorassigner import (
    Assignment,
    AssignmentClusterQueueState,
    FlavorAssignmentDecision,
    Mode,
    PodSetAssignmentResult,
    can_be_partially_admitted,
)
from ..resources import FlavorResource, Requests
from .packing import (MAX_POD_SETS, PackedCycle, PackedStructure, _bucket,
                      coarse_bucket, pack_cycle, pack_structure)
from .cycle import (admit_scan, admit_scan_forests, admit_scan_preempt,
                    classify_np, cycle_order_np, decision_pairs,
                    pick_preempt_slot_np, res_slots, slot_frs)
from .device import on_accelerator, output_devices
from .eligibility import (bind_flavor_lists, skip_mask, slots_of_mask,
                          tas_gate)

# A flat admit scan is one lax.scan step per head; the forest-parallel
# variant processes one head per cohort forest per step.  Below this head
# count the flat scan's lower per-step cost wins.
_FOREST_MIN_HEADS = 64

_DEFAULT_FF = FlavorFungibility()

# coarse shape ladders for the preempt scan's target tensors: each
# distinct (T, MT) is one XLA compilation (see packing.coarse_bucket)
T_LADDER = (64, 512, 4096)
MT_LADDER = (4, 16)


@dataclass
class ClassifiedCycle:
    """Phase-1 output: fixed per-head assignments for one cycle."""
    packed: PackedCycle
    heads: list[Info]
    snapshot: Snapshot
    borrows0: np.ndarray         # [W] bool
    preempt0: np.ndarray         # [W] bool (no fit, preempt-capable)
    preempt_borrows0: np.ndarray  # [W] bool
    preempt_res_fit: np.ndarray  # [W, P, R] bool
    preempt_stopped0: np.ndarray = None    # [W] bool: the fungibility walk
                                           # policy-stopped ON the preempt
                                           # slot (choice is final — no
                                           # reclaim-oracle dependence)
    # the walk's per-slot planes (classify_np), from which the pick
    # among several preempt-capable slots is made once the reclaim
    # oracle has answered (CycleSolver.pick_preempt_slots)
    preempt_slots: np.ndarray = None       # [W, P, G, S] bool
    slot_res_fit: np.ndarray = None        # [W, P, S, R] bool
    slot_borrows: np.ndarray = None        # [W, P, G, S] bool
    oracle_ask: np.ndarray = None          # [W, P, S, R] bool
    # one walk a (PodSet, resource group of the head's queue)
    # (classify_np): the slot each chose (-1: the head is NoFit), the
    # resume state each records, and the walks whose pick is the oracle's
    fit0: np.ndarray = None                # [W] bool: every walk fits
    slots0: np.ndarray = None              # [W, P, G] int32
    tried: np.ndarray = None               # [W, P, G] int32
    oracle_groups: np.ndarray = None       # [W, P, G] bool
    # heads the vectorized math can't classify: the scheduler runs the
    # host FlavorAssigner walk for these and attaches the assignment
    scalar_mask: np.ndarray = None         # [W] bool
    host_assignments: dict = None          # {wi: Assignment}
    host_pairs: dict = None                # {wi: [(F-index, amount)]}
    # the batched topology stage (CycleSolver.tas_stage): a Fit head's
    # placements (one TopologyAssignment or None a PodSet), the heads it
    # turned from Fit to Preempt (no flavor resource needs preemption)
    # and to NoFit, the reasons it gave a head, and whether two Fit
    # heads' placements share a node that cannot hold both
    tas_place: dict = None                 # {wi: [TopologyAssignment|None]}
    tas_demoted: np.ndarray = None         # [W] bool
    tas_nofit: np.ndarray = None           # [W] bool
    tas_reason: dict = None                # {wi: [(PodSet, message)]}
    tas_overlap: bool = False

    @property
    def n(self) -> int:
        return self.packed.wl_count


@dataclass
class PackedTargets:
    """Per-cycle preemption-target tensors for the admit scan."""
    preempt_mask: np.ndarray     # [W] bool
    tgt_mat: np.ndarray          # [W, MT] int32 universe indices, -1 pad
    tu_cq: np.ndarray            # [T] int32 node index
    tu_delta: np.ndarray         # [T, F] int32 scaled usage


@dataclass
class DeviceCycleFinal:
    """Full-cycle device decisions, in cycle order."""
    order: np.ndarray            # [n] head indices, cycle order
    admitted: np.ndarray         # [n] bool (head order)
    reserve_mask: np.ndarray     # [n] bool (head order)
    preempting: np.ndarray = None    # [n] bool: issued preemptions
    overlap_skip: np.ndarray = None  # [n] bool: overlapping targets


@dataclass
class DispatchHandle:
    """An in-flight admit scan: the dispatch has been issued (or decided
    unnecessary) and the host is free to do per-head work while the device
    executes; ``CycleSolver.fetch`` blocks for the decisions."""
    order: np.ndarray
    rmask: np.ndarray            # [W] bool
    n: int
    pending: object = None       # jax array(s) still on device, or None
    admitted: Optional[np.ndarray] = None  # resolved decisions [W]
    preempting: Optional[np.ndarray] = None
    overlap_skip: Optional[np.ndarray] = None
    fit_mask: Optional[np.ndarray] = None  # [W] bool: vector + scalar fits
    # "accel" | "cpu" | "sharded" | "no_fit" | "singleton"
    route: str = ""


class CycleSolver:
    """Batched solver for the admission cycle.

    Every jitted scan runs on the solver device (ops.device), or over
    the mesh when one is set: there is no other engine to route to."""

    def __init__(self, ordering: Ordering | None = None):
        from ..compilecache import enable as _enable_compile_cache
        _enable_compile_cache()
        self.ordering = ordering or Ordering()
        # Disjoint cycle counters: every cycle with heads lands in exactly
        # one of full/classify/host (bench derives shares from these).
        self.stats = {
            "full_cycles": 0,         # fully device-decided cycles
            "fs_full_cycles": 0,      # fair-sharing cycles decided in-scan
            "fs_window_cycles": 0,    # fair-sharing cycles the fused
                                      # window decided and the driver
                                      # applied (controller/driver.py)
            "fs_host_cycles": 0,      # fair-sharing cycles ordered by
                                      # the host tournament
            "fs_tracker_unavailable_cycles": 0,  # of them, the ones
                                      # whose DRS took the scalar path
                                      # (Scheduler.fs_stats)
            "fs_drs_entries": 0,      # heads whose DRS a host tournament
                                      # round computed, summed over rounds
                                      # (a tree's first round: its
                                      # remaining; a later one: those
                                      # crossing a node the last pick
                                      # changed)
            "fs_picks_reused": 0,     # cohort picks a host tournament
                                      # round read from the tree's kept
                                      # answers instead of recomputing
            "fs_noop_skips": 0,       # FS cycles with no fit head: the
                                      # tournament dispatch was skipped
            "fs_noop_reuses": 0,      # no-op FS cycles whose per-head
                                      # walks were fingerprint-reused
            "classify_cycles": 0,     # device nominate + host admit loop
            "host_cycles": 0,         # pure host fallback (classify=None)
            "reserve_entries": 0,
            # where each full cycle's admit scan ran (also disjoint):
            "accel_dispatches": 0,    # jitted scan, accelerator platform
            "cpu_dispatches": 0,      # jitted scan, XLA:CPU platform
            "output_devices": 0,      # most devices one scan's output
                                      # was spread over (mesh: > 1)
            "skipped_dispatches": 0,  # no fit head -> scan provably no-op
            "singleton_dispatches": 0,  # <=1 entry/forest -> no contention
            "structure_rebuilds": 0,
            "snapshot_cqs_recloned": 0,  # queues the cycles' cache
                                         # snapshots cloned again (the
                                         # scheduler counts them)
            "collector_deferred_allocations": 0,  # the young generation's
                                         # count where the driver's
                                         # scheduling sections closed,
                                         # summed (controller/driver.py)
            "scalar_heads": 0,        # heads classified by the host walk
            # flavor-walk telemetry (heterogeneous fast path):
            "scalar_reasons": {},     # {reason: count} for scalar heads
            "resume_heads": 0,        # heads (and count-search probes)
                                      # entering the walk mid-list
            "walk_stop_heads": 0,     # heads whose walk policy-stopped
            "walk_heads": 0,          # heads classified by the vector walk
            "walk_slots": 0,          # flavors their walks visited
            # one walk a resource group (ops/cycle.py walk_groups):
            "group_walks": 0,         # (head, group) walks the vector
                                      # classify did
            "split_mode_heads": 0,    # heads whose groups ended in
                                      # different modes: the join, not
                                      # one walk, set the head's mode
            "cq_shape_heads": 0,      # scalar_reasons["cq_shape"], flat
            # one pass a PodSet (walk_groups):
            "podset_walks": 0,        # (head, PodSet) passes the vector
                                      # classify did; = walk_heads where
                                      # every head has one PodSet
            "gang_heads": 0,          # vector heads of several PodSets
            "charged_walks": 0,       # passes that met, on a slot they
                                      # visited, what an earlier PodSet
                                      # of the head had chosen
            "split_flavor_gangs": 0,  # heads whose PodSets chose
                                      # different flavors in one group
            "podset_scalar_heads": 0,  # scalar_reasons["multi_podset"]
                                       # and ["podset_oracle_order"], flat
            # per-workload flavor eligibility (ops/eligibility.py):
            "walk_ineligible_slots": 0,   # of them, skipped for a taint
                                          # or a selector
            "constrained_heads": 0,   # vector heads that may not take
                                      # every flavor of their queue
            "eligibility_masks_built": 0,  # signatures evaluated, as
                                           # against read from a cache
            # the batched topology stage (tas_stage, ops/tas_kernel.py):
            "tas_heads": 0,           # vector heads it placed or tried
            "tas_queries": 0,         # PodSet queries, against the free
                                      # nodes and against the empty tree
            "tas_unique_queries": 0,  # of them, the distinct ones a
                                      # launch evaluated
            "tas_launches": 0,        # place_queries launches
            "tas_host_queries": 0,    # find_topology_assignment calls
                                      # of the cycles' host walks
            "tas_topology_nofit": 0,  # queries no domain held
            "tas_preferred_climbs": 0,  # preferred queries placed above
                                        # their level
            "tas_refit_skips": 0,     # heads the admit loop skipped: an
                                      # earlier admission of the cycle
                                      # filled a node of their placement
            "tas_host_admit_cycles": 0,  # cycles whose Fit placements
                                         # shared a node: the host admit
                                         # loop decided them in order
            # partial admission's count search on the vector path
            # (Scheduler._search_counts, podset_reducer.go):
            "partial_heads": 0,       # heads whose search it ran
            "partial_passes": 0,      # its lockstep passes
            "partial_probes": 0,      # (head, probe) pairs they walked
            "partial_admitted": 0,    # admissions at counts below full
        }
        self._structure: Optional[PackedStructure] = None
        self._potential0 = None
        # optional jax.sharding.Mesh: when set, admit scans dispatch as
        # mesh-sharded programs (parallel/sharded.py admit_scan_fns)
        self.mesh = None
        self._sharded_fns: dict = {}

    # -- device --------------------------------------------------------

    def _count_dispatch(self, pending) -> str:
        """Count one jitted scan by where its output lives."""
        devs = output_devices(pending)
        route = "accel" if on_accelerator(devs) else "cpu"
        self.stats[f"{route}_dispatches"] += 1
        self.stats["output_devices"] = max(self.stats["output_devices"],
                                           len(devs))
        return route

    def set_mesh(self, mesh) -> None:
        """Route production admit scans through mesh-sharded programs
        (verdict r3 item 5: the sharded cycle is the production path,
        not a dryrun-only artifact)."""
        self.mesh = mesh
        self._sharded_fns = {}
        self.stats.setdefault("sharded_dispatches", 0)
        self.stats.setdefault("sharded_preempt_dispatches", 0)
        self.stats.setdefault("sharded_fs_dispatches", 0)

    def _sharded_for(self, depth: int):
        fns = self._sharded_fns.get(depth)
        if fns is None:
            from ..parallel.sharded import admit_scan_fns
            fns = admit_scan_fns(self.mesh, depth)
            self._sharded_fns[depth] = fns
        return fns

    @staticmethod
    def _pad_rows(a, n_new, fill):
        a = np.asarray(a)
        if a.shape[0] == n_new:
            return a
        pad = np.full((n_new - a.shape[0],) + a.shape[1:], fill,
                      dtype=a.dtype)
        return np.concatenate([a, pad], axis=0)

    def _mesh_pad(self, args, order, st, pmask=None, pre_fr=None,
                  pre_amt=None, tgt_mat=None, forest_of_node=None):
        """Pad the sharded axes to mesh-divisible sizes.

        GSPMD requires dim0 of a tensor sharded over an axis to divide
        the axis size; real clusters rarely oblige (e.g. 35 quota nodes
        on a cq=2 mesh).  Padded nodes are inert (zero quota, parent -1,
        never referenced by a head); padded heads are invalid
        (wl_cq=-1, all masks false) and fetch slices decisions to the
        real head count.  The structure-static tensors (args[1..7] and
        forest_of_node) are padded once per (structure, mesh) and
        cached on the structure; only the per-cycle tensors pay the
        concatenate each dispatch."""
        mesh_cq = self.mesh.shape["cq"]
        mesh_wl = self.mesh.shape["wl"]

        def up(n, m):
            return -(-n // m) * m

        N = args[0].shape[0]
        C = args[6].shape[0]
        W = args[8].shape[0]
        Np, Cp, Wp = up(N, mesh_cq), up(C, mesh_cq), up(W, mesh_wl)
        if (Np, Cp, Wp) == (N, C, W):
            return (args, order, pmask, pre_fr, pre_amt, tgt_mat,
                    forest_of_node)

        rows = self._pad_rows
        key = (mesh_wl, mesh_cq)
        cached = getattr(st, "_mesh_pad_statics", None)
        if cached is None or cached[0] != key:
            statics = (
                rows(args[1], Np, 0), rows(args[2], Np, 0),
                rows(args[3], Np, 0), rows(args[4], Np, False),
                rows(args[5], Np, -1),
                rows(args[6], Cp, 0), rows(args[7], Cp, 0),
                rows(st.forest_of_node, Np, 0))
            st._mesh_pad_statics = cached = (key, statics)
        statics = cached[1]
        args = (
            (rows(args[0], Np, 0),) + statics[:7]
            + (rows(args[8], Wp, -1), rows(args[9], Wp, -1),
               rows(args[10], Wp, 0), rows(args[11], Wp, False),
               rows(args[12], Wp, -1), rows(args[13], Wp, 0),
               rows(args[14], Wp, False), rows(args[15], Wp, False)))
        order = np.concatenate(
            [np.asarray(order),
             np.arange(W, Wp, dtype=np.asarray(order).dtype)])
        if pmask is not None:
            pmask = rows(pmask, Wp, False)
        if pre_fr is not None:
            pre_fr = rows(pre_fr, Wp, -1)
        if pre_amt is not None:
            pre_amt = rows(pre_amt, Wp, 0)
        if tgt_mat is not None:
            tgt_mat = rows(tgt_mat, Wp, -1)
        if forest_of_node is not None:
            forest_of_node = statics[7]
        return args, order, pmask, pre_fr, pre_amt, tgt_mat, forest_of_node

    def _scan(self, st: PackedStructure, args: tuple, order,
              mfw: Optional[int] = None, preempt: Optional[tuple] = None):
        """Issue one admit scan (async) and return its pending output.

        The single launch site for warmup() and dispatch(): sharded over
        the mesh when one is set (the sharded programs take precedence
        over everything else), otherwise one jitted program on the
        solver device.  ``args`` are the 16 scan tensors before
        ``order``; ``mfw`` selects the forest-parallel scan; ``preempt``
        = (pmask, pre_fr, pre_amt, tgt_mat, tu_cq, tu_delta) selects the
        preemption-aware scan."""
        if self.mesh is None:
            if preempt is not None:
                return admit_scan_preempt(*args, *preempt, order,
                                          depth=st.depth)
            if mfw is not None:
                return admit_scan_forests(
                    *args, order, st.forest_of_node, depth=st.depth,
                    n_forests=st.n_forests, max_forest_wl=mfw)
            return admit_scan(*args, order, depth=st.depth)
        fns = self._sharded_for(st.depth)
        if preempt is not None:
            pmask, pre_fr, pre_amt, tgt_mat, tu_cq, tu_delta = preempt
            pargs, porder, pmask, pre_fr, pre_amt, tgt_mat, _ = (
                self._mesh_pad(args, order, st, pmask=pmask,
                               pre_fr=pre_fr, pre_amt=pre_amt,
                               tgt_mat=tgt_mat))
            return fns["preempt"](*pargs, pmask, pre_fr, pre_amt,
                                  tgt_mat, tu_cq, tu_delta, porder)
        if mfw is not None:
            pargs, porder, _, _, _, _, pforest = self._mesh_pad(
                args, order, st, forest_of_node=st.forest_of_node)
            return fns["forest"](
                *pargs, porder, forest_of_node=pforest,
                n_forests=st.n_forests, max_forest_wl=mfw)
        pargs, porder, _, _, _, _, _ = self._mesh_pad(args, order, st)
        return fns["flat"](*pargs, porder)

    def warmup(self, snapshot: Snapshot, max_heads: int,
               pod_sets: int = 1) -> None:
        """One-time setup outside the hot loop: compile every admit-scan
        and preemption-search shape a run of ``max_heads`` heads of up
        to ``pod_sets`` PodSets can reach, through the same launch site
        dispatch() uses (_scan), so the programs are built for the
        solver device — or the mesh — and for nothing else.  Shapes
        only — no scheduling state is touched."""
        import jax
        st = self._structure_for(snapshot, [])
        st.note_pod_sets(pod_sets)
        N, F = st.subtree_quota.shape
        C, S, R = st.slot_fr.shape
        # the scans' decision pairs are as wide as the population's
        # PodSets make them (_build_pair_tensors)
        K = self._pair_width(st)
        W = 8
        buckets = []
        while True:
            buckets.append(W)
            if W >= _bucket(max_heads):
                break
            W *= 2
        for W in buckets:
            args = (
                np.zeros((N, F), np.int32), st.subtree_quota, st.guaranteed,
                st.borrow_cap, st.has_borrow_limit, st.parent,
                st.nominal_cq, st.nominal_plus_blimit_cq,
                np.full(W, -1, np.int32),
                np.full((W, K), -1, np.int32), np.zeros((W, K), np.int32),
                np.zeros(W, bool),
                np.full((W, K), -1, np.int32), np.zeros((W, K), np.int32),
                np.zeros(W, bool), np.zeros(W, bool))
            order = np.arange(W, dtype=np.int32)
            # forest scan lengths for this bucket: 4 .. bucket(max CQs
            # per forest); [None] = the flat scan (forest decomposition
            # doesn't apply)
            mfw_ladder = [None]
            if self._forests_apply(W, st.n_forests):
                per_forest = np.bincount(
                    st.forest_of_node[:len(st.cq_names)],
                    minlength=st.n_forests)
                top = _bucket(int(per_forest.max()), minimum=4)
                mfw_ladder, mfw = [], 4
                while True:
                    mfw_ladder.append(mfw)
                    if mfw >= top:
                        break
                    mfw *= 2
            for mfw in mfw_ladder:
                jax.device_get(self._scan(st, args, order, mfw=mfw))

            # first padded-K bucket (scalar heads with more decision
            # pairs than the vector heads', _build_pair_tensors):
            # compile so such a head can't stall a cycle on compilation
            Kpad = self._pair_width(st, K + 1)
            kargs = (args[:9]
                     + (np.full((W, Kpad), -1, np.int32),
                        np.zeros((W, Kpad), np.int32), args[11],
                        np.full((W, Kpad), -1, np.int32),
                        np.zeros((W, Kpad), np.int32))
                     + args[14:])
            jax.device_get(self._scan(st, kargs, order))

            # every (T, MT) rung that can appear at this head count (an
            # in-scan preemption universe is at most a few targets per
            # head x heads)
            t_top = coarse_bucket(4 * W, T_LADDER)
            for T in [t for t in T_LADDER if t <= t_top]:
                mts = MT_LADDER if T == T_LADDER[0] else MT_LADDER[:1]
                for MT in mts:
                    pre = (np.zeros(W, bool),
                           np.full((W, K), -1, np.int32),
                           np.zeros((W, K), np.int32),
                           np.full((W, MT), -1, np.int32),
                           np.zeros(T, np.int32),
                           np.zeros((T, F), np.int32))
                    jax.device_get(self._scan(st, args, order,
                                              preempt=pre))

        # batched preemption search: compile the (S, K) rungs a run of
        # this size can hit (a launch holds <= 2 specs per head, or with
        # several flavor slots the reclaim oracle's <= 2 a head, slot
        # and resource; K rungs beyond 128 are rare enough to compile
        # on first use)
        from .preemption_kernel import minimal_preemptions_batch
        from .preemption_solver import _ForestPlanes, K_LADDER, S_LADDER
        try:
            planes = _ForestPlanes(st)
        except ValueError:
            planes = None
        if planes is not None:
            st._preempt_planes = planes
            NL = planes.NL
            specs = 2 * max_heads * (S * R if S > 1 else 1)
            s_top = coarse_bucket(min(specs, S_LADDER[-1]), S_LADDER)
            for S in [s for s in S_LADDER if s <= s_top]:
                for K in K_LADDER[:2]:
                    jax.device_get(minimal_preemptions_batch(
                        np.zeros((S, NL, F), np.int32),
                        np.zeros((S, NL, F), np.int32),
                        np.zeros((S, NL, F), np.int32),
                        np.full((S, NL, F), 2**30, np.int32),
                        np.zeros((S, NL, F), bool),
                        np.full((S, NL), -1, np.int32),
                        np.full(S, -1, np.int32),
                        np.zeros((S, F), np.int32),
                        np.zeros((S, F), bool),
                        np.full((S, K), -1, np.int32),
                        np.zeros((S, K, F), np.int32),
                        np.zeros((S, K), bool), np.zeros((S, K), bool),
                        np.zeros(S, bool), np.zeros(S, bool),
                        depth=st.depth))

    # -- structure cache -----------------------------------------------

    def _structure_for(self, snapshot: Snapshot,
                       heads: list[Info]) -> PackedStructure:
        gen = getattr(snapshot, "structure_generation", -1)
        st = self._structure
        if (st is None or st.generation != gen or gen < 0
                or getattr(st, "tas_gate", None) != tas_gate()):
            st = pack_structure(snapshot, heads, generation=gen)
            bind_flavor_lists(snapshot, st)
            self._structure = st
            self._potential0 = None
            self.stats["structure_rebuilds"] += 1
        return st

    # -- eligibility ---------------------------------------------------

    def _scalar_mask(self, snapshot: Snapshot, heads: list[Info],
                     st: PackedStructure) -> np.ndarray:
        """Per-head: True → the head needs the scalar host walk (the
        vectorized classify's assumptions don't hold).  A mid-list
        fungibility resume state is NOT a scalar reason anymore: it
        becomes the head's vector start slot (``resume_start``)."""
        mask = np.zeros(len(heads), dtype=bool)
        cq_ok = st.cq_vector_ok
        reasons = self.stats["scalar_reasons"]
        for wi, h in enumerate(heads):
            ci = st.cq_index.get(h.cluster_queue, -1)
            if ci < 0 or not cq_ok[ci]:
                mask[wi] = True
                reasons["cq_shape"] = reasons.get("cq_shape", 0) + 1
                self.stats["cq_shape_heads"] += 1
                continue
            if not 1 <= len(h.obj.pod_sets) <= MAX_POD_SETS:
                # more PodSets than a plane holds (upstream allows 1 to
                # 8): the vector walk passes over PodSets in order,
                # each charged with the earlier ones' choices, up to
                # that many and no further
                mask[wi] = True
                reasons["multi_podset"] = reasons.get("multi_podset", 0) + 1
                self.stats["podset_scalar_heads"] += 1
        return mask

    def _start_slots(self, snapshot: Snapshot, heads: list[Info],
                     st: PackedStructure) -> np.ndarray:
        """Per-head, per-PodSet, per-group flavor-walk start slot from
        the fungibility resume state (flavorassigner.go:359-366): a
        PodSet whose last attempt stopped mid-list in a group resumes
        that group at last_tried_flavor_idx + 1, unless the CQ's quota
        changed since (allocatable_generation moved on)."""
        G, P = st.n_groups, st.pod_sets
        start = np.zeros((len(heads), P, G), dtype=np.int32)
        for wi, h in enumerate(heads):
            s = resume_starts(h, snapshot.cq(h.cluster_queue),
                              h.cluster_queue in st.cq_covers_pods, G, P)
            if any(s):
                start[wi] = np.reshape(s, (P, G))
                self.stats["resume_heads"] += 1
        return start

    def _eligible_slots(self, heads: list[Info], st: PackedStructure,
                        W: int) -> np.ndarray:
        """The cycle's [W, P, G, S] eligibility plane: False where a
        head's PodSet may not take the flavor of the group
        (ops/eligibility.py).  Rows of pads and of queues the vector
        walk does not decide are True."""
        skip = np.zeros((W, st.pod_sets * st.n_groups), dtype=np.int32)
        if st.masks_needed:
            cq_index = st.cq_index
            for wi, h in enumerate(heads):
                ci = cq_index.get(h.cluster_queue, -1)
                if ci >= 0:
                    skip[wi] = skip_mask(h, st, ci, self.stats)
        return slots_of_mask(
            skip.reshape(W, st.pod_sets, st.n_groups), st.slot_fr.shape[1])

    # -- phase 1 -------------------------------------------------------

    def _walk(self, snapshot: Snapshot, heads: list[Info],
              packed: PackedCycle):
        """The vector flavor walks of ``heads`` (classify_np) on the
        snapshot's usage: (walk outputs, eligibility plane)."""
        st = packed.structure
        start = self._start_slots(snapshot, heads, st)
        if self._potential0 is None or self._potential0.shape != packed.usage0.shape:
            from .cycle import available_all_np
            self._potential0 = available_all_np(
                np.zeros_like(packed.usage0), st.subtree_quota, st.guaranteed,
                st.borrow_cap, st.has_borrow_limit, st.parent, st.depth)
        W = packed.wl_cq.shape[0]
        start_pad = np.zeros((W,) + start.shape[1:], dtype=np.int32)
        start_pad[:len(heads)] = start
        with _span("cycle.nominate.classify.eligibility"):
            eligible = self._eligible_slots(heads, st, W)
        return classify_np(packed, potential0=self._potential0,
                           start_slot=start_pad, eligible=eligible), eligible

    @staticmethod
    def _oracle_chained(out: dict, n: int) -> np.ndarray:
        """Heads (of the first ``n``) whose earlier PodSet's pick is the
        reclaim oracle's, in a group a later PodSet walks too: what the
        later walk is charged with waits for the oracle's answer, so the
        host walk, which asks as it goes, decides the head."""
        later = np.flip(np.cumsum(np.flip(out["walked"], 1), axis=1), 1)
        return (out["oracle_groups"]
                & (later > out["walked"])).any(axis=(1, 2))[:n]

    @staticmethod
    def _classified(packed: PackedCycle, heads: list[Info],
                    snapshot: Snapshot, out: dict,
                    scalar: np.ndarray) -> ClassifiedCycle:
        """The cycle as the walks left it, with the rows of the
        ``scalar`` heads cleared: their decisions come from the host
        assignments attached to them instead."""
        W = packed.wl_cq.shape[0]
        sm = np.zeros(W, dtype=bool)
        sm[:len(scalar)] = scalar
        if sm.any():
            out = dict(out)
            out["fit0"] = out["fit0"] & ~sm
            out["slots0"] = np.where(sm[:, None, None], -1, out["slots0"])
            out["oracle_groups"] = (out["oracle_groups"]
                                    & ~sm[:, None, None])
            out["borrows0"] = out["borrows0"] & ~sm
            out["preempt0"] = out["preempt0"] & ~sm
            out["preempt_borrows0"] = out["preempt_borrows0"] & ~sm
            out["preempt_stopped0"] = out["preempt_stopped0"] & ~sm
        return ClassifiedCycle(
            packed=packed, heads=heads, snapshot=snapshot,
            borrows0=out["borrows0"],
            preempt0=out["preempt0"],
            preempt_borrows0=out["preempt_borrows0"],
            preempt_res_fit=out["preempt_res_fit"],
            preempt_stopped0=out["preempt_stopped0"],
            preempt_slots=out.get("preempt_slots"),
            slot_res_fit=out.get("slot_res_fit"),
            slot_borrows=out.get("slot_borrows"),
            oracle_ask=out.get("oracle_ask"),
            fit0=out["fit0"], slots0=out["slots0"], tried=out["tried"],
            oracle_groups=out["oracle_groups"],
            scalar_mask=sm, host_assignments={}, host_pairs={})

    def _note_scalar(self, mask: np.ndarray, reason: str) -> int:
        n = int(mask.sum())
        if n:
            reasons = self.stats["scalar_reasons"]
            reasons[reason] = reasons.get(reason, 0) + n
        return n

    def classify(self, snapshot: Snapshot, heads: list[Info],
                 elastic_on_host: bool = False
                 ) -> Optional[ClassifiedCycle]:
        """Pack + vectorized nominate.  None → run the host path.

        Heads the vector math can't cover are flagged in ``scalar_mask``
        (their vector rows are cleared); the scheduler host-walks those
        and attaches the assignments via ``attach_host_assignment``.
        A partial-admission head that does not fit at its full counts
        is left to the scheduler's count search on the vector path
        (``classify_probes``), but for one on a queue with a TAS flavor,
        and every one where ``elastic_on_host`` (fair sharing): the
        host walk's PodSetReducer decides those."""
        if not heads:
            return None
        st = self._structure_for(snapshot, heads)
        packed = pack_cycle(snapshot, heads, self.ordering, structure=st)
        if packed is None:
            # topology drifted under an unchanged generation (defensive):
            # rebuild once and retry
            self._structure = None
            st = self._structure_for(snapshot, heads)
            packed = pack_cycle(snapshot, heads, self.ordering, structure=st)
            if packed is None:
                return None
        if not packed.exact:
            # lossy int32 scaling could deny fits the host grants
            return None
        scalar = self._scalar_mask(snapshot, heads, st)
        out, eligible = self._walk(snapshot, heads, packed)
        n = packed.wl_count
        unfit = ~scalar & ~out["fit0"][:n]
        if unfit.any() and (elastic_on_host or st.cq_tas.any()):
            elastic = unfit & np.array(
                [can_be_partially_admitted(h) for h in heads])
            if not elastic_on_host:
                elastic &= st.cq_tas[np.maximum(packed.wl_cq[:n], 0)]
            self._note_scalar(elastic, "partial_fair_sharing"
                              if elastic_on_host else "partial_tas")
            scalar |= elastic
        chained = self._oracle_chained(out, n) & ~scalar
        self.stats["podset_scalar_heads"] += self._note_scalar(
            chained, "podset_oracle_order")
        scalar |= chained
        self.stats["scalar_heads"] += int(scalar.sum())
        self.stats["walk_stop_heads"] += int(
            np.count_nonzero(out["preempt_stopped0"][:n] & ~scalar))
        self.stats["walk_heads"] += int(n - scalar.sum())
        self.stats["walk_slots"] += int(out["walk_slots"][:n][~scalar].sum())
        self.stats["walk_ineligible_slots"] += int(
            out["walk_ineligible"][:n][~scalar].sum())
        self.stats["group_walks"] += int(
            out["group_walks"][:n][~scalar].sum())
        self.stats["split_mode_heads"] += int(np.count_nonzero(
            out["split_mode"][:n] & ~scalar))
        self.stats["podset_walks"] += int(
            out["podset_walks"][:n][~scalar].sum())
        self.stats["gang_heads"] += int(np.count_nonzero(
            (packed.wl_pod_sets[:n] > 1) & ~scalar))
        self.stats["charged_walks"] += int(
            out["charged_walks"][:n][~scalar].sum())
        self.stats["split_flavor_gangs"] += int(np.count_nonzero(
            out["split_flavor"][:n] & ~scalar))
        self.stats["constrained_heads"] += int(np.count_nonzero(
            (st.slot_valid[np.maximum(packed.wl_cq[:n], 0)][:, None]
             & ~eligible[:n]).any(axis=(1, 2, 3)) & ~scalar))
        return self._classified(packed, heads, snapshot, out, scalar)

    def classify_probes(self, cls: ClassifiedCycle,
                        probes: list[Info]) -> Optional[ClassifiedCycle]:
        """The vector walks of one pass of the count search: ``probes``
        are heads of ``cls`` at reduced counts (``probe_info``), walked
        on ``cls``'s snapshot and structure.  None when their requests
        do not pack exactly; a probe whose gang walk waits on the
        reclaim oracle (``_oracle_chained``) is flagged in
        ``scalar_mask``, for the host walk to search its head."""
        packed = pack_cycle(cls.snapshot, probes, self.ordering,
                            structure=cls.packed.structure)
        if packed is None or not packed.exact:
            return None
        out, _ = self._walk(cls.snapshot, probes, packed)
        return self._classified(packed, probes, cls.snapshot, out,
                                self._oracle_chained(out, len(probes)))

    # -- the reclaim oracle's part of the walk --------------------------

    def oracle_queries(self, cls: ClassifiedCycle, wi: int) -> list[tuple]:
        """What the host walk would ask the reclaim oracle for head
        ``wi``: one (PodSet, slot, resource index, FlavorResource,
        quantity) a resource short of quota on each attempted
        preempt-capable slot of each walk whose pick is the oracle's
        (flavorassigner.go:692); the slot is one of the resource's own
        group, and the quantity the walk's ``val``: the PodSet's request
        and what the head's earlier PodSets chose on that
        flavor-resource."""
        st = cls.packed.structure
        h = cls.heads[wi]
        cq = cls.snapshot.cq(h.cluster_queue)
        groups = cq.spec.resource_groups
        grp = st.res_group[st.cq_index[h.cluster_queue]]
        slots = cls.slots0[wi]

        def asks(psr, res):
            return psr.count if res == "pods" else psr.requests.get(res, 0)

        out = []
        for p, s, ri in zip(*np.nonzero(cls.oracle_ask[wi])):
            g = int(grp[ri])
            if not cls.oracle_groups[wi, p, g]:
                continue
            res = st.resource_names[ri]
            qty = asks(h.total_requests[p], res) + sum(
                asks(h.total_requests[q], res) for q in range(p)
                if slots[q, g] == s)
            out.append((int(p), int(s), int(ri),
                        FlavorResource(groups[g].flavors[s].name, res), qty))
        return out

    def pick_preempt_slots(self, cls: ClassifiedCycle, heads: np.ndarray,
                           reclaim: np.ndarray) -> None:
        """Fix, for ``heads``, the preempt slot of each walk that met
        several preempt-capable slots and no stop (``oracle_groups``),
        from the oracle's answers ``reclaim`` [len(heads), P, S, R]: the
        first slot of the walk's best granular mode, and with it the
        head's borrow and per-pair facts that the target search and the
        admit scan read.  No later PodSet walks such a group
        (``classify``), so every other walk of the head stands."""
        st = cls.packed.structure
        grp = st.res_group[np.maximum(cls.packed.wl_cq[heads], 0)]
        for p, g in zip(*np.nonzero(cls.oracle_groups[heads].any(axis=0))):
            on = cls.oracle_groups[heads, p, g]
            slot = pick_preempt_slot_np(
                cls.preempt_slots[heads, p, g], cls.slot_res_fit[heads, p],
                reclaim[:, p], grp == g)
            cls.slots0[heads[on], p, g] = slot[on]
        slots = cls.slots0[heads]                           # [n, P, G]
        cls.preempt_borrows0[heads] = np.take_along_axis(
            cls.slot_borrows[heads], np.maximum(slots, 0)[..., None],
            axis=3).any(axis=(1, 2, 3))
        cls.preempt_res_fit[heads] = np.take_along_axis(
            cls.slot_res_fit[heads], res_slots(grp, slots)[:, :, None, :],
            axis=2)[:, :, 0, :]

    # -- the batched topology stage ------------------------------------

    @staticmethod
    def warm_topology(snapshot: Snapshot, pod_sets: list) -> int:
        """One-time setup for the topology stage: compile every launch
        the placement questions of ``pod_sets`` (the distinct PodSets
        the queues hold: count, requests, topology request; None asks
        unconstrained) can make on each topology flavor, against the
        free nodes and the empty tree (``tas_kernel.warm_queries``).
        Returns the launches built."""
        from ..api.types import PodSetTopologyRequest
        from .tas_kernel import warm_queries
        built = 0
        for snap in snapshot.tas_flavors.values():
            if not snap.layout.leaf_ids:
                continue
            queries = []
            for ps in pod_sets:
                req = (ps.topology_request
                       or PodSetTopologyRequest(unconstrained=True))
                for empty in (False, True):
                    q = snap.question(ps.count, ps.requests, req, empty)
                    if q is not None:
                        queries.append(q)
            built += warm_queries(snap.layout, queries,
                                  snap._device_profile(False),
                                  snap._device_profile(True))
        return built

    def tas_active(self, cls: ClassifiedCycle) -> bool:
        """Does this cycle have vector heads on a queue with a TAS
        flavor for the topology stage to place?"""
        st = cls.packed.structure
        if not st.cq_tas.any():
            return False
        n = cls.n
        on = (cls.fit0[:n] | cls.preempt0[:n]) & ~cls.scalar_mask[:n]
        return bool(on.any() and st.cq_tas[
            np.maximum(cls.packed.wl_cq[:n], 0)][on].any())

    def _tas_requests(self, cls: ClassifiedCycle, wi: int) -> list:
        """Head ``wi``'s placement questions, one a PodSet (None where
        the PodSet asks none): (flavor, request, pods, per-pod requests),
        the flavor the smallest name among those its walk chose, as
        ``FlavorAssigner._find_tas`` asks them."""
        from ..api.types import PodSetTopologyRequest
        st = cls.packed.structure
        h = cls.heads[wi]
        ci = int(cls.packed.wl_cq[wi])
        cq = cls.snapshot.cq(h.cluster_queue)
        groups = cq.spec.resource_groups
        grp = st.res_group[ci]
        covers_pods = h.cluster_queue in st.cq_covers_pods
        implied = bool(st.cq_tas_only[ci])
        out = []
        for p, psr in enumerate(h.total_requests):
            req = psr.topology_request
            if req is None:
                if not implied:
                    out.append(None)
                    continue
                req = PodSetTopologyRequest(unconstrained=True)
            names = set()
            for res in psr.requests:
                if res == "pods" and not covers_pods:
                    continue
                g = int(grp[st.r_index[res]])
                names.add(groups[g].flavors[int(cls.slots0[wi, p, g])].name)
            if not names:
                out.append(None)
                continue
            per_pod = {r: v // max(1, psr.count)
                       for r, v in psr.requests.items()}
            out.append((min(names), req, psr.count, per_pod))
        return out

    def _tas_run(self, cls: ClassifiedCycle, plans: dict,
                 empty: dict) -> dict:
        """The heads' questions, PodSet after PodSet, each rank's in one
        launch a flavor; a head's later PodSets on a flavor are charged
        with its earlier ones' placements there.  Returns {wi: (ok,
        [TopologyAssignment or None a PodSet], (PodSet, reason) or None,
        {flavor: [(leaf, [R] demand)]})}."""
        snaps = cls.snapshot.tas_flavors
        out = {wi: [True, [None] * len(pl), None, {}]
               for wi, pl in plans.items()}
        for p in range(max((len(pl) for pl in plans.values()), default=0)):
            batch: dict[str, list] = {}
            with _span("cycle.nominate.tas.pack"):
                self._tas_batch(plans, p, out, snaps, empty, batch)
            with _span("cycle.nominate.tas.fetch"):
                self._tas_answer(batch, out, snaps)
        return {wi: tuple(row) for wi, row in out.items()}

    @staticmethod
    def _tas_batch(plans: dict, p: int, out: dict, snaps: dict,
                   empty: dict, batch: dict) -> None:
        """The heads' PodSet-``p`` questions, a list a flavor; a head
        whose flavor has no topology fails here."""
        for wi, pl in plans.items():
            row = out[wi]
            if not row[0] or p >= len(pl) or pl[p] is None:
                continue
            f_name, req, count, per_pod = pl[p]
            snap = snaps.get(f_name)
            if snap is None:
                row[0] = False
                row[2] = (p, f"no topology information for flavor "
                             f"{f_name}")
                continue
            q = snap.question(count, per_pod, req, bool(empty[wi]),
                              tuple(row[3].get(f_name, ())))
            batch.setdefault(f_name, []).append((wi, p, q, pl[p]))

    def _tas_answer(self, batch: dict, out: dict, snaps: dict) -> None:
        """One launch a flavor; each answer kept on its head's row.  A
        question the kernel cannot answer exactly is asked of the host
        walk (counted in ``tas_host_queries``)."""
        from . import tas_kernel as tk
        stats = self.stats
        for f_name, items in batch.items():
            snap = snaps[f_name]
            asked = [q for _, _, q, _ in items if q is not None]
            answers = iter(tk.answer_queries(
                snap.layout, snap.free, asked, snap._device_profile(False),
                snap._device_profile(True), stats))
            for wi, pp, q, (_, req, count, per_pod) in items:
                stats["tas_queries"] += 1
                row = out[wi]
                ans = None if q is None else next(answers)
                if q is None:
                    placed, why = None, snap._no_fit(
                        count, 0, snap._level_index(req.required))
                elif ans is None:
                    placed, why = snap.find_topology_assignment(
                        count, per_pod, req, assumed=snap.assumed(q.charges),
                        simulate_empty=q.empty)
                else:
                    placed, why = snap.answered(q, ans)
                if placed is None:
                    stats["tas_topology_nofit"] += 1
                    row[0] = False
                    row[2] = (pp, why)
                    continue
                row[1][pp] = placed
                lay = snap.layout
                row[3].setdefault(f_name, []).extend(
                    (lay.leaf_index[tuple(d.values)], d.count * q.per_pod)
                    for d in placed.domains)
                if (q.kind == tk.QUERY_PREFERRED and ans is not None
                        and ans.level != q.level):
                    stats["tas_preferred_climbs"] += 1

    def tas_stage(self, cls: ClassifiedCycle,
                  preempt_heads: bool = True) -> None:
        """``FlavorAssigner._apply_tas`` for every vector head of the
        cycle at once (reference flavorassigner.go:438-465): a Fit
        head's PodSets are placed against the nodes as the cycle found
        them, and one that no domain holds turns the head to Preempt
        with no flavor resource needing preemption; a Preempt head's,
        and such a head's, are placed against the empty tree, and one
        that fails there is NoFit.  The placements of Fit heads are
        kept on ``cls.tas_place``; ``cls.tas_overlap`` says whether two
        of them share a node that cannot hold both, so that the admit
        loop has to decide them in order.  ``preempt_heads=False``
        leaves the Preempt heads to the host walk."""
        st = cls.packed.structure
        n = cls.n
        W = len(cls.fit0)
        cls.tas_place, cls.tas_reason = {}, {}
        cls.tas_demoted = np.zeros(W, dtype=bool)
        cls.tas_nofit = np.zeros(W, dtype=bool)
        cls.tas_overlap = False
        cq_of = np.maximum(cls.packed.wl_cq[:n], 0)
        on = ((cls.fit0[:n] | (cls.preempt0[:n] & preempt_heads))
              & ~cls.scalar_mask[:n] & st.cq_tas[cq_of])
        plans = {}
        for wi in np.flatnonzero(on).tolist():
            pl = self._tas_requests(cls, wi)
            if any(x is not None for x in pl):
                plans[wi] = pl
        if not plans:
            return
        self.stats["tas_heads"] += len(plans)
        first = self._tas_run(cls, plans, {wi: not cls.fit0[wi]
                                           for wi in plans})
        retry = {wi: plans[wi] for wi in plans
                 if cls.fit0[wi] and not first[wi][0]}
        again = (self._tas_run(cls, retry, {wi: True for wi in retry})
                 if retry else {})
        need: dict[str, list] = {}
        for wi, (ok, places, why, charged) in first.items():
            if ok:
                if cls.fit0[wi]:
                    cls.tas_place[wi] = places
                    for f_name, items in charged.items():
                        need.setdefault(f_name, []).extend(items)
                continue
            reasons = [why]
            if cls.fit0[wi]:
                # every resource fit: its flavors' modes stay Fit
                cls.preempt_res_fit[wi] = True
            if cls.fit0[wi] and again[wi][0]:
                cls.tas_demoted[wi] = True
                cls.fit0[wi] = False
                cls.preempt0[wi] = True
                cls.preempt_borrows0[wi] = cls.borrows0[wi]
            else:
                if cls.fit0[wi]:
                    reasons.append(again[wi][2])
                cls.tas_nofit[wi] = True
                cls.fit0[wi] = False
                cls.preempt0[wi] = False
            cls.tas_reason[wi] = reasons
        for f_name, items in need.items():
            free = cls.snapshot.tas_flavors[f_name].free
            total = np.zeros_like(free)
            idx = np.fromiter((leaf for leaf, _ in items), dtype=np.int64,
                              count=len(items))
            np.add.at(total, idx, np.stack([d for _, d in items]))
            if (total > np.maximum(free, 0)).any():
                cls.tas_overlap = True

    def _with_tas_reasons(self, cls: ClassifiedCycle, wi: int,
                          assignment: Assignment) -> Assignment:
        for p, msg in (cls.tas_reason or {}).get(wi, ()):
            assignment.pod_sets[p].reasons.append(msg)
        return assignment

    def tas_nofit_details(self, cls: ClassifiedCycle, wi: int
                          ) -> tuple[Assignment, str]:
        """Assignment + message of a head the topology stage made NoFit,
        as the host walk records them: the walk's flavors and resume
        state, the quota reasons of a Preempt head, the topology's."""
        if cls.preempt_res_fit[wi].all():
            assignment = self._build_assignment(
                cls, wi, Mode.FIT, bool(cls.borrows0[wi]))
        else:
            assignment, _ = self.reserve_details(cls, wi)
        self._with_tas_reasons(cls, wi, assignment)
        assignment.set_representative_mode(Mode.NO_FIT)
        return assignment, assignment.message()

    # -- scalar-head decisions -----------------------------------------

    def attach_host_assignment(self, cls: ClassifiedCycle, wi: int,
                               assignment) -> bool:
        """Record a host-walked head's assignment for the admit scan.

        The assignment's usage map becomes the head's decision pairs.
        Returns False when the usage can't be represented in the cached
        structure (unknown flavor-resource or inexact scaling) — the
        caller then falls the whole cycle back to the host."""
        pairs = self._assignment_pairs(cls, assignment)
        if pairs is None:
            return False
        cls.host_assignments[wi] = assignment
        cls.host_pairs[wi] = pairs
        return True

    def _assignment_pairs(self, cls: ClassifiedCycle, assignment
                          ) -> Optional[list[tuple[int, int]]]:
        """assignment.usage → [(F-index, scaled amount)], or None."""
        st = cls.packed.structure
        scale_of = {r: int(st.resource_scale[i])
                    for i, r in enumerate(st.resource_names)}
        pairs = []
        for fr, v in assignment.usage.items():
            fi = st.fr_index.get(fr)
            if fi is None:
                return None
            s = scale_of.get(fr.resource)
            if s is None or v % s:
                return None
            q = v // s
            if q > 2**31 - 1:
                return None
            pairs.append((fi, int(q)))
        return pairs

    def _build_pair_tensors(self, cls: ClassifiedCycle,
                            rmask: np.ndarray, pmask: np.ndarray):
        """Merge vector and scalar classifications into the scan's
        decision-pair tensors.

        Returns (dec_fr, dec_amt, fit_mask, res_fr, res_amt, res_borrows,
        pre_fr, pre_amt, borrows) — all [W, K] / [W]."""
        packed = cls.packed
        st = packed.structure
        W = packed.wl_cq.shape[0]

        # vector heads: each (PodSet, resource)'s pair from its own
        # group's slot, one pair a distinct flavor-resource (batched);
        # fit heads', then reserve/preempt entries'
        frs = slot_frs(st.slot_fr, st.res_group, packed.wl_cq, cls.slots0)
        fit_mask = cls.fit0.copy()
        dec_fr, dec_amt = decision_pairs(frs, packed.wl_requests, fit_mask)
        pre_on = rmask | pmask
        res_fr, res_amt = decision_pairs(
            frs, packed.wl_requests, pre_on & cls.preempt0)
        res_borrows = cls.preempt_borrows0 & pre_on
        borrows = cls.borrows0.copy()
        borrows |= res_borrows

        scalar_pairs = cls.host_pairs
        have = dec_fr.shape[1]
        K = self._pair_width(st, max(
            (len(pairs) for pairs in scalar_pairs.values()), default=0))
        if K > have:
            pad = np.full((W, K - have), -1, np.int32)
            zpad = np.zeros((W, K - have), np.int32)
            dec_fr = np.concatenate([dec_fr, pad], axis=1)
            dec_amt = np.concatenate([dec_amt, zpad], axis=1)
            res_fr = np.concatenate([res_fr, pad], axis=1)
            res_amt = np.concatenate([res_amt, zpad], axis=1)

        for wi, assignment in cls.host_assignments.items():
            pairs = scalar_pairs[wi]
            mode = assignment.representative_mode()
            is_fit = mode == Mode.FIT
            fit_mask[wi] = is_fit
            dec_fr[wi] = -1
            dec_amt[wi] = 0
            res_fr[wi] = -1
            res_amt[wi] = 0
            if is_fit:
                for k, (fi, q) in enumerate(pairs):
                    dec_fr[wi, k] = fi
                    dec_amt[wi, k] = q
            elif rmask[wi] or pmask[wi]:
                for k, (fi, q) in enumerate(pairs):
                    res_fr[wi, k] = fi
                    res_amt[wi, k] = q
                res_borrows[wi] = assignment.borrows()
            borrows[wi] = assignment.borrows()
        # preempt entries re-check fits on the same pairs they charge
        pre_fr, pre_amt = res_fr, res_amt
        return (dec_fr, dec_amt, fit_mask, res_fr, res_amt, res_borrows,
                pre_fr, pre_amt, borrows)

    @staticmethod
    def _pair_width(st: PackedStructure, most: int = 0) -> int:
        """K of the admit scans' decision-pair tensors: a pair a
        resource where every head has one PodSet, else a pair a
        (PodSet, resource) of the planes' PodSet extent, and at least
        ``most`` (a scalar head's pairs); past a pair a resource it is
        a bucket, since every K is a compilation."""
        R = len(st.resource_names)
        k = max(most, R * st.pod_sets)
        return k if k == R else _bucket(k, minimum=R if R >= 8 else 8)

    # -- phase 2 -------------------------------------------------------

    def pack_targets(self, cls: ClassifiedCycle,
                     targets_by_wi: dict) -> Optional[PackedTargets]:
        """Pack per-head preemption-target lists into scan tensors.

        ``targets_by_wi``: {head index: [Target]} from the preemptor's
        nominate-time searches.  Returns None when a target's usage can't
        be represented exactly in the cached structure (host fallback)."""
        packed = cls.packed
        st = packed.structure
        W = packed.wl_cq.shape[0]
        F = packed.usage0.shape[1]
        universe: list = []
        uni_idx: dict[str, int] = {}
        cqs: list[int] = []
        per_wi: dict[int, list[int]] = {}
        for wi, targets in targets_by_wi.items():
            idxs = []
            for t in targets:
                key = t.info.key
                ti = uni_idx.get(key)
                if ti is None:
                    ci = st.cq_index.get(t.info.cluster_queue)
                    if ci is None:
                        return None
                    ti = len(universe)
                    uni_idx[key] = ti
                    universe.append(t.info)
                    cqs.append(ci)
                idxs.append(ti)
            per_wi[wi] = idxs

        from .preemption_solver import layout_for
        deltas, exact = layout_for(packed).scaled_usages(
            [info.usage() for info in universe])
        if not exact.all():
            return None
        n_universe = max(1, len(universe))
        n_per_head = max(1, max(len(v) for v in per_wi.values()))
        if n_universe > T_LADDER[-1] or n_per_head > MT_LADDER[-1]:
            return None   # beyond the shape ladders: host path
        T = coarse_bucket(n_universe, T_LADDER)
        MT = coarse_bucket(n_per_head, MT_LADDER)
        tu_cq = np.zeros(T, dtype=np.int32)
        tu_delta = np.zeros((T, F), dtype=np.int32)
        tu_cq[:len(cqs)] = cqs
        tu_delta[:len(universe)] = deltas
        tgt_mat = np.full((W, MT), -1, dtype=np.int32)
        preempt_mask = np.zeros(W, dtype=bool)
        for wi, idxs in per_wi.items():
            preempt_mask[wi] = True
            tgt_mat[wi, :len(idxs)] = idxs
        return PackedTargets(preempt_mask=preempt_mask, tgt_mat=tgt_mat,
                             tu_cq=tu_cq, tu_delta=tu_delta)

    def dispatch(self, cls: ClassifiedCycle, reserve_mask: np.ndarray,
                 targets: Optional[PackedTargets] = None) -> DispatchHandle:
        """Issue the admit scan (async) — or prove it unnecessary.

        ``reserve_mask`` (head order) marks preempt-classified entries the
        scheduler verified have zero preemption candidates — they reserve
        capacity in-scan (resourcesToReserve) and requeue.  ``targets``
        carries the packed preemption targets for preempt heads WITH
        candidates; those entries preempt in-scan (the reference admit
        loop's IssuePreemptions branch, scheduler.go:176-284).

        Decision-identical shortcuts (no dispatch issued):
        - no fit head and no preempt entry → nothing can be admitted,
          reserves requeue anyway;
        - ≤1 entry per cohort forest (and no preempt entry) → zero
          within-cycle contention, every fit head keeps its fit.
        Otherwise the scan is dispatched asynchronously on the solver
        device; the host overlaps per-head work until ``fetch``."""
        packed = cls.packed
        st = packed.structure
        W = packed.wl_cq.shape[0]
        n = cls.n
        rmask = np.zeros(W, dtype=bool)
        rmask[:len(reserve_mask)] = reserve_mask
        pmask = (targets.preempt_mask if targets is not None
                 else np.zeros(W, dtype=bool))
        (dec_fr, dec_amt, fit_mask, res_fr, res_amt, res_borrows,
         pre_fr, pre_amt, borrows) = self._build_pair_tensors(
            cls, rmask, pmask)
        order = cycle_order_np(borrows, packed.wl_priority,
                               packed.wl_timestamp)
        self.stats["reserve_entries"] += int(rmask[:n].sum())
        handle = DispatchHandle(order=order, rmask=rmask, n=n)
        handle.fit_mask = fit_mask
        zeros = np.zeros(W, dtype=bool)

        if not pmask.any():
            handle.preempting = zeros
            handle.overlap_skip = zeros
            if not fit_mask[:n].any():
                self.stats["skipped_dispatches"] += 1
                handle.admitted = zeros
                handle.route = "no_fit"
                return handle
            entry_mask = fit_mask | rmask
            entry_cqs = packed.wl_cq[entry_mask]
            if len(entry_cqs):
                forests = st.forest_of_node[np.maximum(entry_cqs, 0)]
                if np.bincount(forests, minlength=st.n_forests).max() <= 1:
                    # one entry per independent quota forest: the scan's
                    # only job (usage mutation between entries) is a no-op
                    self.stats["singleton_dispatches"] += 1
                    handle.admitted = fit_mask & (packed.wl_cq >= 0)
                    handle.route = "singleton"
                    return handle

        has_preempt = bool(pmask.any())
        mfw = self._forest_bucket(packed) if not has_preempt else None
        args = (packed.usage0, st.subtree_quota, st.guaranteed,
                st.borrow_cap, st.has_borrow_limit, st.parent,
                st.nominal_cq, st.nominal_plus_blimit_cq, packed.wl_cq,
                dec_fr, dec_amt, fit_mask, res_fr, res_amt, rmask,
                res_borrows)
        preempt = ((pmask, pre_fr, pre_amt, targets.tgt_mat,
                    targets.tu_cq, targets.tu_delta)
                   if has_preempt else None)
        sharded = self.mesh is not None
        if sharded:
            # the scan runs as a sharded program over the (wl, cq) mesh
            # with XLA collectives
            self.stats["sharded_dispatches"] += 1
            if has_preempt:
                self.stats["sharded_preempt_dispatches"] += 1
        handle.pending = self._scan(st, args, order, mfw=mfw,
                                    preempt=preempt)
        route = self._count_dispatch(handle.pending)
        handle.route = "sharded" if sharded else route
        return handle

    def dispatch_fs(self, cls: ClassifiedCycle) -> Optional[DispatchHandle]:
        """Dispatch a fair-sharing cycle's tournament + admit loop as one
        jitted scan (ops/fs_scan.py) — FULL-mode FS (verdict r3 item 3).

        Returns None when the FS statics can't be built or the scaled
        DRS math could overflow (host tournament runs instead).  The
        caller guarantees: no scalar heads, no preempt-capable heads, no
        admission-block gate."""
        from .fs_scan import build_fs_statics, fs_admit_scan, fs_bounds_ok
        packed = cls.packed
        st = packed.structure
        statics = getattr(st, "_fs_statics", "unset")
        if isinstance(statics, str):
            statics = build_fs_statics(cls.snapshot, st)
            st._fs_statics = statics
        if statics is None:
            return None
        W = packed.wl_cq.shape[0]
        F = packed.usage0.shape[1]
        n = cls.n
        fit_mask = cls.fit0
        dec_fr, dec_amt = decision_pairs(
            slot_frs(st.slot_fr, st.res_group, packed.wl_cq, cls.slots0),
            packed.wl_requests, fit_mask)
        u_e = np.zeros((W, F), dtype=np.int32)
        rows, cols = np.nonzero(dec_fr >= 0)
        np.add.at(u_e, (rows, dec_fr[rows, cols]), dec_amt[rows, cols])
        if not fs_bounds_ok(statics, packed.usage0, u_e):
            return None
        valid = packed.wl_cq >= 0
        nofit = ~fit_mask
        # equality-preserving timestamp rank (ties must stay ties for
        # entryComparer.less parity)
        _, ts_rank = np.unique(packed.wl_timestamp, return_inverse=True)
        ts_rank = ts_rank.astype(np.int32)
        handle = DispatchHandle(order=np.arange(W, dtype=np.int32),
                                rmask=np.zeros(W, dtype=bool), n=n)
        handle.fit_mask = fit_mask
        fs_args = (packed.usage0, st.subtree_quota, statics.sq_mask,
                   st.guaranteed, st.borrow_cap, st.has_borrow_limit,
                   st.parent, statics.node_level, st.fair_weight_milli,
                   statics.lendable_r, statics.onehot,
                   statics.child_order, packed.wl_cq, u_e, nofit,
                   packed.wl_priority, ts_rank, valid)
        if self.mesh is not None:
            # mesh-sharded FS tournament: the SAME jitted program,
            # partitioned by GSPMD over (wl, cq) — integer DRS math and
            # deterministic argmax tie-breaks make it bit-identical
            key = ("fs", st.depth, statics.n_levels)
            fn = self._sharded_fns.get(key)
            if fn is None:
                from ..parallel.sharded import fs_scan_fn
                fn = fs_scan_fn(self.mesh, st.depth, statics.n_levels)
                self._sharded_fns[key] = fn
            self.stats["sharded_fs_dispatches"] = (
                self.stats.get("sharded_fs_dispatches", 0) + 1)
            out = fn(*fs_args)
        else:
            out = fs_admit_scan(*fs_args, depth=st.depth,
                                n_levels=statics.n_levels)
        handle.route = self._count_dispatch(out)
        handle.pending = ("fs", out)
        return handle

    def fetch(self, handle: DispatchHandle) -> DeviceCycleFinal:
        """Block for an in-flight scan's decisions (head order)."""
        if handle.admitted is None:
            import jax
            if (isinstance(handle.pending, tuple)
                    and len(handle.pending) == 2
                    and handle.pending[0] == "fs"):
                order, admitted, processed = jax.device_get(
                    handle.pending[1])
                handle.pending = None
                handle.admitted = np.asarray(admitted)
                W = len(handle.rmask)
                handle.preempting = np.zeros(W, dtype=bool)
                handle.overlap_skip = np.zeros(W, dtype=bool)
                handle.order = np.asarray(order)
                n = handle.n
                return DeviceCycleFinal(
                    order=handle.order[(handle.order >= 0)
                                       & (handle.order < n)],
                    admitted=handle.admitted[:n],
                    reserve_mask=handle.rmask[:n],
                    preempting=handle.preempting[:n],
                    overlap_skip=handle.overlap_skip[:n])
            out = jax.device_get(handle.pending)
            handle.pending = None
            if isinstance(out, tuple):
                handle.admitted = np.asarray(out[0])
                handle.preempting = np.asarray(out[1])
                handle.overlap_skip = np.asarray(out[2])
            else:
                W = len(handle.rmask)
                handle.admitted = np.asarray(out)
                handle.preempting = np.zeros(W, dtype=bool)
                handle.overlap_skip = np.zeros(W, dtype=bool)
        n = handle.n
        return DeviceCycleFinal(
            order=handle.order[handle.order < n],
            admitted=handle.admitted[:n], reserve_mask=handle.rmask[:n],
            preempting=handle.preempting[:n],
            overlap_skip=handle.overlap_skip[:n])

    def solve_full(self, cls: ClassifiedCycle,
                   reserve_mask: np.ndarray) -> DeviceCycleFinal:
        """dispatch + fetch in one call (tests/probes)."""
        return self.fetch(self.dispatch(cls, reserve_mask))

    @staticmethod
    def _forests_apply(W: int, n_forests: int) -> bool:
        """Single gate for forest-vs-flat scan dispatch (warmup must
        compile exactly what solve_full will run)."""
        return n_forests > 1 and W >= _FOREST_MIN_HEADS

    def _forest_bucket(self, packed: PackedCycle) -> Optional[int]:
        """Power-of-two scan length for the forest-parallel admit scan, or
        None when the flat scan is the better dispatch."""
        st = packed.structure
        if not self._forests_apply(packed.wl_cq.shape[0], st.n_forests):
            return None
        valid = packed.wl_cq >= 0
        if not valid.any():
            return None
        f_of = st.forest_of_node[np.maximum(packed.wl_cq, 0)]
        counts = np.bincount(f_of[valid], minlength=st.n_forests)
        return _bucket(int(counts.max()), minimum=4)

    # -- assignment reconstruction -------------------------------------

    def build_fit_assignment(self, cls: ClassifiedCycle,
                             wi) -> Assignment:
        """Host Assignment for a device-classified Fit head, including the
        fungibility resume state the host walk would record and the
        topology stage's placements."""
        assignment = self._build_assignment(cls, wi, Mode.FIT,
                                            bool(cls.borrows0[wi]))
        for ps, ta in zip(assignment.pod_sets,
                          (cls.tas_place or {}).get(int(wi), ())):
            ps.topology_assignment = ta
        return assignment

    def _build_assignment(self, cls: ClassifiedCycle, wi: int,
                          mode: Mode, borrow: bool,
                          res_modes: Optional[dict] = None) -> Assignment:
        h = cls.heads[wi]
        cq = cls.snapshot.cq(h.cluster_queue)
        return build_slot_assignment(h, cq, cls.slots0[wi], cls.tried[wi],
                                     mode, borrow, res_modes=res_modes)

    def build_preempt_assignment(self, cls: ClassifiedCycle,
                                 wi: int) -> Assignment:
        """Host Assignment for a preempt-classified head with per-resource
        modes (resources fitting on the preempt slot are FIT, the
        shortfall resources PREEMPT — flavorassigner.go:692), as the
        preemptor's target search expects (preemption.go:466)."""
        borrow = bool(cls.preempt_borrows0[wi])
        if cls.tas_demoted is not None and cls.tas_demoted[wi]:
            # quota fits and no domain holds it: every resource Fit, the
            # head Preempt (flavorassigner.go:438-465)
            assignment = self._build_assignment(cls, wi, Mode.FIT, borrow)
            assignment.set_representative_mode(Mode.PREEMPT)
            return self._with_tas_reasons(cls, wi, assignment)
        st = cls.packed.structure
        res_modes = [{res: (Mode.FIT if fit[ri] else Mode.PREEMPT)
                      for res, ri in st.r_index.items()}
                     for fit in cls.preempt_res_fit[wi]]
        return self._build_assignment(cls, wi, Mode.PREEMPT, borrow,
                                      res_modes=res_modes)

    def reserve_details(self, cls: ClassifiedCycle, wi: int
                        ) -> tuple[Assignment, str]:
        """Assignment + inadmissible message for a preempt-classified head
        with no candidates (reachable whenever exactly one slot is
        preempt-capable, including multi-flavor CQs whose other slots are
        NoFit), replicating the host walk's reasons (flavorassigner.go:692
        messages)."""
        h = cls.heads[wi]
        assignment = self.build_preempt_assignment(cls, wi)
        if cls.tas_demoted is not None and cls.tas_demoted[wi]:
            return assignment, assignment.message()
        cq = cls.snapshot.cq(h.cluster_queue)
        acc: dict = {}      # what the earlier PodSets chose, as the walk
        for ps in assignment.pod_sets:
            reasons = []
            for res in sorted(ps.requests):
                fr = FlavorResource(ps.flavors[res].name, res)
                val = ps.requests[res] + acc.get(fr, 0)
                acc[fr] = val
                avail = cq.available(fr)
                if val > avail:
                    reasons.append(
                        f"insufficient unused quota for {res} in flavor "
                        f"{fr.flavor}, {val - avail} more needed")
            ps.reasons = reasons
        return assignment, assignment.message()


def build_slot_assignment(info: Info, cq, slots, tried, mode: Mode,
                          borrow: bool,
                          res_modes: Optional[list] = None) -> Assignment:
    """Reconstruct the host Assignment a device-classified head would get
    from the flavor walks: ``slots[p, g]`` = the flavor index the walk
    of PodSet p in group g chose, ``tried[p, g]`` the resume state it
    recorded (the slot the walk STOPPED on mid-list, -1 when the whole
    list was attempted — flavorassigner.go:386-390 +
    shouldTryNextFlavor), written a resource as
    ``FlavorAssigner._append`` writes it; both [P, G] or flat,
    PodSet-major.  ``res_modes[p]`` gives PodSet p's mode a resource
    where it is not ``mode``.  ``cq`` is any CQState (snapshot or live
    cache) carrying .spec and .allocatable_generation."""
    groups = cq.spec.resource_groups
    covers_pods = any("pods" in rg.covered_resources for rg in groups)
    group_of = {res: g for g, rg in enumerate(groups)
                for res in rg.covered_resources}
    slots, tried = np.atleast_2d(slots), np.atleast_2d(tried)

    assignment = Assignment()
    assignment.borrowing = borrow
    assignment.last_state = AssignmentClusterQueueState(
        cluster_queue_generation=cq.allocatable_generation)
    for p, psr in enumerate(info.total_requests):
        # mirror the host's implicit "pods" handling
        # (flavorassigner.go:226 / _assign_flavors)
        reqs = dict(psr.requests)
        if covers_pods:
            reqs["pods"] = psr.count
        else:
            reqs.pop("pods", None)
        ps_res = PodSetAssignmentResult(
            name=psr.name, requests=Requests(reqs), count=psr.count)
        flavor_idx: dict[str, int] = {}
        for res in reqs:
            g = group_of[res]
            flavor_name = groups[g].flavors[int(slots[p, g])].name
            res_mode = mode if res_modes is None else res_modes[p].get(
                res, mode)
            ps_res.flavors[res] = FlavorAssignmentDecision(
                name=flavor_name, mode=res_mode, borrow=borrow,
                tried_flavor_idx=int(tried[p, g]))
            flavor_idx[res] = int(tried[p, g])
            fr = FlavorResource(flavor_name, res)
            assignment.usage[fr] = (assignment.usage.get(fr, 0)
                                    + reqs[res])
        assignment.pod_sets.append(ps_res)
        assignment.last_state.last_tried_flavor_idx.append(flavor_idx)
    return assignment


def resume_starts(info: Info, cq, covers_pods: bool, n_groups: int,
                  pod_sets: int = 1) -> tuple:
    """Flavor-walk start slot a (PodSet, resource group) for a head with
    fungibility resume state, PodSet-major, padded with 0 to
    ``pod_sets * n_groups``.

    Mirrors the host's entry into each walk (flavorassigner.go:359-366
    via next_flavor_to_try of the PodSet and the group's first resource
    in sorted request order): 0 when there is no usable resume state,
    last_tried + 1 otherwise.  The state is void when the CQ's quota
    changed since it was recorded (assign() clears it on
    allocatable_generation advance)."""
    none = (0,) * (n_groups * pod_sets)
    last = info.last_assignment
    if last is None or cq is None:
        return none
    if cq.allocatable_generation > last.cluster_queue_generation:
        return none
    if not 0 < len(info.total_requests) <= pod_sets:
        return none
    out = list(none)
    for p, psr in enumerate(info.total_requests):
        reqs = set(psr.requests)
        if covers_pods:
            reqs.add("pods")
        else:
            reqs.discard("pods")
        for g, rg in enumerate(cq.spec.resource_groups[:n_groups]):
            mine = reqs.intersection(rg.covered_resources)
            if mine:
                out[p * n_groups + g] = max(
                    0, int(last.next_flavor_to_try(p, min(mine))))
    return tuple(out)
