"""The live cache of admitted state, rebuilt from the event stream.

Capability parity with reference pkg/cache/cache.go:102: holds the cohort
forest of ClusterQueues, resource flavors, admission checks and admitted
workloads; supports optimistic ``assume_workload``/``forget_workload``
(cache.go:610,636) ahead of the durable write; produces per-cycle
snapshots (snapshot.py).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from .. import hierarchy
from ..features import env_value
from ..api.types import (
    Admission,
    AdmissionCheck,
    ClusterQueue,
    Cohort,
    LocalQueue,
    ResourceFlavor,
    StopPolicy,
    Topology,
)
from ..resources import FlavorResourceQuantities
from ..workload import Info, InfoOptions
from .candidates import TableTally
from .snapshot import Snapshot
from .state import (
    CohortState,
    CQState,
    SnapTag,
    build_quotas,
    update_cluster_queue_resource_node,
    update_cohort_resource_node,
)
from .tas_cache import TASCache


class _SnapCache:
    """Clone forest retained between snapshots for incremental reuse.

    Valid for exactly one ``structure_generation``: spec-level edits
    (CQ/cohort/flavor/check churn, activeness recompute) bump the
    generation and force a full rebuild, so the cache only has to track
    *usage*-level dirt.  A cached root tree is reused verbatim when
    (a) the live side didn't touch any of its CQs since the last drain
    (PackJournal ``snap_dirty`` channel) and (b) no snapshot consumer
    scribbled on the clone (SnapTag)."""

    __slots__ = ("generation", "root_order", "root_clones", "root_tags",
                 "free_clones", "free_tags", "tree_of_cq", "cq_map",
                 "inactive", "flavors")

    def __init__(self, generation: int):
        self.generation = generation
        self.root_order: list[str] = []            # roots() build order
        self.root_clones: dict[str, CohortState] = {}
        self.root_tags: dict[str, SnapTag] = {}
        self.free_clones: dict[str, CQState] = {}  # cohortless CQs
        self.free_tags: dict[str, SnapTag] = {}
        self.tree_of_cq: dict[str, str] = {}       # cq name → root name
        self.cq_map: dict[str, CQState] = {}
        self.inactive: set[str] = set()
        self.flavors: dict[str, ResourceFlavor] = {}


class Cache:
    def __init__(self, info_options: InfoOptions | None = None,
                 fair_sharing_enabled: bool = False):
        self._lock = threading.RLock()
        self._mgr: hierarchy.Manager[CQState, CohortState] = hierarchy.Manager(CohortState)
        self.resource_flavors: dict[str, ResourceFlavor] = {}
        self.admission_checks: dict[str, AdmissionCheck] = {}
        self.local_queues: dict[str, LocalQueue] = {}
        self.assumed_workloads: set[str] = set()
        self.info_options = info_options or InfoOptions()
        self.fair_sharing_enabled = fair_sharing_enabled
        self.tas = TASCache()
        # Bumped on any spec-level change (CQ/cohort/flavor/check); the
        # solver caches its packed structure tensors against this.
        self.structure_generation = 0
        # workload key → owning CQ name (O(1) duplicate/ownership lookups;
        # the reference keys cache membership the same way, cache.go:536)
        self._wl_owner: dict[str, str] = {}
        # rows written into the queues' candidate tables and into their
        # snapshot clones (cache/candidates.py); the preemptor reports it
        self.table_tally = TableTally()
        # dirty-CQ journal feeding the incremental burst pack: admitted
        # table / usage / assumed-set mutations mark the owning CQ, and
        # a workload that joins or leaves an admitted table is named
        # with its queue (utils/journal.py touch_admitted); structure
        # edits need no marks — they bump structure_generation, which
        # forces a full repack by key
        from ..utils.journal import PackJournal
        self.pack_journal = PackJournal()
        # Parallel host plane (utils/parallel_host.py): the driver hands
        # its HostPool down so _rebuild can fan the per-root quota
        # recomputation out across workers; None/inactive = serial.
        self.host_pool = None
        # Incremental snapshot maintenance: per-cycle snapshot cost is
        # O(arrivals + dirty rows), not O(universe).  The clone forest
        # is retained across cycles and only journal-dirty or
        # consumer-mutated trees are re-cloned.  KUEUE_TPU_SNAP_INCREMENTAL=0
        # restores the old full-rebuild-every-cycle behavior (used by
        # the parity tests).
        # Bulk-apply support: while deferred, topology mutations mark
        # the hierarchy pending instead of re-deriving the quota trees,
        # so applying N ClusterQueues costs one O(N) rebuild, not N
        # (the O(N^2) setup wall at 100k CQs).
        self._rebuild_deferred = False
        self._rebuild_pending = False
        self._snap_cache: Optional[_SnapCache] = None
        self._snap_incremental = env_value(
            "KUEUE_TPU_SNAP_INCREMENTAL").lower() not in ("0", "false")
        self.snapshot_stats: dict[str, int] = {
            "snap_builds": 0, "snap_full": 0, "snap_incremental": 0,
            "snap_trees_recloned": 0, "snap_trees_reused": 0,
            "snap_cqs_recloned": 0, "snap_cqs_reused": 0,
        }

    # ------------------------------------------------------------------
    # ClusterQueues / Cohorts
    # ------------------------------------------------------------------

    def add_or_update_cluster_queue(self, spec: ClusterQueue) -> None:
        with self._lock:
            existing = self._mgr.cluster_queues.get(spec.name)
            if existing is None:
                self._mgr.add_cluster_queue(
                    spec.name, CQState(spec, self.table_tally))
            else:
                existing.update_quotas(spec)
            self._mgr.update_cluster_queue_edge(spec.name, spec.cohort)
            self._rebuild()

    def delete_cluster_queue(self, name: str) -> None:
        with self._lock:
            cq = self._mgr.cluster_queues.get(name)
            if cq is not None:
                for key, info in cq.workloads.items():
                    self._tas_apply(info, -1)  # release domain capacity
                    self._wl_owner.pop(key, None)
            self._mgr.delete_cluster_queue(name)
            self._rebuild()

    def add_or_update_cohort(self, spec: Cohort) -> None:
        with self._lock:
            node = self._mgr.add_cohort(spec.name)
            node.payload.spec = spec
            node.payload.resource_node.quotas = build_quotas(spec.resource_groups)
            node.payload.fair_weight_milli = int(
                (spec.fair_sharing.weight if spec.fair_sharing else 1.0) * 1000)
            self._mgr.update_cohort_edge(spec.name, spec.parent_name)
            self._rebuild()

    def delete_cohort(self, name: str) -> None:
        with self._lock:
            self._mgr.delete_cohort(name)
            self._rebuild()

    # ------------------------------------------------------------------
    # Flavors / checks / local queues / topologies
    # ------------------------------------------------------------------

    def add_or_update_resource_flavor(self, flavor: ResourceFlavor) -> None:
        with self._lock:
            self.resource_flavors[flavor.name] = flavor
            if flavor.topology_name:
                self.tas.bind_flavor(flavor)
            self._update_all_statuses()

    def delete_resource_flavor(self, name: str) -> None:
        with self._lock:
            self.resource_flavors.pop(name, None)
            self.tas.unbind_flavor(name)
            self._update_all_statuses()

    def add_or_update_admission_check(self, check: AdmissionCheck) -> None:
        with self._lock:
            self.admission_checks[check.name] = check
            self._update_all_statuses()

    def delete_admission_check(self, name: str) -> None:
        with self._lock:
            self.admission_checks.pop(name, None)
            self._update_all_statuses()

    def add_or_update_local_queue(self, lq: LocalQueue) -> None:
        with self._lock:
            self.local_queues[lq.key] = lq

    def delete_local_queue(self, lq_key: str) -> None:
        with self._lock:
            self.local_queues.pop(lq_key, None)

    def add_or_update_topology(self, topology: Topology) -> None:
        with self._lock:
            self.tas.add_topology(topology)

    def delete_topology(self, name: str) -> None:
        with self._lock:
            self.tas.delete_topology(name)

    # ------------------------------------------------------------------
    # Workloads (admitted / assumed) — reference cache.go:536-658
    # ------------------------------------------------------------------

    def cluster_queue(self, name: str) -> Optional[CQState]:
        return self._mgr.cluster_queues.get(name)

    def _tas_apply(self, info: Info, sign: int) -> None:
        """Charge/release the workload's topology-domain usage in the
        TAS cache (the reference tracks TAS usage alongside quota in
        cache.AddOrUpdateWorkload; tas_cache usage feeds the per-cycle
        TASFlavorSnapshot free capacity)."""
        adm = info.obj.admission
        if adm is None:
            return
        # per-pod values from the TRANSFORMED totals (workload.py applies
        # resource transformations/exclusions) so charged usage matches
        # what the assigner's _find_tas checks next cycle; total_requests
        # already carries the implicit "pods" resource
        by_name = {psr.name: psr for psr in info.total_requests}
        for a in adm.pod_set_assignments:
            ta = a.topology_assignment
            if ta is None:
                continue
            flavor = next((f for f in a.flavors.values()
                           if f in self.tas.flavors), None)
            if flavor is None:
                continue
            psr = by_name.get(a.name)
            if psr is None or psr.count <= 0:
                continue
            per_pod = {r: v // max(1, psr.count)
                       for r, v in psr.requests.items()}
            per_pod.setdefault("pods", 1)
            for dom in ta.domains:
                self.tas.add_usage(
                    flavor, tuple(dom.values),
                    {r: v * dom.count for r, v in per_pod.items()},
                    sign)

    def add_or_update_workload(self, info: Info) -> bool:
        with self._lock:
            if info.obj.admission is None:
                return False
            # Remove any previous accounting first — the workload may have
            # been re-admitted to a different CQ (reference cache.go
            # UpdateWorkload removes from the old CQ before adding).
            owner = self._find_owner(info)
            if owner is not None:
                self._tas_apply(owner.workloads[info.key], -1)
                owner.remove_workload(owner.workloads[info.key])
                self._wl_owner.pop(info.key, None)
                self.pack_journal.touch_admitted(owner.name, info.key, False)
            cq = self._mgr.cluster_queues.get(info.obj.admission.cluster_queue)
            if cq is None:
                self.pack_journal.touch(info.obj.admission.cluster_queue)
                self.assumed_workloads.discard(info.key)
                return False
            self.pack_journal.touch_admitted(cq.name, info.key, True)
            info.cluster_queue = cq.name
            cq.add_workload(info)
            self._tas_apply(info, +1)
            self._wl_owner[info.key] = cq.name
            self.assumed_workloads.discard(info.key)
            return True

    def delete_workload(self, info: Info) -> None:
        with self._lock:
            cq = self._find_owner(info)
            if cq is not None:
                self._tas_apply(cq.workloads[info.key], -1)
                cq.remove_workload(cq.workloads[info.key])
                self._wl_owner.pop(info.key, None)
                self.pack_journal.touch_admitted(cq.name, info.key, False)
            elif info.key in self.assumed_workloads:
                # the assumed set gates the owner CQ's pending rows
                owned = getattr(info, "cluster_queue", None)
                if owned:
                    self.pack_journal.touch(owned)
                else:
                    self.pack_journal.touch_all()
            self.assumed_workloads.discard(info.key)

    def assume_workload(self, info: Info) -> bool:
        """Optimistic admission before the durable write lands
        (reference cache.go:610)."""
        with self._lock:
            if info.obj.admission is None or info.key in self.assumed_workloads:
                return False
            if self._find_owner(info) is not None:
                return False  # already accounted — never double-count
            cq = self._mgr.cluster_queues.get(info.obj.admission.cluster_queue)
            if cq is None:
                return False
            info.cluster_queue = cq.name
            cq.add_workload(info)
            self._tas_apply(info, +1)
            self._wl_owner[info.key] = cq.name
            self.assumed_workloads.add(info.key)
            self.pack_journal.touch_admitted(cq.name, info.key, True)
            return True

    def forget_workload(self, info: Info) -> bool:
        """reference cache.go:636."""
        with self._lock:
            if info.key not in self.assumed_workloads:
                return False
            cq = self._find_owner(info)
            if cq is not None:
                self._tas_apply(cq.workloads[info.key], -1)
                cq.remove_workload(cq.workloads[info.key])
                self._wl_owner.pop(info.key, None)
                self.pack_journal.touch_admitted(cq.name, info.key, False)
            else:
                owned = getattr(info, "cluster_queue", None)
                if owned:
                    self.pack_journal.touch(owned)
                else:
                    self.pack_journal.touch_all()
            self.assumed_workloads.discard(info.key)
            return True

    def _find_owner(self, info: Info) -> Optional[CQState]:
        owner = self._wl_owner.get(info.key)
        if owner is not None:
            cq = self._mgr.cluster_queues.get(owner)
            if cq is not None and info.key in cq.workloads:
                return cq
        return None

    # ------------------------------------------------------------------
    # Snapshot — reference snapshot.go:104
    # ------------------------------------------------------------------

    def snapshot(self) -> Snapshot:
        """Per-cycle snapshot.  Incremental: the clone forest from the
        previous snapshot is reused wholesale for every root tree whose
        CQs were neither touched on the live side (PackJournal snapshot
        channel) nor mutated on the clone side (SnapTag) — only dirty
        trees pay the re-clone.  A snapshot is valid until the next
        ``snapshot()`` call (the scheduler's within-cycle use), same as
        the previous full-rebuild contract which already shared Info
        objects with the live store."""
        with self._lock:
            gen = self.structure_generation
            sc = self._snap_cache
            dirty, was_all = self.pack_journal.drain_snapshot()
            if (not self._snap_incremental or sc is None
                    or sc.generation != gen or was_all):
                sc = self._snapshot_full(gen)
            else:
                self._snapshot_refresh(sc, dirty)
            self.snapshot_stats["snap_builds"] += 1
            return Snapshot(
                cluster_queues=dict(sc.cq_map),
                roots=[sc.root_clones[r] for r in sc.root_order],
                inactive_cluster_queues=set(sc.inactive),
                resource_flavors=dict(sc.flavors),
                tas_flavors=self.tas.snapshot(),
                fair_sharing_enabled=self.fair_sharing_enabled,
                structure_generation=gen,
            )

    def _snapshot_full(self, gen: int) -> _SnapCache:
        sc = _SnapCache(gen)
        for node in self._mgr.roots():
            self._snap_clone_root(sc, node)
        for name, cq in self._mgr.cluster_queues.items():
            if name not in sc.cq_map:  # cohortless CQ
                self._snap_clone_free(sc, name, cq)
        sc.inactive = {name for name, cq in self._mgr.cluster_queues.items()
                       if not cq.active}
        sc.flavors = dict(self.resource_flavors)
        self._snap_cache = sc
        self.snapshot_stats["snap_full"] += 1
        return sc

    def _snapshot_refresh(self, sc: _SnapCache, dirty: set) -> None:
        dirty_roots: set[str] = set()
        dirty_free: set[str] = set()
        for name in dirty:
            root = sc.tree_of_cq.get(name)
            if root is not None:
                dirty_roots.add(root)
            elif name in sc.free_clones:
                dirty_free.add(name)
            # else: touch for a CQ unknown at this generation — any
            # add/delete that could explain it bumped the generation
        for rname, tag in sc.root_tags.items():
            if tag.mutated:
                dirty_roots.add(rname)
        for name, tag in sc.free_tags.items():
            if tag.mutated:
                dirty_free.add(name)
        st = self.snapshot_stats
        recloned_before = st["snap_cqs_recloned"]
        for rname in dirty_roots:
            node = self._mgr.cohorts.get(rname)
            if node is not None:
                # same generation → same membership: the re-clone
                # overwrites exactly the stale cq_map/tree_of_cq entries
                self._snap_clone_root(sc, node)
        for name in dirty_free:
            cq = self._mgr.cluster_queues.get(name)
            if cq is not None:
                self._snap_clone_free(sc, name, cq)
        st["snap_incremental"] += 1
        st["snap_trees_reused"] += len(sc.root_clones) - len(dirty_roots)
        st["snap_cqs_reused"] += (
            len(sc.cq_map) - (st["snap_cqs_recloned"] - recloned_before))

    def _snap_clone_root(self, sc: _SnapCache, node) -> None:
        sub: dict[str, CQState] = {}
        clone = node.payload.clone_subtree(None, sub)
        tag = SnapTag()
        for cq in sub.values():
            cq._snap_tag = tag
        name = node.name
        if name not in sc.root_clones:
            sc.root_order.append(name)
        sc.root_clones[name] = clone
        sc.root_tags[name] = tag
        for cq_name in sub:
            sc.tree_of_cq[cq_name] = name
        sc.cq_map.update(sub)
        self.snapshot_stats["snap_trees_recloned"] += 1
        self.snapshot_stats["snap_cqs_recloned"] += len(sub)

    def _snap_clone_free(self, sc: _SnapCache, name: str, cq: CQState) -> None:
        c = cq.clone(parent=None)
        tag = SnapTag()
        c._snap_tag = tag
        sc.free_clones[name] = c
        sc.free_tags[name] = tag
        sc.cq_map[name] = c
        self.snapshot_stats["snap_cqs_recloned"] += 1

    # ------------------------------------------------------------------
    # Status / reporting
    # ------------------------------------------------------------------

    def usage(self, cq_name: str) -> FlavorResourceQuantities:
        cq = self._mgr.cluster_queues.get(cq_name)
        return cq.resource_node.usage.clone() if cq else FlavorResourceQuantities()

    def cluster_queue_names(self) -> list[str]:
        return list(self._mgr.cluster_queues)

    def local_queue_usage(self, namespace: str, lq_name: str
                          ) -> FlavorResourceQuantities:
        """Usage aggregated over a LocalQueue's admitted workloads
        (reference cache.go:786 LocalQueueUsage)."""
        out = FlavorResourceQuantities()
        with self._lock:
            lq = self.local_queues.get(f"{namespace}/{lq_name}")
            if lq is None:
                return out
            cq = self._mgr.cluster_queues.get(lq.cluster_queue)
            if cq is None:
                return out
            infos = list(cq.workloads.values())
        for info in infos:
            wl = info.obj
            if wl.namespace == namespace and wl.queue_name == lq_name:
                for fr, v in info.usage().items():
                    out[fr] = out.get(fr, 0) + v
        return out

    def cohort_state(self, name: str) -> Optional[CohortState]:
        node = self._mgr.cohort(name)
        return node.payload if node else None

    # ------------------------------------------------------------------
    # Internal wiring
    # ------------------------------------------------------------------

    def deferred_rebuild(self):
        """Context manager batching topology mutations: ``_rebuild`` is
        suppressed inside the block and runs exactly once on exit (if
        any mutation asked for it).  Reads inside the block see stale
        quota trees / activeness — callers must not schedule against
        the cache until the block closes."""
        from contextlib import contextmanager

        @contextmanager
        def _ctx():
            with self._lock:
                already = self._rebuild_deferred
                self._rebuild_deferred = True
            try:
                yield self
            finally:
                with self._lock:
                    if not already:
                        self._rebuild_deferred = False
                        if self._rebuild_pending:
                            self._rebuild_pending = False
                            self._rebuild()
        return _ctx()

    def _rebuild(self) -> None:
        """Mirror hierarchy edges into the state payloads and recompute the
        subtree quotas from every root (reference resource_node.go:157)."""
        if self._rebuild_deferred:
            self._rebuild_pending = True
            return
        for node in self._mgr.cohorts.values():
            payload = node.payload
            payload.parent = node.parent.payload if node.parent else None
            payload.child_cohorts = [c.payload for c in node.child_cohorts.values()]
            payload.child_cqs = list(node.child_cqs.values())
        for name, cq in self._mgr.cluster_queues.items():
            parent_node = self._mgr.cq_parent(name)
            cq.parent = parent_node.payload if parent_node else None
        # Cohorts in a parent-edge cycle are unreachable from any root (a
        # cycle member is never parentless); break their mirrored parent
        # pointers so quota queries stay total, and deactivate their CQs.
        reachable: set[str] = set()
        roots = list(self._mgr.roots())
        for node in roots:
            for sub in node.walk_subtree():
                reachable.add(sub.name)
        # Per-root quota recomputation touches only that root's subtree
        # payloads — the cohort forest is the no-shared-state partition —
        # so the host pool can fan the roots out across workers; results
        # are order-free (disjoint writes), the serial loop is the
        # control arm.
        pool = self.host_pool
        if pool is not None and pool.active and len(roots) >= 2:
            pool.run([(lambda p=node.payload:
                       update_cohort_resource_node(p)) for node in roots])
        else:
            for node in roots:
                update_cohort_resource_node(node.payload)
        self._cyclic_cohorts = set(self._mgr.cohorts) - reachable
        for name in self._cyclic_cohorts:
            self._mgr.cohorts[name].payload.parent = None
        loose = [cq for name, cq in self._mgr.cluster_queues.items()
                 if self._mgr.cq_parent(name) is None]
        if pool is not None and pool.active and len(loose) >= 2:
            pool.run([(lambda c=cq:
                       update_cluster_queue_resource_node(c)) for cq in loose])
        else:
            for cq in loose:
                update_cluster_queue_resource_node(cq)
        self._update_all_statuses()

    def _update_all_statuses(self) -> None:
        self.structure_generation += 1
        for name, cq in self._mgr.cluster_queues.items():
            reasons = []
            for rg in cq.spec.resource_groups:
                for fq in rg.flavors:
                    if fq.name not in self.resource_flavors:
                        reasons.append(f"FlavorNotFound:{fq.name}")
            for ac in cq.spec.admission_checks:
                check = self.admission_checks.get(ac)
                if check is None or not check.active:
                    reasons.append(f"CheckNotFoundOrInactive:{ac}")
            if cq.spec.stop_policy != StopPolicy.NONE:
                reasons.append("Stopped")
            parent_node = self._mgr.cq_parent(name)
            if parent_node is not None and getattr(self, "_cyclic_cohorts", None):
                if parent_node.name in self._cyclic_cohorts:
                    reasons.append("CohortCycle")
            cq.active = not reasons
            cq.inactive_reasons = reasons
