"""The control-plane driver: event flow between store, cache, queues, scheduler.

Capability parity with reference cmd/kueue/main.go wiring plus
pkg/controller/core: a durable workload store (the CRD-status equivalent,
§5.4 — restart replays the store), reconciler-equivalent event handlers
keeping cache and queues in sync, admission application, eviction/requeue
handling with backoff, stop policies, and workload finish.

This is the single-process composition root.  The scheduler itself stays a
pure function of (snapshot, heads); everything durable lives here.
"""

from __future__ import annotations

import gc
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Optional

from ..api.types import (
    AdmissionCheck,
    AdmissionCheckState,
    ClusterQueue,
    Cohort,
    ConditionStatus,
    LocalQueue,
    ResourceFlavor,
    StopPolicy,
    Topology,
    Workload,
    EVICTED_BY_DEACTIVATION,
    EVICTED_BY_PREEMPTION,
    WL_ADMITTED,
    WL_EVICTED,
    WL_FINISHED,
    WL_QUOTA_RESERVED,
)
from ..cache.cache import Cache
from ..chaos import injector as _chaos
from ..obs import ObsPlane
from ..obs.trace import span as _span
from ..queue.manager import Manager as QueueManager
from ..utils import journal as _journal
from ..queue.cluster_queue import RequeueReason
from ..scheduler.scheduler import Scheduler
from .. import webhooks
from ..workload import (
    Info,
    InfoOptions,
    Ordering,
    next_requeue_state,
    set_finished_condition,
    set_requeued_condition,
    sync_admitted_condition,
    unset_quota_reservation,
    update_requeue_state,
)
from .. import metrics


#: What the driver collects itself where a scheduling section closes:
#: the two young generations.  Their survivors are promoted to the
#: oldest, which is left to the interpreter's own rule.
_YOUNG_GENERATION = 1


def _unpack_target_rows(words, cand_rows_g):
    """Bit-packed candidate-slot words -> flattened row ids."""
    import numpy as np
    w = np.asarray(words, dtype=np.uint32)
    set_bits = ((w[:, None] >> np.arange(32, dtype=np.uint32)) & 1) > 0
    wi, bi = np.nonzero(set_bits)
    return cand_rows_g[wi * 32 + bi]


@dataclass
class WaitForPodsReadyConfig:
    """reference apis/config/v1beta1 WaitForPodsReady (:216)."""
    enable: bool = False
    timeout_seconds: float = 300.0
    block_admission: bool = False
    requeuing_backoff_base_seconds: int = 60
    requeuing_backoff_max_seconds: int = 3600
    requeuing_backoff_limit_count: Optional[int] = None
    requeuing_timestamp: str = "Eviction"


class Driver:
    """Single-process manager wiring (reference cmd/kueue/main.go:106)."""

    def __init__(self, clock: Callable[[], float] = time.time,
                 fair_sharing: bool = False,
                 fs_preemption_strategies: list[str] | None = None,
                 info_options: InfoOptions | None = None,
                 wait_for_pods_ready: WaitForPodsReadyConfig | None = None,
                 namespaces: Optional[dict[str, dict[str, str]]] = None,
                 use_device_solver: bool = False,
                 validate: bool = True):
        self.clock = clock
        self.wait_for_pods_ready = wait_for_pods_ready or WaitForPodsReadyConfig()
        ordering = Ordering(
            pods_ready_requeuing_timestamp=self.wait_for_pods_ready.requeuing_timestamp)
        self.cache = Cache(info_options=info_options,
                           fair_sharing_enabled=fair_sharing)
        # parallel host apply/pack plane (utils/parallel_host.py):
        # KUEUE_TPU_HOST_WORKERS>=2 fans the post-cycle host work out by
        # cohort forest; the default (0) is the bit-identical serial arm
        from ..utils.parallel_host import host_pool_from_env
        self.host_pool = host_pool_from_env()
        self.cache.host_pool = self.host_pool
        self.queues = QueueManager(ordering=ordering, clock=clock,
                                   info_options=info_options)
        self.scheduler = Scheduler(
            self.queues, self.cache, fair_sharing=fair_sharing,
            fs_preemption_strategies=fs_preemption_strategies,
            ordering=ordering, clock=clock, namespaces=namespaces)
        if use_device_solver:
            from ..ops.solver import CycleSolver
            self.scheduler.solver = CycleSolver(ordering)
            shards = self._env_shards()
            if shards > 1:
                # more shards than devices raises (make_mesh)
                from ..parallel.sharded import make_mesh
                self.scheduler.solver.set_mesh(make_mesh(shards))
        self.scheduler.apply_admission = self._apply_admission
        self.scheduler.preemptor.apply_preemption = self._apply_preemption
        if self.wait_for_pods_ready.enable and self.wait_for_pods_ready.block_admission:
            self.scheduler.admission_blocked = self.admission_blocked
        # durable store: the CRD-status equivalent
        self.workloads: dict[str, Workload] = {}
        self.priority_classes: dict[str, object] = {}
        self.limit_ranges: dict[str, dict[str, object]] = {}
        self.validate = validate
        self.events: list[tuple[str, str, str]] = []  # (kind, key, note)
        self.metrics = metrics.Registry()
        self.scheduler.metrics = self.metrics
        self._burst_solver = None   # lazy BurstSolver (ops/burst.py)
        self._burst_m = 0           # sticky M bucket across burst packs
        self._burst_pack_state = None  # persistent delta-pack records
        self._wal = None            # write-ahead cycle journal (CycleWAL)
        self._bulk_applied_cqs = None  # non-None inside bulk_apply()
        self._cycle_touched = None  # non-None inside cycle_apply()
        # CQs whose interrupted-cycle decision was recovered from the
        # WAL tail: they sit out the first post-recovery cycle so the
        # completed cycle matches the uncrashed one decision-for-decision
        self._resume_mask: set[str] = set()
        # observability plane: event stream + flight recorder, always
        # attached; span tracing opt-in via KUEUE_TPU_OBS_TRACE (obs/)
        self.obs = ObsPlane.from_env(self)

    @staticmethod
    def _env_shards() -> int:
        """KUEUE_TPU_SHARDS=N activates sharded dispatch (0/1 = serial)."""
        from ..features import env_int
        return env_int("KUEUE_TPU_SHARDS")

    @classmethod
    def from_config(cls, cfg, clock: Callable[[], float] = time.time,
                    **kw) -> "Driver":
        """Build a driver from a Configuration (reference cmd/kueue/main.go
        :123-144 config→wiring + feature-gate application)."""
        from .. import features
        from ..workload import ResourceTransformation as _RT
        if cfg.feature_gates:
            features.set_feature_gates(cfg.feature_gates)
        w = cfg.wait_for_pods_ready
        wfpr = WaitForPodsReadyConfig(
            enable=w.enable,
            timeout_seconds=w.timeout_seconds,
            block_admission=w.block_admission,
            requeuing_backoff_base_seconds=(
                w.requeuing_strategy.backoff_base_seconds),
            requeuing_backoff_max_seconds=(
                w.requeuing_strategy.backoff_max_seconds),
            requeuing_backoff_limit_count=(
                w.requeuing_strategy.backoff_limit_count),
            requeuing_timestamp=w.requeuing_strategy.timestamp)
        info_options = InfoOptions(
            excluded_prefixes=list(cfg.resources.exclude_resource_prefixes),
            transformations={
                t.input: _RT(input=t.input, strategy=t.strategy,
                             outputs=dict(t.outputs))
                for t in cfg.resources.transformations})
        return cls(clock=clock,
                   fair_sharing=cfg.fair_sharing.enable,
                   fs_preemption_strategies=list(
                       cfg.fair_sharing.preemption_strategies),
                   info_options=info_options,
                   wait_for_pods_ready=wfpr, **kw)

    # ------------------------------------------------------------------
    # Resource plumbing (reconciler-equivalents)
    # ------------------------------------------------------------------

    def apply_resource_flavor(self, flavor: ResourceFlavor) -> None:
        if self.validate:
            webhooks.validate_resource_flavor(flavor)
        self.cache.add_or_update_resource_flavor(flavor)
        self._wake_all()

    def apply_topology(self, topology: Topology) -> None:
        self.cache.add_or_update_topology(topology)
        self._wake_all()

    def apply_limit_range(self, lr) -> None:
        """Namespace LimitRanges (reference pkg/util/limitrange): defaults
        applied at workload creation, bounds enforced at nomination."""
        from ..limitrange import summarize
        self.limit_ranges.setdefault(lr.namespace, {})[lr.name] = lr
        self.scheduler.limit_range_summaries[lr.namespace] = summarize(
            list(self.limit_ranges[lr.namespace].values()))
        # LimitRange summaries gate pack rows globally (no per-CQ map)
        self.queues.pack_journal.touch_all()
        # a relaxed range can unblock parked workloads
        self._wake_all()

    def apply_workload_priority_class(self, pc) -> None:
        """reference WorkloadPriorityClass (pkg/util/priority)."""
        self.priority_classes[pc.name] = pc

    def resolve_priority_class(self, name: str):
        return self.priority_classes.get(name)

    def apply_admission_check(self, check: AdmissionCheck) -> None:
        self.cache.add_or_update_admission_check(check)
        self._wake_all()

    def apply_cluster_queue(self, spec: ClusterQueue) -> None:
        if self.validate:
            webhooks.validate_cluster_queue(spec)
        self.cache.add_or_update_cluster_queue(spec)
        self.queues.add_cluster_queue(spec)
        if self._bulk_applied_cqs is not None:
            # inside bulk_apply(): activeness sync, inadmissible requeue
            # and status metrics run once over all applied CQs on exit
            self._bulk_applied_cqs.append(spec.name)
        else:
            self._sync_cq_activeness()
            self.queues.queue_inadmissible_workloads([spec.name])
            self.metrics.cluster_queue_status(
                spec.name, self.cache.cluster_queue(spec.name).active)
        if spec.stop_policy == StopPolicy.HOLD_AND_DRAIN:
            self._drain_cluster_queue(spec.name)

    def bulk_apply(self):
        """Context manager for large topology pushes (the CRD re-list on
        startup, scale tests): defers the cache's quota-tree rebuild and
        the per-apply activeness/metrics sync so N ``apply_*`` calls
        cost one O(N) settle on exit instead of N — without it, setup
        is O(N^2) and walls out near 100k CQs.  Scheduling inside the
        block sees stale quota trees; apply everything, then exit."""
        from contextlib import contextmanager

        @contextmanager
        def _ctx():
            outer = self._bulk_applied_cqs is not None
            if not outer:
                self._bulk_applied_cqs = []
            with self.cache.deferred_rebuild():
                yield self
            if not outer:
                names, self._bulk_applied_cqs = \
                    self._bulk_applied_cqs, None
                self._sync_cq_activeness()
                self.queues.queue_inadmissible_workloads(
                    names, pool=self.host_pool)
                for name in names:
                    cq = self.cache.cluster_queue(name)
                    if cq is not None:
                        self.metrics.cluster_queue_status(name, cq.active)
        return _ctx()

    def cycle_apply(self):
        """Context manager batching ONE burst cycle's decision patches:
        every evict/finish inside the block records its CQ instead of
        walking the cohort subtree for an inadmissible requeue, and the
        cache's quota-tree rebuild is deferred — so a cycle with D
        decisions costs one deduped ``queue_inadmissible_workloads``
        pass and one cache settle instead of D of each.  Safe on the
        burst apply path only: the cycle's heads and modeled decisions
        are fixed before the block, and the next cycle's heads are read
        after exit, so the deferred wakeups land at exactly the same
        observable point (the next heads read) as the eager ones.
        Opt-out: ``KUEUE_TPU_CYCLE_BULK_APPLY=0`` makes this a no-op
        passthrough to the classic per-decision path."""
        from contextlib import contextmanager

        @contextmanager
        def _ctx():
            from ..features import env_value
            if (env_value("KUEUE_TPU_CYCLE_BULK_APPLY") == "0"
                    or self._cycle_touched is not None):
                yield self
                return
            self._cycle_touched = []
            try:
                with self.cache.deferred_rebuild():
                    yield self
            finally:
                touched, self._cycle_touched = self._cycle_touched, None
            if touched:
                seen: set = set()
                names = [n for n in touched
                         if not (n in seen or seen.add(n))]
                self.queues.queue_inadmissible_workloads(
                    names, pool=self.host_pool)
        return _ctx()

    def _drain_cluster_queue(self, cq_name: str) -> None:
        """HoldAndDrain evicts admitted workloads (reference
        workload_controller.go:466 ClusterQueueStopped eviction)."""
        from ..api.types import EVICTED_BY_CQ_STOPPED
        for key, wl in list(self.workloads.items()):
            if (wl.admission is not None
                    and wl.admission.cluster_queue == cq_name
                    and wl.has_quota_reservation and not wl.is_finished):
                self._evict(wl, EVICTED_BY_CQ_STOPPED,
                            f"ClusterQueue {cq_name} is stopped")

    def delete_cluster_queue(self, name: str) -> None:
        self.cache.delete_cluster_queue(name)
        self.queues.delete_cluster_queue(name)

    def apply_cohort(self, spec: Cohort) -> None:
        if self.validate:
            webhooks.validate_cohort(spec)
        self.cache.add_or_update_cohort(spec)
        self.queues.update_cohort_edge(spec.name, spec.parent_name)
        self._wake_all()

    def apply_local_queue(self, lq: LocalQueue) -> None:
        if self.validate:
            webhooks.validate_local_queue(lq)
        self.cache.add_or_update_local_queue(lq)
        self.queues.add_local_queue(lq)
        if lq.stop_policy == StopPolicy.HOLD_AND_DRAIN:
            from ..api.types import EVICTED_BY_LQ_STOPPED
            for key, wl in list(self.workloads.items()):
                if (wl.namespace == lq.namespace
                        and wl.queue_name == lq.name
                        and wl.has_quota_reservation
                        and not wl.is_finished):
                    self._evict(wl, EVICTED_BY_LQ_STOPPED,
                                f"LocalQueue {lq.name} is stopped")

    def _sync_cq_activeness(self) -> None:
        for name in self.cache.cluster_queue_names():
            cq = self.cache.cluster_queue(name)
            if cq is not None:
                self.queues.set_cluster_queue_active(name, cq.active)

    def _wake_all(self) -> None:
        self._sync_cq_activeness()
        self.queues.queue_inadmissible_workloads(self.cache.cluster_queue_names())

    # ------------------------------------------------------------------
    # Workload lifecycle (reference core/workload_controller.go)
    # ------------------------------------------------------------------

    def _prepare_workload(self, wl: Workload) -> None:
        """Defaulting + validation + store write — everything
        ``create_workload`` does short of queueing."""
        webhooks.default_workload(wl)
        summary = self.scheduler.limit_range_summaries.get(wl.namespace)
        if summary is not None:
            from ..limitrange import apply_defaults
            for ps in wl.pod_sets:
                ps.requests = apply_defaults(ps.requests, summary)
        if self.validate:
            webhooks.validate_workload(wl)
        if wl.creation_time == 0.0:
            wl.creation_time = self.clock()
        self.workloads[wl.key] = wl

    def create_workload(self, wl: Workload) -> None:
        self._prepare_workload(wl)
        self.queues.add_or_update_workload(wl)
        self.metrics.pending_inc(wl)

    def ingest_workloads(self, wls) -> int:
        """Bulk create for the serving ingest drain: prepare every
        workload, then queue the whole batch under one manager lock
        acquisition (queue.Manager.add_workloads) instead of one per
        workload.  Same per-workload semantics as ``create_workload``;
        returns the batch size."""
        batch = list(wls)
        for wl in batch:
            self._prepare_workload(wl)
        self.queues.add_workloads(batch)
        for wl in batch:
            self.metrics.pending_inc(wl)
        return len(batch)

    def restore_workload(self, wl: Workload) -> None:
        """Crash-recovery replay (SURVEY §5.4): rebuild in-memory state
        from a stored workload — admitted usage goes back into the cache,
        pending workloads back into the queues, like the CRD watch replay
        on reference manager restart."""
        self.workloads[wl.key] = wl
        if wl.is_finished or not wl.is_active:
            return
        if wl.admission is not None and wl.has_quota_reservation:
            info = Info(wl, self.cache.info_options)
            self.cache.add_or_update_workload(info)
        else:
            self.queues.add_or_update_workload(wl)

    def attach_wal(self, wal) -> None:
        """Attach a write-ahead cycle journal (utils.journal.CycleWAL):
        every admit/evict/requeue/finish decision is journaled before
        the store mutation it describes, and each cycle's batch is
        committed at the cycle boundary.  The host pool announces its
        workers to a sharded WAL so segment striping engages (and the
        per-segment commit flushes fan out); with the pool inactive the
        sharded WAL collapses to one hot segment."""
        if self._wal is not None:
            self.host_pool.detach_wal(self._wal)
        self._wal = wal
        if wal is not None:
            self.host_pool.attach_wal(wal)

    def recover_from(self, stored, wal=None) -> int:
        """Crash recovery (SURVEY §5.4 + the WAL): roll the journal's
        uncommitted tail forward over the surviving store — using the
        journaled timestamps, so the replayed status is bit-identical
        to the uncrashed apply — then rebuild cache and queues from the
        rolled-forward store via ``restore_workload``.  ``stored`` is
        the durable workload store of the crashed driver (any iterable
        of Workload); returns the number of tail ops replayed.  The WAL
        stays attached, with its recovered tail committed."""
        store = {wl.key: wl for wl in stored}
        n = 0
        mask: set[str] = set()
        if wal is not None:
            # an admit in the tail means its CQ's head slot for the
            # interrupted cycle was consumed before the crash — that CQ
            # must sit out the cycle's re-run or it would admit its next
            # head a cycle earlier than the uncrashed driver did
            for op in wal.tail:
                if op.get("op") == "admit":
                    mask.add(op["admission"]["cluster_queue"])
            n = wal.replay_tail(store)
            wal.commit()   # the tail is now fully reflected in state
        for wl in store.values():
            self.restore_workload(wl)
        self._wal = wal
        self._resume_mask = mask
        return n

    def delete_workload(self, key: str) -> None:
        wl = self.workloads.pop(key, None)
        if wl is None:
            return
        self.queues.delete_workload(wl)
        if wl.admission is not None:
            self.cache.delete_workload(Info(wl))
            self.queues.queue_inadmissible_workloads([wl.admission.cluster_queue])
        self.events.append(("Deleted", key, ""))
        self.wake_gate_blocked()   # deleting a not-ready blocker opens the gate

    def finish_workload(self, key: str, message: str = "Job finished") -> None:
        """Quota release on completion (reference jobframework finished path)."""
        self.finish_workloads([key], message=message)

    def finish_workloads(self, keys, message: str = "Job finished") -> None:
        """Batched finish: quota released per workload, with ONE
        cohort-wide inadmissible wakeup per touched CQ set instead of a
        subtree walk per workload (manager.go:490 semantics are
        idempotent within a batch — the wakeup sees the post-release
        state either way)."""
        with _span("boundary"):
            touched: list[str] = []
            seen: set[str] = set()
            any_done = False
            now = self.clock()
            if self._wal is not None:
                live = [k for k in keys
                        if (w := self.workloads.get(k)) is not None
                        and not w.is_finished]
                if live:
                    self._wal.log(_journal.finish_op(live, message, now))
            if _chaos.ACTIVE is not None:
                _chaos.ACTIVE.crashpoint("wal.finish")
            for key in keys:
                wl = self.workloads.get(key)
                if wl is None or wl.is_finished:
                    continue
                set_finished_condition(wl, "JobFinished", message, now)
                if wl.admission is not None:
                    cq_name = wl.admission.cluster_queue
                    was_admitted = wl.is_admitted
                    self.cache.delete_workload(Info(wl))
                    self.metrics.release_reservation(cq_name)
                    if was_admitted:
                        self.metrics.release_admitted(cq_name)
                    if cq_name not in seen:
                        seen.add(cq_name)
                        touched.append(cq_name)
                self.queues.delete_workload(wl)
                self.events.append(("Finished", key, message))
                any_done = True
            if touched:
                if self._cycle_touched is not None:
                    self._cycle_touched.extend(touched)
                else:
                    self.queues.queue_inadmissible_workloads(touched)
            if any_done:
                self.wake_gate_blocked()
            if self._wal is not None:
                self.host_pool.commit_wal(self._wal)

    def update_reclaimable_pods(self, key: str, counts: dict[str, int]) -> None:
        """reference workload.UpdateReclaimablePods (KEP 78): shrink the
        quota charged for pods that finished early."""
        from ..api.types import ReclaimablePod
        wl = self.workloads.get(key)
        if wl is None or wl.is_finished:
            return
        existing = {rp.name: rp.count for rp in wl.reclaimable_pods}
        changed = False
        for name, count in counts.items():
            # reclaim counts only grow (reference validation)
            if count > existing.get(name, 0):
                existing[name] = count
                changed = True
        if not changed:
            return
        # the admitted usage shrinks; the fresh Info below replaces the
        # cached one in the cache CQ, so per-Info burst usage vectors
        # (ops/burst.py admitted_usage_vec) can never go stale
        wl.reclaimable_pods = [ReclaimablePod(name=n, count=c)
                               for n, c in sorted(existing.items())]
        if wl.admission is not None:
            # re-charge the cache with the shrunk usage
            self.cache.add_or_update_workload(Info(wl))
            if wl.admission.cluster_queue:
                self.queues.queue_inadmissible_workloads(
                    [wl.admission.cluster_queue])
        else:
            self.queues.add_or_update_workload(wl)

    def deactivate_workload(self, key: str) -> None:
        wl = self.workloads.get(key)
        if wl is None:
            return
        if self._wal is not None:
            self._wal.log(_journal.deactivate_op(key))
        wl.active = False
        now = self.clock()
        if wl.admission is not None:
            self._evict(wl, EVICTED_BY_DEACTIVATION, "The workload is deactivated")
        self.queues.delete_workload(wl)

    def set_admission_check_state(self, key: str, check: str,
                                  state: AdmissionCheckState,
                                  message: str = "") -> None:
        """Two-phase admission: external controllers flip check states
        (reference workload_controller.go:409)."""
        wl = self.workloads.get(key)
        if wl is None or check not in wl.admission_check_states:
            return
        now = self.clock()
        st = wl.admission_check_states[check]
        st.state = state
        st.message = message
        st.last_transition_time = now
        # check states gate pack rows but mutate in place (no queue or
        # cache write on the pending path) — row-grade dirt: exactly
        # this workload's ok bit can move, the CQ's membership and
        # aggregates cannot.  Structural follow-ons below (admitted
        # sync, eviction) journal their own hard touches, which
        # supersede the row entry at drain time.
        lq = self.queues.local_queues.get(f"{wl.namespace}/{wl.queue_name}")
        if lq is not None:
            self.queues.pack_journal.touch_row(lq.cluster_queue, key)
        elif wl.admission is not None:
            self.queues.pack_journal.touch_row(
                wl.admission.cluster_queue, key)
        else:
            self.queues.pack_journal.touch_all()
        if state == AdmissionCheckState.READY:
            if sync_admitted_condition(wl, now):
                cq_name = wl.admission.cluster_queue if wl.admission else ""
                self.metrics.admitted_workload(cq_name,
                                               now - wl.creation_time)
                reserved = wl.conditions.get(WL_QUOTA_RESERVED)
                if reserved is not None:
                    self.metrics.admission_checks_wait(
                        cq_name, now - reserved.last_transition_time)
                if wl.admission is not None:
                    info = Info(wl, self.cache.info_options)
                    self.cache.add_or_update_workload(info)
        elif state in (AdmissionCheckState.RETRY, AdmissionCheckState.REJECTED):
            self._evict(wl, "AdmissionCheck", f"Admission check {check}: {state.value}")
            if state == AdmissionCheckState.REJECTED:
                self.deactivate_workload(key)

    # ------------------------------------------------------------------
    # Scheduler side-effects
    # ------------------------------------------------------------------

    def _apply_admission(self, new_wl: Workload) -> bool:
        """The SSA apply-equivalent: land admission in the store
        (reference scheduler.go applyAdmissionWithSSA)."""
        cur = self.workloads.get(new_wl.key)
        if cur is None or cur.is_finished or not cur.is_active:
            return False
        if self._wal is not None:
            self._wal.log(_journal.admit_op(new_wl))
        if _chaos.ACTIVE is not None:
            _chaos.ACTIVE.crashpoint("wal.admit")
        self.workloads[new_wl.key] = new_wl
        self.queues.delete_workload(new_wl)
        cq = new_wl.admission.cluster_queue
        now = self.clock()
        self.metrics.quota_reserved(cq, now - new_wl.creation_time)
        if new_wl.is_admitted:
            self.metrics.admitted_workload(cq, now - new_wl.creation_time)
        self.events.append(("QuotaReserved", new_wl.key, cq))
        self.obs.emit("admit", new_wl.key, cq, "QuotaReserved")
        return True

    def _apply_preemption(self, info: Info, reason: str, message: str) -> None:
        """Eviction by preemption: update store, release quota, requeue
        (reference WorkloadReconciler eviction path)."""
        wl = self.workloads.get(info.key)
        if wl is None:
            return
        self._evict(wl, EVICTED_BY_PREEMPTION, message, preempted_reason=reason)
        self.events.append(("Preempted", info.key, reason))
        self.obs.emit("preempt", info.key,
                      getattr(info, "cluster_queue", "") or "", reason,
                      note=message)

    def _evict(self, wl: Workload, reason: str, message: str,
               preempted_reason: str | None = None) -> None:
        from ..workload import (set_evicted_condition,
                                set_pods_ready_condition,
                                set_preempted_condition)
        now = self.clock()
        if self._wal is not None:
            self._wal.log(_journal.evict_op(wl.key, reason, message,
                                            preempted_reason, now))
        if _chaos.ACTIVE is not None:
            _chaos.ACTIVE.crashpoint("wal.evict")
        cq_name = wl.admission.cluster_queue if wl.admission else ""
        set_evicted_condition(wl, reason, message, now)
        # eviction stops the pods: a stale PodsReady=True must not exempt
        # a future readmission from the timeout or open the gate
        from ..api.types import WL_PODS_READY
        if WL_PODS_READY in wl.conditions:
            set_pods_ready_condition(wl, False, now)
        if preempted_reason is not None:
            set_preempted_condition(wl, preempted_reason, message, now)
        # reset admission check states on eviction
        for st in wl.admission_check_states.values():
            st.state = AdmissionCheckState.PENDING
        if wl.admission is not None:
            was_admitted = wl.is_admitted
            self.cache.delete_workload(Info(wl))
            self.metrics.release_reservation(cq_name)
            if was_admitted:
                self.metrics.release_admitted(cq_name)
            unset_quota_reservation(wl, reason, message, now)
        self.metrics.evicted(cq_name, reason)
        self.obs.emit("evict", wl.key, cq_name, reason, note=message)
        # requeue: back into the pending queues
        set_requeued_condition(wl, reason, message, True, now)
        if wl.is_active:
            self.queues.add_or_update_workload(wl)
            self.obs.emit("requeue", wl.key, cq_name, reason)
        if cq_name:
            if self._cycle_touched is not None:
                self._cycle_touched.append(cq_name)
            else:
                self.queues.queue_inadmissible_workloads([cq_name])
        self.wake_gate_blocked()   # evicting a not-ready blocker opens the gate

    def refresh_resource_metrics(self) -> None:
        """Per-CQ resource gauges + LQ mirrors (reference
        ClusterQueueReconciler.recordResourceMetrics,
        clusterqueue_controller.go:382)."""
        from ..resources import FlavorResource
        for name in self.cache.cluster_queue_names():
            cq = self.cache.cluster_queue(name)
            if cq is None:
                continue
            usage = self.cache.usage(name)
            for rg in cq.spec.resource_groups:
                for fq in rg.flavors:
                    for rname, quota in fq.resources.items():
                        fr = FlavorResource(fq.name, rname)
                        used = usage.get(fr, 0)
                        self.metrics.report_resource_usage(
                            name, fq.name, rname, used, quota.nominal,
                            reservation=used,
                            borrowing_limit=quota.borrowing_limit,
                            lending_limit=quota.lending_limit)
        self.metrics.sample_pending(self.queues)
        self.metrics.obs_sample(self.obs.events.report(),
                                self.obs.flight.recorded_total)
        # LocalQueue mirrors (LocalQueueMetrics feature gate)
        from .. import features
        if features.enabled("LocalQueueMetrics"):
            per_lq: dict[str, list[int]] = {}
            for wl in self.workloads.values():
                key = f"{wl.namespace}/{wl.queue_name}"
                counts = per_lq.setdefault(key, [0, 0, 0])
                if wl.is_finished or not wl.is_active:
                    continue
                if wl.is_admitted:
                    counts[2] += 1
                    counts[1] += 1
                elif wl.has_quota_reservation:
                    counts[1] += 1
                else:
                    counts[0] += 1
            for key, (pending, reserving, admitted) in per_lq.items():
                ns, _, lq = key.partition("/")
                self.metrics.local_queue_counts(ns, lq, pending,
                                                reserving, admitted)

    def check_maximum_execution_times(self) -> list[str]:
        """Deactivate workloads admitted longer than their
        maximumExecutionTimeSeconds (reference workload_controller.go:354).
        Returns the deactivated keys."""
        now = self.clock()
        out = []
        for key, wl in list(self.workloads.items()):
            limit = wl.maximum_execution_time_seconds
            if limit is None or not wl.is_admitted or wl.is_finished:
                continue
            adm = wl.conditions.get(WL_ADMITTED)
            if adm is not None and now - adm.last_transition_time >= limit:
                self.deactivate_workload(key)
                self.events.append(("MaximumExecutionTimeExceeded", key,
                                    f"exceeded {limit}s"))
                out.append(key)
        return out

    def evict_for_pods_ready_timeout(self, key: str) -> None:
        """WaitForPodsReady timeout (reference workload_controller.go:546)."""
        wl = self.workloads.get(key)
        if wl is None or wl.admission is None:
            return
        cfg = self.wait_for_pods_ready
        now = self.clock()
        if self._wal is not None:
            count, requeue_at = next_requeue_state(
                wl, cfg.requeuing_backoff_base_seconds,
                cfg.requeuing_backoff_max_seconds, now)
            self._wal.log(_journal.requeue_op(key, count, requeue_at))
        if _chaos.ACTIVE is not None:
            _chaos.ACTIVE.crashpoint("wal.requeue")
        update_requeue_state(wl, cfg.requeuing_backoff_base_seconds,
                             cfg.requeuing_backoff_max_seconds, now)
        limit = cfg.requeuing_backoff_limit_count
        if limit is not None and wl.requeue_state.count > limit:
            self.deactivate_workload(key)
            return
        self._evict(wl, "PodsReadyTimeout",
                    f"Exceeded the PodsReady timeout {cfg.timeout_seconds}s")

    # ------------------------------------------------------------------
    # WaitForPodsReady enforcement (reference workload_controller.go:546
    # timeout countdown; scheduler.go:268-279 blockAdmission)
    # ------------------------------------------------------------------

    def set_pods_ready(self, key: str, ready: bool) -> None:
        """Sync a workload's PodsReady condition (the jobframework
        reconciler calls this from the job's pods_ready()); a transition
        to ready wakes the scheduler (cache.podsReadyCond broadcast,
        reference cache.go:214)."""
        if not self.wait_for_pods_ready.enable:
            return  # the reference maintains PodsReady only when enabled
        wl = self.workloads.get(key)
        if wl is None or wl.is_finished:
            return
        from ..workload import set_pods_ready_condition
        if set_pods_ready_condition(wl, ready, self.clock()) and ready:
            self.wake_gate_blocked()

    def wake_gate_blocked(self) -> None:
        """Unpark gate-held entries when the blockAdmission gate opens.

        The gate opens whenever the last admitted-not-ready workload
        stops being one — pods ready, eviction (incl. the PodsReady
        timeout), finish, delete, deactivation — and held entries may be
        parked in ANY cohort, so every gate-opening event must wake all
        of them (the reference blocks in-cycle instead and has no parked
        entries to lose, scheduler.go:277)."""
        cfg = self.wait_for_pods_ready
        if not (cfg.enable and cfg.block_admission):
            return
        if not self.scheduler.gate_parked:
            return  # the gate never held anything: nothing to wake
        if self.pods_ready_for_all_admitted():
            self.scheduler.gate_parked = False
            self.queues.queue_inadmissible_workloads(
                list(self.queues.cluster_queue_names()))
            self.queues.broadcast()

    def pods_ready_for_all_admitted(self) -> bool:
        """reference cache.go:187 PodsReadyForAllAdmittedWorkloads."""
        from ..api.types import WL_PODS_READY
        for wl in list(self.workloads.values()):
            if (wl.is_admitted and wl.is_active and not wl.is_finished
                    and not wl.condition_true(WL_PODS_READY)):
                return False
        return True

    def admission_blocked(self) -> bool:
        """blockAdmission gate: with WaitForPodsReady blocking enabled,
        no new admission while any admitted workload lacks PodsReady
        (reference scheduler.go:268-279; held entries requeue and the
        PodsReady transition wakes them instead of blocking in-cycle)."""
        cfg = self.wait_for_pods_ready
        return (cfg.enable and cfg.block_admission
                and not self.pods_ready_for_all_admitted())

    def enforce_wait_for_pods_ready(self) -> list[str]:
        """Automatic PodsReady deadline tracking: evict every admitted
        workload that exceeded the timeout without reaching PodsReady
        (reference workload_controller.go:546-595 requeue-after timers).
        Runs each cycle and on daemon ticks; returns the evicted keys."""
        cfg = self.wait_for_pods_ready
        if not cfg.enable or not cfg.timeout_seconds:
            return []
        from ..api.types import WL_ADMITTED, WL_PODS_READY
        now = self.clock()
        out = []
        for key, wl in list(self.workloads.items()):
            if (not wl.is_admitted or wl.is_finished
                    or wl.condition_true(WL_PODS_READY)):
                continue
            adm = wl.conditions.get(WL_ADMITTED)
            if adm is None:
                continue
            if now - adm.last_transition_time >= cfg.timeout_seconds:
                self.evict_for_pods_ready_timeout(key)
                out.append(key)
        return out

    # ------------------------------------------------------------------
    # Run loop
    # ------------------------------------------------------------------

    @contextmanager
    def _scheduling_section(self):
        """Hold the cyclic collector off for the length of a scheduling
        call and collect the young generations once at its end.

        The program's objects die by reference count; an automatic
        collection in the middle of a cycle finds next to nothing, and a
        full one walks the whole long-lived heap with the device idle.
        From the entry of ``schedule_burst`` or ``schedule_once`` to the
        call's return no automatic collection starts.  At the return the
        driver reads the young generation's count (the container
        allocations, less the deallocations, that the section held
        back), collects the young generations inside a ``host.collect``
        span and hands the collector back enabled, as it found it.  Full
        collections stay the interpreter's, by its own rule, and so fall
        between two calls.

        Sections nest (``schedule_burst`` falls back to
        ``schedule_once``): an inner one finds the collector held and
        does nothing.  So does every section of a caller that has
        disabled the collector itself: that caller owns it, and finds
        it disabled and uncollected after the call.

        The effect is process-wide.  While a section is open no thread
        triggers a collection (a service's ingest, the WAL writer, the
        host pool): cyclic garbage they make goes at the section's
        exit.  The caller's ``on_cycle_start`` / ``on_cycle`` hooks run
        inside the section."""
        if not gc.isenabled():
            yield
            return
        gc.disable()
        try:
            yield
        finally:
            try:
                solver = self.scheduler.solver
                if solver is not None:
                    solver.stats["collector_deferred_allocations"] += (
                        gc.get_count()[0])
                with _span("host.collect"):
                    gc.collect(_YOUNG_GENERATION)
            finally:
                gc.enable()

    def schedule_once(self):
        with self._scheduling_section():
            if _chaos.ACTIVE is not None:
                _chaos.ACTIVE.crashpoint("cycle.start")
            if self.wait_for_pods_ready.enable:
                self.enforce_wait_for_pods_ready()
            self.queues.wake_expired_backoffs()
            if self._resume_mask:
                # complete the WAL-recovered interrupted cycle: CQs whose
                # decision already replayed are held back (their popped
                # heads go straight back into the queues), so this cycle's
                # decisions land exactly where the uncrashed run put them
                mask, self._resume_mask = self._resume_mask, set()
                kept = []
                for info in self.queues.heads_nonblocking():
                    wl = info.obj
                    lq = self.queues.local_queues.get(
                        f"{wl.namespace}/{wl.queue_name}")
                    if lq is not None and lq.cluster_queue in mask:
                        self.queues.add_or_update_workload(wl)
                    else:
                        kept.append(info)
                stats = self.scheduler.schedule(heads=kept)
            else:
                stats = self.scheduler.schedule()
            self.metrics.admission_attempt(bool(stats.admitted),
                                           stats.duration_s)
            if self._wal is not None:
                self.host_pool.commit_wal(self._wal)
            self.obs.record_cycle(stats)
            return stats

    def schedule_burst(self, max_cycles: int, runtime: int = 0,
                       external_finishes: Optional[dict] = None,
                       on_cycle: Optional[Callable] = None,
                       on_cycle_start: Optional[Callable] = None,
                       pipeline: bool = True) -> list:
        """Run up to ``max_cycles`` cycles, fusing runs of clean cycles
        into single device dispatches (kueue_tpu.ops.burst) and falling
        back to the normal per-cycle path whenever a cycle needs host
        semantics (preemption, scalar heads) or the modeled heads diverge
        from the live queues.

        ``runtime`` > 0 models fake execution: a workload admitted at
        applied-cycle j is finished at cycle j+runtime (the perf
        harness's contract — reference runner/controller/controller.go
        :113).  ``external_finishes`` maps cycle offsets (relative to
        this call) to workload keys admitted BEFORE the call that finish
        at that offset; the driver performs both kinds of finishes
        itself.  ``on_cycle_start(k)`` / ``on_cycle(k, stats)`` bracket
        each applied cycle (clock advancement, bookkeeping).

        ``pipeline`` double-buffers the burst boundary: after a window
        with no modeled-dirty cycle is fetched, the NEXT window is dispatched
        speculatively off the kernel's final carry — device-resident,
        no host re-pack — before this window's apply loop starts, so
        pack+dispatch overlap apply instead of landing serially in one
        cycle.  A speculative window is only ever consumed when every
        cycle of the window it chained from applied exactly as modeled
        and the structure generation is unchanged; anything else
        (dirty truncation, heads divergence, clock-order violation,
        vanished preempt target, structure drift) discards it unused
        and the serial pack path decides — decisions are bit-identical
        to pipeline-off by construction.

        Returns the list of per-cycle CycleStats actually applied."""
        with self._scheduling_section(), _span("burst"):
            return self._schedule_burst(max_cycles, runtime,
                                        external_finishes, on_cycle,
                                        on_cycle_start, pipeline)

    def _schedule_burst(self, max_cycles: int, runtime: int,
                        external_finishes: Optional[dict],
                        on_cycle: Optional[Callable],
                        on_cycle_start: Optional[Callable],
                        pipeline: bool) -> list:
        """``schedule_burst``'s loop, inside its ``burst`` span: what no
        child span covers is the span's self time."""
        import numpy as np
        from ..ops.burst import (BurstSolver, fs_window_statics,
                                 pack_burst_cached, K_BURST_LADDER)

        ext = {int(k): list(v) for k, v in
               (external_finishes or {}).items()}
        out: list = []
        if self._burst_solver is None:
            self._burst_solver = BurstSolver()
            shards = self._env_shards()
            if shards > 1:
                self._burst_solver.set_shards(shards)
        fair = self.scheduler.fair_sharing
        burst_ineligible = (
            (self.wait_for_pods_ready.enable
             and self.wait_for_pods_ready.block_admission)
            # the sharded window has no tournament
            or (fair and self._burst_solver.n_shards > 1))
        solver = self.scheduler.solver
        normal_streak = 0   # cycles to run normally before re-bursting

        from ..api.types import WL_QUOTA_RESERVED

        def _reservation_ts(key):
            wl = self.workloads.get(key)
            if wl is None or not wl.has_quota_reservation:
                return None
            c = wl.conditions.get(WL_QUOTA_RESERVED)
            return c.last_transition_time if c is not None else None

        # a finish obligation is bound to the ADMISSION that scheduled
        # it: a workload preempted and re-admitted in between must get a
        # full new run, not a truncated one (the host harness prunes
        # stale entries the moment the reservation drops)
        sched_ts: dict = {key: _reservation_ts(key)
                          for keys in ext.values() for key in keys}
        # the reservation each applied cycle's admissions took: a
        # modeled run ends ``runtime`` cycles after the admission that
        # began it, and ends that one alone
        adm_ts: list[dict] = []

        def finish_cycle(stats) -> None:
            """Record one applied cycle + its end-of-cycle finishes.

            Finish time is tracked separately on the stats
            (``finish_s``): it is workload-controller work, not
            scheduler-cycle latency — per-cycle benchmarks exclude it
            the same way the per-cycle harness loop does."""
            import time as _time
            k = len(out)
            out.append(stats)
            adm_ts.append({key: _reservation_ts(key)
                           for key in stats.admitted})
            for key in stats.admitted:
                sched_ts[key] = _reservation_ts(key)
            due = [key for key in ext.pop(k, [])
                   if _reservation_ts(key) == sched_ts.get(key)]
            if runtime > 0 and k - runtime >= 0:
                began = adm_ts[k - runtime]
                due.extend(key for key in out[k - runtime].admitted
                           if _reservation_ts(key) == began[key])
            t0 = _time.perf_counter()
            batch = [key for key in due
                     if (wl := self.workloads.get(key)) is not None
                     and wl.has_quota_reservation]
            if batch:
                self.finish_workloads(batch)
            stats.finish_s = _time.perf_counter() - t0
            if self._wal is not None:
                self.host_pool.commit_wal(self._wal)
            self.obs.record_cycle(stats)
            if on_cycle is not None:
                # the caller's code, not this loop's own: a benchmark
                # stops its profiler in here
                with _span("burst.callbacks"):
                    on_cycle(k, stats)

        def quiescent() -> bool:
            """Nothing can make further cycles non-empty: no eligible
            heads now, no pending backoff timer, and no future finish
            (external or modeled-runtime) that could unpark work."""
            if any(off >= len(out) for off in ext):
                return False
            if runtime > 0 and any(
                    out[j].admitted for j in
                    range(max(0, len(out) - runtime), len(out))):
                return False
            for name in self.queues.cluster_queue_names():
                q = self.queues.queue_for(name)
                if q is None or not q.active:
                    continue
                if len(q.heap):
                    return False     # a head exists right now
                for info in q.inadmissible.values():
                    rs = info.obj.requeue_state
                    if rs is not None and rs.requeue_at is not None:
                        return False  # a backoff timer will fire
            return True

        def normal_cycle(heads=None, advance=True) -> bool:
            """One normal-path cycle; False when the queues were empty."""
            if advance and on_cycle_start is not None:
                with _span("burst.callbacks"):
                    on_cycle_start(len(out))
            if heads is None:
                stats = self.schedule_once()
            else:
                stats = self.scheduler.schedule(heads=heads)
                self.metrics.admission_attempt(bool(stats.admitted),
                                               stats.duration_s)
            finish_cycle(stats)
            return bool(stats.admitted or stats.skipped
                        or stats.inadmissible or stats.preempting)

        dirty_backoff = 0
        bstats = self._burst_solver.stats
        spec = None          # speculative BurstHandle for the next window
        plan = handle = None
        last_adm_clock = None
        clock_monotone = True

        def cancel_spec(h):
            """Discard an in-flight speculative window unfetched — its
            assumptions were invalidated; it must never be applied."""
            if h is not None:
                bstats["burst_spec_cancelled"] += 1
                bstats["burst_cycles_discarded"] += h.K
            return None

        while len(out) < max_cycles:
            if _chaos.ACTIVE is not None:
                _chaos.ACTIVE.crashpoint("burst.window_boundary")
                if (spec is not None and _chaos.ACTIVE.hit(
                        "burst.force_spec_divergence") is not None):
                    # chaos forces the pipeline cancel path: the
                    # speculative window is discarded unconsumed and the
                    # serial pack decides — bit-identical by the same
                    # argument as every organic cancel
                    bstats["burst_chaos_divergences"] = (
                        bstats.get("burst_chaos_divergences", 0) + 1)
                    spec = cancel_spec(spec)
            if (burst_ineligible or solver is None or normal_streak > 0
                    or self._resume_mask):
                # a pending resume mask routes the first post-recovery
                # cycle through schedule_once, which completes the
                # WAL-interrupted cycle before bursting resumes
                spec = cancel_spec(spec)
                if normal_streak > 0 and not burst_ineligible:
                    bstats["burst_suppressed_cycles"] += 1
                normal_streak = max(0, normal_streak - 1)
                if not normal_cycle() and quiescent():
                    break
                continue
            st = solver._structure
            if (st is None
                    or st.generation != self.cache.structure_generation):
                # structure drifted: one snapshot rebuilds the cached
                # tensors; steady-state re-packs skip the snapshot cost
                st = solver._structure_for(self.cache.snapshot(), [])
                spec = cancel_spec(spec)
            remaining = max_cycles - len(out)
            if spec is not None:
                # pipelined boundary: this window's pack+dispatch
                # already ran, overlapped with the previous apply loop
                handle, spec = spec, None
                plan, K = handle.plan, handle.K
                st = plan.structure
                bstats["burst_overlapped_packs"] += 1
            else:
                K = next((r for r in K_BURST_LADDER if r >= min(
                    remaining, K_BURST_LADDER[-1])), K_BURST_LADDER[-1])
                # the last window is applied or dropped: let go of it,
                # so that the pack writes this window's planes over its
                # snapshot (cache/arena.py) and maps no fresh pages
                plan = handle = None
                _t_pack = time.perf_counter()
                with _span("burst.pack"):
                    plan, self._burst_pack_state, _ = pack_burst_cached(
                        st, self.queues, self.cache, self.scheduler,
                        self.clock, state=self._burst_pack_state,
                        min_m=self._burst_m, window=K, stats=bstats)
                bstats["burst_pack_s"] += time.perf_counter() - _t_pack
                bstats["burst_packs"] += 1
                if fair and plan is not None:
                    plan.fs_statics = fs_window_statics(
                        st, self.cache.snapshot)
                    if plan.fs_statics is None:
                        plan = None      # the int32 DRS could overflow
                if plan is None:
                    if not normal_cycle() and quiescent():
                        break
                    continue
                self._burst_m = max(self._burst_m, plan.M)
                F = st.n_frs
                ext_release = np.zeros((K, plan.C, F), dtype=np.int32)
                ext_unpark = np.zeros((K, plan.G), dtype=bool)
                # the kernel must model EVERY release during its window:
                # the caller's external schedule plus the still-pending
                # modeled finishes of cycles applied earlier in this
                # call (a re-pack after truncation starts a fresh
                # release ring)
                sched = {k: list(v) for k, v in ext.items()}
                if runtime > 0:
                    for j in range(max(0, len(out) - runtime), len(out)):
                        due = j + runtime
                        keys = [key for key in out[j].admitted
                                if _reservation_ts(key) is not None
                                and _reservation_ts(key)
                                == adm_ts[j][key]]
                        if keys:
                            sched.setdefault(due, []).extend(keys)
                if not self._fill_burst_finishes(st, plan, sched,
                                                 len(out), K,
                                                 ext_release, ext_unpark):
                    if not normal_cycle() and quiescent():
                        break
                    continue
                with _span("burst.dispatch"):
                    handle = self._burst_solver.dispatch(
                        plan, K, runtime, ext_release, ext_unpark)
                # a fresh pack re-read the live reservation timestamps;
                # candidate ordering inside the kernel assumes they
                # strictly increase across applied cycles (and past
                # every pre-burst reservation) — track it and refuse to
                # apply modeled preempt cycles if violated
                last_adm_clock = plan.max_res_ts
                clock_monotone = True
            # flags-first fetch: block only on the tiny replicated dirty
            # flags (the spec gate's whole input) and park the carry, so
            # the chained next-window dispatch is issued BEFORE the full
            # decision planes are assembled — each shard's decision
            # transfer then overlaps the chained kernel and this
            # window's apply loop instead of serializing ahead of them
            with _span("burst.fetch"):
                dirty, dirty_reason = self._burst_solver.fetch_flags(handle)
            base = len(out)
            # two-slot pipeline: chain the NEXT window off this one's
            # final carry before applying, so its kernel computes while
            # the host applies this window.  Only windows whose model is
            # fully clean can seed a chain, and finish events the carry
            # cannot represent force the serial path: external finishes
            # inside or past the next window, or runtime > K (a PRE-pack
            # admission's finish could then land past this window — the
            # carry only models finishes of in-kernel admissions).
            if (pipeline and remaining > K and runtime <= K
                    and not bool(np.asarray(dirty).any())
                    and not any(off >= base + K for off in ext)):
                F = st.n_frs
                with _span("burst.dispatch"):
                    spec = self._burst_solver.dispatch_next(
                        handle,
                        np.zeros((K, plan.C, F), dtype=np.int32),
                        np.zeros((K, plan.G), dtype=bool))
            with _span("burst.fetch"):
                (head_row, kind, slot, tried, borrows, tgt_words, dirty,
                 dirty_reason) = self._burst_solver.fetch(handle)
            from ..ops import burst as _b
            kind_name = {_b.KIND_ADMIT: "admit", _b.KIND_SKIP: "skip",
                         _b.KIND_PARK: "park", _b.KIND_PREEMPT: "preempt",
                         _b.KIND_RESERVE: "reserve",
                         _b.KIND_OVERLAP_SKIP: "overlap_skip",
                         _b.KIND_PRE_NOFIT: "pre_nofit"}
            cand_rows = plan.arrays["cand_rows"]
            forest_of_cq = plan.arrays["forest_of_cq"]
            st_names = st.cq_names
            applied = 0
            consumed = 0     # window cycles applied or empty, not dropped
            drained = False
            window_complete = False
            for k in range(K):
                if len(out) >= max_cycles:
                    break
                modeled: dict = {}
                has_pre_kind = False
                for ci in np.nonzero(head_row[k] >= 0)[0]:
                    ci = int(ci)
                    key = plan.keys[ci][int(head_row[k, ci])]
                    kd = kind_name.get(int(kind[k, ci]), "park")
                    targets = None
                    if kd == "preempt":
                        rows = _unpack_target_rows(
                            tgt_words[k, ci], cand_rows[forest_of_cq[ci]])
                        targets = []
                        for r in rows:
                            tci, tmi = divmod(int(r), plan.M)
                            targets.append((plan.keys[tci][tmi],
                                            st_names[tci]))
                    if kd in ("preempt", "reserve", "overlap_skip",
                              "pre_nofit"):
                        has_pre_kind = True
                    modeled[key] = (kd, slot[k, ci], tried[k, ci],
                                    bool(borrows[k, ci]), targets)
                if not dirty[k] and not modeled and quiescent():
                    drained = True
                    break
                # the cycle boundary in schedule_once order: advance the
                # caller's clock FIRST, then fire deadline/backoff timers
                # at the new time, then pop heads
                if on_cycle_start is not None:
                    with _span("burst.callbacks"):
                        on_cycle_start(len(out))
                with _span("queue.heads"):
                    if self.wait_for_pods_ready.enable:
                        self.enforce_wait_for_pods_ready()
                    self.queues.wake_expired_backoffs()
                    heads = self.queues.heads_nonblocking()
                if dirty[k]:
                    bstats["burst_dirty_cycles"] += 1
                    r = int(dirty_reason[k])
                    if r & _b.DIRTY_PREEMPT:
                        bstats["burst_dirty_preempt"] += 1
                    if r & _b.DIRTY_SCALAR:
                        bstats["burst_dirty_scalar"] += 1
                    if r & _b.DIRTY_RESUME:
                        bstats["burst_dirty_resume"] += 1
                    if r & _b.DIRTY_FAIR_PREEMPT:
                        bstats["burst_fair_dirty_cycles"] += 1
                    normal_cycle(heads=heads, advance=False)
                    if applied == 0:
                        dirty_backoff = min(8, max(1, 2 * dirty_backoff))
                        normal_streak = dirty_backoff
                    break   # kernel state is stale past a host cycle
                if has_pre_kind and not clock_monotone:
                    # modeled candidate order may diverge from the host's
                    # reservation-timestamp order: decide on the host
                    normal_cycle(heads=heads, advance=False)
                    break
                if {h.key for h in heads} != set(modeled):
                    # unmodeled divergence: decide this cycle normally
                    normal_cycle(heads=heads, advance=False)
                    break
                if not modeled:
                    # empty cycle: pending finishes may unpark work
                    normal_cycle(heads=[], advance=False)
                    consumed += 1
                    continue
                # one settle per cycle: evict/finish wakeups inside the
                # block collapse into a single deduped requeue pass at
                # exit — before the next heads read, so the observable
                # order matches the eager path decision-for-decision
                with self.cycle_apply():
                    with _span("burst.apply"):
                        stats = self.scheduler.apply_burst_cycle(heads,
                                                                 modeled)
                    if stats is not None:
                        if has_pre_kind:
                            bstats["burst_preempt_cycles"] += 1
                        if fair:
                            solver.stats["fs_window_cycles"] += 1
                        self.metrics.admission_attempt(
                            bool(stats.admitted), stats.duration_s)
                        if stats.admitted:
                            # the ACTUAL reservation timestamps just
                            # recorded — a resampled clock could tick
                            # between two same-ts admissions and hide
                            # the tie
                            cycle_ts = [
                                t for k2 in stats.admitted
                                if (t := _reservation_ts(k2)) is not None]
                            lo = min(cycle_ts, default=None)
                            if (lo is not None
                                    and last_adm_clock is not None
                                    and lo <= last_adm_clock):
                                clock_monotone = False
                            if len(set(cycle_ts)) > 1:
                                # >1 distinct timestamp inside ONE
                                # cycle: the clock ticked mid-admission,
                                # so modeled preempt ordering can no
                                # longer mirror the host's
                                # candidatesOrdering tie-break
                                clock_monotone = False
                            hi = max(cycle_ts, default=None)
                            if hi is not None:
                                last_adm_clock = (
                                    hi if last_adm_clock is None
                                    else max(last_adm_clock, hi))
                        finish_cycle(stats)
                if stats is None:
                    # a modeled preempt target has no live admitted
                    # counterpart: the model and the real state diverged
                    # — abandon the window and re-decide on the host
                    # (outside cycle_apply: the host cycle must see the
                    # eagerly-settled queue state)
                    bstats["burst_target_divergences"] += 1
                    normal_cycle(heads=heads, advance=False)
                    break
                applied += 1
                consumed += 1
                normal_streak = 0
                dirty_backoff = 0
                if _chaos.ACTIVE is not None:
                    _chaos.ACTIVE.crashpoint("burst.mid_window")
            else:
                window_complete = True
            bstats["burst_cycles_discarded"] += K - consumed
            if spec is not None and not window_complete:
                # the window was truncated (dirty / divergence / clock):
                # live state no longer matches the carry the speculative
                # window chained from — it must never be applied
                spec = cancel_spec(spec)
            if drained:
                spec = cancel_spec(spec)
                break
        spec = cancel_spec(spec)
        return out

    def _fill_burst_finishes(self, st, plan, ext: dict, base: int, K: int,
                             ext_release, ext_unpark) -> bool:
        """Feed the external finish schedule to the kernel: row-backed
        workloads get their ``death0`` cycle set (the kernel releases
        their exact usage and frees the row — preemption-aware), keys
        without rows fall back to the aggregated [K, C, F] release
        tensors.  False when a fallback release isn't representable
        (run normal cycles instead).  Release vectors are cached per
        admission (an Info build + usage walk per workload is too hot
        for re-packs)."""
        from ..workload import Info
        from ..ops.burst import admitted_usage_vec
        death = plan.arrays["death0"]
        row_of_key = plan.row_of_key or {}
        scale_of = {r: int(st.resource_scale[i])
                    for i, r in enumerate(st.resource_names)}
        F = ext_release.shape[2]
        plan.finite_deaths = False
        for off, keys in ext.items():
            k = off - base
            if k < 0 or k >= K:
                continue
            for key in keys:
                wl = self.workloads.get(key)
                if wl is None or wl.admission is None:
                    continue
                loc = row_of_key.get(key)
                if loc is not None and plan.arrays["adm0"][loc]:
                    death[loc] = min(int(death[loc]), k)
                    plan.finite_deaths = True
                    continue
                ci = st.cq_index.get(wl.admission.cluster_queue)
                if ci is None:
                    return False
                # the live cache Info carries the per-Info usage cache
                # (a throwaway Info would rebuild the usage walk every
                # re-pack)
                cq_live = self.cache.cluster_queue(
                    wl.admission.cluster_queue)
                info = (cq_live.workloads.get(key)
                        if cq_live is not None else None)
                if info is None:
                    info = Info(wl, self.cache.info_options)
                uv = admitted_usage_vec(info, st, scale_of, F)
                if uv is None:
                    return False
                ext_release[k, ci] += uv[0]
                if uv[0].any():
                    # zero-usage finishes release nothing the kernel
                    # can observe (matches the death-row path, which
                    # only unparks on released usage); their wakeup
                    # reaches the host through the heads-mismatch break
                    ext_unpark[k,
                               int(plan.arrays["forest_of_cq"][ci])] = True
        return True

    def run(self, stop_event, heads_timeout: float = 0.2) -> None:
        """Daemon mode: the long-running admission loop over blocking
        ``queues.heads()`` with the speed-signal backoff (reference
        scheduler.go:143 Start driven by wait.UntilWithBackoff).  Blocks
        until ``stop_event`` is set; producers on other threads create
        workloads through the normal Driver API and the loop admits them
        as they arrive."""
        def on_cycle(stats):
            self.metrics.admission_attempt(bool(stats.admitted),
                                           stats.duration_s)
            self.obs.record_cycle(stats)

        def on_tick():
            if self.wait_for_pods_ready.enable:
                self.enforce_wait_for_pods_ready()
            self.queues.wake_expired_backoffs()

        self.scheduler.run(stop_event, heads_timeout=heads_timeout,
                           on_cycle=on_cycle, on_tick=on_tick)

    def run_until_settled(self, max_cycles: int = 1000):
        """Run cycles until a fixed point: no admissions/preemptions AND the
        queue state fingerprint repeats (a cycle that merely parks a blocked
        head still makes progress)."""
        all_stats = []
        prev_fp = None
        for _ in range(max_cycles):
            stats = self.schedule_once()
            all_stats.append(stats)
            if stats.admitted or stats.preempting:
                prev_fp = None
                continue
            fp = self._queue_fingerprint()
            if fp == prev_fp:
                break
            prev_fp = fp
        return all_stats

    def _queue_fingerprint(self):
        out = []
        for name in sorted(self.queues.cluster_queue_names()):
            q = self.queues.queue_for(name)
            out.append((name, tuple(sorted(q.heap.keys())),
                        tuple(sorted(q.inadmissible))))
        return tuple(out)

    # -- introspection --

    @property
    def stats(self) -> dict:
        """One-stop counter snapshot shared by the perf harness, the
        chaos report, and the open-loop traffic runner: incremental
        snapshot reuse, queue depth / requeue-storm accounting, and the
        burst solver's dispatch counters when one is live."""
        q = self.queues
        out = {
            "snapshot": dict(self.cache.snapshot_stats),
            "queue": {
                "ready_cqs": len(q._ready),
                "armed_timer_cqs": len(q._timers),
                "requeue_storm_last": q.requeue_storm_last,
                "requeue_storm_peak": q.requeue_storm_peak,
                "requeue_storms_total": q.requeue_storms_total,
                "requeue_unparked_total": q.requeue_unparked_total,
            },
            "admission_attempts": {
                "success": int(self.metrics.counters.get(
                    ("kueue_admission_attempts_total", "success"), 0)),
                "inadmissible": int(self.metrics.counters.get(
                    ("kueue_admission_attempts_total", "inadmissible"), 0)),
            },
        }
        if self._burst_solver is not None:
            out["burst"] = dict(self._burst_solver.stats)
            # streaming-pack host-cost block: the kueue_pack_* series
            # (arena occupancy/growth, row/rank patches, dtype-tighten
            # savings) split out of the flat solver counters
            bs = out["burst"]
            out["pack"] = {k: bs[k] for k in (
                "stream_packs", "stream_full_packs", "stream_pack_bails",
                "stream_pack_s", "pack_last_ms", "pack_row_patches",
                "pack_rows_verified",
                "pack_rank_patches", "pack_arena_growth_events",
                "pack_arena_planes", "pack_arena_bytes",
                "pack_arena_used_bytes", "pack_tighten_bytes_saved",
                "pack_tighten_widened", "burst_launch_bytes_h2d")
                if k in bs}
            # cohort-forest compression block: packed vs compressed
            # admitted rows + the compressible-CQ census (kueue_agg_*)
            agg = {k: bs[k] for k in (
                "agg_rows_compressed", "agg_rows_packed", "agg_heads",
                "agg_cqs_compressible") if k in bs}
            if agg:
                out["agg"] = agg
            # head-only packing block: rows charged to the kernel's
            # 2^19 composite-key budget vs budget-exempt rank context
            hp = {k: bs[k] for k in (
                "head_pack_budget_rows", "head_pack_exempt_rows")
                if k in bs}
            if hp:
                out["head_pack"] = hp
        from ..utils.heap import REPAIR_STATS
        out["heap_repair"] = dict(REPAIR_STATS)
        from ..utils.parallel_host import POOL_STATS
        out["host_pool"] = dict(POOL_STATS,
                                host_pool_workers=self.host_pool.workers)
        if self._wal is not None and hasattr(self._wal, "stats"):
            out["wal"] = dict(self._wal.stats)
            if "wal_shards" in out["wal"]:
                out["wal_shard"] = {
                    "wal_shards": out["wal"]["wal_shards"],
                    "wal_shard_skew": out["wal"]["wal_shard_skew"]}
        solver = self.scheduler.solver
        if solver is not None and hasattr(solver, "stats"):
            ss = solver.stats
            out["flavor_walk"] = {
                "host_cycles": ss.get("host_cycles", 0),
                "scalar_heads": ss.get("scalar_heads", 0),
                "scalar_reasons": dict(ss.get("scalar_reasons", {})),
                "resume_heads": ss.get("resume_heads", 0),
                "walk_stop_heads": ss.get("walk_stop_heads", 0),
            }
        self.metrics.burst_solver_sample(out.get("burst"),
                                         out.get("flavor_walk"))
        self.metrics.pack_sample(out.get("pack"), out.get("wal"))
        self.metrics.scale_opt_sample(out.get("agg"), out["heap_repair"],
                                      out.get("wal_shard"),
                                      out.get("head_pack"),
                                      out["host_pool"])
        # distributed-run blocks, attached by the harness that owns the
        # processes/clients (ProcFederation, dist_soak): summed
        # HttpWorkerClient accounting and the supervisor's report
        rpc_clients = getattr(self, "rpc_clients", None)
        if rpc_clients:
            agg_rpc: dict[str, int] = {}
            for c in rpc_clients:
                for k, v in c.stats.items():
                    agg_rpc[k] = agg_rpc.get(k, 0) + int(v)
            out["rpc"] = agg_rpc
            self.metrics.rpc_sample(agg_rpc)
        dist_stats = getattr(self, "dist_stats", None)
        if dist_stats:
            out["dist"] = dict(dist_stats)
            self.metrics.dist_sample(
                dist_stats.get("by_role", {}),
                proxy_stats=dist_stats.get("proxy"),
                shard_depths=dist_stats.get("shard_depths"))
        out["obs"] = self.obs.report()
        return out

    def admitted_keys(self) -> set[str]:
        """Workloads currently holding quota (reserved and not finished)."""
        return {k for k, wl in self.workloads.items()
                if wl.condition_true(WL_QUOTA_RESERVED) and not wl.is_finished}

    def workload(self, key: str) -> Optional[Workload]:
        return self.workloads.get(key)
