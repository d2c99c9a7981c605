"""Metrics registry: the Prometheus-series equivalent.

Capability parity with reference pkg/metrics/metrics.go:62-386 (namespace
``kueue_``): admission attempts/durations, pending/reserving/admitted
counts, quota-reserved and admission wait times, evictions/preemptions with
reason labels, per-CQ resource usage, weighted shares.  Values are plain
Python numbers; ``render()`` emits Prometheus text exposition format so the
series names stay wire-compatible.
"""

from __future__ import annotations

import math
import threading
from collections import defaultdict
from dataclasses import dataclass, field


def exponential_buckets(start: float, factor: float, count: int) -> list[float]:
    """reference metrics.go:387 generateExponentialBuckets."""
    return [start * factor**i for i in range(count)]


ATTEMPT_BUCKETS = exponential_buckets(0.001, 2, 16)  # seconds
WAIT_BUCKETS = exponential_buckets(1, 2, 14)
# open-loop admission latency (submit→admit, virtual seconds) and
# requeue-storm sizes (workloads unparked per cohort wakeup)
LATENCY_BUCKETS = exponential_buckets(0.25, 2, 18)
STORM_BUCKETS = exponential_buckets(1, 2, 16)
# serving admission latency is wall-clock (accept→admit): 1ms .. ~9min
SVC_LATENCY_BUCKETS = exponential_buckets(0.001, 2, 20)


@dataclass
class Histogram:
    buckets: list[float]
    counts: list[int] = field(default_factory=list)
    total: float = 0.0
    n: int = 0

    def __post_init__(self):
        if not self.counts:
            self.counts = [0] * (len(self.buckets) + 1)

    def observe(self, value: float) -> None:
        self.n += 1
        self.total += value
        for i, b in enumerate(self.buckets):
            if value <= b:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def quantile(self, q: float) -> float:
        if self.n == 0:
            return 0.0
        target = math.ceil(q * self.n)
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                return self.buckets[i] if i < len(self.buckets) else float("inf")
        return float("inf")


class Registry:
    """Thread-safe: the serving path (serving/service.py) updates
    counters and gauges from submitter threads while the HTTP
    ``/metrics`` handler renders from another, so every mutation and
    the full render hold ``_lock``.  An RLock, and uncontended in the
    single-threaded batch harnesses (a few ns per op); the tracer's
    direct histogram inserts (obs/trace.py) take the same lock only on
    the first observation of a phase."""

    def __init__(self):
        self.counters: dict[tuple, float] = defaultdict(float)
        self.gauges: dict[tuple, float] = defaultdict(float)
        self.histograms: dict[tuple, Histogram] = {}
        self._lock = threading.RLock()

    # -- generic --

    def inc(self, name: str, labels: tuple = (), value: float = 1.0) -> None:
        with self._lock:
            self.counters[(name, *labels)] += value

    def set_gauge(self, name: str, labels: tuple, value: float) -> None:
        with self._lock:
            self.gauges[(name, *labels)] = value

    def add_gauge(self, name: str, labels: tuple, delta: float) -> None:
        with self._lock:
            self.gauges[(name, *labels)] += delta

    def observe(self, name: str, labels: tuple, value: float,
                buckets: list[float] = ATTEMPT_BUCKETS) -> None:
        key = (name, *labels)
        with self._lock:
            if key not in self.histograms:
                self.histograms[key] = Histogram(buckets=buckets)
            self.histograms[key].observe(value)

    # -- kueue series (reference metrics.go) --

    def cycle_preemption_skip(self) -> None:
        """reference admission_cycle_preemption_skips (metrics.go)."""
        self.inc("kueue_admission_cycle_preemption_skips", ())

    def admission_checks_wait(self, cq: str, wait_s: float) -> None:
        """Time from quota reservation to all checks ready
        (reference admission_checks_wait_time_seconds)."""
        self.observe("kueue_admission_checks_wait_time_seconds", (cq,),
                     wait_s, WAIT_BUCKETS)

    def admission_attempt(self, success: bool, duration_s: float) -> None:
        result = "success" if success else "inadmissible"
        self.inc("kueue_admission_attempts_total", (result,))
        self.observe("kueue_admission_attempt_duration_seconds", (result,), duration_s)

    def pending_inc(self, wl) -> None:
        pass  # pending gauges are sampled from the queues (see sample_pending)

    def sample_pending(self, queues) -> None:
        for name in queues.cluster_queue_names():
            q = queues.queue_for(name)
            self.set_gauge("kueue_pending_workloads", (name, "active"),
                           q.pending_active())
            self.set_gauge("kueue_pending_workloads", (name, "inadmissible"),
                           q.pending_inadmissible())

    def quota_reserved(self, cq: str, wait_s: float) -> None:
        self.inc("kueue_quota_reserved_workloads_total", (cq,))
        self.observe("kueue_quota_reserved_wait_time_seconds", (cq,), wait_s,
                     WAIT_BUCKETS)
        self.add_gauge("kueue_reserving_active_workloads", (cq,), 1)

    def admitted_workload(self, cq: str, wait_s: float) -> None:
        self.inc("kueue_admitted_workloads_total", (cq,))
        self.observe("kueue_admission_wait_time_seconds", (cq,), wait_s,
                     WAIT_BUCKETS)
        self.add_gauge("kueue_admitted_active_workloads", (cq,), 1)

    def release_reservation(self, cq: str) -> None:
        self.add_gauge("kueue_reserving_active_workloads", (cq,), -1)

    def release_admitted(self, cq: str) -> None:
        self.add_gauge("kueue_admitted_active_workloads", (cq,), -1)

    def evicted(self, cq: str, reason: str) -> None:
        self.inc("kueue_evicted_workloads_total", (cq, reason))

    def preempted(self, preempting_cq: str, reason: str) -> None:
        self.inc("kueue_preempted_workloads_total", (preempting_cq, reason))

    def cluster_queue_status(self, cq: str, active: bool) -> None:
        """Exactly one status series is 1 (reference ReportClusterQueueStatus)."""
        current = "active" if active else "pending"
        for status in ("pending", "active", "terminating"):
            self.set_gauge("kueue_cluster_queue_status", (cq, status),
                           1.0 if status == current else 0.0)

    def report_resource_usage(self, cq: str, flavor: str, resource: str,
                              usage: float, nominal: float,
                              reservation: float | None = None,
                              borrowing_limit: float | None = None,
                              lending_limit: float | None = None) -> None:
        self.set_gauge("kueue_cluster_queue_resource_usage",
                       (cq, flavor, resource), usage)
        self.set_gauge("kueue_cluster_queue_resource_nominal_quota",
                       (cq, flavor, resource), nominal)
        if reservation is not None:
            self.set_gauge("kueue_cluster_queue_resource_reservation",
                           (cq, flavor, resource), reservation)
        if borrowing_limit is not None:
            self.set_gauge("kueue_cluster_queue_resource_borrowing_limit",
                           (cq, flavor, resource), borrowing_limit)
        if lending_limit is not None:
            self.set_gauge("kueue_cluster_queue_resource_lending_limit",
                           (cq, flavor, resource), lending_limit)

    def local_queue_counts(self, namespace: str, lq: str, pending: int,
                           reserving: int, admitted: int) -> None:
        """local_queue_* mirrors (LocalQueueMetrics feature gate)."""
        self.set_gauge("kueue_local_queue_pending_workloads",
                       (namespace, lq), pending)
        self.set_gauge("kueue_local_queue_reserving_active_workloads",
                       (namespace, lq), reserving)
        self.set_gauge("kueue_local_queue_admitted_active_workloads",
                       (namespace, lq), admitted)

    # -- open-loop traffic series (traffic/runner.py; also read back by
    #    Driver.stats so the soak harness and the chaos report share one
    #    source) --

    def open_loop_sample(self, depth_active: int, depth_parked: int,
                         age_p50_s: float, age_p99_s: float,
                         admissions_per_s: float) -> None:
        """Per-sample open-loop gauges: queue depth by status, pending
        age quantiles, and the achieved admissions/s rate."""
        self.set_gauge("kueue_open_loop_queue_depth", ("active",),
                       depth_active)
        self.set_gauge("kueue_open_loop_queue_depth", ("inadmissible",),
                       depth_parked)
        self.set_gauge("kueue_open_loop_pending_age_seconds", ("p50",),
                       age_p50_s)
        self.set_gauge("kueue_open_loop_pending_age_seconds", ("p99",),
                       age_p99_s)
        self.set_gauge("kueue_open_loop_admissions_per_second", (),
                       admissions_per_s)

    def open_loop_latency(self, latency_s: float) -> None:
        self.observe("kueue_open_loop_admission_latency_seconds", (),
                     latency_s, LATENCY_BUCKETS)

    def open_loop_requeue_storm(self, size: int) -> None:
        self.observe("kueue_open_loop_requeue_storm_size", (), size,
                     STORM_BUCKETS)
        cur = self.gauges.get(("kueue_open_loop_requeue_storm_peak",), 0.0)
        self.set_gauge("kueue_open_loop_requeue_storm_peak", (),
                       max(cur, size))

    # -- heterogeneous fast-path series (ops/solver.py + ops/burst.py
    #    classify routing and host-fallback visibility; sampled by
    #    Driver.stats so the perf harness and /metrics agree) --

    def burst_solver_sample(self, burst_stats=None, walk_stats=None) -> None:
        """Publish the burst solver's dirty/fallback counters and the
        cycle solver's flavor-walk telemetry as ``kueue_burst_*`` gauges.

        Gauge names are spelled out literally (no ``"kueue_" + k``
        construction) so the metrics-doc lint can statically prove every
        emitted series is documented."""
        burst_gauge_of = {
            "burst_dispatches": "kueue_burst_dispatches",
            "burst_cycles_decided": "kueue_burst_cycles_decided",
            "burst_suppressed_cycles": "kueue_burst_suppressed_cycles",
            "burst_dirty_cycles": "kueue_burst_dirty_cycles",
            "burst_dirty_preempt": "kueue_burst_dirty_preempt",
            "burst_dirty_scalar": "kueue_burst_dirty_scalar",
            "burst_dirty_resume": "kueue_burst_dirty_resume",
        }
        walk_gauge_of = {
            "host_cycles": "kueue_burst_host_cycles",
            "scalar_heads": "kueue_burst_scalar_heads",
            "resume_heads": "kueue_burst_resume_heads",
            "walk_stop_heads": "kueue_burst_walk_stop_heads",
        }
        if burst_stats:
            for k, gauge in burst_gauge_of.items():
                self.set_gauge(gauge, (), float(burst_stats.get(k, 0)))
        if walk_stats:
            for k, gauge in walk_gauge_of.items():
                self.set_gauge(gauge, (), float(walk_stats.get(k, 0)))
            for reason, n in walk_stats.get("scalar_reasons", {}).items():
                self.set_gauge("kueue_burst_scalar_heads_by_reason",
                               (reason,), float(n))

    # -- streaming-pack + WAL series (ops/stream_pack.py arena patching,
    #    packing.py dtype tightening, utils/journal.py group commit;
    #    sampled by Driver.stats so the scale harness and /metrics
    #    agree) --

    def pack_sample(self, pack_stats=None, wal_stats=None) -> None:
        """Publish the streaming pack's host-cost and arena telemetry as
        ``kueue_pack_*`` gauges and the WAL's group-commit counters as
        ``kueue_wal_*`` gauges."""
        gauge_of = {
            "stream_packs": "kueue_pack_stream_packs",
            "stream_full_packs": "kueue_pack_full_packs",
            "stream_pack_bails": "kueue_pack_stream_bails",
            "stream_pack_s": "kueue_pack_host_seconds",
            "pack_last_ms": "kueue_pack_last_ms",
            "pack_row_patches": "kueue_pack_row_patches",
            "pack_rows_verified": "kueue_pack_rows_verified",
            "pack_rank_patches": "kueue_pack_rank_patches",
            "pack_arena_growth_events": "kueue_pack_arena_growth_events",
            "pack_arena_planes": "kueue_pack_arena_planes",
            "pack_arena_bytes": "kueue_pack_arena_bytes",
            "pack_arena_used_bytes": "kueue_pack_arena_used_bytes",
            "pack_tighten_bytes_saved": "kueue_pack_tighten_bytes_saved",
            "pack_tighten_widened": "kueue_pack_tighten_widened",
            "burst_launch_bytes_h2d": "kueue_pack_bytes_to_device",
        }
        if pack_stats:
            for k, gauge in gauge_of.items():
                if k in pack_stats:
                    self.set_gauge(gauge, (), float(pack_stats[k]))
        wal_gauge_of = {
            "wal_appends": "kueue_wal_appends",
            "wal_commits": "kueue_wal_commits",
            "wal_flushes": "kueue_wal_flushes",
            "wal_fsyncs": "kueue_wal_fsyncs",
            "wal_compactions": "kueue_wal_compactions",
        }
        if wal_stats:
            for k, gauge in wal_gauge_of.items():
                if k in wal_stats:
                    self.set_gauge(gauge, (), float(wal_stats[k]))

    def scale_opt_sample(self, agg_stats=None, heap_stats=None,
                         wal_shard_stats=None, head_pack_stats=None,
                         host_pool_stats=None) -> None:
        """Publish the 1M-CQ scale-path telemetry: cohort-forest
        aggregate compression (``kueue_agg_*``, ops/aggregate.py), lazy
        heap repair (``kueue_heap_repair_*``, utils/heap.py), sharded
        WAL striping (``kueue_wal_shard_*``, utils/journal.py),
        head-only packing (``kueue_head_pack_*``, ops/burst.py budget
        scoping), and the parallel host plane (``kueue_host_pool_*``,
        utils/parallel_host.py).  Sampled by ``Driver.stats`` like the
        pack/WAL series."""
        agg_gauge_of = {
            "agg_rows_compressed": "kueue_agg_rows_compressed",
            "agg_rows_packed": "kueue_agg_rows_packed",
            "agg_heads": "kueue_agg_heads",
            "agg_cqs_compressible": "kueue_agg_cqs_compressible",
        }
        heap_gauge_of = {
            "heap_repair_settles": "kueue_heap_repair_settles",
            "heap_repair_deferred": "kueue_heap_repair_deferred",
            "heap_repair_settled_items": "kueue_heap_repair_settled_items",
            "heap_repair_bulk": "kueue_heap_repair_bulk",
        }
        shard_gauge_of = {
            "wal_shards": "kueue_wal_shards",
            "wal_shard_skew": "kueue_wal_shard_skew",
        }
        head_pack_gauge_of = {
            "head_pack_budget_rows": "kueue_head_pack_budget_rows",
            "head_pack_exempt_rows": "kueue_head_pack_exempt_rows",
        }
        pool_gauge_of = {
            "host_pool_workers": "kueue_host_pool_workers",
            "host_pool_tasks": "kueue_host_pool_tasks",
            "host_pool_batches": "kueue_host_pool_batches",
            "host_pool_partitions": "kueue_host_pool_partitions",
        }
        if agg_stats:
            for k, gauge in agg_gauge_of.items():
                if k in agg_stats:
                    self.set_gauge(gauge, (), float(agg_stats[k]))
        if heap_stats:
            for k, gauge in heap_gauge_of.items():
                if k in heap_stats:
                    self.set_gauge(gauge, (), float(heap_stats[k]))
        if wal_shard_stats:
            for k, gauge in shard_gauge_of.items():
                if k in wal_shard_stats:
                    self.set_gauge(gauge, (), float(wal_shard_stats[k]))
        if head_pack_stats:
            for k, gauge in head_pack_gauge_of.items():
                if k in head_pack_stats:
                    self.set_gauge(gauge, (), float(head_pack_stats[k]))
        if host_pool_stats:
            for k, gauge in pool_gauge_of.items():
                if k in host_pool_stats:
                    self.set_gauge(gauge, (), float(host_pool_stats[k]))

    def report_weighted_share(self, cq: str, share: float) -> None:
        self.set_gauge("kueue_cluster_queue_weighted_share", (cq,), share)

    def report_cohort_weighted_share(self, cohort: str, share: float) -> None:
        self.set_gauge("kueue_cohort_weighted_share", (cohort,), share)

    # -- observability-plane series (obs/: event stream + flight
    #    recorder; sampled by Driver.refresh_resource_metrics so
    #    /metrics always carries the current counts) --

    def obs_sample(self, events_report=None, flight_recorded: int = 0) -> None:
        """Publish the event stream's per-kind totals and the flight
        recorder's cycle count as ``kueue_obs_*`` / ``kueue_flight_*``."""
        if events_report:
            for kind, n in events_report.get("counts", {}).items():
                self.set_gauge("kueue_obs_events_total", (kind,), float(n))
            self.set_gauge("kueue_obs_events_dropped_total", (),
                           float(events_report.get("dropped", 0)))
        self.set_gauge("kueue_flight_cycles_recorded", (),
                       float(flight_recorded))

    # -- serving series (serving/service.py: thread-safe ingest +
    #    adaptive burst window; the only series written from submitter
    #    threads, which is why the registry carries a lock) --

    def svc_submission(self, result: str) -> None:
        """One submission outcome: accepted / rejected / duplicate /
        shed / draining."""
        self.inc("kueue_svc_submissions_total", (result,))

    def svc_admission_latency(self, seconds: float) -> None:
        """Wall-clock accept→admit latency of one served workload."""
        self.observe("kueue_svc_admission_latency_seconds", (), seconds,
                     SVC_LATENCY_BUCKETS)

    def svc_sample(self, depth: int, high_water: int, burst_k: int,
                   ewma_rate: float, retry_after_s: float) -> None:
        """Per-step serving telemetry: ingest depth vs the backpressure
        high-water mark, the online-chosen burst window, the arrival
        EWMA, and the current retry-after estimate."""
        self.set_gauge("kueue_svc_ingest_depth", (), float(depth))
        self.set_gauge("kueue_svc_ingest_high_water", (), float(high_water))
        self.set_gauge("kueue_svc_burst_window", (), float(burst_k))
        self.set_gauge("kueue_svc_arrival_rate_ewma", (), float(ewma_rate))
        self.set_gauge("kueue_svc_retry_after_seconds", (),
                       float(retry_after_s))

    def dist_sample(self, by_role: dict, proxy_stats=None,
                    shard_depths=None) -> None:
        """Distributed-run telemetry: supervisor per-role lifecycle
        counts, socket-fault proxy totals, per-shard ingest depths."""
        for role, counts in by_role.items():
            self.set_gauge("kueue_dist_process_spawns_total", (role,),
                           float(counts.get("spawns", 0)))
            self.set_gauge("kueue_dist_process_kills_total", (role,),
                           float(counts.get("kills", 0)))
            self.set_gauge("kueue_dist_process_restarts_total", (role,),
                           float(counts.get("restarts", 0)))
        if proxy_stats:
            self.set_gauge("kueue_dist_proxy_connections_total", (),
                           float(proxy_stats.get("connections", 0)))
            for kind, stat in (("reset", "resets"),
                               ("latency", "latencies"),
                               ("truncate", "truncations"),
                               ("blackhole", "blackholes")):
                self.set_gauge("kueue_dist_proxy_faults_total", (kind,),
                               float(proxy_stats.get(stat, 0)))
        for shard, depth in (shard_depths or {}).items():
            self.set_gauge("kueue_dist_shard_ingest_depth",
                           (str(shard),), float(depth))

    def rpc_sample(self, stats: dict) -> None:
        """HTTP worker-client accounting (one client's ``.stats`` or a
        summed aggregate): requests, retries by transport cause,
        exhausted deadlines, noticed watch-epoch changes."""
        self.set_gauge("kueue_rpc_requests_total", (),
                       float(stats.get("requests", 0)))
        refused = stats.get("refused_retries", 0)
        midbody = stats.get("midbody_retries", 0)
        other = max(0, stats.get("retries", 0) - refused - midbody)
        self.set_gauge("kueue_rpc_retries_total", ("refused",),
                       float(refused))
        self.set_gauge("kueue_rpc_retries_total", ("mid_body",),
                       float(midbody))
        self.set_gauge("kueue_rpc_retries_total", ("other",),
                       float(other))
        self.set_gauge("kueue_rpc_deadline_exhausted_total", (),
                       float(stats.get("deadline_exhausted", 0)))
        self.set_gauge("kueue_rpc_epoch_resyncs_total", (),
                       float(stats.get("epoch_resyncs", 0)))

    # -- exposition --

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4: per-family ``# HELP``
        / ``# TYPE`` headers, cumulative ``_bucket{le=...}`` series ending
        in ``+Inf`` plus ``_sum``/``_count`` for histograms, and escaped
        label values.  Round-trip checked against a strict parser in
        tests/test_obs.py."""
        with self._lock:
            families: dict[str, list] = defaultdict(list)
            for key, val in self.counters.items():
                families[key[0]].append((key[1:], val))
            for key, val in self.gauges.items():
                families[key[0]].append((key[1:], val))
            for key, h in self.histograms.items():
                families[key[0]].append((key[1:], h))
            lines: list[str] = []
            for name in sorted(families):
                spec = SERIES.get(name)
                kind = spec.kind if spec else (
                    "histogram"
                    if isinstance(families[name][0][1], Histogram)
                    else "untyped")
                help_text = spec.help if spec else name
                lines.append(f"# HELP {name} {_escape_help(help_text)}")
                lines.append(f"# TYPE {name} {kind}")
                for labels, val in sorted(families[name],
                                          key=lambda kv: kv[0]):
                    if isinstance(val, Histogram):
                        lines.extend(_render_histogram(name, labels, val))
                    else:
                        lines.append(
                            f"{name}{_fmt_labels(name, labels)}"
                            f" {_fmt_value(val)}")
            return "\n".join(lines) + "\n"


@dataclass(frozen=True)
class Series:
    """One documented metric family: exposition type, label names in
    emission order, and the HELP string."""
    name: str
    kind: str            # "counter" | "gauge" | "histogram"
    labels: tuple
    help: str


# Every series this registry emits, in one place.  The metrics-doc lint
# (analysis/metrics_doc.py) proves two invariants statically: every
# ``kueue_*`` string literal in this module names a row here, and this
# table matches the README "## Metrics" table in both directions.
_SERIES_DEFS = [
    # reference pkg/metrics parity
    ("kueue_admission_attempts_total", "counter", ("result",),
     "Admission attempts by result (success / inadmissible)."),
    ("kueue_admission_attempt_duration_seconds", "histogram", ("result",),
     "Latency of one admission attempt, by result."),
    ("kueue_admission_cycle_preemption_skips", "counter", (),
     "Workloads skipped in a cycle because preemption was still pending."),
    ("kueue_pending_workloads", "gauge", ("cluster_queue", "status"),
     "Pending workloads per cluster queue, by active/inadmissible status."),
    ("kueue_quota_reserved_workloads_total", "counter", ("cluster_queue",),
     "Workloads that reserved quota, cumulative per cluster queue."),
    ("kueue_quota_reserved_wait_time_seconds", "histogram",
     ("cluster_queue",),
     "Wait from creation to quota reservation."),
    ("kueue_reserving_active_workloads", "gauge", ("cluster_queue",),
     "Workloads currently holding a quota reservation."),
    ("kueue_admitted_workloads_total", "counter", ("cluster_queue",),
     "Admitted workloads, cumulative per cluster queue."),
    ("kueue_admission_wait_time_seconds", "histogram", ("cluster_queue",),
     "Wait from creation to admission."),
    ("kueue_admission_checks_wait_time_seconds", "histogram",
     ("cluster_queue",),
     "Wait from quota reservation to all admission checks ready."),
    ("kueue_admitted_active_workloads", "gauge", ("cluster_queue",),
     "Workloads currently admitted."),
    ("kueue_evicted_workloads_total", "counter", ("cluster_queue", "reason"),
     "Evictions by cluster queue and reason."),
    ("kueue_preempted_workloads_total", "counter",
     ("preempting_cluster_queue", "reason"),
     "Preemptions by preempting cluster queue and reason."),
    ("kueue_cluster_queue_status", "gauge", ("cluster_queue", "status"),
     "Cluster queue status one-hot (pending / active / terminating)."),
    ("kueue_cluster_queue_resource_usage", "gauge",
     ("cluster_queue", "flavor", "resource"),
     "Admitted resource usage per cluster queue, flavor, and resource."),
    ("kueue_cluster_queue_resource_reservation", "gauge",
     ("cluster_queue", "flavor", "resource"),
     "Reserved (incl. non-admitted) quota per cluster queue and flavor."),
    ("kueue_cluster_queue_resource_nominal_quota", "gauge",
     ("cluster_queue", "flavor", "resource"),
     "Configured nominal quota per cluster queue and flavor."),
    ("kueue_cluster_queue_resource_borrowing_limit", "gauge",
     ("cluster_queue", "flavor", "resource"),
     "Configured borrowing limit, when set."),
    ("kueue_cluster_queue_resource_lending_limit", "gauge",
     ("cluster_queue", "flavor", "resource"),
     "Configured lending limit, when set."),
    ("kueue_cluster_queue_weighted_share", "gauge", ("cluster_queue",),
     "Fair-sharing weighted share per cluster queue."),
    ("kueue_cohort_weighted_share", "gauge", ("cohort",),
     "Fair-sharing weighted share per cohort."),
    ("kueue_local_queue_pending_workloads", "gauge",
     ("namespace", "local_queue"),
     "Pending workloads per local queue (LocalQueueMetrics gate)."),
    ("kueue_local_queue_reserving_active_workloads", "gauge",
     ("namespace", "local_queue"),
     "Reserving workloads per local queue (LocalQueueMetrics gate)."),
    ("kueue_local_queue_admitted_active_workloads", "gauge",
     ("namespace", "local_queue"),
     "Admitted workloads per local queue (LocalQueueMetrics gate)."),
    # open-loop traffic soak
    ("kueue_open_loop_queue_depth", "gauge", ("status",),
     "Open-loop soak queue depth by active/inadmissible status."),
    ("kueue_open_loop_pending_age_seconds", "gauge", ("quantile",),
     "Open-loop pending-age quantiles (p50/p99), virtual seconds."),
    ("kueue_open_loop_admissions_per_second", "gauge", (),
     "Achieved open-loop admission rate."),
    ("kueue_open_loop_admission_latency_seconds", "histogram", (),
     "Submit-to-admit latency in the open-loop soak, virtual seconds."),
    ("kueue_open_loop_requeue_storm_size", "histogram", (),
     "Workloads unparked per cohort wakeup."),
    ("kueue_open_loop_requeue_storm_peak", "gauge", (),
     "Largest requeue storm observed."),
    # burst solver + flavor walk
    ("kueue_burst_dispatches", "gauge", (),
     "Fused burst-kernel dispatches."),
    ("kueue_burst_cycles_decided", "gauge", (),
     "Cycles decided on-device by the burst solver."),
    ("kueue_burst_suppressed_cycles", "gauge", (),
     "Burst cycles suppressed by the dirty-set check."),
    ("kueue_burst_dirty_cycles", "gauge", (),
     "Burst cycles invalidated and replayed on host."),
    ("kueue_burst_dirty_preempt", "gauge", (),
     "Burst invalidations caused by preemption."),
    ("kueue_burst_dirty_scalar", "gauge", (),
     "Burst invalidations caused by scalar-path heads."),
    ("kueue_burst_dirty_resume", "gauge", (),
     "Burst invalidations caused by resume heads."),
    ("kueue_burst_host_cycles", "gauge", (),
     "Cycles that fell back to the host solver."),
    ("kueue_burst_scalar_heads", "gauge", (),
     "Heads routed to the scalar path."),
    ("kueue_burst_resume_heads", "gauge", (),
     "Heads resumed mid-walk after a preempting flavor."),
    ("kueue_burst_walk_stop_heads", "gauge", (),
     "Heads whose flavor walk stopped early."),
    ("kueue_burst_scalar_heads_by_reason", "gauge", ("reason",),
     "Scalar-path heads broken down by routing reason."),
    # streaming pack + arena + WAL
    ("kueue_pack_stream_packs", "gauge", (),
     "Streaming (delta) pack invocations."),
    ("kueue_pack_full_packs", "gauge", (),
     "Full repacks (stream path unavailable or bailed)."),
    ("kueue_pack_stream_bails", "gauge", (),
     "Streaming packs that bailed to a full repack."),
    ("kueue_pack_host_seconds", "gauge", (),
     "Cumulative host seconds spent packing."),
    ("kueue_pack_last_ms", "gauge", (),
     "Duration of the most recent pack, milliseconds."),
    ("kueue_pack_row_patches", "gauge", (),
     "Arena row patches applied by streaming packs."),
    ("kueue_pack_rows_verified", "gauge", (),
     "Arena rows verified against a full repack."),
    ("kueue_pack_rank_patches", "gauge", (),
     "Rank-plane patches applied by streaming packs."),
    ("kueue_pack_arena_growth_events", "gauge", (),
     "Times the pinned arena had to grow."),
    ("kueue_pack_arena_planes", "gauge", (),
     "Planes resident in the pinned arena."),
    ("kueue_pack_arena_bytes", "gauge", (),
     "Pinned arena capacity, bytes."),
    ("kueue_pack_arena_used_bytes", "gauge", (),
     "Pinned arena bytes in use."),
    ("kueue_pack_tighten_bytes_saved", "gauge", (),
     "Bytes saved by dtype tightening."),
    ("kueue_pack_tighten_widened", "gauge", (),
     "Planes widened back after a tightening overflow."),
    ("kueue_pack_bytes_to_device", "gauge", (),
     "Host-to-device bytes shipped per burst launch."),
    ("kueue_wal_appends", "gauge", (),
     "WAL operation records appended."),
    ("kueue_wal_commits", "gauge", (),
     "WAL cycle commits."),
    ("kueue_wal_flushes", "gauge", (),
     "WAL buffered-write flushes."),
    ("kueue_wal_fsyncs", "gauge", (),
     "WAL fsync calls."),
    ("kueue_wal_compactions", "gauge", (),
     "WAL checkpoint compactions."),
    # 1M-CQ scale path: aggregate compression, lazy heap, WAL shards
    ("kueue_agg_rows_compressed", "gauge", (),
     "Admitted rows held as per-CQ aggregates instead of packed rows."),
    ("kueue_agg_rows_packed", "gauge", (),
     "Admitted rows materialized as packed kernel rows."),
    ("kueue_agg_heads", "gauge", (),
     "Pending heads tracked by the aggregate planes."),
    ("kueue_agg_cqs_compressible", "gauge", (),
     "CQs in non-preempting forests eligible for row compression."),
    ("kueue_heap_repair_settles", "gauge", (),
     "Lazy-heap settle passes (one per ordered read after mutations)."),
    ("kueue_heap_repair_deferred", "gauge", (),
     "Heap pushes/updates buffered by lazy repair."),
    ("kueue_heap_repair_settled_items", "gauge", (),
     "Buffered heap items applied during settle passes."),
    ("kueue_heap_repair_bulk", "gauge", (),
     "Settle passes that used the O(n) bulk heapify."),
    ("kueue_wal_shards", "gauge", (),
     "Configured CycleWAL segment count (1 = unsharded)."),
    ("kueue_wal_shard_skew", "gauge", (),
     "Max-minus-min appended ops across WAL segments."),
    # r19 scale path: head-only packing + parallel host plane
    ("kueue_head_pack_budget_rows", "gauge", (),
     "Packed rows charged against the kernel's 2^19 composite-key "
     "budget (rows of preempting forests)."),
    ("kueue_head_pack_exempt_rows", "gauge", (),
     "Packed rows exempt from the composite-key budget (rank context "
     "of never-preempting forests)."),
    ("kueue_host_pool_workers", "gauge", (),
     "Configured host-plane worker threads (0/1 = serial)."),
    ("kueue_host_pool_tasks", "gauge", (),
     "Tasks executed on host-pool worker threads."),
    ("kueue_host_pool_batches", "gauge", (),
     "Fork-join rounds the host pool fanned out."),
    ("kueue_host_pool_partitions", "gauge", (),
     "Cohort-forest partitions dispatched by the host pool."),
    # observability plane (obs/)
    ("kueue_span_duration_seconds", "histogram", ("phase",),
     "Traced hot-path phase durations (obs tracer), wall seconds."),
    ("kueue_obs_events_total", "gauge", ("kind",),
     "Events emitted, by kind (admit/evict/preempt/requeue/eject)."),
    ("kueue_obs_events_dropped_total", "gauge", (),
     "Events dropped from the bounded stream after overflow."),
    ("kueue_flight_cycles_recorded", "gauge", (),
     "Cycles recorded by the flight recorder, cumulative."),
    # serving plane (serving/)
    ("kueue_svc_submissions_total", "counter", ("result",),
     "Service submissions by outcome "
     "(accepted/rejected/duplicate/shed/draining)."),
    ("kueue_svc_admission_latency_seconds", "histogram", (),
     "Wall-clock accept-to-admit latency through the service."),
    ("kueue_svc_ingest_depth", "gauge", (),
     "Pending submissions in the service ingest queue."),
    ("kueue_svc_ingest_high_water", "gauge", (),
     "Configured ingest backpressure high-water mark."),
    ("kueue_svc_burst_window", "gauge", (),
     "Burst-window K chosen online for the current service step."),
    ("kueue_svc_arrival_rate_ewma", "gauge", (),
     "EWMA of the submission arrival rate, events/s."),
    ("kueue_svc_retry_after_seconds", "gauge", (),
     "Current retry-after hint handed to rejected submitters."),
    # distributed control plane (dist/)
    ("kueue_dist_process_spawns_total", "gauge", ("role",),
     "Child processes spawned by the supervisor, by role."),
    ("kueue_dist_process_kills_total", "gauge", ("role",),
     "Child processes SIGKILLed by the supervisor, by role."),
    ("kueue_dist_process_restarts_total", "gauge", ("role",),
     "Killed child processes respawned by the supervisor, by role."),
    ("kueue_dist_proxy_connections_total", "gauge", (),
     "Connections accepted by the socket-fault proxy."),
    ("kueue_dist_proxy_faults_total", "gauge", ("kind",),
     "Wire faults injected by the socket-fault proxy "
     "(reset/latency/truncate/blackhole)."),
    ("kueue_dist_shard_ingest_depth", "gauge", ("shard",),
     "Pending submissions per front-end shard process."),
    # remote-transport client accounting (remote.py HttpWorkerClient)
    ("kueue_rpc_requests_total", "gauge", (),
     "HTTP worker-client requests issued, attempts included."),
    ("kueue_rpc_retries_total", "gauge", ("cause",),
     "HTTP worker-client in-place retries by transport cause "
     "(refused/mid_body/other)."),
    ("kueue_rpc_deadline_exhausted_total", "gauge", (),
     "Requests whose retry budget ran out (surfaced ConnectionLost)."),
    ("kueue_rpc_epoch_resyncs_total", "gauge", (),
     "Watch-epoch changes noticed by clients (worker restarts)."),
]

SERIES: dict[str, Series] = {
    name: Series(name, kind, labels, help)
    for name, kind, labels, help in _SERIES_DEFS
}

# Label-name tables per series, derived from SERIES (reference
# metrics.go label definitions).
LABEL_NAMES = {s.name: s.labels for s in SERIES.values() if s.labels}


def _escape_label(value) -> str:
    return (str(value).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


def _escape_help(text: str) -> str:
    return text.replace("\\", r"\\").replace("\n", r"\n")


def _fmt_value(val: float) -> str:
    f = float(val)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


def _fmt_labels(name: str, labels, extra: str = "") -> str:
    if not labels and not extra:
        return ""
    names = LABEL_NAMES.get(name)
    parts = [
        f'{names[i] if names and i < len(names) else f"l{i}"}'
        f'="{_escape_label(v)}"'
        for i, v in enumerate(labels)]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}"


def _render_histogram(name: str, labels, h: Histogram) -> list[str]:
    lines = []
    cum = 0
    for i, b in enumerate(h.buckets):
        cum += h.counts[i]
        le = _fmt_value(b) if float(b) == int(b) else repr(float(b))
        extra = 'le="' + le + '"'
        lines.append(f"{name}_bucket"
                     f"{_fmt_labels(name, labels, extra)} {cum}")
    cum += h.counts[-1]
    inf_extra = 'le="+Inf"'
    lines.append(f"{name}_bucket"
                 f"{_fmt_labels(name, labels, inf_extra)} {cum}")
    lines.append(f"{name}_sum{_fmt_labels(name, labels)}"
                 f" {_fmt_value(h.total)}")
    lines.append(f"{name}_count{_fmt_labels(name, labels)} {h.n}")
    return lines
