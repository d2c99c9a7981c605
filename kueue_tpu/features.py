"""Feature gates and the environment-flag registry.

Feature gates (reference pkg/features/kube_features.go:31-255):
versioned defaults mirroring the reference at its snapshot (≈ v0.11);
each gate carries (default, stage, lock_to_default).  ``enabled(name)``
is the runtime check; ``set_feature_gate_during_test`` is the test
override (kube_features.go:257 SetFeatureGateDuringTest).

``ENV_FLAGS`` is the single declared registry of every ``KUEUE_TPU_*``
environment variable the stack reads.  All reads go through
:func:`env_value` / :func:`env_int`, which refuse names missing from
the registry — the static-analysis env pass (``analysis/env_flags.py``)
flags any ad-hoc ``os.environ`` read of a ``KUEUE_TPU_*`` name and any
drift between this table and the README flag table.
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass


@dataclass(frozen=True)
class FeatureSpec:
    default: bool
    stage: str                # Alpha | Beta | GA | Deprecated
    lock_to_default: bool = False


# Defaults as of the reference snapshot (kube_features.go:179-255, the
# highest-version entry of each VersionedSpecs list).
DEFAULT_FEATURE_GATES: dict[str, FeatureSpec] = {
    "PartialAdmission": FeatureSpec(True, "Beta"),
    "QueueVisibility": FeatureSpec(False, "Deprecated"),
    "FlavorFungibility": FeatureSpec(True, "Beta"),
    "ProvisioningACC": FeatureSpec(True, "Beta"),
    "VisibilityOnDemand": FeatureSpec(True, "Beta"),
    "PrioritySortingWithinCohort": FeatureSpec(True, "Beta"),
    "MultiKueue": FeatureSpec(True, "Beta"),
    "LendingLimit": FeatureSpec(True, "Beta"),
    "MultiKueueBatchJobWithManagedBy": FeatureSpec(False, "Alpha"),
    "MultiplePreemptions": FeatureSpec(True, "GA", lock_to_default=True),
    "TopologyAwareScheduling": FeatureSpec(False, "Alpha"),
    "ConfigurableResourceTransformations": FeatureSpec(True, "Beta"),
    "WorkloadResourceRequestsSummary": FeatureSpec(True, "GA",
                                                   lock_to_default=True),
    "ExposeFlavorsInLocalQueue": FeatureSpec(True, "Beta"),
    "AdmissionCheckValidationRules": FeatureSpec(False, "Deprecated"),
    "KeepQuotaForProvReqRetry": FeatureSpec(False, "Deprecated"),
    "ManagedJobsNamespaceSelector": FeatureSpec(True, "Beta"),
    "LocalQueueMetrics": FeatureSpec(False, "Alpha"),
    "LocalQueueDefaulting": FeatureSpec(False, "Alpha"),
    "TASProfileMostFreeCapacity": FeatureSpec(False, "Alpha"),
    "TASProfileLeastFreeCapacity": FeatureSpec(False, "Alpha"),
    "TASProfileMixed": FeatureSpec(False, "Alpha"),
    # kueue-tpu extension: the host walk's find_topology_assignment
    # asks its one question of the batched kernel
    # (ops/tas_kernel.answer_queries) instead of the scalar tree walk —
    # all three TAS profiles, bit-matching it; a question the int32
    # kernel cannot answer exactly is walked.  The per-cycle engine's
    # vector heads are placed by the batched stage (CycleSolver.tas_stage:
    # a cycle's questions a launch) either way
    "TASDeviceKernel": FeatureSpec(True, "Beta"),
}

_overrides: dict[str, bool] = {}


class UnknownFeatureError(KeyError):
    pass


def enabled(name: str) -> bool:
    if name in _overrides:
        return _overrides[name]
    spec = DEFAULT_FEATURE_GATES.get(name)
    if spec is None:
        raise UnknownFeatureError(name)
    return spec.default


def set_feature_gates(gates: dict[str, bool]) -> None:
    """Apply --feature-gates style overrides (cmd/kueue/main.go:129-144)."""
    for name, value in gates.items():
        spec = DEFAULT_FEATURE_GATES.get(name)
        if spec is None:
            raise UnknownFeatureError(name)
        if spec.lock_to_default and value != spec.default:
            raise ValueError(
                f"cannot set feature gate {name} to {value}: locked to "
                f"{spec.default} ({spec.stage})")
        _overrides[name] = value


def reset_feature_gates() -> None:
    _overrides.clear()


# ---------------------------------------------------------------------------
# Environment-flag registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class EnvFlag:
    """One declared ``KUEUE_TPU_*`` environment variable.

    ``default`` is the *raw string* handed back when the variable is
    unset — call sites keep their own parse/compare idiom (``!= "0"``,
    ``int(...)``, truthiness) so centralizing the read cannot change
    semantics.  ``type`` is documentation for the README table."""
    name: str
    default: str
    type: str                 # bool | int | str | path
    doc: str


# Every KUEUE_TPU_* variable the stack reads, in one place.  The env
# pass fails the lint if a read bypasses this table or if the README
# "Environment flags" table disagrees with it.
ENV_FLAGS: dict[str, EnvFlag] = {f.name: f for f in (
    EnvFlag("KUEUE_TPU_STATE", ".kueue-tpu", "path",
            "CLI state directory (durable store + WAL)."),
    EnvFlag("KUEUE_TPU_SHARDS", "0", "int",
            "Shard count for the (\"cq\",) mesh; 0 = serial path."),
    EnvFlag("KUEUE_TPU_REQUIRE_ACCEL", "0", "bool",
            "Fail a bench run that finds no accelerator or dispatches "
            "nothing to it (same as --require-accel)."),
    EnvFlag("KUEUE_TPU_PACK_TIGHTEN", "1", "bool",
            "Dtype-tighten launch planes (int32 -> int16/int8)."),
    EnvFlag("KUEUE_TPU_RESIDENT", "1", "bool",
            "Burst row and state planes stay on the device (one chip "
            "or the mesh) between windows; 0 sends them whole."),
    EnvFlag("KUEUE_TPU_RESIDENT_VERIFY", "", "bool",
            "Cross-check resident planes against the host's."),
    EnvFlag("KUEUE_TPU_SNAP_INCREMENTAL", "1", "bool",
            "Incremental O(dirty) snapshot maintenance in the cache."),
    EnvFlag("KUEUE_TPU_COMPILE_CACHE", "1", "bool",
            "Persistent XLA compile cache; 0 disables.  Placed by "
            "JAX_COMPILATION_CACHE_DIR, else .kueue-tpu/xla-cache in "
            "the checkout."),
    EnvFlag("KUEUE_TPU_WAL_COMMIT_EVERY", "1", "int",
            "CycleWAL group-commit interval (ops per fsync)."),
    EnvFlag("KUEUE_TPU_CHAOS_SEED", "", "int",
            "Seed the process-default chaos injector; empty = off."),
    EnvFlag("KUEUE_TPU_SCALE_SEED", "1307", "int",
            "Seed for the scale-soak scenario generator."),
    EnvFlag("KUEUE_TPU_TRAFFIC_SEED", "1109", "int",
            "Seed for the open-loop traffic soak."),
    EnvFlag("KUEUE_TPU_FED_SEED", "1511", "int",
            "Seed for the federation soak."),
    EnvFlag("KUEUE_TPU_REMOTE_RETRIES", "2", "int",
            "Per-request retry budget for HttpWorkerClient."),
    EnvFlag("KUEUE_TPU_REMOTE_DEADLINE_S", "15", "int",
            "Total per-request deadline (attempts + backoff sleeps) "
            "for HttpWorkerClient, seconds."),
    EnvFlag("KUEUE_TPU_OBS_TRACE", "0", "bool",
            "Enable hot-path span tracing at driver construction."),
    EnvFlag("KUEUE_TPU_OBS_EVENTS", "4096", "int",
            "Event-stream ring capacity (admit/evict/preempt/...)."),
    EnvFlag("KUEUE_TPU_FLIGHT_CYCLES", "256", "int",
            "Flight-recorder ring capacity, in cycles."),
    EnvFlag("KUEUE_TPU_SVC_HIGH_WATER", "4096", "int",
            "Serving ingest-queue depth past which backpressure "
            "rejects/sheds submissions."),
    EnvFlag("KUEUE_TPU_SVC_SLO_P99_S", "8.0", "str",
            "Serving p99 admission-latency SLO target, seconds."),
    EnvFlag("KUEUE_TPU_SVC_DRAIN_TIMEOUT_S", "30", "int",
            "Graceful-drain deadline after SIGTERM, wall seconds."),
    EnvFlag("KUEUE_TPU_SVC_INGEST_JOURNAL", "", "path",
            "Durable ingest-journal path; empty = in-memory only."),
    EnvFlag("KUEUE_TPU_SVC_SEED", "1709", "int",
            "Seed for the serving soak."),
    EnvFlag("KUEUE_TPU_LAZY_HEAP", "1", "bool",
            "Lazy heap repair: buffer pushes/updates and settle with "
            "one amortized sift pass at the next ordered read."),
    EnvFlag("KUEUE_TPU_CYCLE_BULK_APPLY", "1", "bool",
            "Batch each burst cycle's decision patches into one "
            "requeue-wakeup pass and one deferred cache rebuild."),
    EnvFlag("KUEUE_TPU_WAL_SHARDS", "1", "int",
            "CycleWAL segment count; >1 stripes group-commit across "
            "that many journal files with merged total-order replay."),
    EnvFlag("KUEUE_TPU_HOST_WORKERS", "0", "int",
            "Worker threads for the parallel host apply/pack plane "
            "(cache rebuild fan-out, dirty-CQ pack walk, requeue "
            "wakeups, WAL shard appends); 0 or 1 = serial."),
    EnvFlag("KUEUE_TPU_DIST_SEED", "2003", "int",
            "Seed for the distributed soak: process-kill schedule and "
            "the socket-fault proxy's per-connection rolls."),
    EnvFlag("KUEUE_TPU_DIST_SHARDS", "2", "int",
            "Front-end shard processes in the distributed soak (the "
            "LocalQueue-sharded admission services)."),
    EnvFlag("KUEUE_TPU_DIST_SUBMITTERS", "2", "int",
            "Submitter processes hammering the serving API in the "
            "distributed soak."),
    EnvFlag("KUEUE_TPU_DIST_WORKERS", "2", "int",
            "Federation worker processes in the distributed soak."),
    EnvFlag("KUEUE_TPU_DIST_PROXY_RESET", "0.0", "str",
            "Socket-fault proxy: per-connection probability of a hard "
            "RST before the request reaches upstream."),
    EnvFlag("KUEUE_TPU_DIST_PROXY_LATENCY_S", "0.0", "str",
            "Socket-fault proxy: seconds of added latency before "
            "dialing upstream (0 disables the latency fault)."),
    EnvFlag("KUEUE_TPU_DIST_PROXY_TRUNCATE", "0.0", "str",
            "Socket-fault proxy: per-connection probability of "
            "truncating the response mid-body and resetting."),
    EnvFlag("KUEUE_TPU_DIST_PROXY_BLACKHOLE", "0.0", "str",
            "Socket-fault proxy: per-connection probability of "
            "swallowing the request and never answering."),
)}


class UnknownEnvFlagError(KeyError):
    pass


def env_value(name: str, default: str | None = None) -> str:
    """Read a registered ``KUEUE_TPU_*`` variable as a raw string.

    ``default`` overrides the registry default for call sites whose
    fallback is context-dependent (e.g. the soaks); it must still name
    a registered flag."""
    spec = ENV_FLAGS.get(name)
    if spec is None:
        raise UnknownEnvFlagError(name)
    return os.environ.get(name, spec.default if default is None else default)


def env_int(name: str, default: int | None = None) -> int:
    """Read a registered flag as an int; malformed values fall back to
    the (registry or caller) default instead of raising."""
    spec = ENV_FLAGS.get(name)
    if spec is None:
        raise UnknownEnvFlagError(name)
    fallback = spec.default if default is None else str(default)
    raw = os.environ.get(name, fallback) or fallback
    try:
        return int(raw)
    except ValueError:
        return int(fallback or 0)


@contextlib.contextmanager
def set_feature_gate_during_test(name: str, value: bool):
    """reference kube_features.go:257."""
    had = name in _overrides
    prev = _overrides.get(name)
    set_feature_gates({name: value})
    try:
        yield
    finally:
        if had:
            _overrides[name] = prev
        else:
            _overrides.pop(name, None)
