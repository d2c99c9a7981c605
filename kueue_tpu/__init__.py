"""kueue_tpu: a TPU-native job-queueing framework with the capabilities of Kueue.

Quota-based admission of gang workloads across hierarchical cohorts of
ClusterQueues with resource flavors, borrowing/lending, priority and
fair-share (DRF) preemption, two-phase admission checks, topology-aware
placement and multi-cluster dispatch.  The per-cycle admission core runs as
a batched JAX/XLA solver (see kueue_tpu.ops) driven by a thin control plane
that mirrors the reference's cache/queue/event semantics.
"""

__version__ = "0.1.0"

# Loading an XLA:CPU AOT compilation-cache entry logs two multi-KB ERROR
# lines about tuning pseudo-features ("+prefer-no-scatter is not
# supported"); the env var must be set before jaxlib's static
# initialization, so it lives here.  Only when the caller chose the CPU:
# on the chip path nothing is silenced, because level 3 also swallows
# libtpu's own start-up errors — the lines that say why a chip was not
# found.
import os as _os

if _os.environ.get("JAX_PLATFORMS") == "cpu":
    _os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
del _os
