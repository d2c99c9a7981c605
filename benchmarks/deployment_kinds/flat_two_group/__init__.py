"""Deployment kind ``flat_two_group``: ``flat_one_flavor``'s cluster
(ClusterQueues in flat cohorts of equal size, one PodSet a workload,
BestEffortFIFO, ``borrowWithinCohort: Never``, no fair sharing) whose
ClusterQueues declare several resource groups, as upstream's
"Multiple ResourceFlavors" does: cpu under the flavors ``x86`` and
``arm``, memory under ``default-flavor``.  A resource and a flavor
belong to one group each; a PodSet gets one flavor a group, all
resources of a group the same flavor, the groups independently: one
flavor walk a group (from that group's own resume slot, over the
flavors the job may take *in that group*, under the queue's
``flavorFungibility``), the head as good as its worst group, the
oracle asked a group, the eviction targets found over the
flavor-resources that need preemption in any group.  Quota is held a
(flavor, resource), each pair in its own group.

The names below are the whole of what the harness, the comparison and
the control call of a kind (the contract: benchmarks/harness.py).
"""

from .cluster import plan_cluster, problem, summary
from .ledger import ledger
from .program import build_driver, warm_up
from .reference import COMPARED, CONTROLS, Reference

__all__ = ["plan_cluster", "summary", "problem", "build_driver", "warm_up",
           "Reference", "CONTROLS", "COMPARED", "ledger"]
