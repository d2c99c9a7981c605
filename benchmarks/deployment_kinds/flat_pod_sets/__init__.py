"""Deployment kind ``flat_pod_sets``: ``flat_two_group``'s cluster
(ClusterQueues in flat cohorts of equal size, BestEffortFIFO,
``borrowWithinCohort: Never``, no fair sharing; cpu under the flavors
``x86`` and ``arm``, memory under ``default-flavor``) whose jobs are what
upstream's job integrations submit: a Workload of several PodSets
(``spec.podSets``, 1 to 8: an MPIJob's launcher and workers, a
RayJob's head and worker groups, a JobSet's replicated jobs, a
LeaderWorkerSet's leader and workers), each PodSet with its own node
selector and tolerations.

The flavor assigner walks a Workload's PodSets in order.  Each PodSet
gets one flavor a resource group, by ``flat_two_group``'s walk, tested
at ``val = the PodSet's request + what the earlier PodSets of the same
Workload already took on that (flavor, resource)``.  The Workload is as
good as its worst PodSet; a PodSet with no flavor ends the walk, the
Workload is NoFit and is admitted whole or not at all; the resume state
is kept a (PodSet, group), and a NoFit Workload keeps that of the
PodSets before the one that found no flavor; the oracle is asked at
``val``; eviction targets are found over the union of the pairs short
of quota in any PodSet, against the Workload's summed usage.  Quota is
held a (flavor, resource) and counts every PodSet of an admission.

The names below are the whole of what the harness, the comparison and
the control call of a kind (the contract: benchmarks/harness.py).
"""

from .cluster import plan_cluster, problem, summary
from .ledger import ledger
from .program import build_driver, warm_up
from .reference import COMPARED, CONTROLS, Reference

__all__ = ["plan_cluster", "summary", "problem", "build_driver", "warm_up",
           "Reference", "CONTROLS", "COMPARED", "ledger"]
