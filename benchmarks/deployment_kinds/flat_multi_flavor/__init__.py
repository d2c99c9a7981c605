"""Deployment kind ``flat_multi_flavor``: ``flat_one_flavor``'s cluster
(ClusterQueues in flat cohorts of equal size, one PodSet a workload,
BestEffortFIFO, ``borrowWithinCohort: Never``, no fair sharing) with
several plain ResourceFlavors in the one resource group of every queue,
tried in the order the queue lists them under its ``flavorFungibility``.
Quota is held a (flavor, resource): a queue may use its nominal quota
plus its borrowing limit, a cohort the sum of its queues' nominals, and
all resources of an admission take one flavor.

The names below are the whole of what the harness, the comparison and
the control call of a kind (the contract: benchmarks/harness.py).
"""

from .cluster import plan_cluster, problem, summary
from .ledger import ledger
from .program import build_driver, warm_up
from .reference import COMPARED, CONTROLS, Reference

__all__ = ["plan_cluster", "summary", "problem", "build_driver", "warm_up",
           "Reference", "CONTROLS", "COMPARED", "ledger"]
