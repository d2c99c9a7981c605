"""Deployment kind ``flat_one_flavor``: ClusterQueues in flat cohorts of
equal size, one ResourceFlavor, one resource group a queue, one PodSet
a workload, BestEffortFIFO, ``borrowWithinCohort: Never``, no fair
sharing.  A queue may use its nominal quota plus its borrowing limit; a
cohort holds the sum of its queues' nominals.

The names below are the whole of what the harness, the comparison and
the control call of a kind (the contract: benchmarks/harness.py).
"""

from .cluster import plan_cluster, problem, summary
from .ledger import ledger
from .program import build_driver, warm_up
from .reference import COMPARED, CONTROLS, Reference

__all__ = ["plan_cluster", "summary", "problem", "build_driver", "warm_up",
           "Reference", "CONTROLS", "COMPARED", "ledger"]
