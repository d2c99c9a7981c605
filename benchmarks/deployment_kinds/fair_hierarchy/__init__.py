"""Deployment kind ``fair_hierarchy``: ClusterQueues in a tree of
cohorts (a department a root, its teams under it, a team's tenants
under the team), one ResourceFlavor, one resource group a queue, one
PodSet a workload, BestEffortFIFO, ``borrowWithinCohort: Never``, fair
sharing on (upstream KEP 79 and KEP 1714): a cycle's heads are ordered
by the tournament over each tree's dominant resource shares, and a
preempting head's targets are found by the fair-sharing strategies.  A
cohort holds the sum of its children's quotas; no node has a borrowing
or lending limit.

The names below are the whole of what the harness, the comparison and
the control call of a kind (the contract: benchmarks/harness.py).
"""

from .cluster import plan_cluster, problem, summary
from .ledger import ledger
from .program import build_driver, warm_up
from .reference import COMPARED, CONTROLS, Reference

__all__ = ["plan_cluster", "summary", "problem", "build_driver", "warm_up",
           "Reference", "CONTROLS", "COMPARED", "ledger"]
