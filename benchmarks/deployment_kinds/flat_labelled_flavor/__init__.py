"""Deployment kind ``flat_labelled_flavor``: ``flat_multi_flavor``'s
cluster (ClusterQueues in flat cohorts of equal size, one PodSet a
workload, BestEffortFIFO, ``borrowWithinCohort: Never``, no fair
sharing, several ResourceFlavors in the one resource group of every
queue, tried in order under the queue's ``flavorFungibility``) with the
flavors declared as a cluster declares them: node labels, and a taint on
some, and the jobs carrying a node selector and tolerations.  A flavor
that a job may not take (a taint it does not tolerate, or a selector
that does not match the flavor's labels on a key some flavor of the
group carries) is passed over by its walk: visited, no stop, no
candidate for the oracle.  Quota is held a (flavor, resource), as in
``flat_multi_flavor``.

The names below are the whole of what the harness, the comparison and
the control call of a kind (the contract: benchmarks/harness.py).
"""

from .cluster import plan_cluster, problem, summary
from .ledger import ledger
from .program import build_driver, warm_up
from .reference import COMPARED, CONTROLS, Reference

__all__ = ["plan_cluster", "summary", "problem", "build_driver", "warm_up",
           "Reference", "CONTROLS", "COMPARED", "ledger"]
