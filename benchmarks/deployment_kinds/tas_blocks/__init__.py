"""Deployment kind ``tas_blocks``: ``flat_one_flavor``'s queues and
population on a cluster of nodes in a block / rack / host topology,
whose one ResourceFlavor is topology-aware (upstream KEP 2724): every
ClusterQueue's flavors are all TAS, so every admission carries a
placement of each pod on a node, found by the two-phase BestFit
algorithm (a required level: one domain of it; a preferred level: the
lowest level from it upward whose best domain holds the PodSet, else a
split over the top level; none: an unconstrained placement).  No node's
placed requests pass its allocatable; a head whose placement an earlier
admission of its cycle filled is skipped.

The names below are the whole of what the harness, the comparison and
the control call of a kind (the contract: benchmarks/harness.py).
"""

from .cluster import plan_cluster, problem, summary
from .ledger import ledger
from .program import build_driver, warm_up
from .reference import COMPARED, CONTROLS, Reference

__all__ = ["plan_cluster", "summary", "problem", "build_driver", "warm_up",
           "Reference", "CONTROLS", "COMPARED", "ledger"]
