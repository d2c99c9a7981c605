"""Deployment kind ``flat_elastic``: ``flat_one_flavor``'s cluster
(ClusterQueues in flat cohorts of equal size, one ResourceFlavor, one
resource group a queue, BestEffortFIFO, ``borrowWithinCohort: Never``,
no fair sharing) whose small jobs are elastic batch/v1 Jobs: a Job
annotated ``kueue.x-k8s.io/job-min-parallelism`` has one PodSet whose
``minCount`` is under its ``count`` (partial admission, KEP 420).

A head that neither fits nor finds eviction targets at its full count
is tried at fewer pods: upstream's binary search over the reduction
index i in [0, count - minCount] (podset_reducer.go), at ``count - i``
pods, for the smallest i that fits or finds targets.  The Job is
admitted on that many pods and its quota is charged at them; one that
no count fits keeps its full-count decision (a Preempt-mode head with
no targets reserves its full request).  An evicted Job asks for its
full count again.

The names below are the whole of what the harness, the comparison and
the control call of a kind (the contract: benchmarks/harness.py).
"""

from .cluster import plan_cluster, problem, summary
from .ledger import ledger
from .program import build_driver, warm_up
from .reference import COMPARED, CONTROLS, Reference

__all__ = ["plan_cluster", "summary", "problem", "build_driver", "warm_up",
           "Reference", "CONTROLS", "COMPARED", "ledger"]
