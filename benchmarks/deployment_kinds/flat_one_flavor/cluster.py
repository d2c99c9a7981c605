"""A configuration file -> the cluster it describes, as plain data.

Nothing here imports the program.  ``plan_cluster`` turns one file of
``benchmarks/configs/`` and a seed into queues (name, cohort, quotas)
and workloads (queue, class, requests, timestamps, running or pending).
The program's builder (``program.py`` beside this file) and the plain
reference (``reference.py``) both start from this plan, so neither
takes anything the other has made.

The seed draws labels and nothing else: which ClusterQueue name holds
which Zipf rank, and so the order of the queues and of their rows in
everything the program builds.  The tenant of a given rank is the same
in every seed, down to its workloads' creation and reservation times,
which come from one fixed permutation.  So two seeds run the same
cluster under other names, and the work of a run does not depend on
the seed.  (Creation orders drawn from the seed gave windows of 40 to
55 s: the program's batched search falls back to one launch a head for
a whole cycle when one head has over 1,024 candidates, and the seed
decided in which cycles one did; PERF.md, section 6.)
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

GIB = 1 << 30


def unit_scale(cfg: dict) -> dict[str, int]:
    """Canonical integers a unit: cpu in m, memory in bytes."""
    return {r: (GIB if u == "GiB" else 1)
            for r, u in cfg["deployment"]["resource_units"].items()}


def zipf_counts(total: int, n: int, exponent: float,
                minimum: int = 0) -> list[int]:
    """round(total x share) for ranks 1..n (halves round up)."""
    w = [1.0 / (r ** exponent) for r in range(1, n + 1)]
    h = sum(w)
    return [max(minimum, int(total * x / h + 0.5)) for x in w]


def _ceil_to(x: int, step: int) -> int:
    return -(-x // step) * step


@dataclass
class Queue:
    name: str
    cohort: str
    rank: int                      # 1-based Zipf rank
    nominal: dict[str, int]
    borrowing_limit: dict[str, int]
    running: int = 0
    pending: int = 0


@dataclass
class ClusterPlan:
    config: dict
    resources: list[str]
    queues: list[Queue]
    # one row a workload, in queue order then k
    wl_queue: np.ndarray           # [N] index into queues
    wl_name: list[str]             # "wl-<rank>-<k>": the same in every seed
    wl_priority: np.ndarray        # [N]
    wl_pods: np.ndarray            # [N]
    wl_request: np.ndarray         # [N, R] totals (pods x per pod), canonical
    wl_created: np.ndarray         # [N] float seconds
    wl_running: np.ndarray         # [N] bool
    wl_reserved: np.ndarray        # [N] float seconds (running rows)
    clock_start: float = 0.0
    cycle_s: float = 1.0
    namespace: str = "default"

    def key(self, i: int) -> str:
        return f"{self.namespace}/{self.wl_name[i]}"


def queue_rows(cfg: dict) -> dict:
    """What the fused window's grid holds, from the sizes alone: the
    running count of each Zipf rank, the hottest queue, the grid's row
    bucket M (next power of two at or above the hottest queue's admitted
    rows plus the pending rows a queue packs), the slot count and the
    rows in forests that can preempt."""
    dep, pop = cfg["deployment"], cfg["population"]
    n = dep["cluster_queues"]
    running = zipf_counts(pop["running"], n, pop["zipf_exponent"])
    pending = zipf_counts(pop["pending"], n, pop["zipf_exponent"],
                          pop["pending_min_per_queue"])
    per_queue = cfg["fused_path_limits"]["pending_rows_per_queue"]
    deepest = max(r + min(p, per_queue)
                  for r, p in zip(running, pending))
    m = 1
    while m < deepest:
        m *= 2
    preempts = (dep["preemption"]["withinClusterQueue"] != "Never"
                or dep["preemption"]["reclaimWithinCohort"] != "Never")
    forest_rows = (sum(r + min(p, per_queue)
                       for r, p in zip(running, pending))
                   if preempts else 0)
    return {"running": running, "pending": pending,
            "hottest_running": max(running), "deepest_rows": deepest,
            "M": m, "slots": n * m, "preempting_forest_rows": forest_rows}


def problem(cfg: dict, plan: ClusterPlan) -> dict:
    """What ``benchmarks/peaks.py`` counts a decided cycle's bytes from:
    the rows a cycle can decide about, the queues and the resources."""
    return {"real_rows": queue_rows(cfg)["preempting_forest_rows"],
            "queues": len(plan.queues), "resources": len(plan.resources)}


def summary(plan: ClusterPlan) -> str:
    return (f"built {len(plan.queues)} queues, "
            f"{int(plan.wl_running.sum())} restored, "
            f"{int((~plan.wl_running).sum())} ingested")


def plan_cluster(cfg: dict, seed: int) -> ClusterPlan:
    dep, pop = cfg["deployment"], cfg["population"]
    resources = list(dep["resources"])
    scale = unit_scale(cfg)
    n_q = dep["cluster_queues"]
    n_cohorts = dep["cohorts"]
    rng = np.random.default_rng([int(seed), 0x6B756575])   # any whole seed
    rows = queue_rows(cfg)
    # labels: ClusterQueue cq-<i> holds rank ranks[i]
    ranks = rng.permutation(n_q) + 1

    classes = cfg["classes"]
    cls_of_mod = {}
    for ci, c in enumerate(classes):
        for m in c["k_mod_3"]:
            cls_of_mod[m] = ci
    R = len(resources)

    q_idx, names, prio, pods, req, run = [], [], [], [], [], []
    queues: list[Queue] = []
    usage = np.zeros((n_q, R), dtype=np.int64)
    for i in range(n_q):
        rank = int(ranks[i])
        n_run = rows["running"][rank - 1]
        n_pen = rows["pending"][rank - 1]
        n = n_run + n_pen
        k = np.arange(n)
        cls = np.array([cls_of_mod[m] for m in range(3)])[k % 3]
        p = np.zeros(n, dtype=np.int64)
        c_pods = np.zeros(n, dtype=np.int64)
        c_req = np.zeros((n, R), dtype=np.int64)
        for ci, c in enumerate(classes):
            sel = cls == ci
            p[sel] = c["priority"]
            cyc = np.array(c["pods"])[(k[sel] // 3) % len(c["pods"])]
            c_pods[sel] = cyc
            for ri, r in enumerate(resources):
                c_req[sel, ri] = cyc * c["per_pod"][r] * scale[r]
        # which hold quota: higher priority first, then lower k
        order = np.lexsort((k, -p))
        running = np.zeros(n, dtype=bool)
        running[order[:n_run]] = True
        usage[i] = c_req[running].sum(axis=0)
        q_idx.append(np.full(n, i))
        names.extend(f"wl-{rank}-{kk}" for kk in range(n))
        prio.append(p)
        pods.append(c_pods)
        req.append(c_req)
        run.append(running)
        queues.append(Queue(name=f"cq-{i}",
                            cohort=f"cohort-{rank % n_cohorts}",
                            rank=rank, nominal={}, borrowing_limit={},
                            running=n_run, pending=n_pen))

    # quotas
    step = {r: dep["quota_round_up"][r] * scale[r] for r in resources}
    if dep["quota_rule"] != "queue_usage":
        raise ValueError(f"quota_rule {dep['quota_rule']!r}")
    for i, q in enumerate(queues):
        for ri, r in enumerate(resources):
            q.nominal[r] = _ceil_to(int(usage[i, ri]), step[r])
            q.borrowing_limit[r] = dep["borrowing_limit"][r] * scale[r]

    wl_queue = np.concatenate(q_idx)
    N = len(wl_queue)
    # one permutation of 1..N for every seed, laid out rank by rank;
    # the queue that holds rank r takes rank r's stretch of it
    by_rank = np.random.default_rng(0x6B756575).permutation(N) + 1
    depth = [a + b for a, b in zip(rows["running"], rows["pending"])]
    start = np.concatenate(([0], np.cumsum(depth)))
    created = np.concatenate(
        [by_rank[start[r - 1]:start[r]] for r in ranks]).astype(np.float64)
    clock = cfg["clock"]
    return ClusterPlan(
        config=cfg, resources=resources, queues=queues,
        wl_queue=wl_queue, wl_name=names,
        wl_priority=np.concatenate(prio), wl_pods=np.concatenate(pods),
        wl_request=np.concatenate(req), wl_created=created,
        wl_running=np.concatenate(run),
        wl_reserved=created + clock["restored_reservation_offset_s"],
        clock_start=float(clock["start_s"]), cycle_s=float(clock["cycle_s"]))
