"""A configuration file -> the cluster it describes, as plain data.

Nothing here imports the program.  The population (queues, classes,
quotas, which workloads run, every timestamp, what the seed draws) is
``flat_one_flavor``'s, planned by its module from the same keys.  This
kind adds the nodes and where the running workloads sit on them:

- the topology is a regular tree of ``blocks`` x ``racks_per_block`` x
  ``hosts_per_rack`` nodes, each of the configuration's node shape;
  host ``h`` is node ``h`` in id order (block-major), and its label
  values are ``block-BB``, ``rack-BB-RR``, ``node-BB-RR-HH``, zero-padded
  so that id order and label order agree;
- each workload's PodSet carries the topology request its class and
  pod count give (none, preferred at a level, or required at a level);
- the running workloads are placed by a cheap deterministic fill, in
  Zipf rank then k order, so that every seed places the same tenant on
  the same hosts: a cursor walks the hosts in id order and never goes
  back; a one-pod job takes the first host from the cursor with room
  for it, a gang with a preferred level one host that holds all of it,
  a gang with a required level consecutive hosts inside one domain of
  that level (the next domain when the cursor's cannot hold it).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..flat_one_flavor import cluster as one_flavor
from ..flat_one_flavor.cluster import queue_rows, summary, unit_scale

__all__ = ["plan_cluster", "problem", "summary", "queue_rows",
           "NONE", "PREFERRED", "REQUIRED"]

# a PodSet's topology request
NONE, PREFERRED, REQUIRED = 0, 1, 2


@dataclass
class TASPlan(one_flavor.ClusterPlan):
    """``flat_one_flavor``'s plan on a cluster of nodes.  The node plane
    has three columns: cpu (m), memory (bytes), pods."""
    levels: list = field(default_factory=list)
    fanout: tuple = ()                 # (blocks, racks a block, hosts a rack)
    node_cap: np.ndarray = None        # [3] int64: one node's allocatable
    wl_topology: np.ndarray = None     # [N] int8: NONE / PREFERRED / REQUIRED
    wl_level: np.ndarray = None        # [N] int8: the request's level index
    # the running workloads' placements: row i's pods sit on hosts
    # place_host[place_start[i]:place_start[i + 1]], place_pods of each
    place_start: np.ndarray = None     # [N + 1] int64
    place_host: np.ndarray = None      # [P] int32
    place_pods: np.ndarray = None      # [P] int32

    @property
    def n_hosts(self) -> int:
        b, r, h = self.fanout
        return b * r * h

    def host_values(self, h: int) -> tuple:
        """The label values of host ``h``, top level first."""
        _, racks, hosts = self.fanout
        b, rest = divmod(int(h), racks * hosts)
        r, hh = divmod(rest, hosts)
        return (f"block-{b:02d}", f"rack-{b:02d}-{r:02d}",
                f"node-{b:02d}-{r:02d}-{hh:02d}")

    def per_pod(self, i: int) -> tuple:
        """Row ``i``'s request a pod on the node plane's columns."""
        n = int(self.wl_pods[i])
        return tuple(int(v) // n for v in self.wl_request[i]) + (1,)


def topology_of(cfg: dict) -> dict:
    topo = cfg["deployment"]["topology"]
    if topo.get("profile", "BestFit") != "BestFit":
        raise ValueError("the plain reference covers the BestFit profile "
                         "only")
    if len(topo["levels"]) != 3:
        raise ValueError("the topology is block / rack / host")
    return topo


def _requests_of(cfg: dict, levels: list) -> dict:
    """(class index, pods) -> (request kind, level index)."""
    out = {}
    for ci, c in enumerate(cfg["classes"]):
        for rule in c["topology"]:
            req = rule["request"]
            for n in rule["pods"]:
                if req is None:
                    out[ci, n] = (NONE, -1)
                elif "required" in req:
                    out[ci, n] = (REQUIRED, levels.index(req["required"]))
                else:
                    out[ci, n] = (PREFERRED, levels.index(req["preferred"]))
    return out


def _class_of(cfg: dict, plan) -> np.ndarray:
    """Each row's class index, from its priority (the classes' are
    distinct)."""
    by_prio = {c["priority"]: ci for ci, c in enumerate(cfg["classes"])}
    if len(by_prio) != len(cfg["classes"]):
        raise ValueError("classes must differ in priority")
    return np.array([by_prio[p] for p in plan.wl_priority.tolist()],
                    dtype=np.int8)


def place_running(plan: TASPlan) -> None:
    """The deterministic fill of the module's docstring, into
    ``place_start`` / ``place_host`` / ``place_pods``."""
    _, racks, hosts = plan.fanout
    H = plan.n_hosts
    cap = plan.node_cap.tolist()
    free = [[cap[0]] * H, [cap[1]] * H, [cap[2]] * H]
    span = {2: 1, 1: hosts, 0: racks * hosts}     # hosts in a domain
    # rank order, then the plan's row order (k) inside a queue
    n_q = len(plan.queues)
    by_rank = sorted(range(n_q), key=lambda c: plan.queues[c].rank)
    rows_of = np.split(np.arange(len(plan.wl_queue)),
                       np.flatnonzero(np.diff(plan.wl_queue)) + 1)
    running = plan.wl_running
    kinds = plan.wl_topology.tolist()
    levels = plan.wl_level.tolist()
    pods = plan.wl_pods.tolist()
    req = plan.wl_request.tolist()
    starts = np.zeros(len(plan.wl_queue) + 1, dtype=np.int64)
    out_h: list[int] = []
    out_n: list[int] = []
    cur = 0

    def room(h, c, m):
        return min(free[0][h] // c, free[1][h] // m, free[2][h])

    def take(h, k, c, m):
        free[0][h] -= k * c
        free[1][h] -= k * m
        free[2][h] -= k
        out_h.append(h)
        out_n.append(k)

    order = [i for c in by_rank for i in rows_of[c].tolist() if running[i]]
    marks = {}
    for i in order:
        n = pods[i]
        c, m = req[i][0] // n, req[i][1] // n
        marks[i] = len(out_h)
        if kinds[i] == REQUIRED:
            width = span[levels[i]]
            while True:
                if cur >= H:
                    raise ValueError("the running set does not fit the "
                                     "nodes")
                end = (cur // width + 1) * width
                plan_h, left, h = [], n, cur
                while left and h < end:
                    k = min(left, room(h, c, m))
                    if k > 0:
                        plan_h.append((h, k))
                        left -= k
                    if left:
                        h += 1
                if not left:
                    for hh, k in plan_h:
                        take(hh, k, c, m)
                    cur = h
                    break
                cur = end
        else:
            while cur < H and room(cur, c, m) < n:
                cur += 1
            if cur >= H:
                raise ValueError("the running set does not fit the nodes")
            take(cur, n, c, m)
    # the placements were made in fill order: lay them out in row order
    first = np.zeros(len(plan.wl_queue), dtype=np.int64)
    cnt = np.zeros(len(plan.wl_queue), dtype=np.int64)
    bounds = [marks[i] for i in order] + [len(out_h)]
    first[order] = bounds[:-1]
    cnt[order] = np.diff(bounds)
    rows = np.flatnonzero(cnt)
    perm = (np.concatenate([np.arange(first[i], first[i] + cnt[i])
                            for i in rows.tolist()])
            if len(rows) else np.zeros(0, dtype=np.int64))
    starts[1:] = np.cumsum(cnt)
    plan.place_start = starts
    plan.place_host = np.asarray(out_h, dtype=np.int32)[perm]
    plan.place_pods = np.asarray(out_n, dtype=np.int32)[perm]


def plan_cluster(cfg: dict, seed: int) -> TASPlan:
    base = one_flavor.plan_cluster(cfg, seed)
    topo = topology_of(cfg)
    levels = list(topo["levels"])
    scale = unit_scale(cfg)
    node = topo["node"]
    cap = np.array([node["cpu"] * scale["cpu"],
                    node["memory"] * scale["memory"], node["pods"]],
                   dtype=np.int64)
    rule = _requests_of(cfg, levels)
    cls = _class_of(cfg, base)
    pods = base.wl_pods.tolist()
    pairs = [rule[c, n] for c, n in zip(cls.tolist(), pods)]
    fields = {k: getattr(base, k) for k in base.__dataclass_fields__}
    plan = TASPlan(
        **fields, levels=levels,
        fanout=(topo["blocks"], topo["racks_per_block"],
                topo["hosts_per_rack"]),
        node_cap=cap,
        wl_topology=np.array([p[0] for p in pairs], dtype=np.int8),
        wl_level=np.array([p[1] for p in pairs], dtype=np.int8))
    place_running(plan)
    return plan


def problem(cfg: dict, plan: TASPlan) -> dict:
    """What ``benchmarks/peaks.py`` counts a decided cycle's bytes from
    (the first kind's rows, queues and resources), and what the
    topology stage's roofline reads: the leaves, the domains a level
    and the node plane's resources."""
    b, r, h = plan.fanout
    return {"real_rows": queue_rows(cfg)["preempting_forest_rows"],
            "queues": len(plan.queues), "resources": len(plan.resources),
            "tas_leaves": b * r * h, "tas_domains": [b, b * r, b * r * h],
            "tas_resources": len(plan.node_cap)}
