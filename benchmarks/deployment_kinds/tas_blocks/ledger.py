"""The ledger of this deployment kind, which trusts neither the program
nor the reference: the first kind's quota ledger, and the nodes added
up from the program's own placements (the cycle record's ``placed``,
one ``"<workload key>@<PodSet>=<block>/<rack>/<host>x<pods>"`` a node).
It knows the capacity model and nothing of how a cycle decides: a node
holds at most its allocatable in cpu, memory and pods; every pod of a
PodSet whose request names a required level lies in one domain of that
level; an admission places every pod of its workload.
"""

from __future__ import annotations

import sys

import numpy as np

from ..flat_one_flavor.ledger import ledger as quota_ledger
from .cluster import REQUIRED


def _host_of(plan) -> dict:
    """Label path -> host index, for every node of the plan."""
    return {"/".join(plan.host_values(h)): h for h in range(plan.n_hosts)}


def ledger(plan, rounds) -> dict:
    """Adds up the program's own answers.  ``quota_violations`` holds
    three counts added up: the first kind's (a queue or a cohort over
    its quota after any cycle), ``node_overcommits`` (a node over its
    allocatable in any resource after any cycle, one a node and cycle)
    and ``required_split`` (an admitted PodSet with a required level
    whose pods lie in more than one domain of that level, or whose
    placement does not hold all its pods or names an unknown node);
    both are also returned apart."""
    out = quota_ledger(plan, rounds)
    host_of = _host_of(plan)
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    cap = plan.node_cap
    used = np.zeros((plan.n_hosts, len(cap)), dtype=np.int64)
    place: dict[str, list] = {}
    starts = plan.place_start.tolist()
    hosts = plan.place_host.tolist()
    pods = plan.place_pods.tolist()
    per_pod = {}

    def pp(i):
        v = per_pod.get(i)
        if v is None:
            v = per_pod[i] = np.asarray(plan.per_pod(i), dtype=np.int64)
        return v

    for i in np.flatnonzero(plan.wl_running).tolist():
        pl = list(zip(hosts[starts[i]:starts[i + 1]],
                      pods[starts[i]:starts[i + 1]]))
        place[plan.key(i)] = pl
        for h, k in pl:
            used[h] += k * pp(i)
    _, racks, per_rack = plan.fanout
    width = {0: racks * per_rack, 1: per_rack, 2: 1}

    def release(k):
        pl = place.pop(k, None)
        if pl is not None:
            i = row[k]
            for h, n in pl:
                used[h] -= n * pp(i)

    overcommits = split = 0
    for rnd in rounds:
        for k in rnd.finished:
            release(k)
        for cyc in rnd.cycles:
            for k in cyc.evicted:
                release(k)
            by_key: dict[str, list] = {}
            for entry in getattr(cyc, "placed", []):
                key, _, rest = entry.partition("@")
                _, _, where = rest.partition("=")
                path, _, n = where.rpartition("x")
                h = host_of.get(path)
                if h is None or not n.isdigit():
                    split += 1
                    continue
                by_key.setdefault(key, []).append((h, int(n)))
            touched = set()
            for k in cyc.admitted:
                i = row.get(k)
                pl = by_key.get(k, [])
                if i is None:
                    continue
                if sum(n for _, n in pl) != int(plan.wl_pods[i]):
                    split += 1
                if (int(plan.wl_topology[i]) == REQUIRED and len(
                        {h // width[int(plan.wl_level[i])]
                         for h, _ in pl}) > 1):
                    split += 1
                place[k] = pl
                for h, n in pl:
                    used[h] += n * pp(i)
                    touched.add(h)
            overcommits += sum(1 for h in touched if (used[h] > cap).any())
    print(f"ledger: node_overcommits {overcommits}, required_split {split}",
          file=sys.stderr)
    out["node_overcommits"] = overcommits
    out["required_split"] = split
    out["quota_violations"] += overcommits + split
    return out
