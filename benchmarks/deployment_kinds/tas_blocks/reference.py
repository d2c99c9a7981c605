"""The plain reference: ``flat_one_flavor``'s admission cycle with
upstream Kueue's topology-aware scheduling (KEP 2724) written out
directly.

It imports nothing of the program and takes nothing the program has
made.  The quota rules (heads, Fit / Preempt / NoFit, targets, the
admit loop) are the first kind's, line for line.  What this kind adds
(pkg/cache/tas_flavor_snapshot.go, pkg/scheduler/flavorassigner
``assignFlavors``' TAS hook, pkg/scheduler ``fits``):

  1. Every head whose quota mode is Fit asks for a placement of its
     PodSet against the nodes as they stand at the cycle's start: the
     request of its class (required or preferred at a level), or an
     unconstrained one (the queue's flavor is topology-aware, so TAS is
     implied).  If none is found the head is Preempt, with no flavor
     resource needing preemption: it finds no target and reserves.
  2. A Preempt head asks the same against the empty cluster (every
     node at its allocatable); if none is found there either it is
     NoFit.  Its placement is not kept.
  3. Phase 1 counts a domain's pods: a node holds ``min(free // per
     pod)`` over cpu, memory and pods; a rack or block the sum of its
     children.  Phase 2 (BestFit): a required level takes the domain of
     that level with the fewest pods that still holds the PodSet
     (lowest id on a tie); a preferred level tries its level, then each
     level above; an unconstrained request, or a preferred one no
     domain holds, is split over the blocks.  A split, and the descent
     from a picked domain to the nodes, walks the children in (most
     pods, id) order, taking each whole until what is left fits in one,
     and gives the rest to the tightest child that holds it.
  4. In the admit loop a Fit head that passed the quota check is
     skipped ("no longer fits") when a node of its placement no longer
     holds it after the cycle's earlier admissions; otherwise its
     placement is charged to the nodes.  Evictions of the cycle give
     their nodes back for the next cycle.

``broken`` switches one stated guarantee off and makes a control the
comparison has to fail.  ``CONTROLS`` fail at the cell's size;
``TOY_CONTROLS`` only where a cluster lets their guarantee decide (the
CPU tests hold them).
"""

from __future__ import annotations

import heapq

import numpy as np

from ..flat_one_flavor.reference import (FIT, NOFIT, PREEMPT, CycleResult,
                                         Reference as FlatReference)
from .cluster import NONE, PREFERRED, REQUIRED

CONTROLS = ("best_fit_ignored", "in_cycle_usage_ignored")
TOY_CONTROLS = ("required_as_preferred",)

COMPARED = ("admitted", "evicted", "skipped", "preempting", "placed")

__all__ = ["COMPARED", "CONTROLS", "TOY_CONTROLS", "Reference"]


class Tree:
    """The nodes' free capacity and, for each pod shape asked so far,
    the pods each domain holds (phase 1), kept current as nodes change."""

    def __init__(self, plan, free: np.ndarray):
        self.B, self.RK, self.HS = plan.fanout
        self.free = free                       # [H, 3] int64
        self.shapes: dict[tuple, list] = {}    # per pod -> [block, rack, leaf]

    def _leaf_state(self, pp, free):
        return np.min(free // np.array(pp, dtype=np.int64), axis=-1)

    def states(self, pp) -> list:
        st = self.shapes.get(pp)
        if st is None:
            leaf = self._leaf_state(pp, self.free)
            rack = leaf.reshape(-1, self.HS).sum(axis=1)
            block = rack.reshape(-1, self.RK).sum(axis=1)
            st = self.shapes[pp] = [block, rack, leaf]
        return st

    def add(self, h: int, delta) -> None:
        """Node ``h``'s free capacity moves by ``delta``."""
        self.free[h] += np.asarray(delta, dtype=np.int64)
        for pp, (block, rack, leaf) in self.shapes.items():
            new = int(self._leaf_state(pp, self.free[h]))
            d = new - int(leaf[h])
            if d:
                leaf[h] = new
                rack[h // self.HS] += d
                block[h // (self.HS * self.RK)] += d


class Reference(FlatReference):
    def __init__(self, plan, broken: str | None = None):
        if broken is not None and broken not in CONTROLS + TOY_CONTROLS:
            raise ValueError(f"unknown control {broken!r}")
        self.plan = plan
        H = plan.n_hosts
        self.tree = Tree(plan, np.tile(plan.node_cap, (H, 1)))
        self.empty = Tree(plan, np.tile(plan.node_cap, (H, 1)))
        self.empty_memo: dict[tuple, bool] = {}
        self.place: dict[int, list] = {}
        starts = plan.place_start.tolist()
        hosts = plan.place_host.tolist()
        pods = plan.place_pods.tolist()
        for i in np.flatnonzero(plan.wl_running).tolist():
            self.place[i] = list(zip(hosts[starts[i]:starts[i + 1]],
                                     pods[starts[i]:starts[i + 1]]))
        self.pp = [plan.per_pod(i) for i in range(len(plan.wl_name))]
        self.kind = plan.wl_topology.tolist()
        self.level = plan.wl_level.tolist()
        self.ps_name = "main"
        # the running rows' usage is charged in bulk, then the first
        # kind's set-up (its _add charges nothing more: see _add)
        self._bulk = True
        used = np.zeros_like(self.tree.free)
        for i, pl in self.place.items():
            for h, k in pl:
                used[h] += k * np.asarray(self.pp[i], dtype=np.int64)
        self.tree.free -= used
        super().__init__(plan, broken=None)
        self._bulk = False
        self.broken = broken

    # -- state ---------------------------------------------------------

    def _add(self, i, now, sort=True):
        super()._add(i, now, sort)
        if not self._bulk:
            for h, k in self.place[i]:
                self.tree.add(h, [-k * v for v in self.pp[i]])

    def _drop(self, i):
        super()._drop(i)
        for h, k in self.place.pop(i):
            self.tree.add(h, [k * v for v in self.pp[i]])

    # -- phase 2 ---------------------------------------------------------

    def _best(self, st, n):
        """The domain of a level that holds ``n`` pods with the fewest
        (lowest id on a tie), or None."""
        cand = np.flatnonzero(st >= n)
        if not len(cand):
            return None
        if self.broken == "best_fit_ignored":
            return int(cand[0])
        return int(cand[np.argmin(st[cand])])

    def _select(self, ids, st, n, out: dict) -> None:
        """``n`` pods over the domains ``ids`` (states ``st``): whole
        domains in (most pods, id) order until the rest fits in one,
        which goes to the tightest that holds it."""
        order = np.lexsort((ids, -st))
        ids, st = ids[order], st[order]
        rem = n
        for j in range(len(ids)):
            if rem <= 0:
                return
            s = int(st[j])
            if s >= rem:
                pick = j
                if self.broken != "best_fit_ignored":
                    rest = np.flatnonzero(st[j:] >= rem) + j
                    key = np.lexsort((ids[rest], st[rest]))
                    pick = int(rest[key[0]])
                out[int(ids[pick])] = out.get(int(ids[pick]), 0) + rem
                return
            if s > 0:
                out[int(ids[j])] = out.get(int(ids[j]), 0) + s
                rem -= s

    def _descend(self, states, level, dom, n, out: dict) -> None:
        if level == 2:
            out[dom] = out.get(dom, 0) + n
            return
        fan = self.tree.RK if level == 0 else self.tree.HS
        ids = np.arange(dom * fan, (dom + 1) * fan)
        picked: dict = {}
        self._select(ids, states[level + 1][ids], n, picked)
        for child, k in picked.items():
            self._descend(states, level + 1, child, k, out)

    def _find(self, tree: Tree, i: int) -> dict | None:
        """Row ``i``'s placement on ``tree``: {host: pods}, or None."""
        n = int(self.plan.wl_pods[i])
        states = tree.states(self.pp[i])
        kind, level = self.kind[i], self.level[i]
        if kind == REQUIRED and self.broken == "required_as_preferred":
            kind = PREFERRED
        out: dict = {}
        if kind != NONE:
            top = level if kind == REQUIRED else 0
            for lvl in range(level, top - 1, -1):
                best = self._best(states[lvl], n)
                if best is not None:
                    self._descend(states, lvl, best, n, out)
                    return out
            if kind == REQUIRED:
                return None
        if int(states[0].sum()) < n:
            return None
        roots: dict = {}
        self._select(np.arange(len(states[0])), states[0], n, roots)
        for b, k in roots.items():
            self._descend(states, 0, b, k, out)
        return out

    def _fits_empty(self, i: int) -> bool:
        key = (self.pp[i], int(self.plan.wl_pods[i]), self.kind[i],
               self.level[i])
        ok = self.empty_memo.get(key)
        if ok is None:
            ok = self.empty_memo[key] = self._find(self.empty, i) is not None
        return ok

    # -- one cycle ----------------------------------------------------------

    def cycle(self, now: float) -> CycleResult:
        out = CycleResult()
        out.placed = []
        heads = []
        for c in range(self.C):
            if self.heap[c]:
                heads.append(heapq.heappop(self.heap[c])[3])
        out.heads = len(heads)
        if not heads:
            return out

        entries = []
        for i in heads:
            c = self.cq[i]
            h = self.cohort_of[c]
            u, hu = self.usage[c], self.cohort_usage[h]
            nom, bl, quota = self.nominal[c], self.blimit[c], self.cohort_quota[h]
            rep, borrows, frs = FIT, False, []
            for r in self.res_order:
                v = self.req[i][r]
                if v > self._potential(c, r):
                    rep = NOFIT
                    break
                if v <= self._available(u, hu, nom, bl, quota, r):
                    mode = FIT
                elif v <= nom[r]:
                    mode = PREEMPT
                else:
                    mode = NOFIT
                rep = min(rep, mode)
                if rep == NOFIT:
                    break
                borrows = borrows or u[r] + v > nom[r]
                if mode == PREEMPT:
                    frs.append(r)
            placement = None
            if rep == FIT:
                placement = self._find(self.tree, i)
                if placement is None:
                    # no flavor resource needs preemption: no target
                    rep, frs = PREEMPT, []
            if rep == PREEMPT and not self._fits_empty(i):
                rep = NOFIT
            targets = []
            if rep == NOFIT:
                borrows = False
            elif rep == PREEMPT and frs:
                targets = self._targets(i, c, frs)
            entries.append([i, c, rep, borrows, targets, "", placement])

        order = sorted(entries, key=lambda e: (
            e[3], -self.prio[e[0]], self.created[e[0]]))

        su = [list(u) for u in self.usage]
        shu = [list(u) for u in self.cohort_usage]
        cfree = self.tree.free.copy()     # the nodes as this cycle holds them
        preempted: dict[int, None] = {}
        woken = set()
        for e in order:
            i, c, rep, borrows, targets, _, placement = e
            h = self.cohort_of[c]
            if rep == NOFIT:
                continue
            req = self.req[i]
            if rep == PREEMPT and not targets:
                for r in range(self.R):
                    if borrows:
                        amt = min(req[r], self.nominal[c][r]
                                  + self.blimit[c][r] - su[c][r])
                    else:
                        amt = max(0, min(req[r],
                                         self.nominal[c][r] - su[c][r]))
                    su[c][r] += amt
                    shu[h][r] += amt
                continue
            if any(t in preempted for t in targets):
                e[5] = "skipped"
                continue
            gone = [t for t in list(preempted) + targets
                    if self.cohort_of[self.cq[t]] == h]
            gone = list(dict.fromkeys(gone))
            for t in gone:
                for r in range(self.R):
                    su[self.cq[t]][r] -= self.req[t][r]
                    shu[h][r] -= self.req[t][r]
            fits = all(req[r] <= self._available(
                su[c], shu[h], self.nominal[c], self.blimit[c],
                self.cohort_quota[h], r) for r in range(self.R))
            for t in gone:
                for r in range(self.R):
                    su[self.cq[t]][r] += self.req[t][r]
                    shu[h][r] += self.req[t][r]
            if not fits:
                e[5] = "skipped"
                continue
            if placement is not None:
                need = np.asarray(self.pp[i], dtype=np.int64)
                if (self.broken != "in_cycle_usage_ignored"
                        and any((cfree[hh] < k * need).any()
                                for hh, k in placement.items())):
                    e[5] = "skipped"
                    continue
                for hh, k in placement.items():
                    cfree[hh] -= k * need
            for t in targets:
                preempted[t] = None
            for r in range(self.R):
                su[c][r] += req[r]
                shu[h][r] += req[r]
            if rep == PREEMPT:
                for t in targets:
                    c2 = self.cq[t]
                    self._drop(t)
                    heapq.heappush(self.heap[c2], self._heap_item(t))
                    woken.add(self.cohort_of[c2])
                    out.evicted.append(self.key[t])
                    if c2 != c:
                        out.cross_queue_evictions += 1
                out.preempting.append(self.key[i])
                e[5] = "preempting"
                continue
            self.place[i] = sorted(placement.items())
            self._add(i, now)
            out.admitted.append(self.key[i])
            for hh, k in self.place[i]:
                out.placed.append(
                    f"{self.key[i]}@{self.ps_name}="
                    f"{'/'.join(self.plan.host_values(hh))}x{k}")
            e[5] = "admitted"

        for i, c, rep, borrows, targets, status, _ in entries:
            if status == "admitted":
                continue
            if status == "skipped":
                out.skipped.append(self.key[i])
            else:
                out.inadmissible.append(self.key[i])
            if (status in ("skipped", "preempting")
                    or self.cohort_of[c] in woken):
                heapq.heappush(self.heap[c], self._heap_item(i))
            else:
                self.parked[c].append(i)
        for h in woken:
            self._wake(h)
        return out
