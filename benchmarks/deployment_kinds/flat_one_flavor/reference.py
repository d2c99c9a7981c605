"""The plain reference: Kueue's admission cycle, written out directly.

It imports nothing of the program and takes nothing the program has
made: it starts from the ``ClusterPlan`` (``cluster.py`` beside this
file) and is fed only the inputs the program was fed: which workloads
finished at each boundary and what the clock read at each cycle.  It
covers exactly what this deployment kind is: flat cohorts, one flavor, any number of
resources, BestEffortFIFO, no fair sharing, ``borrowWithinCohort:
Never``.  The semantics are upstream Kueue's (pkg/scheduler/scheduler.go
schedule(), flavorassigner.go fitsResourceQuota, preemption.go
getTargets / minimalPreemptions / fillBackWorkloads, queue/
cluster_queue.go requeueIfNotPresent):

  1. pop one head a ClusterQueue (highest priority, then oldest);
  2. nominate each on the untouched state: per resource, in name order,
     Fit / Preempt / NoFit against the queue's quota, borrowing limit
     and the cohort's unused quota; for Preempt, find eviction targets;
  3. order the entries: not borrowing first, then priority, then age;
  4. admit in that order against a scratch copy of the usage: reserve
     for preempt-without-targets, skip on overlapping targets or when it
     no longer fits, evict targets or admit;
  5. requeue: skipped and preempting heads go straight back to the
     heap; the others park until quota moves in their cohort.

``broken`` switches one stated guarantee off and makes the control that
the comparison has to fail (benchmarks/correct.py).
"""

from __future__ import annotations

import heapq
from bisect import bisect_left, insort
from dataclasses import dataclass, field

FIT, PREEMPT, NOFIT = 2, 1, 0

CONTROLS = ("memory_unenforced",)

# the fields of a cycle's result that the comparison holds the program's
# record to (benchmarks/correct.py), each without regard to order
COMPARED = ("admitted", "evicted", "skipped", "preempting")


@dataclass
class CycleResult:
    admitted: list = field(default_factory=list)     # workload keys
    evicted: list = field(default_factory=list)
    preempting: list = field(default_factory=list)
    skipped: list = field(default_factory=list)
    inadmissible: list = field(default_factory=list)
    heads: int = 0
    cross_queue_evictions: int = 0


class Reference:
    def __init__(self, plan, broken: str | None = None):
        if broken is not None and broken not in CONTROLS:
            raise ValueError(f"unknown control {broken!r}")
        self.broken = broken
        names = plan.resources
        # the flavor assigner walks a pod set's resources in name order
        self.res_order = sorted(range(len(names)), key=lambda i: names[i])
        self.R = len(names)
        C = len(plan.queues)
        self.C = C
        self.nominal = [[q.nominal[r] for r in names] for q in plan.queues]
        self.blimit = [[q.borrowing_limit[r] for r in names]
                       for q in plan.queues]
        cohorts: dict[str, int] = {}
        self.cohort_of = []
        for q in plan.queues:
            self.cohort_of.append(cohorts.setdefault(q.cohort, len(cohorts)))
        H = len(cohorts)
        self.members = [[] for _ in range(H)]
        for c, h in enumerate(self.cohort_of):
            self.members[h].append(c)
        self.cohort_quota = [[sum(self.nominal[c][r] for c in self.members[h])
                              for r in range(self.R)] for h in range(H)]
        self.usage = [[0] * self.R for _ in range(C)]
        self.cohort_usage = [[0] * self.R for _ in range(H)]

        self.key = [plan.key(i) for i in range(len(plan.wl_name))]
        self.id_of = {k: i for i, k in enumerate(self.key)}
        self.cq = plan.wl_queue.tolist()
        self.prio = plan.wl_priority.tolist()
        self.created = plan.wl_created.tolist()
        req = plan.wl_request.tolist()
        if broken == "memory_unenforced":
            mi = names.index("memory")
            for row in req:
                row[mi] = 0
        self.req = [tuple(r) for r in req]

        self.reserved_at: dict[int, float] = {}
        # admitted rows a queue in candidate order: lower priority
        # first, then later reservation, then uid
        self.order: list[list] = [[] for _ in range(C)]
        self.heap: list[list] = [[] for _ in range(C)]
        self.parked: list[list] = [[] for _ in range(C)]
        running = plan.wl_running.tolist()
        reserved = plan.wl_reserved.tolist()
        for i, is_running in enumerate(running):
            if is_running:
                self._add(i, reserved[i], sort=False)
            else:
                self.heap[self.cq[i]].append(self._heap_item(i))
        for c in range(C):
            self.order[c].sort()
            heapq.heapify(self.heap[c])

    # -- state ---------------------------------------------------------

    def _heap_item(self, i):
        return (-self.prio[i], self.created[i], self.key[i], i)

    def _order_item(self, i):
        return (self.prio[i], -self.reserved_at[i], self.key[i], i)

    def _add(self, i, now, sort=True):
        c = self.cq[i]
        self.reserved_at[i] = now
        if sort:
            insort(self.order[c], self._order_item(i))
        else:
            self.order[c].append(self._order_item(i))
        self._use(c, self.req[i], +1)

    def _drop(self, i):
        c = self.cq[i]
        item = self._order_item(i)
        lst = self.order[c]
        j = bisect_left(lst, item)
        assert lst[j] == item
        del lst[j]
        del self.reserved_at[i]
        self._use(c, self.req[i], -1)

    def _use(self, c, req, sign):
        u, hu = self.usage[c], self.cohort_usage[self.cohort_of[c]]
        for r in range(self.R):
            u[r] += sign * req[r]
            hu[r] += sign * req[r]

    def _wake(self, h):
        for c in self.members[h]:
            if self.parked[c]:
                for i in self.parked[c]:
                    heapq.heappush(self.heap[c], self._heap_item(i))
                self.parked[c] = []

    def has_heads(self) -> bool:
        return any(self.heap)

    # -- quota arithmetic (flat cohort, nothing guaranteed) ---------------

    @staticmethod
    def _available(usage, cohort_usage, nominal, blimit, quota, r):
        return min(nominal[r] - usage[r] + blimit[r],
                   quota[r] - cohort_usage[r])

    def _potential(self, c, r):
        return min(self.nominal[c][r] + self.blimit[c][r],
                   self.cohort_quota[self.cohort_of[c]][r])

    # -- boundary -------------------------------------------------------------

    def begin_round(self, rnd) -> int:
        """What the round fed the program before its cycles; of this
        kind's traffic, the workloads that finished at the boundary.
        Releases their quota and wakes their cohorts.  Returns how many
        were not running (a finish of a workload the reference does not
        hold)."""
        unknown = 0
        for k in rnd.finished:
            i = self.id_of.get(k)
            if i is None or i not in self.reserved_at:
                unknown += 1
                continue
            self._drop(i)
            self._wake(self.cohort_of[self.cq[i]])
        return unknown

    # -- preemption targets ----------------------------------------------------

    def _borrowing(self, c, frs) -> bool:
        return any(self.usage[c][r] > self.nominal[c][r] for r in frs)

    def _fits(self, i, c, allow_borrowing) -> bool:
        h = self.cohort_of[c]
        u, hu = self.usage[c], self.cohort_usage[h]
        nom, bl, quota = self.nominal[c], self.blimit[c], self.cohort_quota[h]
        for r in range(self.R):
            v = self.req[i][r]
            if not allow_borrowing and u[r] + v > nom[r]:
                return False
            if v > self._available(u, hu, nom, bl, quota, r):
                return False
        return True

    def _minimal(self, i, c, frs, candidates, allow_borrowing):
        removed = []
        fits = False
        for item in candidates:
            t = item[3]
            c2 = self.cq[t]
            if c2 != c and not self._borrowing(c2, frs):
                continue
            self._use(c2, self.req[t], -1)
            removed.append(t)
            if self._fits(i, c, allow_borrowing):
                fits = True
                break
        if not fits:
            for t in removed:
                self._use(self.cq[t], self.req[t], +1)
            return []
        j = len(removed) - 2
        while j >= 0:
            t = removed[j]
            self._use(self.cq[t], self.req[t], +1)
            if self._fits(i, c, allow_borrowing):
                removed[j] = removed[-1]
                removed.pop()
            else:
                self._use(self.cq[t], self.req[t], -1)
            j -= 1
        for t in removed:
            self._use(self.cq[t], self.req[t], +1)
        return removed

    def _targets(self, i, c, frs):
        own_all = self.order[c]
        # LowerPriority: rows of strictly lower priority, a prefix
        n_own = bisect_left(own_all, (self.prio[i],))
        lenders = [c2 for c2 in self.members[self.cohort_of[c]]
                   if c2 != c and self.order[c2]
                   and self._borrowing(c2, frs)]
        if not n_own and not lenders:
            return []

        def own():
            return iter(own_all[:n_own])

        def everyone():
            # other queues' rows first, then the head's own queue
            yield from heapq.merge(*(self.order[c2] for c2 in lenders))
            yield from own_all[:n_own]

        if not lenders:
            return self._minimal(i, c, frs, own(), True)
        under_nominal = all(self.usage[c][r] < self.nominal[c][r]
                            for r in frs)
        if under_nominal:
            first = self._minimal(i, c, frs, everyone(), False)
            if first:
                return first
        return self._minimal(i, c, frs, own(), True)

    # -- one cycle ----------------------------------------------------------------

    def cycle(self, now: float) -> CycleResult:
        out = CycleResult()
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
                else:        # borrowWithinCohort Never: no preempting
                    mode = NOFIT   # while borrowing
                rep = min(rep, mode)
                if rep == NOFIT:
                    break
                borrows = borrows or u[r] + v > nom[r]
                if mode == PREEMPT:
                    frs.append(r)
            targets = []
            if rep == NOFIT:
                borrows = False
            elif rep == PREEMPT:
                targets = self._targets(i, c, frs)
            entries.append([i, c, rep, borrows, targets, ""])

        order = sorted(entries, key=lambda e: (
            e[3], -self.prio[e[0]], self.created[e[0]]))

        su = [list(u) for u in self.usage]
        shu = [list(u) for u in self.cohort_usage]
        preempted: dict[int, None] = {}
        woken = set()
        for e in order:
            i, c, rep, borrows, targets, _ = e
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
            self._add(i, now)
            out.admitted.append(self.key[i])
            e[5] = "admitted"

        for i, c, rep, borrows, targets, status in entries:
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
