"""The plain reference: Kueue's admission cycle with several flavors in
one resource group, written out directly.

It imports nothing of the program and takes nothing the program has
made: it starts from the ``FlavorPlan`` (``cluster.py`` beside this
file) and is fed only the inputs the program was fed: which workloads
finished at each boundary and what the clock read at each cycle.  It
covers exactly what this deployment kind is: flat cohorts, S plain
flavors in one resource group, any number of resources, one PodSet,
BestEffortFIFO, no fair sharing, ``borrowWithinCohort: Never``.  The
semantics are upstream Kueue's (pkg/scheduler/scheduler.go schedule(),
flavorassigner.go findFlavorForPodSetResource / fitsResourceQuota /
shouldTryNextFlavor, preemption_oracle.go IsReclaimPossible,
preemption.go getTargets / minimalPreemptions / fillBackWorkloads,
queue/cluster_queue.go requeueIfNotPresent):

  1. pop one head a ClusterQueue (highest priority, then oldest);
  2. nominate each on the untouched state by the flavor walk: from the
     slot after the one its last attempt stopped on (the resume state,
     void once the queue's quota has moved), every flavor in the
     queue's order; all resources of the group take the flavor, in name
     order, each Fit / Reclaim / Preempt / NoFit against the queue's
     quota, borrowing limit and the cohort's unused quota of that
     flavor.  Reclaim or Preempt is the oracle's answer: a target
     search of its own for that flavor and resource alone, Reclaim when
     it evicts nobody of the head's own queue.  A flavor is as good as
     its worst resource.  The walk stops on a flavor by the queue's
     ``flavorFungibility`` (a fit; a preempt-capable one under
     ``whenCanPreempt: Preempt``; neither while borrowing under
     ``whenCanBorrow: TryNextFlavor``), else keeps the first flavor of
     the best mode, Fit > Reclaim > Preempt > NoFit.  For a preempting
     head, find eviction targets in the chosen flavor only;
  3. order the entries: not borrowing first, then priority, then age;
  4. admit in that order against a scratch copy of the usage: reserve
     for preempt-without-targets, skip on overlapping targets or when it
     no longer fits, evict targets or admit;
  5. requeue: skipped and preempting heads, and one whose walk stopped
     short of the last flavor, go straight back to the heap; the others
     park until quota moves in their cohort.

``broken`` switches one stated guarantee off and makes the control that
the comparison has to fail (benchmarks/correct.py).
"""

from __future__ import annotations

import heapq
from bisect import bisect_left, insort
from dataclasses import dataclass, field

NOFIT, PREEMPT, RECLAIM, FIT = 0, 1, 2, 3

CONTROLS = ("memory_unenforced", "flavor_order_ignored", "oracle_off")

# the fields of a cycle's result that the comparison holds the program's
# record to (benchmarks/correct.py), each without regard to order;
# ``placed`` is one "<workload key>@<flavor>" an admission
COMPARED = ("admitted", "evicted", "skipped", "preempting", "placed")


@dataclass
class CycleResult:
    admitted: list = field(default_factory=list)     # workload keys
    placed: list = field(default_factory=list)       # "<key>@<flavor>"
    evicted: list = field(default_factory=list)
    preempting: list = field(default_factory=list)
    skipped: list = field(default_factory=list)
    inadmissible: list = field(default_factory=list)
    heads: int = 0
    cross_queue_evictions: int = 0


@dataclass
class Entry:
    i: int                  # workload row
    c: int                  # its queue
    mode: int = NOFIT       # FIT, PREEMPT (Reclaim included) or NOFIT
    f: int = -1             # the flavor slot chosen
    borrows: bool = False
    targets: list = field(default_factory=list)
    status: str = ""


class Reference:
    def __init__(self, plan, broken: str | None = None):
        if broken is not None and broken not in CONTROLS:
            raise ValueError(f"unknown control {broken!r}")
        self.broken = broken
        names = plan.resources
        # the flavor assigner walks a pod set's resources in name order
        self.res_order = sorted(range(len(names)), key=lambda i: names[i])
        self.R = R = len(names)
        self.flavors = list(plan.flavors)
        self.S = S = len(self.flavors)
        ff = plan.config["deployment"]["flavor_fungibility"]
        self.stop_on_preempt = ff["whenCanPreempt"] == "Preempt"
        self.stop_on_borrow = ff["whenCanBorrow"] == "Borrow"
        C = len(plan.queues)
        self.C = C
        self.nominal = [[[q.nominal[f][r] for r in names]
                         for f in self.flavors] for q in plan.queues]
        self.blimit = [[[q.borrowing_limit[f][r] for r in names]
                        for f in self.flavors] for q in plan.queues]
        cohorts: dict[str, int] = {}
        self.cohort_of = []
        for q in plan.queues:
            self.cohort_of.append(cohorts.setdefault(q.cohort, len(cohorts)))
        H = len(cohorts)
        self.members = [[] for _ in range(H)]
        for c, h in enumerate(self.cohort_of):
            self.members[h].append(c)
        self.cohort_quota = [
            [[sum(self.nominal[c][f][r] for c in self.members[h])
              for r in range(R)] for f in range(S)] for h in range(H)]
        self.usage = [[[0] * R for _ in range(S)] for _ in range(C)]
        self.cohort_usage = [[[0] * R for _ in range(S)] for _ in range(H)]
        # bumped when a queue's quota moves; this kind's traffic never
        # moves one, so a resume state lives until it is used
        self.generation = [0] * C

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
        self.flavor_of: dict[int, int] = {}
        # workload -> (the slot its last walk stopped on, the queue's
        # generation then); absent when the walk reached the last flavor
        self.resume: dict[int, tuple] = {}
        # admitted rows a queue and flavor in candidate order: lower
        # priority first, then later reservation, then uid
        self.order = [[[] for _ in range(S)] for _ in range(C)]
        self.heap: list[list] = [[] for _ in range(C)]
        self.parked: list[list] = [[] for _ in range(C)]
        flavor = plan.wl_flavor.tolist()
        reserved = plan.wl_reserved.tolist()
        for i, f in enumerate(flavor):
            if f >= 0:
                self._add(i, f, reserved[i], sort=False)
            else:
                self.heap[self.cq[i]].append(self._heap_item(i))
        for c in range(C):
            for f in range(S):
                self.order[c][f].sort()
            heapq.heapify(self.heap[c])

    # -- state ---------------------------------------------------------

    def _heap_item(self, i):
        return (-self.prio[i], self.created[i], self.key[i], i)

    def _order_item(self, i):
        return (self.prio[i], -self.reserved_at[i], self.key[i], i)

    def _add(self, i, f, now, sort=True):
        c = self.cq[i]
        self.reserved_at[i] = now
        self.flavor_of[i] = f
        if sort:
            insort(self.order[c][f], self._order_item(i))
        else:
            self.order[c][f].append(self._order_item(i))
        self._use(c, f, self.req[i], +1)

    def _drop(self, i):
        c, f = self.cq[i], self.flavor_of.pop(i)
        item = self._order_item(i)
        lst = self.order[c][f]
        j = bisect_left(lst, item)
        assert lst[j] == item
        del lst[j]
        del self.reserved_at[i]
        self._use(c, f, self.req[i], -1)

    def _use(self, c, f, req, sign):
        u, hu = self.usage[c][f], self.cohort_usage[self.cohort_of[c]][f]
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

    def _potential(self, c, f, r):
        return min(self.nominal[c][f][r] + self.blimit[c][f][r],
                   self.cohort_quota[self.cohort_of[c]][f][r])

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

    def _borrowing(self, c, f, frs) -> bool:
        return any(self.usage[c][f][r] > self.nominal[c][f][r] for r in frs)

    def _fits(self, c, f, want, allow_borrowing) -> bool:
        h = self.cohort_of[c]
        u, hu = self.usage[c][f], self.cohort_usage[h][f]
        nom, bl = self.nominal[c][f], self.blimit[c][f]
        quota = self.cohort_quota[h][f]
        for r, v in want:
            if not allow_borrowing and u[r] + v > nom[r]:
                return False
            if v > self._available(u, hu, nom, bl, quota, r):
                return False
        return True

    def _minimal(self, c, f, frs, want, candidates, allow_borrowing):
        removed = []
        fits = False
        for item in candidates:
            t = item[3]
            c2 = self.cq[t]
            if c2 != c and not self._borrowing(c2, f, frs):
                continue
            self._use(c2, f, self.req[t], -1)
            removed.append(t)
            if self._fits(c, f, want, allow_borrowing):
                fits = True
                break
        if not fits:
            for t in removed:
                self._use(self.cq[t], f, self.req[t], +1)
            return []
        j = len(removed) - 2
        while j >= 0:
            t = removed[j]
            self._use(self.cq[t], f, self.req[t], +1)
            if self._fits(c, f, want, allow_borrowing):
                removed[j] = removed[-1]
                removed.pop()
            else:
                self._use(self.cq[t], f, self.req[t], -1)
            j -= 1
        for t in removed:
            self._use(self.cq[t], f, self.req[t], +1)
        return removed

    def _targets(self, i, c, f, frs, want):
        """Whom head ``i`` of queue ``c`` evicts to hold ``want``
        ([(resource, amount)]) of flavor ``f``, short in resources
        ``frs``: only workloads on that flavor are candidates."""
        own_all = self.order[c][f]
        # LowerPriority: rows of strictly lower priority, a prefix
        n_own = bisect_left(own_all, (self.prio[i],))
        lenders = [c2 for c2 in self.members[self.cohort_of[c]]
                   if c2 != c and self.order[c2][f]
                   and self._borrowing(c2, f, frs)]
        if not n_own and not lenders:
            return []

        def own():
            return iter(own_all[:n_own])

        def everyone():
            # other queues' rows first, then the head's own queue
            yield from heapq.merge(*(self.order[c2][f] for c2 in lenders))
            yield from own_all[:n_own]

        if not lenders:
            return self._minimal(c, f, frs, want, own(), True)
        under_nominal = all(self.usage[c][f][r] < self.nominal[c][f][r]
                            for r in frs)
        if under_nominal:
            first = self._minimal(c, f, frs, want, everyone(), False)
            if first:
                return first
        return self._minimal(c, f, frs, want, own(), True)

    def _reclaim_possible(self, i, c, f, r, v) -> bool:
        """The preemption oracle: can ``v`` of (f, r) be had from other
        queues' borrowers alone?"""
        if self.broken == "oracle_off":
            return False
        if self.usage[c][f][r] + v > self.nominal[c][f][r]:
            return False
        return all(self.cq[t] != c
                   for t in self._targets(i, c, f, [r], [(r, v)]))

    # -- the flavor walk ---------------------------------------------------------

    def _try_next(self, mode, borrows) -> bool:
        if mode in (PREEMPT, RECLAIM) and self.stop_on_preempt and (
                not borrows or self.stop_on_borrow):
            return False
        if mode == FIT and (not borrows or self.stop_on_borrow):
            return False
        return True

    def _walk(self, i, c) -> Entry:
        e = Entry(i, c)
        h = self.cohort_of[c]
        tried, gen = self.resume.pop(i, (-1, 0))
        start = tried + 1 if gen == self.generation[c] else 0
        best, short_of = NOFIT, []
        last = self.S - 1
        # the control: no stop rule, and the last flavor of the best mode
        unordered = self.broken == "flavor_order_ignored"
        for f in range(start, self.S):
            u, hu = self.usage[c][f], self.cohort_usage[h][f]
            nom, bl = self.nominal[c][f], self.blimit[c][f]
            quota = self.cohort_quota[h][f]
            rep, borrows, frs = FIT, False, []
            for r in self.res_order:
                v = self.req[i][r]
                if v > self._potential(c, f, r):
                    rep = NOFIT
                    break
                if v <= self._available(u, hu, nom, bl, quota, r):
                    mode = FIT
                elif v <= nom[r]:
                    mode = (RECLAIM if self._reclaim_possible(i, c, f, r, v)
                            else PREEMPT)
                else:        # borrowWithinCohort Never: no preempting
                    mode = NOFIT   # while borrowing
                rep = min(rep, mode)
                if rep == NOFIT:
                    break
                borrows = borrows or u[r] + v > nom[r]
                if mode != FIT:
                    frs.append(r)
            stop = not unordered and not self._try_next(rep, borrows)
            if stop or rep > best or (unordered and rep == best != NOFIT):
                best, short_of = rep, frs
                e.f, e.borrows = f, borrows
            if stop:
                last = f
                break
        if last < self.S - 1:
            self.resume[i] = (last, self.generation[c])
        if best == NOFIT:
            return e
        e.mode = FIT if best == FIT else PREEMPT
        if e.mode == PREEMPT:
            e.targets = self._targets(
                i, c, e.f, short_of,
                [(r, self.req[i][r]) for r in range(self.R)])
        return e

    # -- one cycle ----------------------------------------------------------------

    def _lift(self, su, shu_h, gone, sign):
        """Takes the evicted rows ``gone`` ({row: flavor}, one cohort's)
        out of the scratch usage, or puts them back."""
        for t, tf in gone.items():
            for r in range(self.R):
                su[self.cq[t]][tf][r] += sign * self.req[t][r]
                shu_h[tf][r] += sign * self.req[t][r]

    def cycle(self, now: float) -> CycleResult:
        out = CycleResult()
        heads = []
        for c in range(self.C):
            if self.heap[c]:
                heads.append(heapq.heappop(self.heap[c])[3])
        out.heads = len(heads)
        if not heads:
            return out

        entries = [self._walk(i, self.cq[i]) for i in heads]
        order = sorted(entries, key=lambda e: (
            e.borrows, -self.prio[e.i], self.created[e.i]))

        su = [[list(u) for u in q] for q in self.usage]
        shu = [[list(u) for u in q] for q in self.cohort_usage]
        preempted: dict[int, int] = {}      # evicted row -> its flavor
        woken = set()
        for e in order:
            i, c, f = e.i, e.c, e.f
            h = self.cohort_of[c]
            if e.mode == NOFIT:
                continue
            req = self.req[i]
            nom, bl = self.nominal[c][f], self.blimit[c][f]
            if e.mode == PREEMPT and not e.targets:
                for r in range(self.R):
                    if e.borrows:
                        amt = min(req[r], nom[r] + bl[r] - su[c][f][r])
                    else:
                        amt = max(0, min(req[r], nom[r] - su[c][f][r]))
                    su[c][f][r] += amt
                    shu[h][f][r] += amt
                continue
            if any(t in preempted for t in e.targets):
                e.status = "skipped"
                continue
            gone = {t: tf for t, tf in preempted.items()
                    if self.cohort_of[self.cq[t]] == h}
            gone.update((t, self.flavor_of[t]) for t in e.targets)
            self._lift(su, shu[h], gone, -1)
            fits = all(req[r] <= self._available(
                su[c][f], shu[h][f], nom, bl, self.cohort_quota[h][f], r)
                for r in range(self.R))
            self._lift(su, shu[h], gone, +1)
            if not fits:
                e.status = "skipped"
                continue
            for r in range(self.R):
                su[c][f][r] += req[r]
                shu[h][f][r] += req[r]
            if e.mode == PREEMPT:
                for t in e.targets:
                    c2 = self.cq[t]
                    preempted[t] = self.flavor_of[t]
                    self._drop(t)
                    heapq.heappush(self.heap[c2], self._heap_item(t))
                    woken.add(self.cohort_of[c2])
                    out.evicted.append(self.key[t])
                    if c2 != c:
                        out.cross_queue_evictions += 1
                self.resume.pop(i, None)     # retry every flavor next time
                out.preempting.append(self.key[i])
                e.status = "preempting"
                continue
            self.resume.pop(i, None)
            self._add(i, f, now)
            out.admitted.append(self.key[i])
            out.placed.append(f"{self.key[i]}@{self.flavors[f]}")
            e.status = "admitted"

        for e in entries:
            if e.status == "admitted":
                continue
            if e.status == "skipped":
                out.skipped.append(self.key[e.i])
            else:
                out.inadmissible.append(self.key[e.i])
            if (e.status in ("skipped", "preempting")
                    or e.i in self.resume
                    or self.cohort_of[e.c] in woken):
                heapq.heappush(self.heap[e.c], self._heap_item(e.i))
            else:
                self.parked[e.c].append(e.i)
        for h in woken:
            self._wake(h)
        return out
