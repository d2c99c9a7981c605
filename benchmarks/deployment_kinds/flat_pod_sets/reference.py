"""The plain reference: Kueue's admission cycle for flat cohorts whose
ClusterQueues declare several resource groups and whose Workloads have
several PodSets, written out directly.

It imports nothing of the program and takes nothing the program has
made: it starts from the ``PodSetPlan`` (``cluster.py`` beside this
file) and is fed only what the program was fed: which workloads
finished at each boundary and what the clock read at each cycle.  It is
the fourth kind's reference (``flat_two_group/reference.py``: heads,
nominate, order, admit against a scratch copy, requeue; one flavor walk
a resource group, the third kind's eligibility rule a group, quota held
a (flavor, resource) pair) grown by the loop over a Workload's PodSets
(upstream docs concepts/workload, "Pod sets";
pkg/scheduler/flavorassigner/flavorassigner.go assignFlavors,
findFlavorForPodSetResource; preemption.go):

  - a Workload's PodSets are walked in order.  Each PodSet has its own
    constraint (node selector, tolerations), so its own flavors in each
    group, and its own resume slot a group;
  - PodSet p's walk of a group tests, a resource, ``val = its request +
    acc``, where ``acc`` is what the Workload's earlier PodSets chose
    on that (flavor, resource): Fit, the oracle's question, NoFit and
    borrowing are all read at ``val``; when the PodSet has its flavors
    they are added to ``acc``;
  - a PodSet is as good as its worst group and the Workload as its
    worst PodSet.  A PodSet that is NoFit in a group ends the walk: the
    Workload is NoFit, admitted whole or not at all, and keeps the
    resume state of the PodSets *before* that one (the assignment
    returns with what it had appended), so a gang whose launcher
    stopped mid-list comes back at once for the launcher's next flavor
    and is parked only when nothing is left to try;
  - a Workload that is not Fit everywhere searches for eviction targets
    over the (flavor, resource) pairs short of quota in *any* PodSet
    and group, against its summed usage a pair; an admission charges
    every PodSet, and an eviction frees every PodSet.

``broken`` switches one stated guarantee off and makes the control that
the comparison has to fail (benchmarks/correct.py).
"""

from __future__ import annotations

import heapq
from bisect import bisect_left, insort
from dataclasses import dataclass, field

from ..flat_labelled_flavor import reference as labelled
from ..flat_labelled_flavor.reference import eligible
from ..flat_multi_flavor.reference import (COMPARED, FIT, NOFIT, PREEMPT,
                                           RECLAIM, CycleResult)

# ``placed`` is one "<workload key>@<PodSet>:<resource>=<flavor>" a
# (PodSet, resource) of an admission
CONTROLS = labelled.CONTROLS + (
    "first_group_decides", "one_mask_all_groups",
    # each PodSet walked as if it were alone: ``acc`` = 0
    "podsets_uncharged",
    # a gang decided as one PodSet of its summed request under its first
    # PodSet's constraint
    "podsets_summed")

__all__ = ["COMPARED", "CONTROLS", "Reference"]


@dataclass
class Entry:
    i: int                  # workload row
    c: int                  # its queue
    mode: int = NOFIT       # FIT, PREEMPT (Reclaim included) or NOFIT
    # a PodSet after another, group -> flavor slot
    slots: list = field(default_factory=list)
    borrows: bool = False
    short: list = field(default_factory=list)    # pairs short of quota
    targets: list = field(default_factory=list)
    status: str = ""


class Reference:
    def __init__(self, plan, broken: str | None = None):
        if broken is not None and broken not in CONTROLS:
            raise ValueError(f"unknown control {broken!r}")
        self.broken = broken
        names = plan.resources
        self.names = list(names)
        self.R = R = len(names)
        # the flavor assigner walks a pod set's resources in name order
        self.res_order = sorted(range(R), key=lambda r: names[r])
        ff = plan.config["deployment"]["flavor_fungibility"]
        self.stop_on_preempt = ff["whenCanPreempt"] == "Preempt"
        self.stop_on_borrow = ff["whenCanBorrow"] == "Borrow"

        # groups, and the (group, slot, resource) pairs quota is held in
        self.flavors = [list(g.flavors) for g in plan.groups]
        self.group_res = [sorted(g.resources, key=lambda r: names[r])
                          for g in plan.groups]
        self.group_of = {r: g for g, rs in enumerate(self.group_res)
                         for r in rs}
        self.pair: dict[tuple, int] = {}
        for g, rs in enumerate(self.group_res):
            for s in range(len(self.flavors[g])):
                for r in rs:
                    self.pair[g, s, r] = len(self.pair)
        P = len(self.pair)
        all_keys = {k for g in plan.groups for k in g.label_keys}
        # [group][constraint class][flavor slot]
        self.may_take = [
            [[broken == "eligibility_off" or eligible(
                job, f, all_keys if broken == "one_mask_all_groups"
                else g.label_keys) for f in g.specs]
             for job in plan.job_classes] for g in plan.groups]
        # a workload's PodSets in order: (name, constraint class, request)
        first = plan.wl_first.tolist()
        ps_job = plan.ps_job.tolist()
        ps_req = plan.ps_request.tolist()
        if broken == "memory_unenforced":
            mi = names.index("memory")
            for row in ps_req:
                row[mi] = 0
        self.pods = [
            [(plan.ps_name[j], ps_job[j], tuple(ps_req[j]))
             for j in range(first[i], first[i + 1])]
            for i in range(len(plan.wl_name))]
        if broken == "podsets_summed":
            self.pods = [
                [(ps[0][0], ps[0][1],
                  tuple(sum(col) for col in zip(*(p[2] for p in ps))))]
                + [(p[0], p[1], (0,) * R) for p in ps[1:]]
                for ps in self.pods]

        C = len(plan.queues)
        self.C = C
        self.nominal = [[0] * P for _ in range(C)]
        self.blimit = [[0] * P for _ in range(C)]
        for c, q in enumerate(plan.queues):
            for (g, s, r), p in self.pair.items():
                f = self.flavors[g][s]
                self.nominal[c][p] = q.nominal[f][names[r]]
                self.blimit[c][p] = q.borrowing_limit[f][names[r]]
        cohorts: dict[str, int] = {}
        self.cohort_of = [cohorts.setdefault(q.cohort, len(cohorts))
                          for q in plan.queues]
        H = len(cohorts)
        self.members = [[] for _ in range(H)]
        for c, h in enumerate(self.cohort_of):
            self.members[h].append(c)
        self.cohort_quota = [[sum(self.nominal[c][p] for c in ms)
                              for p in range(P)] for ms in self.members]
        self.usage = [[0] * P for _ in range(C)]
        self.cohort_usage = [[0] * P for _ in range(H)]
        # bumped when a queue's quota moves; this kind's traffic never
        # moves one, so a resume state lives until it is used
        self.generation = [0] * C

        self.key = [plan.key(i) for i in range(len(plan.wl_name))]
        self.id_of = {k: i for i, k in enumerate(self.key)}
        self.cq = plan.wl_queue.tolist()
        self.prio = plan.wl_priority.tolist()
        self.created = plan.wl_created.tolist()

        self.reserved_at: dict[int, float] = {}
        self.holds: dict[int, list] = {}     # row -> [(pair, amount)]
        self.held_on: dict[int, list] = {}   # row -> [{group: slot}]
        # workload -> ({(PodSet, group): the slot its last walk stopped
        # on}, the queue's generation then); a walk that reached its
        # last flavor is absent
        self.resume: dict[int, tuple] = {}
        # admitted rows a queue and pair in candidate order: lower
        # priority first, then later reservation, then uid
        self.order = [[[] for _ in range(P)] for _ in range(C)]
        self.heap: list[list] = [[] for _ in range(C)]
        self.parked: list[list] = [[] for _ in range(C)]
        reserved = plan.wl_reserved.tolist()
        ps_flavor = plan.ps_flavor.tolist()
        for i in range(len(plan.wl_name)):
            if ps_flavor[first[i]][0] >= 0:
                self._add(i, [dict(enumerate(ps_flavor[j]))
                              for j in range(first[i], first[i + 1])],
                          reserved[i], sort=False)
            else:
                self.heap[self.cq[i]].append(self._heap_item(i))
        for c in range(C):
            for lst in self.order[c]:
                lst.sort()
            heapq.heapify(self.heap[c])

    # -- state ---------------------------------------------------------

    def _heap_item(self, i):
        return (-self.prio[i], self.created[i], self.key[i], i)

    def _order_item(self, i):
        return (self.prio[i], -self.reserved_at[i], self.key[i], i)

    def _pairs(self, i, slots) -> list:
        """[(pair, amount)] of workload ``i`` on ``slots``: each
        requested resource of each PodSet on the flavor its group took,
        a pair once, with the sum of the PodSets on it."""
        total: dict = {}
        for (_, _, req), on in zip(self.pods[i], slots):
            for g, s in on.items():
                for r in self.group_res[g]:
                    if req[r] > 0:
                        p = self.pair[g, s, r]
                        total[p] = total.get(p, 0) + req[r]
        return list(total.items())

    def _add(self, i, slots, now, sort=True):
        c = self.cq[i]
        self.reserved_at[i] = now
        self.held_on[i] = slots
        self.holds[i] = self._pairs(i, slots)
        item = self._order_item(i)
        for p, _ in self.holds[i]:
            if sort:
                insort(self.order[c][p], item)
            else:
                self.order[c][p].append(item)
        self._use(i, +1)

    def _drop(self, i):
        c = self.cq[i]
        item = self._order_item(i)
        for p, _ in self.holds[i]:
            lst = self.order[c][p]
            j = bisect_left(lst, item)
            assert lst[j] == item
            del lst[j]
        self._use(i, -1)
        del self.reserved_at[i], self.holds[i], self.held_on[i]

    def _use(self, i, sign):
        """Everything row ``i`` holds, charged or released."""
        c = self.cq[i]
        u, hu = self.usage[c], self.cohort_usage[self.cohort_of[c]]
        for p, v in self.holds[i]:
            u[p] += sign * v
            hu[p] += sign * v

    def _wake(self, h):
        for c in self.members[h]:
            if self.parked[c]:
                for i in self.parked[c]:
                    heapq.heappush(self.heap[c], self._heap_item(i))
                self.parked[c] = []

    def has_heads(self) -> bool:
        return any(self.heap)

    # -- quota arithmetic (flat cohort, nothing guaranteed) ---------------

    def _available(self, u, hu, c, p):
        return min(self.nominal[c][p] - u[p] + self.blimit[c][p],
                   self.cohort_quota[self.cohort_of[c]][p] - hu[p])

    def _potential(self, c, p):
        return min(self.nominal[c][p] + self.blimit[c][p],
                   self.cohort_quota[self.cohort_of[c]][p])

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
            h = self.cohort_of[self.cq[i]]
            self._drop(i)
            self._wake(h)
        return unknown

    # -- preemption targets ----------------------------------------------------

    def _borrowing(self, c, short) -> bool:
        return any(self.usage[c][p] > self.nominal[c][p] for p in short)

    def _fits(self, c, want, allow_borrowing) -> bool:
        u, hu = self.usage[c], self.cohort_usage[self.cohort_of[c]]
        for p, v in want:
            if not allow_borrowing and u[p] + v > self.nominal[c][p]:
                return False
            if v > self._available(u, hu, c, p):
                return False
        return True

    def _minimal(self, c, short, want, candidates, allow_borrowing):
        removed = []
        fits = False
        for item in candidates:
            t = item[3]
            c2 = self.cq[t]
            if c2 != c and not self._borrowing(c2, short):
                continue
            self._use(t, -1)
            removed.append(t)
            if self._fits(c, want, allow_borrowing):
                fits = True
                break
        if not fits:
            for t in removed:
                self._use(t, +1)
            return []
        j = len(removed) - 2
        while j >= 0:
            t = removed[j]
            self._use(t, +1)
            if self._fits(c, want, allow_borrowing):
                removed[j] = removed[-1]
                removed.pop()
            else:
                self._use(t, -1)
            j -= 1
        for t in removed:
            self._use(t, +1)
        return removed

    @staticmethod
    def _once(items):
        """A merge of sorted lists that share rows, each row once."""
        last = None
        for item in items:
            if item != last:
                yield item
            last = item

    def _targets(self, i, c, short, want):
        """Whom head ``i`` of queue ``c`` evicts to hold ``want``
        ([(pair, amount)]), short of quota in the pairs ``short``: a
        candidate is a workload that holds one of those pairs."""
        # LowerPriority: rows of strictly lower priority, a prefix
        own_lists = [lst[:bisect_left(lst, (self.prio[i],))]
                     for lst in (self.order[c][p] for p in short)]
        has_own = any(own_lists)
        lenders = [c2 for c2 in self.members[self.cohort_of[c]]
                   if c2 != c and self._borrowing(c2, short)
                   and any(self.order[c2][p] for p in short)]
        if not has_own and not lenders:
            return []

        def own():
            return self._once(heapq.merge(*own_lists))

        def everyone():
            # other queues' rows first, then the head's own queue
            yield from self._once(heapq.merge(
                *(self.order[c2][p] for c2 in lenders for p in short)))
            yield from own()

        if not lenders:
            return self._minimal(c, short, want, own(), True)
        under_nominal = all(self.usage[c][p] < self.nominal[c][p]
                            for p in short)
        if under_nominal:
            first = self._minimal(c, short, want, everyone(), False)
            if first:
                return first
        return self._minimal(c, short, want, own(), True)

    def _reclaim_possible(self, i, c, p, v) -> bool:
        """The preemption oracle: can ``v`` of pair ``p`` be had from
        other queues' borrowers alone?"""
        if self.broken == "oracle_off":
            return False
        if self.usage[c][p] + v > self.nominal[c][p]:
            return False
        return all(self.cq[t] != c
                   for t in self._targets(i, c, [p], [(p, v)]))

    # -- the flavor walks ---------------------------------------------------------

    def _try_next(self, mode, borrows) -> bool:
        if mode in (PREEMPT, RECLAIM) and self.stop_on_preempt and (
                not borrows or self.stop_on_borrow):
            return False
        if mode == FIT and (not borrows or self.stop_on_borrow):
            return False
        return True

    def _walk_group(self, i, c, job, req, acc, g, start):
        """One group's walk for a PodSet (constraint class ``job``,
        request ``req``) of head ``i``, charged with ``acc`` ({pair:
        what the head's earlier PodSets chose there}): (mode, slot,
        borrows, the pairs short of quota, the slot it stopped on or
        None)."""
        may_take = self.may_take[g][job]
        u, hu = self.usage[c], self.cohort_usage[self.cohort_of[c]]
        S = len(self.flavors[g])
        best, slot, best_borrows, short_of, stopped = NOFIT, -1, False, [], None
        # the control: no stop rule, and the last flavor of the best mode
        unordered = self.broken == "flavor_order_ignored"
        for s in range(start, S):
            if not may_take[s]:
                continue           # the rule: attempted, and passed over
            rep, borrows, short = FIT, False, []
            for r in self.group_res[g]:
                p = self.pair[g, s, r]
                v = req[r] + acc.get(p, 0)
                if v > self._potential(c, p):
                    rep = NOFIT
                    break
                if v <= self._available(u, hu, c, p):
                    mode = FIT
                elif v <= self.nominal[c][p]:
                    mode = (RECLAIM if self._reclaim_possible(i, c, p, v)
                            else PREEMPT)
                else:        # borrowWithinCohort Never: no preempting
                    mode = NOFIT   # while borrowing
                rep = min(rep, mode)
                if rep == NOFIT:
                    break
                borrows = borrows or u[p] + v > self.nominal[c][p]
                if mode != FIT:
                    short.append(p)
            stop = not unordered and not self._try_next(rep, borrows)
            if stop or rep > best or (unordered and rep == best != NOFIT):
                best, slot, best_borrows, short_of = rep, s, borrows, short
            if stop:
                if s < S - 1:
                    stopped = s
                break
        return best, slot, best_borrows, short_of, stopped

    def _walk(self, i, c) -> Entry:
        e = Entry(i, c)
        tried, gen = self.resume.pop(i, ({}, 0))
        if gen != self.generation[c]:
            tried = {}
        stopped_on = {}
        worst = FIT
        acc: dict = {}          # pair -> what the earlier PodSets chose
        for n, (_, job, req) in enumerate(self.pods[i]):
            on: dict = {}       # this PodSet's group -> slot
            stopped_here = {}
            charged = {} if self.broken == "podsets_uncharged" else acc
            for r in self.res_order:
                g = self.group_of[r]
                if g in on or req[r] <= 0:
                    continue       # the group is decided, or not asked
                mode, slot, borrows, short, stopped = self._walk_group(
                    i, c, job, req, charged, g, tried.get((n, g), -1) + 1)
                if mode == NOFIT:
                    # no flavor for a PodSet ends the walk: NoFit; what
                    # the PodSets before it recorded stands
                    e.slots, e.short, e.borrows = [], [], False
                    if stopped_on:
                        self.resume[i] = (stopped_on, self.generation[c])
                    return e
                on[g] = slot
                e.borrows = e.borrows or borrows
                if (self.broken != "first_group_decides"
                        or (n == 0 and len(on) == 1)):
                    # (the control reads the first walk's mode and
                    # shortfall, and no other's)
                    worst = min(worst, mode)
                    e.short.extend(p for p in short if p not in e.short)
                if stopped is not None:
                    stopped_here[n, g] = stopped
            stopped_on.update(stopped_here)
            e.slots.append(on)
            for g, s in on.items():
                for r in self.group_res[g]:
                    if req[r] > 0:
                        p = self.pair[g, s, r]
                        acc[p] = acc.get(p, 0) + req[r]
        if stopped_on:
            self.resume[i] = (stopped_on, self.generation[c])
        e.mode = FIT if worst == FIT else PREEMPT
        if e.mode == PREEMPT:
            e.targets = self._targets(i, c, e.short,
                                      self._pairs(i, e.slots))
        return e

    # -- one cycle ----------------------------------------------------------------

    def _lift(self, su, shu_h, gone, sign):
        """Takes the evicted rows ``gone`` ({row: what it held}, one
        cohort's) out of the scratch usage, or puts them back."""
        for t, held in gone.items():
            for p, v in held:
                su[self.cq[t]][p] += sign * v
                shu_h[p] += sign * v

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

        su = [list(u) for u in self.usage]
        shu = [list(u) for u in self.cohort_usage]
        preempted: dict[int, list] = {}     # evicted row -> what it held
        woken = set()
        for e in order:
            i, c = e.i, e.c
            h = self.cohort_of[c]
            if e.mode == NOFIT:
                continue
            want = self._pairs(i, e.slots)
            if e.mode == PREEMPT and not e.targets:
                for p, v in want:
                    nom, bl = self.nominal[c][p], self.blimit[c][p]
                    if e.borrows:
                        amt = min(v, nom + bl - su[c][p])
                    else:
                        amt = max(0, min(v, nom - su[c][p]))
                    su[c][p] += amt
                    shu[h][p] += amt
                continue
            if any(t in preempted for t in e.targets):
                e.status = "skipped"
                continue
            gone = {t: held for t, held in preempted.items()
                    if self.cohort_of[self.cq[t]] == h}
            gone.update((t, self.holds[t]) for t in e.targets)
            self._lift(su, shu[h], gone, -1)
            fits = all(v <= self._available(su[c], shu[h], c, p)
                       for p, v in want)
            self._lift(su, shu[h], gone, +1)
            if not fits:
                e.status = "skipped"
                continue
            for p, v in want:
                su[c][p] += v
                shu[h][p] += v
            if e.mode == PREEMPT:
                for t in e.targets:
                    c2 = self.cq[t]
                    preempted[t] = self.holds[t]
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
            self._add(i, [dict(on) for on in e.slots], now)
            out.admitted.append(self.key[i])
            out.placed.extend(
                f"{self.key[i]}@{name}:{self.names[r]}="
                f"{self.flavors[g][s]}"
                for (name, _, req), on in zip(self.pods[i], e.slots)
                for g, s in on.items() for r in self.group_res[g]
                if req[r] > 0)
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
