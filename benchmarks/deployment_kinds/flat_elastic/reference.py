"""The plain reference of this kind: ``flat_one_flavor``'s admission
cycle with partial admission written in.  It imports nothing of the
program and takes nothing the program has made.

Nomination at a head's full request is the first kind's.  A head that
is neither Fit nor Preempt with targets there, and whose Job may run on
fewer pods, searches its count (upstream podset_reducer.go, one PodSet):
a binary search over the reduction index i in [0, count - minCount] for
the smallest i at which the head, asking ``count - i`` pods, is Fit or
Preempt with targets.  It is nominated at that count, its request is
that many pods, and that is what it reserves, admits, evicts for and
holds; without one it keeps the full count's decision.  A Job that
leaves quota (finished or evicted) asks for its full count again.

Each cycle's result also lists ``counts``: one ``"<key>@main=<pods>"``
for every admission.

``broken`` switches one stated guarantee off (benchmarks/correct.py):
``full_count_only`` never reduces a count; ``smallest_fit`` takes the
largest index that fits, the fewest pods; ``min_count_ignored``
searches down to one pod.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass, field

from ..flat_one_flavor import reference as one_flavor
from ..flat_one_flavor.reference import FIT, NOFIT, PREEMPT

CONTROLS = ("full_count_only", "smallest_fit", "min_count_ignored")

# the fields of a cycle's result that the comparison holds the program's
# record to (benchmarks/correct.py), each without regard to order
COMPARED = one_flavor.COMPARED + ("counts",)


@dataclass
class CycleResult(one_flavor.CycleResult):
    counts: list = field(default_factory=list)


class Reference(one_flavor.Reference):
    def __init__(self, plan, broken: str | None = None):
        if broken is not None and broken not in CONTROLS:
            raise ValueError(f"unknown control {broken!r}")
        super().__init__(plan)
        self.broken = broken
        self.pods = plan.wl_pods.tolist()
        self.least = plan.wl_min_pods.tolist()
        if broken == "min_count_ignored":
            self.least = [1 if m < n else n
                          for m, n in zip(self.least, self.pods)]
        self.per_pod = [tuple(v // n for v in r)
                        for r, n in zip(self.req, self.pods)]
        # self.req[i] is row i's request at the pods it holds quota on,
        # or is being nominated on (a nomination starts at full count);
        # a row evicted in a cycle keeps it for the rest of the cycle

    # -- nomination ------------------------------------------------------

    def _at(self, i, pods: int) -> None:
        self.req[i] = tuple(v * pods for v in self.per_pod[i])

    def _decide(self, i, c):
        """(mode, borrows, targets) of head i at its request now."""
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
        if rep == NOFIT:
            return NOFIT, False, []
        targets = self._targets(i, c, frs) if rep == PREEMPT else []
        return rep, borrows, targets

    @staticmethod
    def _takes(decision) -> bool:
        rep, _, targets = decision
        return rep == FIT or (rep == PREEMPT and bool(targets))

    def _nominate(self, i, c):
        """Head i's decision, and the pods it is nominated on; leaves
        self.req[i] at their request."""
        n = self.pods[i]
        self._at(i, n)
        full = self._decide(i, c)
        if (self._takes(full) or self.least[i] >= n
                or self.broken == "full_count_only"):
            return full, n
        lo, hi = 0, n - self.least[i]
        best = None
        while lo <= hi:
            idx = (lo + hi) // 2
            self._at(i, n - idx)
            got = self._decide(i, c)
            fits = self._takes(got)
            if fits:
                best = (got, n - idx)
            if fits == (self.broken != "smallest_fit"):
                hi = idx - 1
            else:
                lo = idx + 1
        if best is None:
            self._at(i, n)
            return full, n
        self._at(i, best[1])
        return best

    # -- one cycle ---------------------------------------------------------

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
            (rep, borrows, targets), pods = self._nominate(i, c)
            entries.append([i, c, rep, borrows, targets, "", pods])

        order = sorted(entries, key=lambda e: (
            e[3], -self.prio[e[0]], self.created[e[0]]))

        su = [list(u) for u in self.usage]
        shu = [list(u) for u in self.cohort_usage]
        preempted: dict[int, None] = {}
        woken = set()
        for e in order:
            i, c, rep, borrows, targets, _, pods = e
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
            out.counts.append(f"{self.key[i]}@main={pods}")
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
