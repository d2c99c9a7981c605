"""The plain reference: Kueue's admission cycle under fair sharing over a
tree of cohorts, written out directly.

It imports nothing of the program and takes nothing the program has
made: it starts from the ``FairPlan`` (``cluster.py`` beside this file)
and is fed only the inputs the program was fed (which workloads finished
at each boundary, what the clock read at each cycle).  It covers what
this deployment kind is: a tree of cohorts of any depth with no quota
of their own, ClusterQueues with a nominal quota and no borrowing or
lending limit, one flavor, any number of resources, BestEffortFIFO,
``borrowWithinCohort: Never``, fair sharing on.  The semantics are
upstream Kueue's (pkg/scheduler/scheduler.go schedule(),
fair_sharing_iterator.go, pkg/cache/resource_node.go available,
pkg/cache/fair_sharing.go dominantResourceShare, flavorassigner.go
fitsResourceQuota, preemption/preemption.go fairPreemptions and
fillBackWorkloads, preemption/fairsharing ordering.go and
least_common_ancestor.go):

  1. pop one head a ClusterQueue (highest priority, then oldest);
  2. nominate each on the untouched state, per resource in name order:
     NoFit above what the tree could ever lend, Fit within what it lends
     now, else Preempt (fair sharing with ``reclaimWithinCohort`` other
     than Never may preempt while borrowing); a Preempt head's targets
     come from the fair-sharing search (below);
  3. the tournament: a round takes the first remaining head; a head of a
     queue with no parent is taken at once, else at every cohort of its
     tree the survivor minimizes the DRS of its child-of-this-cohort
     ancestor with the head's usage added, then priority descending,
     then timestamp ascending, then the child's place in the cohort's
     list (cohorts before queues); the tree's survivor is processed:
  4. NoFit dropped; Preempt with no target reserves; a target already
     taken skips; a head that no longer fits, with every workload
     preempted this cycle taken out, skips; else it charges its usage
     and is admitted, or evicts its targets;
  5. requeue: skipped and preempting heads go straight back to the heap;
     the others park until quota moves in their tree.

DRS of a node with a parent: over the resources it borrows (usage above
its subtree quota), the largest ``borrowed * 1000 // lendable``, where
lendable is what the root's quota could lend the node's parent; then
``* 1000 // weight`` (milli); 0 when it borrows nothing or is a root,
the maximum when its weight is 0.

The fair-sharing search (preemption.go:372-460): candidates are the
head's own queue's workloads of lower priority (``withinClusterQueue:
LowerPriority``) and every workload of the other queues of its tree
that borrow in a resource the head needs preempted, a queue's in
candidate order (priority ascending, newest reservation first, key).
With the head's usage added, the target queue is found by descending
from the root into the child of highest DRS (a queue pruned when it
borrows nothing and is not the head's own, or has no candidate left; a
cohort when it borrows nothing and is not the head's ancestor; ties to
the later child; a cohort beats a queue on equal DRS).  Its own queue:
the next candidate goes.  Another: the shares at the two almost-lowest
common ancestors; a candidate goes if the head's share is at most the
target's after the removal (``LessThanOrEqualToFinalShare``), and the
target is found again; one that may not go is kept for the second
strategy.  The second strategy (``LessThanInitialShare``) takes, from a
queue found the same way over the kept candidates, one candidate where
the head's share is below the target's, then drops the queue.  The head
fits when its usage fits where it stands without its own addition.
Found: the targets are filled back (the last kept, earlier ones put back
where the head still fits).  Each eviction carries its reason:
``InClusterQueue`` for the head's own queue, ``InCohortFairSharing`` for
another.

``broken`` switches one stated guarantee off and makes the control that
the comparison has to fail (benchmarks/correct.py).  ``CONTROLS`` are
the ones that fail at the cell's size, where ``control.py`` replays
them; ``TOY_CONTROLS`` fail only where a cluster lets their guarantee
decide (the CPU tests): at the cell's size no head is fitted by the
second strategy alone and no pick turns on a team's weight.
"""

from __future__ import annotations

import heapq
import sys
from bisect import bisect_left, insort
from dataclasses import dataclass, field

FIT, PREEMPT, NOFIT = 2, 1, 0
MAX_DRS = sys.maxsize
IN_CQ = "InClusterQueue"
IN_COHORT_FS = "InCohortFairSharing"

# classic_order: heads in priority/timestamp order, no tournament;
# tree_flattened: the tournament reads each head's own queue's DRS at
# every cohort, not its child-of-this-cohort ancestor's;
# final_share_ignored: LessThanOrEqualToFinalShare takes any candidate
# of a borrowing queue, whatever the shares after the removal
CONTROLS = ("classic_order", "tree_flattened", "final_share_ignored")
TOY_CONTROLS = ("first_strategy_only", "weights_ignored")

# the fields of a cycle's result that the comparison holds the program's
# record to (benchmarks/correct.py), each without regard to order;
# ``evicted_for``: one "<key>=<reason>" an eviction
COMPARED = ("admitted", "evicted", "skipped", "preempting", "evicted_for")


@dataclass
class CycleResult:
    admitted: list = field(default_factory=list)     # workload keys
    evicted: list = field(default_factory=list)
    evicted_for: list = field(default_factory=list)
    preempting: list = field(default_factory=list)
    skipped: list = field(default_factory=list)
    inadmissible: list = field(default_factory=list)
    heads: int = 0
    cross_queue_evictions: int = 0


class Reference:
    def __init__(self, plan, broken: str | None = None):
        if broken is not None and broken not in CONTROLS + TOY_CONTROLS:
            raise ValueError(f"unknown control {broken!r}")
        self.broken = broken
        names = plan.resources
        R = self.R = len(names)
        # the flavor assigner walks a pod set's resources in name order
        self.res_order = sorted(range(R), key=lambda i: names[i])
        C = self.C = len(plan.queues)
        self.strategies = list(plan.strategies)
        if broken == "first_strategy_only":
            self.strategies = self.strategies[:1]
        pre = plan.config["deployment"]["preemption"]
        self.within_lower = pre["withinClusterQueue"] == "LowerPriority"
        self.reclaim = pre["reclaimWithinCohort"] != "Never"

        # nodes: the queues, then the teams, then the departments; a
        # node's children in the order the tree lists them
        idx = {q.name: i for i, q in enumerate(plan.queues)}
        for name in plan.teams + plan.departments:
            idx[name] = len(idx)
        self.N = N = len(idx)
        self.parent = [-1] * N
        self.child_cohorts = [[] for _ in range(N)]
        self.child_cqs = [[] for _ in range(N)]
        for name in plan.teams:
            p = idx[plan.team_parent[name]]
            self.parent[idx[name]] = p
            self.child_cohorts[p].append(idx[name])
        for c, q in enumerate(plan.queues):
            p = idx[q.cohort] if q.cohort else -1
            self.parent[c] = p
            if p >= 0:
                self.child_cqs[p].append(c)
        self.root = []
        self.depth_path = []
        for n in range(N):
            path = [n]
            while self.parent[path[-1]] >= 0:
                path.append(self.parent[path[-1]])
            self.depth_path.append(path)
            self.root.append(path[-1])
        self.weight = [0] * N
        for name, i in idx.items():
            w = plan.weight.get(name, 1.0)
            if broken == "weights_ignored":
                w = 1.0
            self.weight[i] = int(w * 1000)

        # quota: a queue's nominal; a cohort's is its children's sum
        self.nominal = [[q.nominal[r] for r in names] for q in plan.queues]
        self.sq = [[0] * R for _ in range(N)]
        for c in range(C):
            for n in self.depth_path[c]:
                for r in range(R):
                    self.sq[n][r] += self.nominal[c][r]
        # what the root could lend its subtree (potential available of
        # any node of the tree: nothing is guaranteed, nothing limited)
        self.lendable = [self.sq[self.root[n]] for n in range(N)]
        self.usage = [[0] * R for _ in range(N)]
        self.members = {}
        for c in range(C):
            self.members.setdefault(self.root[c], []).append(c)

        self.key = [plan.key(i) for i in range(len(plan.wl_name))]
        self.id_of = {k: i for i, k in enumerate(self.key)}
        self.cq = plan.wl_queue.tolist()
        self.prio = plan.wl_priority.tolist()
        self.created = plan.wl_created.tolist()
        self.req = [tuple(r) for r in plan.wl_request.tolist()]

        self.reserved_at: dict[int, float] = {}
        # admitted rows a queue in candidate order: lower priority
        # first, then later reservation, then key
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
        self._use(self.usage, c, self.req[i], +1)

    def _drop(self, i):
        c = self.cq[i]
        item = self._order_item(i)
        lst = self.order[c]
        j = bisect_left(lst, item)
        assert lst[j] == item
        del lst[j]
        del self.reserved_at[i]
        self._use(self.usage, c, self.req[i], -1)

    def _use(self, usage, c, req, sign):
        """Charge a queue and every cohort above it (nothing guaranteed,
        so a cohort's usage is its children's sum)."""
        for n in self.depth_path[c]:
            u = usage[n]
            for r in range(self.R):
                u[r] += sign * req[r]

    def _wake(self, root):
        for c in self.members.get(root, ()):
            if self.parked[c]:
                for i in self.parked[c]:
                    heapq.heappush(self.heap[c], self._heap_item(i))
                self.parked[c] = []

    def has_heads(self) -> bool:
        return any(self.heap)

    # -- quota arithmetic (resource_node.go, fair_sharing.go) ----------

    def _available(self, usage, n, r):
        """available(): the root's unused subtree quota, each level's
        guaranteed headroom (none here) added on the way down."""
        root = self.root[n]
        return self.sq[root][r] - usage[root][r]

    def _potential(self, n, r):
        return self.sq[self.root[n]][r]

    def _drs(self, usage, n):
        if self.parent[n] < 0:
            return 0
        if self.weight[n] == 0:
            return MAX_DRS
        u, sq, lend = usage[n], self.sq[n], self.lendable[n]
        drs = -1
        borrows = False
        for r in range(self.R):
            b = u[r] - sq[r]
            if b > 0:
                borrows = True
                if lend[r] > 0:
                    drs = max(drs, b * 1000 // lend[r])
        if not borrows:
            return 0
        return drs * 1000 // self.weight[n]

    def _drs_with(self, usage, n, c, req):
        """DRS of node ``n`` (on queue ``c``'s path) with ``req`` added
        to ``c``."""
        self._use(usage, c, req, +1)
        d = self._drs(usage, n)
        self._use(usage, c, req, -1)
        return d

    # -- boundary ---------------------------------------------------------

    def begin_round(self, rnd) -> int:
        """What the round fed the program before its cycles: the
        workloads that finished at the boundary.  Releases their quota
        and wakes their trees.  Returns how many were not running."""
        unknown = 0
        for k in rnd.finished:
            i = self.id_of.get(k)
            if i is None or i not in self.reserved_at:
                unknown += 1
                continue
            self._drop(i)
            self._wake(self.root[self.cq[i]])
        return unknown

    # -- the fair-sharing search ------------------------------------------

    def _ancestors(self, c):
        return set(self.depth_path[c][1:])

    def _almost_lca(self, c, lca):
        """The child of ``lca`` on ``c``'s path (``c`` itself where its
        parent is the lca, or where there is none)."""
        prev = c
        for n in self.depth_path[c][1:]:
            if n == lca:
                return prev
            prev = n
        return prev

    def _lca(self, target_c, anc):
        for n in self.depth_path[target_c][1:]:
            if n in anc:
                return n
        return None

    def _next_target(self, usage, cohort, me, anc, groups, pruned):
        best_q, best_q_drs = None, -1
        for c in self.child_cqs[cohort]:
            if c in pruned:
                continue
            d = self._drs(usage, c)
            if (d == 0 and c != me) or not groups.get(c):
                pruned.add(c)
            elif d >= best_q_drs:
                best_q_drs, best_q = d, c
        best_h, best_h_drs = None, -1
        for h in self.child_cohorts[cohort]:
            if h in pruned:
                continue
            d = self._drs(usage, h)
            if d == 0 and h not in anc:
                pruned.add(h)
            elif d >= best_h_drs:
                best_h_drs, best_h = d, h
        if best_h is None and best_q is None:
            pruned.add(cohort)
            return None
        if best_h is not None and best_h_drs >= best_q_drs:
            return self._next_target(usage, best_h, me, anc, groups, pruned)
        return best_q

    def _iterate(self, usage, me, groups, pruned):
        """ordering.go: the queues to take from, found again each time."""
        if self.parent[me] < 0:
            while groups.get(me):
                yield me
            return
        root = self.root[me]
        anc = self._ancestors(me)
        while root not in pruned:
            c = self._next_target(usage, root, me, anc, groups, pruned)
            if c is not None:
                yield c

    def _fits_fs(self, usage, me, req):
        """workloadFits with the head's own addition taken out."""
        self._use(usage, me, req, -1)
        ok = all(req[r] <= self._available(usage, me, r)
                 for r in range(self.R))
        self._use(usage, me, req, +1)
        return ok

    def _shares(self, usage, me, anc, tc):
        lca = self._lca(tc, anc)
        return (self._drs(usage, self._almost_lca(me, lca)),
                self._drs(usage, self._almost_lca(tc, lca)), lca)

    def _fair_targets(self, i, c, frs):
        usage = self.usage
        req = self.req[i]
        groups: dict[int, list] = {}
        if self.within_lower:
            n_own = bisect_left(self.order[c], (self.prio[i],))
            if n_own:
                groups[c] = [it[3] for it in self.order[c][:n_own]]
        if self.reclaim and self.parent[c] >= 0:
            for c2 in self.members[self.root[c]]:
                if c2 == c or not self.order[c2]:
                    continue
                if any(usage[c2][r] > self.sq[c2][r] for r in frs):
                    groups[c2] = [it[3] for it in self.order[c2]]
        if not groups:
            return []
        anc = self._ancestors(c)
        self._use(usage, c, req, +1)
        targets: list[tuple[int, str]] = []
        retry: dict[int, list] = {}
        fits = False
        # LessThanOrEqualToFinalShare
        pruned: set = set()
        for tc in self._iterate(usage, c, groups, pruned):
            if tc == c:
                t = groups[tc].pop(0)
                self._use(usage, tc, self.req[t], -1)
                targets.append((t, IN_CQ))
                if self._fits_fs(usage, c, req):
                    fits = True
                    break
                continue
            pre_new, tgt_old, lca = self._shares(usage, c, anc, tc)
            tnode = self._almost_lca(tc, lca)
            found = False
            while groups[tc]:
                t = groups[tc].pop(0)
                self._use(usage, tc, self.req[t], -1)
                tgt_new = self._drs(usage, tnode)
                if (pre_new <= tgt_new
                        or self.broken == "final_share_ignored"):
                    targets.append((t, IN_COHORT_FS))
                    if self._fits_fs(usage, c, req):
                        fits = found = True
                    break
                self._use(usage, tc, self.req[t], +1)
                retry.setdefault(tc, []).append(t)
            if found:
                break
        # LessThanInitialShare, over what the first kept
        if not fits and len(self.strategies) > 1:
            pruned = set()
            for tc in self._iterate(usage, c, retry, pruned):
                pre_new, tgt_old, _ = self._shares(usage, c, anc, tc)
                if pre_new < tgt_old:
                    t = retry[tc].pop(0)
                    self._use(usage, tc, self.req[t], -1)
                    targets.append((t, IN_COHORT_FS))
                    if self._fits_fs(usage, c, req):
                        fits = True
                        break
                pruned.add(tc)
        self._use(usage, c, req, -1)
        if fits:
            # fillBackWorkloads
            j = len(targets) - 2
            while j >= 0:
                t = targets[j][0]
                self._use(usage, self.cq[t], self.req[t], +1)
                if all(req[r] <= self._available(usage, c, r)
                       for r in range(self.R)):
                    targets[j] = targets[-1]
                    targets.pop()
                else:
                    self._use(usage, self.cq[t], self.req[t], -1)
                j -= 1
        for t, _ in targets:
            self._use(usage, self.cq[t], self.req[t], +1)
        return targets if fits else []

    # -- one cycle ----------------------------------------------------------

    def _nominate(self, i):
        c = self.cq[i]
        u, nom = self.usage[c], self.nominal[c]
        rep, borrows, frs = FIT, False, []
        has_parent = self.parent[c] >= 0
        for r in self.res_order:
            v = self.req[i][r]
            borrows = borrows or (has_parent and u[r] + v > nom[r])
            if v > self._potential(c, r):
                return NOFIT, False, []
            if v <= self._available(self.usage, c, r):
                mode = FIT
            elif v <= nom[r] or self.reclaim:
                # fair sharing may preempt while borrowing
                mode = PREEMPT
            else:
                mode = NOFIT
            rep = min(rep, mode)
            if rep == NOFIT:
                return NOFIT, False, []
            if mode == PREEMPT:
                frs.append(r)
        return rep, borrows, frs

    def _tournament_order(self, entries, scratch):
        """fair_sharing_iterator.go, as a generator over ``entries``
        (``[i, c, rep, borrows, targets, status]``), with the DRS read
        off ``scratch`` as the admit loop leaves it."""
        remaining = {e[1]: e for e in entries}
        while remaining:
            c0 = next(iter(remaining))
            if self.parent[c0] < 0:
                yield remaining.pop(c0)
                continue
            win = self._pick(self.root[c0], remaining, scratch)
            del remaining[win[1]]
            yield win

    def _pick(self, node, remaining, scratch):
        """runTournament: the survivor of ``node``'s subtree, as
        (entry, its DRS at ``node``'s child on its path)."""
        cands = []
        for h in self.child_cohorts[node]:
            w = self._pick(h, remaining, scratch)
            if w is not None:
                cands.append((w, h))
        for c in self.child_cqs[node]:
            e = remaining.get(c)
            if e is not None:
                cands.append((e, c))
        if not cands:
            return None
        best, best_key = None, None
        for e, child in cands:
            i, c = e[0], e[1]
            req = self.req[i] if e[2] != NOFIT else (0,) * self.R
            if self.broken == "tree_flattened":
                child = c
            key = (self._drs_with(scratch, child, c, req), -self.prio[i],
                   self.created[i])
            if best is None or key < best_key:
                best, best_key = e, key
        return best

    def _classic_order(self, entries, _scratch):
        return iter(sorted(entries, key=lambda e: (
            e[3], -self.prio[e[0]], self.created[e[0]])))

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
            rep, borrows, frs = self._nominate(i)
            targets = (self._fair_targets(i, c, frs)
                       if rep == PREEMPT else [])
            entries.append([i, c, rep, borrows, targets, ""])

        scratch = [list(u) for u in self.usage]
        order = (self._classic_order if self.broken == "classic_order"
                 else self._tournament_order)(entries, scratch)
        preempted: dict[int, None] = {}
        woken = set()
        for e in order:
            i, c, rep, borrows, targets, _ = e
            if rep == NOFIT:
                continue
            req = self.req[i]
            if rep == PREEMPT and not targets:
                # resourcesToReserve
                amt = [req[r] if borrows else max(0, min(
                    req[r], self.nominal[c][r] - scratch[c][r]))
                    for r in range(self.R)]
                self._use(scratch, c, amt, +1)
                continue
            if any(t in preempted for t, _ in targets):
                e[5] = "skipped"
                continue
            gone = list(dict.fromkeys(
                [t for t in preempted if self.root[self.cq[t]]
                 == self.root[c]]
                + [t for t, _ in targets]))
            for t in gone:
                self._use(scratch, self.cq[t], self.req[t], -1)
            fits = all(req[r] <= self._available(scratch, c, r)
                       for r in range(self.R))
            for t in gone:
                self._use(scratch, self.cq[t], self.req[t], +1)
            if not fits:
                e[5] = "skipped"
                continue
            for t, _ in targets:
                preempted[t] = None
            self._use(scratch, c, req, +1)
            if rep == PREEMPT:
                for t, reason in targets:
                    c2 = self.cq[t]
                    self._drop(t)
                    heapq.heappush(self.heap[c2], self._heap_item(t))
                    woken.add(self.root[c2])
                    out.evicted.append(self.key[t])
                    out.evicted_for.append(f"{self.key[t]}={reason}")
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
                    or self.root[c] in woken):
                heapq.heappush(self.heap[c], self._heap_item(i))
            else:
                self.parked[c].append(i)
        for root in woken:
            self._wake(root)
        return out
