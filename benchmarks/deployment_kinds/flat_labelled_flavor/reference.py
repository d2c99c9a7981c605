"""The plain reference: ``flat_multi_flavor``'s cycle with one rule
added, which flavors a workload may take.

It imports nothing of the program and takes nothing the program has
made: it starts from the ``LabelledPlan`` (``cluster.py`` beside this
file) and is fed what the program was fed.  Everything but the rule is
the second kind's reference (``flat_multi_flavor/reference.py``: heads,
the flavor walk under the stop rules with its resume state, the oracle,
the target search, the admit loop, the requeue), whose class this one
extends; the walk is written out again here with the rule in it.

The rule (upstream pkg/scheduler/flavorassigner/flavorassigner.go,
findFlavorForPodSetResource, before any quota is looked at; docs
concepts/resource_flavor): a workload may take a flavor when

  - every ``NoSchedule`` or ``NoExecute`` taint of the flavor is
    tolerated by one of the workload's tolerations or of the flavor's
    own ``tolerations`` (a toleration matches a taint when its effect is
    empty or equal, its key is empty or equal, and its operator is
    ``Exists`` or its value equal; ``PreferNoSchedule`` never bars), and
  - every entry of the workload's ``nodeSelector`` whose key is a label
    key of *some* flavor of the queue's resource group equals that
    flavor's label of the key (a flavor without the key does not match);
    other keys are ignored.

A flavor the workload may not take is passed over by the walk: it
counts as attempted (so the resume state after a walk that reached the
list's end is void, and one recorded on a later stop names that stop),
it never stops the walk, it is never the best flavor, and the oracle is
asked nothing about it.

``broken`` switches one stated guarantee off and makes the control that
the comparison has to fail (benchmarks/correct.py).
"""

from __future__ import annotations

from ..flat_multi_flavor import reference as multi_flavor
from ..flat_multi_flavor.reference import (COMPARED, FIT, NOFIT, PREEMPT,
                                           RECLAIM, Entry)

CONTROLS = multi_flavor.CONTROLS + ("eligibility_off",)

__all__ = ["COMPARED", "CONTROLS", "Reference", "eligible", "tolerates"]


def tolerates(toleration: dict, taint: dict) -> bool:
    if toleration.get("effect") and toleration["effect"] != taint["effect"]:
        return False
    if toleration.get("key") and toleration["key"] != taint["key"]:
        return False
    if toleration.get("operator", "Equal") == "Exists":
        return True
    return toleration.get("value", "") == taint.get("value", "")


def eligible(job: dict, flavor: dict, group_label_keys: set) -> bool:
    """May a workload of constraint class ``job`` (``nodeSelector``,
    ``tolerations``) take ``flavor`` (``nodeLabels``, ``nodeTaints``,
    ``tolerations``), in a resource group whose flavors carry the label
    keys ``group_label_keys``?"""
    tolerations = (list(job.get("tolerations", ()))
                   + list(flavor.get("tolerations", ())))
    for taint in flavor.get("nodeTaints", ()):
        if taint["effect"] == "PreferNoSchedule":
            continue
        if not any(tolerates(t, taint) for t in tolerations):
            return False
    labels = flavor.get("nodeLabels", {})
    for key, want in job.get("nodeSelector", {}).items():
        if key in group_label_keys and labels.get(key) != want:
            return False
    return True


class Reference(multi_flavor.Reference):
    def __init__(self, plan, broken: str | None = None):
        if broken is not None and broken not in CONTROLS:
            raise ValueError(f"unknown control {broken!r}")
        super().__init__(
            plan, broken=None if broken == "eligibility_off" else broken)
        self.broken = broken
        keys = {k for f in plan.flavor_specs for k in f.get("nodeLabels", {})}
        # [constraint class][flavor slot]
        self.may_take = [
            [broken == "eligibility_off" or eligible(job, flavor, keys)
             for flavor in plan.flavor_specs] for job in plan.job_classes]
        self.job = plan.wl_job.tolist()

    def _walk(self, i, c) -> Entry:
        e = Entry(i, c)
        h = self.cohort_of[c]
        may_take = self.may_take[self.job[i]]
        tried, gen = self.resume.pop(i, (-1, 0))
        start = tried + 1 if gen == self.generation[c] else 0
        best, short_of = NOFIT, []
        last = self.S - 1
        # the control: no stop rule, and the last flavor of the best mode
        unordered = self.broken == "flavor_order_ignored"
        for f in range(start, self.S):
            if not may_take[f]:
                continue           # the rule: attempted, and passed over
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
