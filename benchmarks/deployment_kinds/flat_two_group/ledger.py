"""The quota ledger of this deployment kind, which trusts neither the
program nor the reference's cycle: it adds up what the program says it
admitted (and on which flavors: the cycle record's ``placed``), evicted
and finished, and holds it to the plan's quotas.  It knows the kind's
capacity model and nothing of how a cycle decides: a queue may hold its
nominal quota plus its borrowing limit, a cohort the sum of its queues'
nominals, in every (flavor, resource) of every resource group; an
admission takes one flavor of each group whose resources it requests,
and only a flavor the job may take.
"""

from __future__ import annotations


def ledger(plan, rounds) -> dict:
    """Adds up the program's own answers.  ``quota_violations`` holds
    three counts added up: a queue over nominal + borrowing limit or a
    cohort over the sum of its nominals, in any of the (flavor,
    resource) pairs of any group, after any cycle; every admission the
    program placed on a flavor that the workload's node selector or an
    untolerated taint bars it from in that flavor's group
    (``Group.may_take``); and every admission that names no flavor for
    a resource it requests (a group left out: quota nobody accounted).
    ``double_admissions`` counts admissions of a workload that already
    holds quota, that the plan does not know, or that name a flavor of
    no group or two of one; ``unknown_finishes`` evictions or finishes
    of one that holds none."""
    res = plan.resources
    pairs = [(g, s, r) for g, grp in enumerate(plan.groups)
             for s in range(len(grp.flavors)) for r in grp.resources]
    at = {p: i for i, p in enumerate(pairs)}
    name = {(g, s): f for g, grp in enumerate(plan.groups)
            for s, f in enumerate(grp.flavors)}
    nominal = [[q.nominal[name[g, s]][res[r]] for g, s, r in pairs]
               for q in plan.queues]
    cap = [[q.nominal[name[g, s]][res[r]]
            + q.borrowing_limit[name[g, s]][res[r]] for g, s, r in pairs]
           for q in plan.queues]
    cohorts: dict[str, list] = {}
    for c, q in enumerate(plan.queues):
        cohorts.setdefault(q.cohort, []).append(c)
    cohort_of = {c: h for h, ms in cohorts.items() for c in ms}
    quota = {h: [sum(nominal[c][i] for c in ms) for i in range(len(pairs))]
             for h, ms in cohorts.items()}
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    q_of = plan.wl_queue.tolist()
    req = plan.wl_request.tolist()
    job = plan.wl_job.tolist()
    usage = [[0] * len(pairs) for _ in plan.queues]
    cusage = {h: [0] * len(pairs) for h in cohorts}

    def move(k, slots, sign) -> tuple:
        """Charges workload ``k`` on ``slots`` ({group: slot}); returns
        its queue and the pairs touched."""
        i = row[k]
        c = q_of[i]
        touched = []
        for g, s in slots.items():
            for r in plan.groups[g].resources:
                p = at[g, s, r]
                usage[c][p] += sign * req[i][r]
                cusage[cohort_of[c]][p] += sign * req[i][r]
                touched.append(p)
        return c, touched

    holds: dict[str, dict] = {}           # key -> {group: slot}
    for i, slots in enumerate(plan.wl_flavor.tolist()):
        if slots[0] >= 0:
            holds[plan.key(i)] = dict(enumerate(slots))
            move(plan.key(i), holds[plan.key(i)], +1)

    violations = double = unknown = 0
    for rnd in rounds:
        for k in rnd.finished:
            if k in holds:
                move(k, holds.pop(k), -1)
            else:
                unknown += 1
        for cyc in rnd.cycles:
            checks = set()
            for k in cyc.evicted:
                if k in holds:
                    move(k, holds.pop(k), -1)
                else:
                    unknown += 1
            # (an admission missing from ``placed`` holds no quota here;
            # the comparison's ``placed`` field is what fails it)
            for entry in cyc.placed:
                k, _, flavors = entry.rpartition("@")
                where = [plan.group_of(f) for f in flavors.split("+")]
                if (k in holds or k not in row or None in where
                        or len({g for g, _ in where}) != len(where)):
                    double += 1
                    continue
                slots = dict(where)
                i = row[k]
                # a flavor the job may not take, and a requested
                # resource that no named flavor accounts for
                violations += sum(
                    not plan.groups[g].may_take[job[i], s]
                    for g, s in slots.items())
                violations += sum(
                    g not in slots and any(req[i][r] > 0
                                           for r in grp.resources)
                    for g, grp in enumerate(plan.groups))
                holds[k] = slots
                c, touched = move(k, slots, +1)
                checks.update((c, p) for p in touched)
            for c, p in checks:
                h = cohort_of[c]
                if usage[c][p] > cap[c][p] or cusage[h][p] > quota[h][p]:
                    violations += 1
    return {"quota_violations": violations, "double_admissions": double,
            "unknown_finishes": unknown}
