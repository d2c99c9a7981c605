"""The quota ledger of this deployment kind, which trusts neither the
program nor the reference: it adds up what the program says it admitted
(and on which flavor: the cycle record's ``placed``), evicted and
finished, and holds it to the plan's quotas.  It knows the kind's
capacity model and nothing of how a cycle decides: a queue may hold its
nominal quota plus its borrowing limit, a cohort the sum of its queues'
nominals, in every (flavor, resource).
"""

from __future__ import annotations


def ledger(plan, rounds) -> dict:
    """Adds up the program's own answers.  Counts quota violations (a
    queue over nominal + borrowing limit, or a cohort over the sum of
    its nominals, in any flavor and resource, after any cycle),
    admissions of a workload that already holds quota, that the plan
    does not know or that name no flavor of the plan, and evictions or
    finishes of one that holds none."""
    res, flavors = plan.resources, plan.flavors
    R, S = len(res), len(flavors)
    slot = {f: s for s, f in enumerate(flavors)}
    nominal = [[[q.nominal[f][r] for r in res] for f in flavors]
               for q in plan.queues]
    cap = [[[q.nominal[f][r] + q.borrowing_limit[f][r] for r in res]
            for f in flavors] for q in plan.queues]
    cohorts: dict[str, list] = {}
    for c, q in enumerate(plan.queues):
        cohorts.setdefault(q.cohort, []).append(c)
    cohort_of = {c: name for name, ms in cohorts.items() for c in ms}
    quota = {name: [[sum(nominal[c][s][r] for c in ms) for r in range(R)]
                    for s in range(S)] for name, ms in cohorts.items()}
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    q_of = plan.wl_queue.tolist()
    req = plan.wl_request.tolist()
    usage = [[[0] * R for _ in range(S)] for _ in plan.queues]
    cusage = {name: [[0] * R for _ in range(S)] for name in cohorts}

    def move(k, s, sign):
        i = row[k]
        c = q_of[i]
        for r in range(R):
            usage[c][s][r] += sign * req[i][r]
            cusage[cohort_of[c]][s][r] += sign * req[i][r]
        return c

    holds: dict[str, int] = {}            # key -> the flavor slot it holds
    for i, s in enumerate(plan.wl_flavor.tolist()):
        if s >= 0:
            holds[plan.key(i)] = s
            move(plan.key(i), s, +1)

    violations = double = unknown = 0
    for rnd in rounds:
        for k in rnd.finished:
            if k in holds:
                move(k, holds.pop(k), -1)
            else:
                unknown += 1
        for cyc in rnd.cycles:
            touched = set()
            for k in cyc.evicted:
                if k in holds:
                    move(k, holds.pop(k), -1)
                else:
                    unknown += 1
            # (an admission missing from ``placed`` holds no quota here;
            # the comparison's ``placed`` field is what fails it)
            for entry in cyc.placed:
                k, _, flavor = entry.rpartition("@")
                if k in holds or k not in row or flavor not in slot:
                    double += 1
                    continue
                holds[k] = slot[flavor]
                touched.add((move(k, slot[flavor], +1), slot[flavor]))
            for c, s in touched:
                h = cohort_of[c]
                if any(usage[c][s][r] > cap[c][s][r]
                       or cusage[h][s][r] > quota[h][s][r]
                       for r in range(R)):
                    violations += 1
    return {"quota_violations": violations, "double_admissions": double,
            "unknown_finishes": unknown}
