"""The quota ledger of this deployment kind, which trusts neither the
program nor the reference: it adds up what the program says it admitted,
evicted and finished, and holds it to the plan's quotas.  It knows the
kind's capacity model and nothing of how a cycle decides: a queue may
hold its nominal quota plus its borrowing limit, a cohort the sum of its
queues' nominals, in every resource.
"""

from __future__ import annotations


def ledger(plan, rounds) -> dict:
    """Adds up the program's own answers.  Counts quota violations
    (a queue over nominal + borrowing limit, or a cohort over the sum
    of its nominals, in any resource, after any cycle), admissions of a
    workload that already holds quota, and evictions or finishes of one
    that holds none."""
    res = plan.resources
    R = len(res)
    nominal = [[q.nominal[r] for r in res] for q in plan.queues]
    cap = [[q.nominal[r] + q.borrowing_limit[r] for r in res]
           for q in plan.queues]
    cohorts: dict[str, list] = {}
    for c, q in enumerate(plan.queues):
        cohorts.setdefault(q.cohort, []).append(c)
    cohort_of = {c: name for name, ms in cohorts.items() for c in ms}
    quota = {name: [sum(nominal[c][r] for c in ms) for r in range(R)]
             for name, ms in cohorts.items()}
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    q_of = plan.wl_queue.tolist()
    req = plan.wl_request.tolist()
    holds = {plan.key(i) for i, on in enumerate(plan.wl_running.tolist())
             if on}
    usage = [[0] * R for _ in plan.queues]
    cusage = {name: [0] * R for name in cohorts}
    for k in holds:
        i = row[k]
        for r in range(R):
            usage[q_of[i]][r] += req[i][r]
            cusage[cohort_of[q_of[i]]][r] += req[i][r]

    def move(k, sign):
        i = row[k]
        c = q_of[i]
        for r in range(R):
            usage[c][r] += sign * req[i][r]
            cusage[cohort_of[c]][r] += sign * req[i][r]
        return c

    violations = double = unknown = 0
    for rnd in rounds:
        for k in rnd.finished:
            if k in holds:
                holds.discard(k)
                move(k, -1)
            else:
                unknown += 1
        for cyc in rnd.cycles:
            touched = set()
            for k in cyc.evicted:
                if k in holds:
                    holds.discard(k)
                    move(k, -1)
                else:
                    unknown += 1
            for k in cyc.admitted:
                if k in holds or k not in row:
                    double += 1
                    continue
                holds.add(k)
                touched.add(move(k, +1))
            for c in touched:
                h = cohort_of[c]
                if any(usage[c][r] > cap[c][r] or cusage[h][r] > quota[h][r]
                       for r in range(R)):
                    violations += 1
    return {"quota_violations": violations, "double_admissions": double,
            "unknown_finishes": unknown}
