"""The quota ledger of this deployment kind, which trusts neither the
program nor the reference: it adds up what the program says it admitted,
evicted and finished, and holds it to the plan's quotas at every level
of the tree.  It knows the kind's capacity model and nothing of how a
cycle decides: a cohort holds the sum of its children's quotas, no
node has a borrowing or a lending limit, so a queue, a team and a
department may each use up to what their department holds, and no
more, in every resource.
"""

from __future__ import annotations


def ledger(plan, rounds) -> dict:
    """Adds up the program's own answers.  Counts quota violations (a
    queue, a team or a department over its department's quota in any
    resource after any cycle, one a node), admissions of a workload
    that already holds quota, and evictions or finishes of one that
    holds none."""
    res = plan.resources
    R = len(res)
    team_of = [q.cohort for q in plan.queues]
    dept_of_team = plan.team_parent
    quota = {d: [0] * R for d in plan.departments}
    for q in plan.queues:
        d = dept_of_team[q.cohort]
        for r in range(R):
            quota[d][r] += q.nominal[res[r]]
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    q_of = plan.wl_queue.tolist()
    req = plan.wl_request.tolist()
    holds = {plan.key(i) for i, on in enumerate(plan.wl_running.tolist())
             if on}
    usage = [[0] * R for _ in plan.queues]
    t_usage = {t: [0] * R for t in plan.teams}
    d_usage = {d: [0] * R for d in plan.departments}

    def move(k, sign):
        i = row[k]
        c = q_of[i]
        t = team_of[c]
        for r in range(R):
            usage[c][r] += sign * req[i][r]
            t_usage[t][r] += sign * req[i][r]
            d_usage[dept_of_team[t]][r] += sign * req[i][r]
        return c

    for k in holds:
        move(k, +1)
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
                t = team_of[c]
                d = dept_of_team[t]
                cap = quota[d]
                for use in (usage[c], t_usage[t], d_usage[d]):
                    if any(use[r] > cap[r] for r in range(R)):
                        violations += 1
    return {"quota_violations": violations, "double_admissions": double,
            "unknown_finishes": unknown}
