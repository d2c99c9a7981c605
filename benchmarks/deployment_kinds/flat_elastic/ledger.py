"""The quota ledger of this deployment kind, which trusts neither the
program nor the reference's cycle: it adds up what the program says it
admitted (and on how many pods: the cycle record's ``counts``, one
``"<workload key>@<PodSet>=<pods>"`` a PodSet of an admission),
evicted and finished, and holds it to the plan's quotas.  It knows the
kind's capacity model and nothing of how a cycle decides: a queue may
hold its nominal quota plus its borrowing limit, a cohort the sum of its
queues' nominals, in every resource; an admitted Job is charged its
per-pod request times the pods it was admitted on, which lie between
its job-min-parallelism and its parallelism.
"""

from __future__ import annotations


def ledger(plan, rounds) -> dict:
    """Adds up the program's own answers.  ``quota_violations`` holds
    two counts added up: a queue over nominal + borrowing limit or a
    cohort over the sum of its nominals, in any resource, after any
    cycle; and every admission whose pods are not named once, or lie
    outside [job-min-parallelism, parallelism] (quota nobody accounted,
    or a Job run on fewer pods than it can run on).
    ``double_admissions`` counts admissions of a workload that already
    holds quota or that the plan does not know, and count entries of a
    PodSet the Job has not or of a workload the cycle did not admit;
    ``unknown_finishes`` evictions or finishes of one that holds none."""
    res = plan.resources
    R = len(res)
    cap = [[q.nominal[r] + q.borrowing_limit[r] for r in res]
           for q in plan.queues]
    cohorts: dict[str, list] = {}
    for c, q in enumerate(plan.queues):
        cohorts.setdefault(q.cohort, []).append(c)
    cohort_of = {c: name for name, ms in cohorts.items() for c in ms}
    quota = {name: [sum(plan.queues[c].nominal[r] for c in ms)
                    for r in res] for name, ms in cohorts.items()}
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    q_of = plan.wl_queue.tolist()
    pods = plan.wl_pods.tolist()
    least = plan.wl_min_pods.tolist()
    per_pod = plan.per_pod().tolist()
    held: dict[str, list] = {}       # key -> the request it holds
    usage = [[0] * R for _ in plan.queues]
    cusage = {name: [0] * R for name in cohorts}

    def move(k, req, sign):
        c = q_of[row[k]]
        for r in range(R):
            usage[c][r] += sign * req[r]
            cusage[cohort_of[c]][r] += sign * req[r]
        return c

    for i, on in enumerate(plan.wl_running.tolist()):
        if on:
            k = plan.key(i)
            held[k] = [v * pods[i] for v in per_pod[i]]
            move(k, held[k], +1)

    violations = double = unknown = 0
    for rnd in rounds:
        for k in rnd.finished:
            if k in held:
                move(k, held.pop(k), -1)
            else:
                unknown += 1
        for cyc in rnd.cycles:
            named: dict[str, list] = {}
            for entry in cyc.counts:
                key, _, rest = entry.partition("@")
                ps, _, n = rest.partition("=")
                if ps != "main" or key not in row:
                    double += 1
                    continue
                named.setdefault(key, []).append(int(n))
            touched = set()
            for k in cyc.evicted:
                if k in held:
                    move(k, held.pop(k), -1)
                else:
                    unknown += 1
            for k in cyc.admitted:
                if k in held or k not in row:
                    double += 1
                    continue
                i = row[k]
                got = named.pop(k, [])
                if len(got) != 1 or not least[i] <= got[0] <= pods[i]:
                    violations += 1
                    got = [pods[i]]
                held[k] = [v * got[0] for v in per_pod[i]]
                touched.add(move(k, held[k], +1))
            double += len(named)
            for c in touched:
                h = cohort_of[c]
                if any(usage[c][r] > cap[c][r] or cusage[h][r] > quota[h][r]
                       for r in range(R)):
                    violations += 1
    return {"quota_violations": violations, "double_admissions": double,
            "unknown_finishes": unknown}
