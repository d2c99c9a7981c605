"""The quota ledger of this deployment kind, which trusts neither the
program nor the reference's cycle: it adds up what the program says it
admitted (and on which flavors: the cycle record's ``placed``, one
``"<workload key>@<PodSet>:<resource>=<flavor>"`` a (PodSet, resource)),
evicted and finished, and holds it to the plan's quotas.  It knows the
kind's capacity model and nothing of how a cycle decides: a queue may
hold its nominal quota plus its borrowing limit, a cohort the sum of its
queues' nominals, in every (flavor, resource) of every resource group,
every PodSet of its admitted Workloads counted; every PodSet of an
admission names a flavor for every resource it requests, one flavor a
group, and only a flavor that PodSet may take; a Workload is admitted
whole.
"""

from __future__ import annotations


def ledger(plan, rounds) -> dict:
    """Adds up the program's own answers.  ``quota_violations`` holds
    three counts added up: a queue over nominal + borrowing limit or a
    cohort over the sum of its nominals, in any of the (flavor,
    resource) pairs of any group, after any cycle; every (PodSet,
    resource) the program placed on a flavor that the PodSet's node
    selector or an untolerated taint bars it from in that flavor's
    group (``Group.may_take``), or on a flavor of another group than
    the resource's; and every (PodSet, resource) of an admission that
    is requested and named on no flavor (a PodSet left out, a Workload
    admitted in part: quota nobody accounted).  ``double_admissions``
    counts admissions of a workload that already holds quota or that
    the plan does not know, and entries that name a PodSet or a
    resource the workload has not, a flavor of no group, or one (PodSet,
    resource) twice, or two flavors of one group for one PodSet;
    ``unknown_finishes`` evictions or finishes of one that holds
    none."""
    res = plan.resources
    r_of = {r: ri for ri, r in enumerate(res)}
    pairs = [(g, s, r) for g, grp in enumerate(plan.groups)
             for s in range(len(grp.flavors)) for r in grp.resources]
    at = {p: i for i, p in enumerate(pairs)}
    name = {(g, s): f for g, grp in enumerate(plan.groups)
            for s, f in enumerate(grp.flavors)}
    group_of_res = {r: g for g, grp in enumerate(plan.groups)
                    for r in grp.resources}
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
    first = plan.wl_first.tolist()
    ps_req = plan.ps_request.tolist()
    ps_job = plan.ps_job.tolist()
    usage = [[0] * len(pairs) for _ in plan.queues]
    cusage = {h: [0] * len(pairs) for h in cohorts}

    def move(k, held, sign) -> tuple:
        """Charges workload ``k``'s ``held`` ([(pair, amount)]);
        returns its queue and the pairs touched."""
        c = q_of[row[k]]
        for p, v in held:
            usage[c][p] += sign * v
            cusage[cohort_of[c]][p] += sign * v
        return c, [p for p, _ in held]

    holds: dict[str, list] = {}           # key -> [(pair, amount)]
    for k, i in row.items():
        held = [(at[g, s, r], ps_req[j][r])
                for j in range(first[i], first[i + 1])
                for g, s in enumerate(plan.ps_flavor[j].tolist()) if s >= 0
                for r in plan.groups[g].resources if ps_req[j][r] > 0]
        if held:
            holds[k] = held
            move(k, held, +1)

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
            by_key: dict[str, list] = {}
            for entry in cyc.placed:
                k, _, what = entry.rpartition("@")
                by_key.setdefault(k, []).append(what)
            for k, entries in by_key.items():
                if k in holds or k not in row:
                    double += 1
                    continue
                i = row[k]
                names = {plan.ps_name[j]: j
                         for j in range(first[i], first[i + 1])}
                on: dict = {}          # (PodSet row, resource) -> (g, s)
                bad = False
                for what in entries:
                    ps, _, rest = what.partition(":")
                    r, _, flavor = rest.partition("=")
                    where = plan.group_of(flavor)
                    j = names.get(ps)
                    if (j is None or r not in r_of or where is None
                            or (j, r_of[r]) in on):
                        bad = True
                        break
                    on[j, r_of[r]] = where
                # one flavor a group for a PodSet
                for (j, r), (g, s) in on.items():
                    if any(j2 == j and g2 == g and s2 != s
                           for (j2, _), (g2, s2) in on.items()):
                        bad = True
                if bad:
                    double += 1
                    continue
                held: dict = {}
                for j in names.values():
                    for r in range(len(res)):
                        if ps_req[j][r] <= 0:
                            continue
                        if (j, r) not in on:
                            violations += 1     # requested, unaccounted
                            continue
                        g, s = on[j, r]
                        if (g != group_of_res[r]
                                or not plan.groups[g].may_take[ps_job[j], s]):
                            violations += 1
                            continue
                        p = at[g, s, r]
                        held[p] = held.get(p, 0) + ps_req[j][r]
                holds[k] = list(held.items())
                c, touched = move(k, holds[k], +1)
                checks.update((c, p) for p in touched)
            for c, p in checks:
                h = cohort_of[c]
                if usage[c][p] > cap[c][p] or cusage[h][p] > quota[h][p]:
                    violations += 1
    return {"quota_violations": violations, "double_admissions": double,
            "unknown_finishes": unknown}
