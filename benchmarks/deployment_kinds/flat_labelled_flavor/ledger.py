"""The quota ledger of this deployment kind, which trusts neither the
program nor the reference's cycle: ``flat_multi_flavor``'s ledger (what
the program says it admitted, on which flavor, evicted and finished,
added up and held to the plan's quotas a (flavor, resource)), and beside
it the rule this kind adds: an admission may stand only on a flavor the
job may take.
"""

from __future__ import annotations

from ..flat_multi_flavor.ledger import ledger as quota_ledger


def ledger(plan, rounds) -> dict:
    """``quota_violations`` holds two counts added up: a queue over
    nominal + borrowing limit or a cohort over the sum of its nominals,
    in any flavor and resource, after any cycle (the second kind's
    count); and every admission the program placed on a flavor that the
    workload's node selector or an untolerated taint bars it from
    (``plan.may_take``, from the configuration's labels, taints,
    selectors and tolerations alone).  Quota on a flavor the job cannot
    run on is quota the cluster cannot give.  ``double_admissions`` and
    ``unknown_finishes`` are the second kind's."""
    out = quota_ledger(plan, rounds)
    slot = {f: s for s, f in enumerate(plan.flavors)}
    row = {plan.key(i): i for i in range(len(plan.wl_name))}
    job = plan.wl_job.tolist()
    barred = 0
    for rnd in rounds:
        for cyc in rnd.cycles:
            for entry in cyc.placed:
                k, _, flavor = entry.rpartition("@")
                if (k in row and flavor in slot
                        and not plan.may_take[job[row[k]], slot[flavor]]):
                    barred += 1
    out["quota_violations"] += barred
    return out
