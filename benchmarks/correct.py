"""The comparison that decides ``correct``.

Every applied cycle of the run, warm rounds included, is replayed
through the plain reference (benchmarks/reference.py) from the same
inputs: the finished workloads of each boundary and the clock of each
cycle.  A cycle matches when its admitted set, its evicted set, its
skipped set and its preempting set equal the reference's.  Beside the
replay a quota ledger, which trusts neither side, adds up what the
program says it admitted, evicted and finished, and holds it to the
configuration's quotas on every resource.

Each number compared is exact, so each limit is 0.
"""

from __future__ import annotations

import time


def replay(reference, rounds):
    """Feeds the rounds to a reference.  Returns the per-cycle results
    (one list a round), how many rounds stopped early while the
    reference still had a head to decide, and the unknown finishes."""
    out, short, unknown = [], 0, 0
    for rnd in rounds:
        unknown += reference.finish(rnd.finished)
        out.append([reference.cycle(cyc.clock) for cyc in rnd.cycles])
        if len(rnd.cycles) < rnd.max_cycles and reference.has_heads():
            short += 1
    return out, short, unknown


def _same(cyc, ref) -> bool:
    return (set(cyc.admitted) == set(ref.admitted)
            and sorted(cyc.evicted) == sorted(ref.evicted)
            and sorted(cyc.skipped) == sorted(ref.skipped)
            and sorted(cyc.preempting) == sorted(ref.preempting)
            and len(cyc.admitted) == len(ref.admitted))


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


def compare(plan, rounds, measured_from: int, reference_cls,
            broken=None) -> dict:
    """``rounds``: every round of the run in order; the measured window
    starts at index ``measured_from``.  Returns the numbers compared,
    each with its limit, and the facts printed beside them."""
    t0 = time.perf_counter()
    ref = reference_cls(plan, broken=broken)
    results, short, unknown_ref = replay(ref, rounds)
    mismatched = compared = 0
    first = None
    with_evictions = cross = evictions = 0
    for ri, (rnd, cycles) in enumerate(zip(rounds, results)):
        for ci, (cyc, r) in enumerate(zip(rnd.cycles, cycles)):
            compared += 1
            if not _same(cyc, r):
                mismatched += 1
                if first is None:
                    first = {
                        "round": ri, "cycle": ci,
                        "admitted": [len(cyc.admitted), len(r.admitted)],
                        "evicted": [len(cyc.evicted), len(r.evicted)],
                        "skipped": [len(cyc.skipped), len(r.skipped)],
                        "only_program": sorted(
                            set(cyc.admitted) - set(r.admitted))[:3],
                        "only_reference": sorted(
                            set(r.admitted) - set(cyc.admitted))[:3]}
            if r.evicted:
                with_evictions += 1
                evictions += len(r.evicted)
                cross += r.cross_queue_evictions
    led = ledger(plan, rounds)
    window = rounds[measured_from:]
    stalled = int(bool(window) and not any(
        cyc.admitted for rnd in window for cyc in rnd.cycles))
    numbers = {
        "mismatched_cycles": mismatched,
        "cycles_short": short,
        "quota_violations": led["quota_violations"],
        "double_admissions": led["double_admissions"],
        "unknown_finishes": led["unknown_finishes"] + unknown_ref,
        "window_stalled": stalled,
    }
    compared_out = {k: {"value": v, "limit": 0} for k, v in numbers.items()}
    return {
        "correct": all(v == 0 for v in numbers.values()),
        "compared": compared_out,
        "facts": {"cycles_compared": compared,
                  "cycles_with_evictions": with_evictions,
                  "evictions": evictions,
                  "cross_queue_evictions": cross,
                  "first_mismatch": first,
                  "reference_s": time.perf_counter() - t0},
    }
