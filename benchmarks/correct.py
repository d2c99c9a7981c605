"""The comparison that decides ``correct``: what is common to every
deployment kind.

Every applied cycle of the run, warm rounds included, is replayed
through the plain reference of the configuration's deployment kind
(``deployment_kinds/<kind>/``; the contract is in benchmarks/harness.py)
from the same inputs: each round's record as the traffic wrote it (the
workloads that finished at its boundary, and whatever else a later
traffic kind records there) and the clock of each cycle.  A cycle
matches when every field the kind names in ``COMPARED`` (for the first
kind its admitted, evicted, skipped and preempting workloads) holds the
same entries as the reference's, in any order.  Beside the replay the
kind's quota ledger, which trusts neither side, adds up what the
program says it admitted, evicted and finished, and holds it to the
configuration's quotas.

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
        unknown += reference.begin_round(rnd)
        out.append([reference.cycle(cyc.clock) for cyc in rnd.cycles])
        if len(rnd.cycles) < rnd.max_cycles and reference.has_heads():
            short += 1
    return out, short, unknown


def _same(cyc, ref, fields) -> bool:
    return all(sorted(getattr(cyc, name)) == sorted(getattr(ref, name))
               for name in fields)


def compare(kind, plan, rounds, measured_from: int, broken=None) -> dict:
    """``kind``: the module of the plan's deployment kind.  ``rounds``:
    every round of the run in order; the measured window starts at index
    ``measured_from``.  Returns the numbers compared, each with its
    limit, and the facts printed beside them."""
    t0 = time.perf_counter()
    fields = kind.COMPARED
    ref = kind.Reference(plan, broken=broken)
    results, short, unknown_ref = replay(ref, rounds)
    mismatched = compared = 0
    first = None
    with_evictions = cross = evictions = 0
    for ri, (rnd, cycles) in enumerate(zip(rounds, results)):
        for ci, (cyc, r) in enumerate(zip(rnd.cycles, cycles)):
            compared += 1
            if not _same(cyc, r, fields):
                mismatched += 1
                if first is None:
                    first = {"round": ri, "cycle": ci}
                    for name in fields:
                        mine, theirs = getattr(cyc, name), getattr(r, name)
                        first[name] = {
                            "counts": [len(mine), len(theirs)],
                            "only_program": sorted(
                                set(mine) - set(theirs))[:3],
                            "only_reference": sorted(
                                set(theirs) - set(mine))[:3]}
            if r.evicted:
                with_evictions += 1
                evictions += len(r.evicted)
                cross += r.cross_queue_evictions
    led = kind.ledger(plan, rounds)
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
