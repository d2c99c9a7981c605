"""Traffic kind ``burst_rounds_fair``: ``burst_rounds``' round, whose
cycle records also say why each workload was evicted.

``evicted_for`` holds one ``"<workload key>=<reason>"`` an eviction of
the cycle, the reason read as the cycle is recorded from the Preempted
condition the Driver wrote on the workload (``InClusterQueue`` for a
target in the preemptor's own queue, ``InCohortFairSharing`` for one
the fair-sharing strategies took from another), as
``burst_rounds_podsets`` reads an admission.  An evicted workload with
no such condition is recorded as ``"<key>=none"``, which matches no
reference entry.
"""

from __future__ import annotations

from traffic_kinds import burst_rounds

PREEMPTED = "Preempted"


class _Recording:
    """The Driver as ``burst_rounds`` drives it, with the reasons of
    each applied cycle's evictions noted before the cycle is
    recorded."""

    def __init__(self, driver, reasons: list):
        self._driver = driver
        self._reasons = reasons

    def __getattr__(self, name):
        return getattr(self._driver, name)

    def schedule_burst(self, max_cycles, runtime, on_cycle_start, on_cycle):
        def note_then_record(k, stats):
            self._reasons.append([f"{key}={self._reason(key)}"
                                  for key in stats.preempted_targets])
            on_cycle(k, stats)
        return self._driver.schedule_burst(
            max_cycles, runtime=runtime, on_cycle_start=on_cycle_start,
            on_cycle=note_then_record)

    def _reason(self, key) -> str:
        wl = self._driver.workload(key)
        cond = None if wl is None else wl.conditions.get(PREEMPTED)
        return "none" if cond is None else cond.reason


class Traffic(burst_rounds.Traffic):
    def round(self, driver, clock, mark=None, max_cycles=None,
              after_cycle=None):
        reasons: list = []
        rec = super().round(_Recording(driver, reasons), clock, mark,
                            max_cycles, after_cycle)
        for cyc, entries in zip(rec.cycles, reasons, strict=True):
            cyc.evicted_for = entries
        return rec
