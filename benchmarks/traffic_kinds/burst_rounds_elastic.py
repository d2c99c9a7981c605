"""Traffic kind ``burst_rounds_elastic``: ``burst_rounds``' round, whose
cycle records also say on how many pods each admission runs.

``counts`` holds one ``"<workload key>@<PodSet>=<pods>"`` a PodSet of
every admission, read as the cycle is recorded from the admission the
Driver wrote on the workload (``Workload.admission``, what the Job
controller reads to set the Job's parallelism), as
``burst_rounds_podsets`` reads its placements, not from the solver's
planes: a later cycle of the same round may evict the workload and
clear it.  An admitted workload with no admission is recorded as
``"<key>@none=0"``, which no Job's PodSet matches.
"""

from __future__ import annotations

from traffic_kinds import burst_rounds


class _Recording:
    """The Driver as ``burst_rounds`` drives it, with the admitted
    counts of each applied cycle noted before the cycle is recorded."""

    def __init__(self, driver, counts: list):
        self._driver = driver
        self._counts = counts

    def __getattr__(self, name):
        return getattr(self._driver, name)

    def schedule_burst(self, max_cycles, runtime, on_cycle_start, on_cycle):
        def note_then_record(k, stats):
            self._counts.append([entry for key in stats.admitted
                                 for entry in self._entries(key)])
            on_cycle(k, stats)
        return self._driver.schedule_burst(
            max_cycles, runtime=runtime, on_cycle_start=on_cycle_start,
            on_cycle=note_then_record)

    def _entries(self, key) -> list:
        admission = self._driver.workload(key).admission
        if admission is None:
            return [f"{key}@none=0"]
        return [f"{key}@{ps.name}={ps.count}"
                for ps in admission.pod_set_assignments]


class Traffic(burst_rounds.Traffic):
    def round(self, driver, clock, mark=None, max_cycles=None,
              after_cycle=None):
        counts: list = []
        rec = super().round(_Recording(driver, counts), clock, mark,
                            max_cycles, after_cycle)
        for cyc, entries in zip(rec.cycles, counts, strict=True):
            cyc.counts = entries
        return rec
