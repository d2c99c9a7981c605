"""Traffic kind ``burst_rounds_podsets``: ``burst_rounds``' round, whose
cycle records also say which flavor each resource of each PodSet of
each admission took.

``placed`` holds one ``"<workload key>@<PodSet>:<resource>=<flavor>"`` a
(PodSet, resource) of every admission, read as the cycle is recorded
from the admission the Driver wrote on the workload
(``Workload.admission``, what a kubelet would see), as
``burst_rounds_flavors`` reads it, not from the solver's planes: a
later cycle of the same round may evict the workload and clear it.  An
admitted workload with no admission is recorded as ``"<key>@none:="``,
which matches no reference entry.
"""

from __future__ import annotations

from traffic_kinds import burst_rounds


class _Recording:
    """The Driver as ``burst_rounds`` drives it, with the placements of
    each applied cycle noted before the cycle is recorded."""

    def __init__(self, driver, placed: list):
        self._driver = driver
        self._placed = placed

    def __getattr__(self, name):
        return getattr(self._driver, name)

    def schedule_burst(self, max_cycles, runtime, on_cycle_start, on_cycle):
        def note_then_record(k, stats):
            self._placed.append([entry for key in stats.admitted
                                 for entry in self._entries(key)])
            on_cycle(k, stats)
        return self._driver.schedule_burst(
            max_cycles, runtime=runtime, on_cycle_start=on_cycle_start,
            on_cycle=note_then_record)

    def _entries(self, key) -> list:
        admission = self._driver.workload(key).admission
        if admission is None:
            return [f"{key}@none:="]
        return [f"{key}@{ps.name}:{res}={flavor}"
                for ps in admission.pod_set_assignments
                for res, flavor in ps.flavors.items()]


class Traffic(burst_rounds.Traffic):
    def round(self, driver, clock, mark=None, max_cycles=None,
              after_cycle=None):
        placed: list = []
        rec = super().round(_Recording(driver, placed), clock, mark,
                            max_cycles, after_cycle)
        for cyc, entries in zip(rec.cycles, placed, strict=True):
            cyc.placed = entries
        return rec
