"""Traffic kind ``burst_rounds_tas``: ``burst_rounds``' round, whose
cycle records also say on which nodes each admission's pods sit.

``placed`` holds one ``"<workload key>@<PodSet>=<block>/<rack>/<host>x<pods>"``
a (PodSet, node) of every admission, read as the cycle is recorded from
the admission the Driver wrote on the workload (``Workload.admission``'s
topology assignment, what the topology ungater reads), as
``burst_rounds_podsets`` reads the flavors, not from the solver's
planes: a later cycle of the same round may evict the workload and
clear it.  An admitted PodSet with no topology assignment is recorded
as ``"<key>@<PodSet>=none"``, and an admitted workload with no
admission as ``"<key>@none=none"``; neither matches a reference entry.
"""

from __future__ import annotations

from traffic_kinds import burst_rounds
from traffic_kinds.burst_rounds_podsets import _Recording


class _Placements(_Recording):
    def _entries(self, key) -> list:
        admission = self._driver.workload(key).admission
        if admission is None:
            return [f"{key}@none=none"]
        out = []
        for ps in admission.pod_set_assignments:
            ta = ps.topology_assignment
            if ta is None:
                out.append(f"{key}@{ps.name}=none")
                continue
            out.extend(f"{key}@{ps.name}={'/'.join(d.values)}x{d.count}"
                       for d in ta.domains)
        return out


class Traffic(burst_rounds.Traffic):
    def round(self, driver, clock, mark=None, max_cycles=None,
              after_cycle=None):
        placed: list = []
        rec = super().round(_Placements(driver, placed), clock, mark,
                            max_cycles, after_cycle)
        for cyc, entries in zip(rec.cycles, placed, strict=True):
            cyc.placed = entries
        return rec
