"""The candidate table: columns over one ClusterQueue's admitted workloads.

A preempting head asks of every workload of its queue, and of its
cohort's borrowing queues, the same few things each cycle: its
priority, whether it uses a flavor-resource the head needs, its usage.
From admission to removal none of them changes, so a ``CQState`` keeps
them as numpy columns, a row a workload, written once when the workload
is added (``CQState.add_workload``: append) and dropped when it goes
(``remove_workload``: the last row moves into the hole).  A snapshot's
clone shares the columns with the queue it is a clone of until either
is written (``clone``), so a cycle pays Python for the rows that
changed, a memcpy for the queues they are in, and nothing for the rest.

What a condition decides (the Evicted flag, the quota reservation
time, the queue-order timestamp) is not a column: conditions change
under a workload while it stays in its queue, and the preemptor reads
them off the ``Info`` of the rows it has chosen.

The flavor-resource columns are the table's own: a column a
(flavor, resource) any of its workloads used, kept sorted, so that
tables over the same flavors have the same columns.  Quantities are
unscaled; the device search's scale is its pack's, applied where the
planes are gathered (``ops/preemption_solver.py``).
"""

from __future__ import annotations

import numpy as np

from ..resources import FlavorResource, FlavorResourceQuantities
from ..workload import Info


class TableTally:
    """Rows written into the tables that share it: those of one cache's
    queues and of their snapshot clones."""

    __slots__ = ("built",)

    def __init__(self):
        self.built = 0


def used_flavor_resources(info: Info) -> list[FlavorResource]:
    """The flavor-resources a workload was assigned, PodSet by PodSet."""
    return [FlavorResource(flavor, res) for psr in info.total_requests
            for res, flavor in psr.flavors.items()]


class CandidateTable:
    """Rows 0..n-1, in no order a reader may rely on (``seq`` gives the
    order of ``CQState.workloads``); rows from ``n`` on are blank."""

    # a row, column by column: [cap] or [cap, len(frs)]
    COLUMNS = ("infos", "uid", "priority", "seq", "uses", "has", "raw")
    __slots__ = COLUMNS + ("tally", "n", "row_of", "frs", "col_of",
                           "_next_seq", "_shared")

    def __init__(self, tally: TableTally | None = None):
        self.tally = tally if tally is not None else TableTally()
        self.n = 0
        self.row_of: dict[str, int] = {}        # workload key -> row
        self.frs: tuple[FlavorResource, ...] = ()
        self.col_of: dict[FlavorResource, int] = {}
        self._next_seq = 0
        # the columns and row_of are also a clone's, or the table's this
        # is a clone of: copy them before the first write (clone)
        self._shared = False
        cap = 8
        self.infos = np.full(cap, None, dtype=object)
        self.uid = np.full(cap, None, dtype=object)
        self.priority = np.zeros(cap, dtype=np.int64)
        # the order of insertion, which is the order of the queue's dict
        self.seq = np.zeros(cap, dtype=np.int64)
        # uses: the workload was assigned the flavor for the resource
        # (what findCandidates' workloadUsesResources tests); has, raw:
        # the keys and the quantities of its usage()
        self.uses = np.zeros((cap, 0), dtype=bool)
        self.has = np.zeros((cap, 0), dtype=bool)
        self.raw = np.zeros((cap, 0), dtype=np.int64)

    def _resize(self, cap: int, frs: tuple[FlavorResource, ...]) -> None:
        """Room for ``cap`` rows over the columns ``frs`` (a superset,
        sorted), every row where it was, in arrays of the table's own."""
        n = self.n
        if self._shared:
            self.row_of = dict(self.row_of)
            self._shared = False
        at = [frs.index(fr) for fr in self.frs]
        for name in self.COLUMNS:
            old = getattr(self, name)
            if old.ndim == 1:
                new = np.zeros(cap, dtype=old.dtype)
                if old.dtype == object:
                    new[:] = None
                new[:n] = old[:n]
            else:
                new = np.zeros((cap, len(frs)), dtype=old.dtype)
                new[:n, at] = old[:n]
            setattr(self, name, new)
        if frs != self.frs:
            self.frs = frs
            self.col_of = {fr: i for i, fr in enumerate(frs)}

    def add(self, info: Info, usage: FlavorResourceQuantities) -> None:
        """Append the row of a workload the table does not hold;
        ``usage`` is its ``usage()``."""
        used = used_flavor_resources(info)
        cols = self.col_of
        new = {fr for fr in used if fr not in cols}
        new.update(fr for fr in usage if fr not in cols)
        i = self.n
        if new or self._shared or i == len(self.priority):
            self._resize(
                len(self.priority) * 2 if i == len(self.priority)
                else len(self.priority),
                tuple(sorted(new.union(cols))) if new else self.frs)
            cols = self.col_of
        self.infos[i] = info
        self.uid[i] = info.obj.uid
        self.priority[i] = info.obj.priority
        self.seq[i] = self._next_seq
        self._next_seq += 1
        for fr in used:
            self.uses[i, cols[fr]] = True
        for fr, v in usage.items():
            c = cols[fr]
            self.has[i, c] = True
            self.raw[i, c] = v
        self.row_of[info.key] = i
        self.n = i + 1
        self.tally.built += 1

    def remove(self, key: str) -> None:
        """Drop the row of a workload the table holds: the last row
        moves into its place."""
        if self._shared:
            self._resize(len(self.priority), self.frs)
        i = self.row_of.pop(key)
        last = self.n - 1
        if i != last:
            for name in self.COLUMNS:
                col = getattr(self, name)
                col[i] = col[last]
            self.row_of[self.infos[i].key] = i
        self.infos[last] = None
        self.uid[last] = None
        self.uses[last] = False
        self.has[last] = False
        self.raw[last] = 0
        self.n = last

    def clone(self) -> "CandidateTable":
        """A table of the same rows that shares the ``Info``s and the
        tally for good, and the columns until either side writes: a
        snapshot's clone is read far more often than written, so the
        copy waits for the first ``add`` or ``remove``."""
        t = CandidateTable.__new__(CandidateTable)
        for name in self.__slots__:
            setattr(t, name, getattr(self, name))
        self._shared = t._shared = True
        return t

    def using(self, frs) -> np.ndarray | None:
        """[n] bool, the rows whose workload uses any of ``frs``; None
        where no row can.  A view of the table where one column answers:
        not the caller's to write."""
        cols = [self.col_of[fr] for fr in frs if fr in self.col_of]
        if not cols:
            return None
        if len(cols) == 1:
            return self.uses[:self.n, cols[0]]
        return self.uses[:self.n, cols].any(axis=1)
