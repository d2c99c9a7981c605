"""Persistent packed-plane arena with slab-doubling growth.

The streaming burst pack (ops/stream_pack.py) patches a persistent
copy of the dense ``[C, M]`` packed universe in place instead of
rebuilding it every window.  The arena owns the backing slabs: each
named plane lives in a buffer whose leading (row-ish) dimensions are
rounded up to powers of two, so C and M can grow across structure
generations without reallocating — and, downstream, without changing
the plan shapes the XLA kernel was compiled for more often than the
sticky-``M`` bucketing already does.

Growth policy: when ``ensure`` asks for a shape that exceeds a slab's
capacity along any axis, the slab is reallocated at the next power of
two per overflowing axis (doubling amortizes to O(1) per row ever
stored), the live region is copied over and the new territory is
filled with the plane's pad value.  Shrink never happens — a smaller
request just views a prefix of the slab, so transient peaks don't
cause realloc churn.

A plan owns a copy of the live region, not the slab (the next window
patches the slab in place).  ``snapshot`` makes that copy in a buffer
it keeps and writes over for the next plan once nothing else refers to
the last one: a window of 5 GB in pages the process already holds is
copied and sent to the device at the bus's rate, where pages mapped
fresh every window are faulted in one by one first and transfer at a
rate that changes from window to window.  It also remembers which state
of the slab the kept buffer holds (the caller's token), so a caller
that says where the slab has changed since that state gets the buffer
brought up to date by those cells, not copied whole.

The arena also keeps the occupancy/growth counters surfaced as
``kueue_pack_arena_*`` gauges.
"""

from __future__ import annotations

import sys

import numpy as np


def _cap(n: int) -> int:
    """Slab capacity for a requested extent: next power of two ≥ n
    (min 4, so early growth doesn't realloc every other row)."""
    c = 4
    while c < n:
        c <<= 1
    return c


def _copy_runs(buf: np.ndarray, view: np.ndarray, n: int, W: int,
               where: tuple) -> int:
    """Copy the runs ``where`` of ``view`` into ``buf`` (see
    ``PlaneArena.snapshot``); returns the bytes copied."""
    if view.shape[1] != n:
        np.copyto(buf, view)
        return buf.nbytes
    cut = (view.shape[0], n // W, W) + view.shape[2:]
    # splitting one axis of a strided view is itself a view: no copy
    vals = view.reshape(cut)[where]
    buf.reshape(cut)[where] = vals
    return vals.nbytes


class PlaneArena:
    """Named persistent plane slabs; see module docstring."""

    def __init__(self):
        self._slabs: dict[str, np.ndarray] = {}
        self._fills: dict[str, object] = {}
        self._snaps: dict[str, np.ndarray] = {}
        # the caller's token for the state a kept buffer holds
        self._snap_tokens: dict[str, object] = {}
        self.stats = {"arena_growth_events": 0, "arena_planes": 0,
                      "arena_bytes": 0, "arena_used_bytes": 0,
                      # buffers written over in place, whole or by cells
                      "arena_snapshots_reused": 0,
                      "arena_snapshots_fresh": 0,
                      # every snapshot is one or the other: brought up
                      # to date by the cells that changed, or copied whole
                      "arena_snapshots_delta": 0,
                      "arena_snapshots_whole": 0,
                      # bytes copied, not bytes held
                      "arena_snapshot_bytes": 0}

    def drop(self) -> None:
        """Forget every slab (structure change with new trailing axes)."""
        self._slabs.clear()
        self._fills.clear()
        self._snaps.clear()
        self._snap_tokens.clear()

    def reset(self) -> None:
        """Every slab back to its fill value, whole."""
        for name, slab in self._slabs.items():
            slab[...] = self._fills[name]

    def ensure(self, name: str, shape: tuple, dtype, fill,
               grow_axes: int = 2) -> np.ndarray:
        """Return a ``shape``-sized view of the named slab, growing (or
        creating) the slab as needed.  The first ``grow_axes`` axes get
        power-of-two capacity; trailing axes are exact — a trailing-axis
        or dtype mismatch (new structure with different R/F) drops and
        reallocates the slab.  New territory is filled with ``fill``."""
        shape = tuple(int(s) for s in shape)
        grow_axes = min(grow_axes, len(shape))
        slab = self._slabs.get(name)
        want = tuple(_cap(s) for s in shape[:grow_axes]) + shape[grow_axes:]
        if (slab is None or slab.dtype != np.dtype(dtype)
                or slab.ndim != len(shape)
                or slab.shape[grow_axes:] != shape[grow_axes:]):
            slab = np.full(want, fill, dtype=dtype)
            if name in self._slabs:
                self.stats["arena_growth_events"] += 1
            self._slabs[name] = slab
            self._fills[name] = fill
        elif any(slab.shape[i] < shape[i] for i in range(grow_axes)):
            cap = tuple(max(slab.shape[i], want[i])
                        for i in range(grow_axes)) + shape[grow_axes:]
            grown = np.full(cap, fill, dtype=dtype)
            grown[tuple(slice(0, s) for s in slab.shape)] = slab
            self._slabs[name] = slab = grown
            self.stats["arena_growth_events"] += 1
        return slab[tuple(slice(0, s) for s in shape)]

    def view(self, name: str, shape: tuple) -> np.ndarray:
        return self._slabs[name][tuple(slice(0, int(s)) for s in shape)]

    def snapshot(self, name: str, view: np.ndarray, token=None,
                 prev_token=None, runs=None) -> np.ndarray:
        """A contiguous copy of ``view`` for a plan to own.  The buffer
        of the last snapshot under ``name`` is written over when the
        arena holds the only reference to it, that is when the plan it
        was made for, every view of it and any transfer still reading it
        are gone; a buffer somebody still holds is left to its holder
        and a fresh one takes its place.

        ``token`` names the state of the slab this snapshot holds.  A
        caller that knows where the slab has changed since the state
        ``prev_token`` passes ``runs``, ``(n, W, (i, j))``: axis 1, of
        length ``n``, cut into runs of ``W``, and run ``j[k]`` of row
        ``i[k]`` for every k, which together cover every cell that has
        changed, every trailing axis whole.  Where the kept buffer is
        nobody else's, has the view's shape and dtype and holds the
        state ``prev_token``, only those runs are copied into it; a
        plane whose axis 1 is not the one the runs were laid out for
        (one column that stands for a grid) is small and copied whole
        under the same conditions.  In every other case (no runs, no
        kept buffer, another shape, a buffer still held, a state in
        between that no snapshot was taken of) the copy is whole.  The
        result is the array a whole copy would have given, provided the
        caller's statement holds: a cell that changed outside the runs
        is not picked up.  What the last plan's holder wrote into its
        copy inside the runs is written over like any other cell."""
        buf = self._snaps.pop(name, None)
        held = self._snap_tokens.pop(name, None)
        stats = self.stats
        # two references: ``buf`` and getrefcount's own argument
        if (buf is not None and buf.shape == view.shape
                and buf.dtype == view.dtype and sys.getrefcount(buf) == 2):
            if (runs is not None and prev_token is not None
                    and held == prev_token):
                copied = _copy_runs(buf, view, *runs)
                stats["arena_snapshots_delta"] += 1
            else:
                np.copyto(buf, view)
                copied = buf.nbytes
                stats["arena_snapshots_whole"] += 1
            stats["arena_snapshots_reused"] += 1
        else:
            buf = view.copy()
            copied = buf.nbytes
            stats["arena_snapshots_fresh"] += 1
            stats["arena_snapshots_whole"] += 1
        self._snaps[name] = buf
        self._snap_tokens[name] = token
        stats["arena_snapshot_bytes"] += copied
        return buf

    def refresh_stats(self, used_shapes: dict | None = None) -> dict:
        """Recompute the byte counters; ``used_shapes`` maps plane name
        → live view shape for the occupancy ratio."""
        total = sum(s.nbytes for s in self._slabs.values())
        used = 0
        if used_shapes:
            for name, shp in used_shapes.items():
                slab = self._slabs.get(name)
                if slab is None:
                    continue
                n = slab.dtype.itemsize
                for s in shp:
                    n *= int(s)
                used += n
        self.stats["arena_planes"] = len(self._slabs)
        self.stats["arena_bytes"] = int(total)
        self.stats["arena_used_bytes"] = int(used)
        return self.stats
