"""Streaming delta-pack: patch a persistent packed universe in place.

The full pack (ops/burst.py pack_burst) *reassembles* the whole dense
``[C, M]`` plan: a walk of every CQ, a full concatenate of the per-CQ
row records, three global lexsorts over every row, a fresh grid
allocation + scatter, a ``tolist`` of every key and a rebuilt
``row_of_key`` dict.  All of that is O(total rows) — too much to pay at
every window boundary.

This module, the one incremental pack behind ``pack_burst_cached``,
keeps the packed universe *resident on the host* between
windows (cache/arena.py PlaneArena slabs, slab-doubling growth) and
patches it from the PackJournal.  The first window of a structure, a
forced-full drain (a lost or spurious journal touch, a global input
changed) and a changed ``window`` pack in full (``_init_full``: every
CQ walked, its rows laid out by the delta window's own code from an
empty grid); every other window is a delta pack, at any share of dirty
CQs, and costs what changed:

- **a dirty CQ's pending side** is found afresh (its heap and parking
  lot cut to ``window + 2`` rows): a member whose ``Info`` the old
  record holds keeps its row, and only what can move under an ``Info``
  (parked, ``vec_ok``, the resume slot) is looked at again; its
  **admitted side is kept by row events**: the cache journals which workload
  joined or left a CQ's admitted table (``PackJournal.touch_admitted``),
  the new record is the old one less the rows that went, with the rows
  that came derived from their ``Info`` (``_pack_cq_rows`` with
  ``old``).  A CQ dirty on its admitted side with no key (the cache's
  plain ``touch``), one a row-grade check escalated, and one whose
  heads position moved is walked whole; the table as it stands settles
  every event, and one that disagrees with it sends its CQ to the
  whole walk;
- **the grid takes row-grade updates**: the rows that went are cleared
  where the CQ shrank, the rows that came and the CQ's kept rows from
  the first changed position on are written (``_write_rows``, one
  scatter a plane for the whole window); ``row_of_key`` is patched;
- **row-grade touches** (``PackJournal.touch_row``, deduped
  last-writer-wins by ``drain_into``) patch single cells — the dynamic
  bits a check-state flip can move (``vec_ok``, parked, resume) — with
  verify-and-escalate when anything structural moved;
- **global ranks** (``wl_cycle_rank``, ``wl_uidrank``, ``adm_seq0``)
  are maintained as order-statistic updates over sorted key arrays
  (``_Order``): the entries of the rows that went are found by key and
  dropped, those of the rows that came merge-inserted (vectorized
  ``searchsorted`` + ``insert``), the ``(ci, mi)`` locators of a CQ
  whose rows changed place remapped in one pass, and the dense rank
  planes are rewritten only from the first shifted position onward and
  for the CQs that moved — the ``kueue_pack_rank_patches`` gauge counts
  exactly those rewrites.

``rows_repacked`` counts the rows derived from an ``Info`` (every row in
a full pack; in a delta window the pending rows whose ``Info`` is new
and the admitted rows that came), ``rows_reused`` the rest of the grid's
rows.

The reference sort orders are reproduced bit for bit by encoding each
lexsort key into a fixed-width big-endian byte string (order-preserving
integer/float maps + the ASCII workload key), so one memcmp order
equals the reference ``np.lexsort`` order; non-ASCII or oversized keys
poison the structure (``_StreamBail``), which ``pack_burst_cached``
then packs in full every window.  An order that does not hold an entry
the records say it holds (``_StreamDesync``: a duplicate uid, say)
drops the state, and the window packs in full.

The produced plan is bit-identical to ``pack_burst`` of the same live
state (enforced by tests/test_streaming_pack.py and
tests/test_delta_pack.py); plans carry snapshot *copies* of the live
planes, so consumers (pipeline speculation, the resident scatter,
parity tests) never observe later patches.  A delta plan also says
where it differs from the plan before it: ``dirty_cqs`` (the CQs walked
or patched) and ``row_extent`` (a CQ's rows as they were or as they
are, whichever reach further: every cell a delta window writes lies
under it), which the launch's device-resident copies update from
(ops/burst.py ``_resident_inputs``, ``_resident_rows``).

The host copy is made the same way (``_materialize``,
``PlaneArena.snapshot``): the arena keeps the buffers of the last plan
and the pack token whose state they hold, and a delta window whose
``prev_token`` is that token, with the buffers released and of the
same shapes, copies the runs of ``RESIDENT_RUN`` slots that cover
``[ci, 0:row_extent[ci])`` into them, every trailing axis whole, one
indexed assignment a plane.  The copy is whole whenever that does not
hold: a full pack (no ``row_extent``), a grid whose M grew (another
shape), a window after a delta pack that made no plan (its token is
not the buffers'), a plan somebody still holds (a fresh buffer).
``arena_snapshots_delta`` / ``arena_snapshots_whole`` count the two,
``arena_snapshot_bytes`` the bytes copied.
"""

from __future__ import annotations

import itertools
import time
from typing import NamedTuple, Optional

import numpy as np

from ..cache.arena import PlaneArena
from ..obs.trace import span as _span
from . import burst as _b
from .packing import _ROW_PLANES, _bucket, row_plane_shapes

_KEY_BYTES = 48          # workload key width in the encoded sort keys
_UID_BYTES = 64
_SKEY_DT = np.dtype([("p", ">u8"), ("t", ">u8"), ("o", ">u4"),
                     ("k", f"S{_KEY_BYTES}")])
_SKEY_S = f"S{_SKEY_DT.itemsize}"

# the admitted reservation-time order: memcmp order == (ts, key)
_AKEY_DT = np.dtype([("t", ">u8"), ("k", f"S{_KEY_BYTES}")])
_AKEY_S = f"S{_AKEY_DT.itemsize}"


class _StreamBail(Exception):
    """This structure can't be streamed (non-ASCII / oversized keys):
    poison it, so that every window packs it in full."""


class _StreamDesync(Exception):
    """A maintained order does not hold an entry the records say it
    holds: the state is dropped and this window packs in full."""


def _enc_i64(x: np.ndarray) -> np.ndarray:
    """Order-preserving int64 → uint64 (offset binary)."""
    return x.astype(np.int64).astype(np.uint64) ^ np.uint64(1 << 63)


def _enc_f64(x: np.ndarray) -> np.ndarray:
    """Order-preserving float64 → uint64 (sign-magnitude flip).
    Zeros are canonicalized first: the reference lexsort compares
    -0.0 == 0.0 (tie broken by the next key) and the byte encoding
    must not order them."""
    x = np.asarray(x, dtype=np.float64)
    x = np.where(x == 0.0, 0.0, x)
    b = np.ascontiguousarray(x).view(np.uint64).copy()
    neg = (b >> np.uint64(63)).astype(bool)
    b[neg] = ~b[neg]
    b[~neg] |= np.uint64(1 << 63)
    return b


def _enc_str(arr: np.ndarray, width: int) -> np.ndarray:
    """ASCII-encode a unicode array into fixed-width bytes whose memcmp
    order equals the unicode code-point order; bail when a value can't
    be represented."""
    a = np.asarray(arr, dtype=np.str_)
    if a.size and int(np.char.str_len(a).max(initial=0)) > width:
        raise _StreamBail(f"key longer than {width} bytes")
    try:
        out = np.char.encode(a.astype(f"U{width}"), "ascii")
    except UnicodeEncodeError as e:
        raise _StreamBail("non-ascii key") from e
    return out.astype(f"S{width}")


def _crank_skey(prio, ts, pos, kbytes) -> np.ndarray:
    """Encoded key for the global cycle-order rank: memcmp order ==
    ``np.lexsort((key, pos, ts, -prio))`` order."""
    n = len(kbytes)
    out = np.empty(n, dtype=_SKEY_DT)
    out["p"] = _enc_i64(-np.asarray(prio, dtype=np.int64))
    out["t"] = _enc_f64(ts)
    out["o"] = np.asarray(pos, dtype=np.uint32)
    out["k"] = kbytes
    return out.view(_SKEY_S).reshape(n)


def _adm_skey(res_ts, kbytes) -> np.ndarray:
    """Encoded key for the admitted reservation-time order: memcmp
    order == (reservation ts, key); the key only tells entries of one
    timestamp apart, so that one can be found and dropped."""
    n = len(kbytes)
    out = np.empty(n, dtype=_AKEY_DT)
    out["t"] = _enc_f64(res_ts)
    out["k"] = kbytes
    return out.view(_AKEY_S).reshape(n)


class _Order:
    """A maintained sorted total order: encoded sort keys plus the
    parallel (ci, mi) grid locators of each entry, and any further
    parallel columns in ``aux``."""
    __slots__ = ("skey", "ci", "mi", "aux")

    def __init__(self, dtype, **aux):
        self.skey = np.empty(0, dtype=dtype)
        self.ci = np.empty(0, dtype=np.int32)
        self.mi = np.empty(0, dtype=np.int32)
        self.aux = aux

    def update(self, drop, moved, new) -> Optional[int]:
        """Row-grade update, in three steps.  ``drop`` = (skey, ci, mi)
        of the entries that leave: each is found by its key and must
        sit at the locator given.  ``moved`` = (mask [C], offset [C],
        table): the entries of the CQs in ``mask`` get their ``mi``
        remapped in place through ``table[offset[ci] + mi]``.  ``new``
        = (skey, ci, mi, aux) is merge-inserted.  Returns the first
        final position whose dense rank may have changed (None = the
        ranks stand)."""
        first = None
        dsk, dci, dmi = drop
        n = len(self.skey)
        if len(dsk):
            pos = np.searchsorted(self.skey, dsk)
            at = np.minimum(pos, max(n - 1, 0))
            if (n == 0 or (self.skey[at] != dsk).any()
                    or (self.ci[at] != dci).any()
                    or (self.mi[at] != dmi).any()):
                raise _StreamDesync("dropped entry not in the order")
            keep = np.ones(n, dtype=bool)
            keep[pos] = False
            first = int(pos.min())
            self.skey = self.skey[keep]
            self.ci = self.ci[keep]
            self.mi = self.mi[keep]
            self.aux = {name: col[keep] for name, col in self.aux.items()}
        mask, offset, table = moved
        if len(table) and len(self.ci):
            sel = np.nonzero(mask[self.ci])[0]
            if len(sel):
                to = table[offset[self.ci[sel]] + self.mi[sel]]
                if (to < 0).any():
                    raise _StreamDesync("a kept entry points at a row "
                                        "that went")
                self.mi[sel] = to
        nskey, nci, nmi, naux = new
        if len(nskey):
            srt = np.argsort(nskey, kind="stable")
            nskey = nskey[srt]
            pos = np.searchsorted(self.skey, nskey)
            fi = int(pos[0])
            first = fi if first is None else min(first, fi)
            self.skey = np.insert(self.skey, pos, nskey)
            self.ci = np.insert(self.ci, pos, np.asarray(nci, np.int32)[srt])
            self.mi = np.insert(self.mi, pos, np.asarray(nmi, np.int32)[srt])
            self.aux = {name: np.insert(col, pos, naux[name][srt])
                        for name, col in self.aux.items()}
        return first

    def rewrite_from(self, first, mask) -> np.ndarray:
        """Positions whose rank cell has to be written again: from
        ``first`` on, and every entry of a CQ whose rows moved."""
        todo = mask[self.ci]
        if first is not None:
            todo[first:] = True
        return np.nonzero(todo)[0]


def _crank_values(state, todo: np.ndarray) -> np.ndarray:
    """``wl_cycle_rank`` at the positions ``todo`` of the cycle order:
    the position itself, or under fair sharing the first position of
    its (priority, timestamp) (``burst.tie_starts``)."""
    if not state.fair:
        return todo.astype(np.int32)
    sk = state.crank.skey.view(_SKEY_DT)
    return _b.tie_starts(sk["p"], sk["t"])[todo].astype(np.int32)


class StreamState:
    """Persistent streaming pack state, valid for one (structure
    generation, resource scale, CQ set, window) key.  ``token`` is a
    process-wide monotone serial: plans record the tokens they
    consumed/produced so a shard-resident device copy can prove it
    chains from the same state (object identity is not enough — ids
    alias after GC)."""
    __slots__ = ("key", "records", "token", "arena", "fair",
                 "crank", "uord", "aord", "seq_base",
                 "mi_of", "sk_of",
                 "n_rows_cq", "n_pend_cq", "maxabs_prio_cq", "bad_cq",
                 "strict_cq", "pos_cq", "cq_names_list",
                 "n_comp_cq", "comp_max_cq",
                 "row_of_key", "keys_grid", "M")

    _next_token = itertools.count(1)

    def __init__(self, key, arena):
        self.key = key
        self.arena = arena
        self.token = next(StreamState._next_token)


def _views(arena: PlaneArena, C: int, M: int, st) -> dict:
    out = {}
    for name, shape in row_plane_shapes(st, C, M).items():
        pad, dt = _ROW_PLANES[name][:2]
        out[name] = arena.ensure(name, shape, dt, pad)
    out["u_cq0"] = arena.ensure("u_cq0", (C, st.n_frs), np.int32, 0,
                                grow_axes=1)
    out["keys_grid"] = arena.ensure("keys_grid", (C, M), object, None)
    return out


def _slab(view: np.ndarray) -> np.ndarray:
    while view.base is not None:
        view = view.base
    return view


def _row_slabs(views: dict) -> list:
    """(slab, pad) of every plane that holds a value a row: what a
    CQ's cells past its last row are reset in, in the slabs, so that
    later M growth exposes pads."""
    out = [(_slab(views[name]), pad)
           for name, (pad, _, _, _) in _ROW_PLANES.items()
           if name != "death0" and not (
               # every flavor plain: the one column stays 0
               name == "wl_flavor_skip" and views[name].shape[1] == 1)]
    out.append((_slab(views["keys_grid"]), None))
    return out


def _write_rows(state: "StreamState", views: dict, batch: list) -> None:
    """Scatter records' rows into the grid planes (the per-row half;
    global rank planes are patched separately), one write a plane for
    the whole batch.  ``batch`` holds (ci, record, grid positions,
    rows): every row of the record (rows None) or, of a record built
    from the last window's, the rows named, the others lying in the
    grid as they are.  The one writer of a streaming plan's rows, in a
    full build (``_init_full``) as in a delta window."""
    ci_l, mi_l, part = [], [], {a: [] for a in (
        "req", "prio", "ok", "skip", "parked", "adm", "resume", "usage",
        "uses", "keys")}
    for ci, rec, mi, sel in batch:
        views["u_cq0"][ci] = rec.u_row
        if sel is not None:
            mi = mi[sel]
        if not len(mi):
            continue
        ci_l.append(np.full(len(mi), ci, np.int32))
        mi_l.append(mi)
        for attr, acc in part.items():
            col = getattr(rec, attr)
            acc.append(col if sel is None else col[sel])
    if not ci_l:
        return
    ci = np.concatenate(ci_l)
    mi = np.concatenate(mi_l)
    col = {attr: np.concatenate(acc) for attr, acc in part.items()}
    views["wl_req"][ci, mi] = col["req"]
    views["wl_rank"][ci, mi] = mi
    views["wl_prio"][ci, mi] = np.clip(col["prio"], -_b.I32_MAX, _b.I32_MAX)
    views["vec_ok"][ci, mi] = col["ok"]
    if views["wl_flavor_skip"].shape[1] > 1:   # else: a column of 0s
        views["wl_flavor_skip"][ci, mi] = col["skip"]
    views["parked0"][ci, mi] = col["parked"]
    views["elig0"][ci, mi] = ~col["parked"] & ~col["adm"]
    views["resume0"][ci, mi] = col["resume"]
    views["adm0"][ci, mi] = col["adm"]
    # a cell's reservation rank is its last tenant's until the admitted
    # order writes the row's own
    views["adm_seq0"][ci, mi] = 0
    views["adm_usage0"][ci, mi] = col["usage"]
    views["adm_uses0"][ci, mi] = col["uses"]
    keys = col["keys"].tolist()
    views["keys_grid"][ci, mi] = np.array(keys, dtype=object)
    state.row_of_key.update(zip(keys, zip(ci.tolist(), mi.tolist())))


def _cq_mi(rec) -> np.ndarray:
    """Per-CQ heap rank — the ci-segment of the reference global
    ``lexsort((key, ts, -prio, ci))`` (total order via the unique key
    tiebreak, so the segmented and per-CQ sorts agree exactly)."""
    mi = np.empty(rec.n_rows, dtype=np.int32)
    mi[np.lexsort((rec.keys, rec.ts, -rec.prio))] = \
        np.arange(rec.n_rows, dtype=np.int32)
    return mi


_ESCALATE = object()


def _row_patch_job(state, st, queues, cache, scheduler, ci, key):
    """Re-derive one row's dynamic bits (parked / resume / vec_ok) from
    the live queue + cache state.  Returns None (nothing moved), a
    ``(ci, idx, parked, resume, ok)`` patch, or ``_ESCALATE`` when the
    change is beyond row grade (membership, identity, admission)."""
    from ..api.types import AdmissionCheckState
    from .solver import resume_starts
    rec = state.records[ci]
    idx = rec.find(key)
    if idx is None:
        # benign absences: below a window-truncation cutoff, or an
        # aggregate-compressed admitted row (its only row-grade bit,
        # vec_ok, never reaches the kernel — no candidates are drawn
        # from a compressible forest).  Membership changes always come
        # through hard journal touches, which dirty the CQ before row
        # jobs run, so an unknown key here can't be a new workload; a
        # workload left out as ``bad`` may have stopped being so.
        if key in rec.bad_keys:
            return _ESCALATE
        return None if (rec.truncated or rec.n_comp) else _ESCALATE
    cq_name = st.cq_names[ci]
    q = queues.queue_for(cq_name)
    cq_live = cache.cluster_queue(cq_name)
    if cq_live is None:
        return _ESCALATE
    covers_pods = cq_name in st.cq_covers_pods
    cq_ok = st.cq_window_ok
    cq_vec = bool(cq_ok[ci]) if cq_ok is not None else False
    if cq_vec and cq_live.spec.namespace_selector:
        cq_vec = False
    if idx >= rec.n_pend:
        # admitted row: only the vec_ok gate can move at row grade; a
        # row's Info is the admitted table's, which an event keeps
        info = cq_live.workloads.get(key)
        if info is None:
            return _ESCALATE
        obj = info.obj
        from ..api.types import WL_EVICTED, WL_QUOTA_RESERVED
        if (obj.condition_true(WL_EVICTED)
                or obj.conditions.get(WL_QUOTA_RESERVED) is None):
            return _ESCALATE
        row = getattr(info, "_burst_row", None)
        if row is None or row[0] != st.generation:
            return _ESCALATE
        ok = cq_vec and row[3]
        if ok:
            lr = scheduler.limit_range_summaries
            if lr and lr.get(obj.namespace):
                ok = False
            elif obj.admission_check_states and any(
                    s.state in (AdmissionCheckState.RETRY,
                                AdmissionCheckState.REJECTED)
                    for s in obj.admission_check_states.values()):
                ok = False
        if ok == bool(rec.ok[idx]):
            return None
        return (ci, idx, bool(rec.parked[idx]),
                tuple(rec.resume[idx].tolist()), ok)
    if q is None or not q.active:
        return _ESCALATE
    parked_now = False
    info = q.heap.get(key)
    if info is None:
        info = q.inadmissible.get(key)
        if info is None:
            return _ESCALATE
        rs = info.obj.requeue_state
        if rs is not None and rs.requeue_at is not None:
            return _ESCALATE   # backoff-parked: membership changed
        parked_now = True
    if rec.infos[idx] is not info:
        return _ESCALATE
    row = getattr(info, "_burst_row", None)
    if row is None or row[0] != st.generation:
        return _ESCALATE
    obj = info.obj
    ok = cq_vec and row[3]
    if ok:
        lr = scheduler.limit_range_summaries
        if lr and lr.get(obj.namespace):
            ok = False
        elif key in cache.assumed_workloads or obj.admission is not None:
            ok = False
        elif obj.admission_check_states and any(
                s.state in (AdmissionCheckState.RETRY,
                            AdmissionCheckState.REJECTED)
                for s in obj.admission_check_states.values()):
            ok = False
    resume_now = resume_starts(info, cq_live, covers_pods,
                               st.n_groups, st.pod_sets)
    if (parked_now == bool(rec.parked[idx])
            and resume_now == tuple(rec.resume[idx].tolist())
            and ok == bool(rec.ok[idx])):
        return None
    return (ci, idx, parked_now, resume_now, ok)


def _bump(stats, key, n=1):
    if stats is not None:
        stats[key] = stats.get(key, 0) + n


def agg_summary(state, comp_cq) -> dict:
    """The pack's row census for the driver's stats block and the
    ``kueue_agg_*`` metrics."""
    return {
        "agg_rows_compressed": int(state.n_comp_cq.sum()),
        "agg_rows_packed": int(state.n_rows_cq.sum()),
        "agg_heads": int(state.n_pend_cq.sum()),
        "agg_cqs_compressible": int(np.count_nonzero(comp_cq)),
    }


def _materialize(st, state, s, views, scheduler, dirty_cis, prev_token,
                 rank_patches, stats, row_extent=None):
    """Build the BurstPlan snapshot from the patched arena state.

    The plan owns a copy of every row plane and of the keys' grid
    (``PlaneArena.snapshot``).  A delta window passes the token of the
    state before it and its ``row_extent``: where the buffer the last
    plan left holds that state and nobody holds the buffer, the copy is
    the cells ``[ci, 0:row_extent[ci])`` laid out as runs, since every
    cell the window wrote lies under them; a full pack, a grid that
    grew, a window after one that made no plan and a plan still held
    get the whole copy by the arena's own conditions.  The one thing a
    plan's holder writes into its copy is a finish into ``death0``
    (``Driver._fill_burst_finishes``), in a row of that plan, so under
    the next window's ``rows_before`` and its ``row_extent``: the runs
    write the arena's constant plane back over it."""
    M = state.M
    n = int(state.n_rows_cq.sum())
    # the rows of preempting forests are the budget rows
    bm = ~s.comp_cq
    n_budget = int(state.n_rows_cq[bm].sum())
    arena = state.arena
    with _span("burst.pack.grid.snapshot"):
        # the runs the launch's device mirror is updated by
        # (BurstSolver._send_runs), laid out once for every plane
        runs = None
        if row_extent is not None:
            W = min(_b.RESIDENT_RUN, M)
            at, _ = _b._row_runs(row_extent, W,
                                 int((-(-row_extent // W)).sum()))
            runs = (M, W, (at[:, 0], at[:, 1] // W))
        planes = {name: arena.snapshot(name, views[name], state.token,
                                       prev_token, runs)
                  for name in _ROW_PLANES}
        keys_grid = arena.snapshot("keys_grid", views["keys_grid"],
                                   state.token, prev_token, runs)
    planes["u_cq0"] = views["u_cq0"].copy()
    # seq_base and the newest packed reservation from the maintained
    # admitted order
    adm_ts = state.aord.aux["ts"]
    plan = _b._burst_plan(
        st, s, scheduler, planes, _KeysView(keys_grid), state.row_of_key,
        bad_cq=state.bad_cq, strict_cq=state.strict_cq.copy(),
        budget_rows=n_budget,
        budget_prio=int(state.maxabs_prio_cq[bm].max(initial=0)),
        grid_rows=n, seq_base=state.seq_base,
        newest_res_ts=float(adm_ts[-1]) if len(adm_ts) else None,
        comp_max_ts=state.comp_max_cq)
    plan.pack_token = state.token
    plan.prev_token = prev_token
    plan.row_extent = row_extent
    if dirty_cis is not None:
        plan.dirty_cqs = np.asarray(sorted(dirty_cis), dtype=np.int64)
        from ..utils.journal import PackJournal
        plan.dirty_ranges = PackJournal.coalesce(sorted(dirty_cis))
    if stats is not None:
        stats["pack_rank_patches"] = (
            stats.get("pack_rank_patches", 0) + int(rank_patches))
        state.arena.refresh_stats({name: a.shape
                                   for name, a in planes.items()})
        stats.update({("pack_" + k): v
                      for k, v in state.arena.stats.items()})
        stats.update(agg_summary(state, s.comp_cq))
        stats["head_pack_budget_rows"] = n_budget
        stats["head_pack_exempt_rows"] = n - n_budget
    return plan


class _KeysView:
    """Lazy ``plan.keys``: an object grid supporting the consumers'
    ``plan.keys[ci][mi]`` indexing without materializing C×M Python
    lists every window.  Equality compares against list-of-lists (the
    full pack's plan shape) for the parity tests."""
    __slots__ = ("_g",)

    def __init__(self, grid):
        self._g = grid

    def __getitem__(self, ci):
        return self._g[ci]

    def __len__(self):
        return len(self._g)

    def __iter__(self):
        return iter(self._g)

    def tolist(self):
        return self._g.tolist()

    def __eq__(self, other):
        if isinstance(other, _KeysView):
            return self._g.tolist() == other._g.tolist()
        if isinstance(other, list):
            return self._g.tolist() == other
        return NotImplemented

    def __ne__(self, other):
        eq = self.__eq__(other)
        return eq if eq is NotImplemented else not eq


def _reseq(state, views, moved_cq) -> int:
    """Dense reservation-time ranks over the admitted order (entries of
    one timestamp share a rank); writes the cells whose rank changed and
    those of the CQs in ``moved_cq``, whose rows changed place.  Returns
    the cells written."""
    aord = state.aord
    ts = aord.aux["ts"]
    if not len(ts):
        state.seq_base = 2
        return 0
    first = np.ones(len(ts), dtype=bool)
    first[1:] = ts[1:] != ts[:-1]
    seq = np.cumsum(first, dtype=np.int32)
    state.seq_base = int(seq[-1]) + 2
    todo = np.nonzero((seq != aord.aux["seq"]) | moved_cq[aord.ci])[0]
    aord.aux["seq"] = seq
    if len(todo):
        views["adm_seq0"][aord.ci[todo], aord.mi[todo]] = seq[todo]
    return len(todo)


def _init_full(st, queues, cache, scheduler, key, min_m, window, arena,
               stats, t0):
    """Full streaming (re)build: walk every CQ, reset the arena, then
    lay the rows out as a delta window does, from an empty grid:
    ``_place_rows`` places every record's rows and ``_patch_grid``
    writes them and merges them into the three orders."""
    if _b._unknown_active_cq(st, queues):
        return None, None, False
    with _span("burst.pack.walk"):
        records = _b._walk_records(st, queues, cache, scheduler, window)
    if records is None:
        return None, None, False
    with _span("burst.pack.grid"):
        C = len(st.cq_names)
        s = _b._pack_statics(st, cache)
        state = StreamState(key, arena)
        state.cq_names_list = list(queues.cluster_queue_names())
        pos_of = {name: i for i, name in enumerate(state.cq_names_list)}
        state.pos_cq = np.fromiter(
            (pos_of.get(nm, C) for nm in st.cq_names), np.int32, C)
        for rec in records:
            rec.pos = int(state.pos_cq[rec.ci])
        # the empty grid: no record, no row, every order empty
        state.records = [None] * C
        state.mi_of = dict.fromkeys(range(C), np.empty(0, np.int32))
        state.sk_of = dict.fromkeys(range(C), np.empty(0, _SKEY_S))
        state.row_of_key = {}
        for name, dt in (("n_rows_cq", np.int64), ("n_pend_cq", np.int64),
                         ("n_comp_cq", np.int64),
                         ("maxabs_prio_cq", np.int64),
                         ("comp_max_cq", np.float64),
                         ("bad_cq", bool), ("strict_cq", bool)):
            setattr(state, name, np.zeros(C, dt))
        state.fair = bool(getattr(scheduler, "fair_sharing", False))
        state.crank = _Order(_SKEY_S)
        state.uord = _Order(f"S{_UID_BYTES}")
        state.aord = _Order(_AKEY_S, ts=np.empty(0, np.float64),
                            seq=np.empty(0, np.int32))
        placed = _place_rows(state, records)   # a bail writes nothing
        arena.reset()
        views, _, repacked = _patch_grid(st, state, s, arena, placed, (),
                                         min_m)

        _bump(stats, "burst_full_packs")
        _bump(stats, "stream_full_packs")
        _bump(stats, "rows_repacked", repacked)
        if int(state.n_pend_cq.sum()) == 0:
            _note_ms(stats, t0)
            return None, state, False
        plan = _materialize(st, state, s, views, scheduler, None,
                            None, 0, stats)
        _note_ms(stats, t0)
        return plan, state, False


def _note_ms(stats, t0, delta=False):
    if stats is not None:
        dt = time.perf_counter() - t0
        stats["stream_pack_s"] = stats.get("stream_pack_s", 0.0) + dt
        stats["pack_last_ms"] = dt * 1e3
        if delta:
            # time spent on incremental (non-full) packs
            stats["delta_pack_s"] = stats.get("delta_pack_s", 0.0) + dt


def _walk_dirty(state, st, ci, whole, events, walk_args):
    """Stage A for one dirty CQ: its new record, built on the old one
    unless ``whole``."""
    queues, cache, scheduler, assumed, scale_of, window, comp_cq = walk_args
    old = None if whole else state.records[ci]
    old_idx = {}
    if old is not None and events:
        # a key's row in the old record, by way of its grid position
        old_mi = state.mi_of[ci]
        at = np.empty(len(old_mi), dtype=np.int32)
        at[old_mi] = np.arange(len(old_mi), dtype=np.int32)
        row_of = state.row_of_key
        for key in events:
            loc = row_of.get(key)
            if loc is not None and loc[0] == ci:
                old_idx[key] = int(at[loc[1]])
    rec = _b._pack_cq_rows(st, ci, int(state.pos_cq[ci]), queues, cache,
                           scheduler, assumed, scale_of, window,
                           compress=bool(comp_cq[ci]),
                           old=old, events=events, old_idx=old_idx)
    return None if rec is _b._PACK_FAIL else rec


class _Placed(NamedTuple):
    """Where one walked CQ's rows go (``_place_rows``)."""
    ci: int
    rec: object
    sk: np.ndarray        # crank key a row of the record
    ub_came: np.ndarray   # uid key a row derived
    mi: np.ndarray        # grid position a row of the record
    gone: np.ndarray      # the old record's rows that went
    came: np.ndarray      # the record's rows derived this window
    to: Optional[np.ndarray]   # old grid position -> new, -1 = went;
    #                            None: no kept row changed place


def _place_rows(state, recs) -> list:
    """Where the walked records' rows go.  The rows derived this window
    (every row of a record walked whole) get their order keys in one
    encoding for all the CQs; a record built on the old one then keeps
    its kept rows' relative places: they close up over the rows that
    went and open up for the rows that came, each found its place among
    them by its key in the CQ's (-prio, ts, key) order, which is the
    crank order of one CQ.  Returns a ``_Placed`` a CQ."""
    cis = np.fromiter((r.ci for r in recs), np.int32, len(recs))
    came_of = []
    for rec in recs:
        if rec.kept_idx is None:
            came_of.append(np.arange(rec.n_rows, dtype=np.int64))
        else:
            came_of.append(np.concatenate((
                np.arange(rec.kept_at, dtype=np.int64),
                np.arange(rec.kept_at + len(rec.kept_idx), rec.n_rows,
                          dtype=np.int64))))
    counts = np.fromiter((len(c) for c in came_of), np.int64, len(recs))
    ends = np.cumsum(counts)

    def derived(attr, dtype):
        parts = [getattr(r, attr)[c] for r, c in zip(recs, came_of)
                 if len(c)]
        return np.concatenate(parts) if parts else np.empty(0, dtype)

    ub_all = _enc_str(derived("uids", "U1"), _UID_BYTES)
    sk_all = _crank_skey(
        derived("prio", np.int64), derived("ts", np.float64),
        np.repeat(state.pos_cq[cis], counts),
        _enc_str(derived("keys", "U1"), _KEY_BYTES))
    out = []
    for rec, came, end in zip(recs, came_of, ends.tolist()):
        ci = rec.ci
        sk_came = sk_all[end - len(came):end]
        ub_came = ub_all[end - len(came):end]
        old_mi = state.mi_of[ci]
        n_old, n = len(old_mi), rec.n_rows
        kept, rec.kept_idx = rec.kept_idx, None
        if kept is None:
            out.append(_Placed(ci, rec, sk_came, ub_came, _cq_mi(rec),
                               np.arange(n_old, dtype=np.int64), came,
                               None))
            continue
        P, K = rec.kept_at, len(kept)
        if K == n_old == n:
            # every row kept and none derived (``came`` is empty, and
            # so is what went): the rows lie where they lay
            out.append(_Placed(ci, rec, state.sk_of[ci][kept], ub_came,
                               old_mi[kept], gone=came, came=came, to=None))
            continue
        gone = np.ones(n_old, dtype=bool)
        gone[kept] = False
        gone = np.nonzero(gone)[0]
        mi_kept = old_mi[kept]
        if len(gone):
            mi_kept = mi_kept - np.searchsorted(
                np.sort(old_mi[gone]), mi_kept).astype(np.int32)
        sk = np.empty(n, dtype=_SKEY_S)
        mi = np.empty(n, dtype=np.int32)
        sk[P:P + K] = state.sk_of[ci][kept]
        if len(came):
            sk[came] = sk_came
            srt = np.argsort(sk_came, kind="stable")
            in_order = np.empty(K, dtype=_SKEY_S)
            in_order[mi_kept] = sk[P:P + K]
            at = np.searchsorted(in_order, sk_came[srt]).astype(np.int32)
            mi[came[srt]] = at + np.arange(len(came), dtype=np.int32)
            mi_kept = mi_kept + np.searchsorted(
                at, mi_kept, side="right").astype(np.int32)
        mi[P:P + K] = mi_kept
        to = np.full(n_old, -1, dtype=np.int32)
        to[old_mi[kept]] = mi_kept
        out.append(_Placed(ci, rec, sk, ub_came, mi, gone, came, to))
    return out


def _patch_grid(st, state, statics, arena, placed, pos_dirty_cis, min_m):
    """Stage B of a delta window, and of a full build from an empty
    grid, row grade: the rows that went leave the grid, ``row_of_key``
    and the three maintained orders; the rows that came are written and
    merge-inserted; a CQ's kept rows that changed place are written
    again at their new cells (``_write_rows``, the one writer of a
    window's rows) and their locators in the orders are remapped in
    place.  Returns (the grid's views, rank cells
    rewritten, rows derived)."""
    C = len(st.cq_names)
    for p in placed:
        ci, rec = p.ci, p.rec
        state.n_rows_cq[ci] = rec.n_rows
        state.n_pend_cq[ci] = rec.n_pend
        state.bad_cq[ci] = rec.bad
        state.strict_cq[ci] = rec.strict
        state.n_comp_cq[ci] = rec.n_comp
        state.comp_max_cq[ci] = rec.comp_max_ts
        state.maxabs_prio_cq[ci] = int(np.abs(rec.prio).max(initial=0))
    rows_per_cq = int(state.n_rows_cq.max(initial=0))
    state.M = M = max(_bucket(rows_per_cq, minimum=4), min_m)
    views = _views(arena, C, M, st)
    slabs = _row_slabs(views)
    row_of = state.row_of_key

    # what leaves and what joins each order, and the CQs whose kept
    # rows changed place with the map of their old positions to the new
    moved = np.zeros(C, dtype=bool)
    offset = np.zeros(C, dtype=np.int64)
    tables, n_table = [], 0
    gone_cols = {c: [] for c in ("ci", "mi", "sk", "uids", "adm", "ts")}
    came_cols = {c: [] for c in ("ci", "mi", "sk", "ub", "adm", "ts")}
    batch = []
    repacked = 0
    for ci, rec, sk, ub_came, mi, gone, came, to in placed:
        old = state.records[ci]
        old_mi = state.mi_of[ci]
        n_old = len(old_mi)
        first = n_old    # the first cell of the CQ that changes
        if len(gone):
            mi_gone = old_mi[gone]
            first = int(mi_gone.min())
            for c, col in (("ci", np.full(len(gone), ci, np.int32)),
                           ("mi", mi_gone), ("sk", state.sk_of[ci][gone]),
                           ("uids", old.uids[gone]), ("adm", old.adm[gone]),
                           ("ts", old.res_ts[gone])):
                gone_cols[c].append(col)
            for k in old.keys[gone].tolist():
                row_of.pop(k, None)
        if len(came):
            mi_came = mi[came]
            first = min(first, int(mi_came.min()))
            for c, col in (("ci", np.full(len(came), ci, np.int32)),
                           ("mi", mi_came),
                           ("sk", sk if to is None else sk[came]),
                           ("ub", ub_came), ("adm", rec.adm[came]),
                           ("ts", rec.res_ts[came])):
                came_cols[c].append(col)
            repacked += len(came)
        if to is not None and first < n_old:
            moved[ci] = True
            offset[ci] = n_table
            tables.append(to)
            n_table += n_old
        if rec.n_rows < n_old:
            # the cells of a CQ that shrank, past its last row: every
            # other cell from ``first`` on is written over below ...
            for slab, pad in slabs:
                slab[ci, rec.n_rows:n_old] = pad
        # ... and a pending row kept may have moved under its Info
        batch.append((ci, rec, mi, None if first == 0 else np.nonzero(
            (mi >= first) | ~rec.adm)[0]))
    # the writes follow every CQ's removals: a key that left one CQ for
    # another keeps the locator of the row it has now
    _write_rows(state, views, batch)
    for p in placed:
        state.records[p.ci] = p.rec
        state.mi_of[p.ci] = p.mi
        state.sk_of[p.ci] = p.sk
    walked_cis = {p.ci for p in placed}

    def cat(cols, c, dtype):
        return (np.concatenate(cols[c]) if cols[c]
                else np.empty(0, dtype=dtype))

    g = {c: cat(gone_cols, c, d) for c, d in (
        ("ci", np.int32), ("mi", np.int32), ("sk", _SKEY_S), ("uids", "U1"),
        ("adm", bool), ("ts", np.float64))}
    j = {c: cat(came_cols, c, d) for c, d in (
        ("ci", np.int32), ("mi", np.int32), ("sk", _SKEY_S),
        ("ub", f"S{_UID_BYTES}"), ("adm", bool), ("ts", np.float64))}
    table = (np.concatenate(tables) if tables
             else np.empty(0, dtype=np.int32))
    remap = (moved, offset, table)

    # cycle-order rank; a CQ whose heads position moved takes new keys
    drop = [g["sk"], g["ci"], g["mi"]]
    join = [j["sk"], j["ci"], j["mi"]]
    for ci in pos_dirty_cis:
        n = state.records[ci].n_rows
        if ci in walked_cis or not n:
            continue
        where = (np.full(n, ci, np.int32), state.mi_of[ci])
        sk = state.sk_of[ci]
        drop = [np.concatenate(p) for p in zip(drop, (sk, *where))]
        sk = sk.copy()
        sk.view(_SKEY_DT)["o"] = state.pos_cq[ci]
        state.sk_of[ci] = sk
        join = [np.concatenate(p) for p in zip(join, (sk, *where))]
    rank_patches = 0
    first = state.crank.update(tuple(drop), remap, (*join, {}))
    todo = state.crank.rewrite_from(first, moved)
    if len(todo):
        views["wl_cycle_rank"][state.crank.ci[todo], state.crank.mi[todo]] \
            = _crank_values(state, todo)
        rank_patches += len(todo)

    # uid rank: over the budget rows alone (``_b.compressible_cqs``)
    in_uord = ~statics.comp_cq
    gu, ju = in_uord[g["ci"]], in_uord[j["ci"]]
    first = state.uord.update(
        (_enc_str(g["uids"][gu], _UID_BYTES), g["ci"][gu], g["mi"][gu]),
        remap, (j["ub"][ju], j["ci"][ju], j["mi"][ju], {}))
    todo = state.uord.rewrite_from(first, moved)
    if len(todo):
        views["wl_uidrank"][state.uord.ci[todo], state.uord.mi[todo]] = \
            todo.astype(np.int32)
        rank_patches += len(todo)

    # admitted reservation-seq: ranks shared by equal timestamps
    ga, ja = g["adm"], j["adm"]
    state.aord.update(
        (_adm_skey(g["ts"][ga], g["sk"][ga].view(_SKEY_DT)["k"]),
         g["ci"][ga], g["mi"][ga]),
        remap,
        (_adm_skey(j["ts"][ja], j["sk"][ja].view(_SKEY_DT)["k"]),
         j["ci"][ja], j["mi"][ja],
         {"ts": j["ts"][ja],
          "seq": np.full(int(ja.sum()), -1, np.int32)}))
    rank_patches += _reseq(state, views, moved)
    return views, rank_patches, repacked


def pack_burst_streaming(structure, queues, cache, scheduler, clock,
                         state=None, min_m: int = 0, window: int = 0,
                         stats=None):
    """Streaming counterpart of ``pack_burst_cached``; same return
    contract ``(plan, state, was_delta)``, bit-identical plans."""
    st = structure
    t0 = time.perf_counter()
    def state_key():
        return (st.generation, st.resource_scale.tobytes(),
                tuple(st.cq_names), window, st.pod_sets)
    key = state_key()
    # hard dirt by journal: the queue manager's is the pending side's;
    # the cache's, where it names no workload, is a whole queue's
    dirty: set = set()
    whole: set = set()
    soft: dict = {}
    rows: dict = {}
    events: dict = {}
    jranges: list = []
    force_full = False
    with _span("burst.pack.drain"):
        for j, into in ((getattr(queues, "pack_journal", None), dirty),
                        (getattr(cache, "pack_journal", None), whole)):
            if j is None:
                force_full = True
            else:
                force_full |= j.drain_into(
                    into, soft, row_of=st.cq_index, ranges_out=jranges,
                    rows_out=rows, admitted_out=events)
        dirty |= whole
        dirty.update(events)
    arena = getattr(cache, "_pack_arena", None)
    if arena is None:
        arena = cache._pack_arena = PlaneArena()

    try:
        if (not isinstance(state, StreamState) or state.key != key
                or force_full):
            return _init_full(st, queues, cache, scheduler, key, min_m,
                              window, arena, stats, t0)

        with _span("burst.pack.drain"):
            index_of = st.cq_index
            C = len(st.cq_names)
            for name in set(dirty) | set(soft) | set(rows.values()):
                if name not in index_of:
                    q = queues.queue_for(name)
                    if q is not None and q.active and q.pending_active():
                        return None, None, False
            for name, skeys in soft.items():
                ci = index_of.get(name)
                if ci is None or name in dirty:
                    continue
                if not _b._roundtrips_clean(
                        state.records[ci], queues.queue_for(name),
                        cache.cluster_queue(name), skeys,
                        name in st.cq_covers_pods, st):
                    dirty.add(name)   # the pending side's own facts
            row_jobs = []
            rows_verified = 0
            for wkey, name in rows.items():
                ci = index_of.get(name)
                if ci is None or name in whole:
                    continue
                if name in dirty:
                    # the pending side is walked anyway; an admitted
                    # row is derived again from the table as it stands
                    events.setdefault(name, {}).setdefault(wkey, None)
                    continue
                job = _row_patch_job(state, st, queues, cache, scheduler,
                                     ci, wkey)
                if job is _ESCALATE:
                    dirty.add(name)
                    whole.add(name)
                elif job is not None:
                    row_jobs.append(job)
                else:
                    rows_verified += 1
            if rows_verified:
                _bump(stats, "pack_rows_verified", rows_verified)

        with _span("burst.pack.walk"):
            # heads-enumeration position drift (CQs joined/left the queue
            # manager without a structure change): the crank sort keys of
            # every row of a moved CQ change, nothing else does
            pos_dirty_cis: list = []
            names_now = queues.cluster_queue_names()
            if state.cq_names_list != names_now:
                pos_of = {nm: i for i, nm in enumerate(names_now)}
                newpos = np.fromiter(
                    (pos_of.get(nm, C) for nm in st.cq_names), np.int32, C)
                for ci in np.nonzero(newpos != state.pos_cq)[0]:
                    ci = int(ci)
                    pos_dirty_cis.append(ci)
                    state.records[ci].pos = int(newpos[ci])
                state.pos_cq = newpos
                state.cq_names_list = list(names_now)

            # stage A over the dirty CQs only; encode before mutating so a
            # bail leaves the state coherent
            scale_of = {r: int(st.resource_scale[i])
                        for i, r in enumerate(st.resource_names)}
            statics = _b._pack_statics(st, cache)
            walk_args = (queues, cache, scheduler, cache.assumed_workloads,
                         scale_of, window, statics.comp_cq)
            # a CQ whose rows all take a new crank key is walked whole
            whole_cis = {index_of[name] for name in whole
                         if name in index_of} | set(pos_dirty_cis)

            def _walk_one(ci):
                return _walk_dirty(state, st, ci, ci in whole_cis,
                                   events.get(st.cq_names[ci], {}),
                                   walk_args)

            cis = sorted(ci for name in dirty
                         if (ci := index_of.get(name)) is not None)
            # stage A is per-CQ pure (each walk reads shared structure and
            # writes only its own CQ's rows/memos), so the host pool fans
            # the dirty walk out by cohort forest; the gather is in
            # ascending (forest, ci) order, and every downstream merge is
            # order-insensitive (sorted-order updates, disjoint row writes),
            # so pooled and serial walks build identical states
            pool = getattr(cache, "host_pool", None)
            if pool is not None and pool.active and len(cis) >= 2:
                fcq = statics.forest_of_cq
                parts = pool.map_partitions(
                    cis, lambda ci: int(fcq[ci]),
                    lambda g, part: [_walk_one(ci) for ci in part])
                walked = [w for part in parts for w in part]
            else:
                walked = [_walk_one(ci) for ci in cis]
            if any(rec is None for rec in walked):
                return None, None, False
            if state_key() != key:
                # a row that came has more PodSets than the planes hold:
                # the grid is laid out again at the structure's new
                # extent (PackedStructure.note_pod_sets)
                return _init_full(st, queues, cache, scheduler,
                                  state_key(), min_m, window, arena,
                                  stats, t0)
            placed = _place_rows(state, walked)

        with _span("burst.pack.grid"):
            # every cell this window writes lies in a CQ's rows as they
            # were or as they come to be: the rows that came, went or
            # changed place, the pads of a CQ that shrank, the rank
            # cells, the row-grade patches
            rows_before = state.n_rows_cq.copy()
            with _span("burst.pack.grid.patch"):
                views, rank_patches, repacked = _patch_grid(
                    st, state, statics, arena, placed, pos_dirty_cis,
                    min_m)
                row_extent = np.maximum(rows_before, state.n_rows_cq)

                # row-grade patches (deduped by the journal): single
                # cells.  A job queued before a later row escalated its
                # CQ to dirty is stale — the re-walk rebuilt the record
                # (and row order), so its idx no longer addresses the
                # row it was derived from.
                wset_cis = {rec.ci for rec in walked}
                row_jobs = [j for j in row_jobs if j[0] not in wset_cis]
                for ci, idx, parked_now, resume_now, ok_now in row_jobs:
                    rec = state.records[ci]
                    mi = int(state.mi_of[ci][idx])
                    rec.parked[idx] = parked_now
                    rec.resume[idx] = resume_now
                    rec.ok[idx] = ok_now
                    views["parked0"][ci, mi] = parked_now
                    views["elig0"][ci, mi] = (not parked_now
                                              and not bool(rec.adm[idx]))
                    views["resume0"][ci, mi] = resume_now
                    views["vec_ok"][ci, mi] = ok_now
            _bump(stats, "pack_row_patches", len(row_jobs))

            prev_token = state.token
            state.token = next(StreamState._next_token)
            _bump(stats, "burst_delta_packs")
            _bump(stats, "stream_packs")
            _bump(stats, "rows_repacked", repacked)
            _bump(stats, "rows_reused",
                  int(state.n_rows_cq.sum()) - repacked)
            _bump(stats, "burst_journal_dirty_ranges", len(jranges))

            if int(state.n_pend_cq.sum()) == 0:
                _note_ms(stats, t0)
                return None, state, False
            dirty_cis = wset_cis | {j[0] for j in row_jobs}
            plan = _materialize(st, state, statics, views, scheduler,
                                dirty_cis, prev_token, rank_patches, stats,
                                row_extent)
            _note_ms(stats, t0, delta=True)
            return plan, state, True
    except _StreamDesync:
        _bump(stats, "stream_pack_desyncs")
        return _init_full(st, queues, cache, scheduler, key, min_m,
                          window, arena, stats, t0)
    except _StreamBail:
        st._stream_poison = True
        _bump(stats, "stream_pack_bails")
        return _b.pack_burst_cached(
            structure, queues, cache, scheduler, clock, state=None,
            min_m=min_m, window=window, stats=stats)
