"""Journals: the between-window pack journal and the write-ahead cycle log.

``PackJournal`` is the in-memory mutation journal feeding the
incremental burst pack.  The queue manager and the cache each own one;
their mutators mark the ClusterQueues whose packed rows may have
changed.  The burst pack (ops/burst.py pack_burst_cached) drains both
journals at every window boundary and re-walks only what changed of
the dirty CQs, reusing the persistent per-CQ row records for everything
else.

Three dirt grades keep the hot path clean:

- ``touch``: the CQ's row set or row facts changed (arrival, deletion,
  park/unpark, admission accounting) — the CQ must be re-walked: its
  pending side where the queue manager's journal says so, the whole CQ
  where the cache's does.
- ``touch_admitted``: one workload joined or left the CQ's admitted
  table (the cache's add, assume, forget and delete).  The CQ is dirty
  as under ``touch``, and the channel also keeps which key, and whether
  it came or went: the streaming pack re-walks the pending side and
  drops or derives the rows named, keeping the CQ's other admitted
  rows.  ``drain_into`` hands the channel to the consumer that asks for
  it (``admitted_out``) and gives every other the queue-grade mark.
- ``note_roundtrip``: a head was popped and requeued straight back
  (every scheduled head, every cycle).  The row set is unchanged; only
  per-row dynamic facts (the flavor-resume bit, the parked bit) could
  have moved, so the pack verifies those in O(1) per key instead of
  re-walking the CQ.

``touch_all`` covers global inputs the journal doesn't model per-CQ
(e.g. LimitRange summaries).  A fresh journal starts dirty-all so the
first pack is always a full walk.

The journal also feeds a second, independent consumer: the cache's
incremental snapshot builder reads its own ``snap_dirty``/``snap_all``
channel via ``drain_snapshot`` so the burst pack's destructive
``drain_into`` and the snapshot's per-cycle drain never race for the
same dirt.

``CycleWAL`` is the durable sibling: a write-ahead log of the driver's
per-cycle decision batches (admits, evictions, requeue-state updates,
finishes).  Every op is journaled *before* the store mutation it
describes, and a commit mark closes each cycle's batch, so a crash at
any point leaves at most one partially-applied batch — the uncommitted
tail.  Recovery rolls the tail forward over the surviving workload
store (``replay_tail``, idempotent, using the journaled timestamps so
the replayed status is bit-identical), then ``Driver.restore_workload``
rebuilds cache and queues from the rolled-forward store.

The on-disk format is one JSON object per line::

    {"wal": "op", "op": "admit", "key": ..., ...}
    {"wal": "commit", "batch": 0, "n": 3}

``CycleWAL(path=...)`` appends per line and *group-commits*: the file
buffer is flushed (and optionally fsynced) every ``commit_every``-th
``commit()`` instead of per line, so a 1M-decision window pays
O(decisions / commit_every) syscalls.  ``KUEUE_TPU_WAL_COMMIT_EVERY``
sets the default interval (1 = the durable-per-cycle seed behaviour).
With an interval of N, a crash can lose at most the last N-1 *committed*
batches plus the open tail — recovery then observes a consistent,
slightly older prefix, exactly as if the crash had happened N-1 cycles
earlier.  When a chaos injector is installed the WAL falls back to
per-line flushing regardless of the interval, because the crash-parity
harness reasons about single-op boundaries.

``CycleWAL.compact()`` folds all committed batches into one checkpoint
record and rewrites the file as checkpoint + uncommitted tail
(atomically, via ``os.replace``), so recovery never re-reads a
1M-decision history: replay only ever needed the tail, and the
checkpoint preserves batch numbering (``folded_batches``).
``CycleWAL.load(path)`` rebuilds batches and tail from the file.

``IngestJournal`` is the serving-side third journal: accepted
submissions (serving/service.py) are journaled durably before their
ack, apply markers record cycle-boundary drains, and shed markers
record backpressure drops — together with the CycleWAL tail this is
what makes SIGKILL+restart lose zero accepted submissions and
duplicate zero admissions.
"""

from __future__ import annotations

import json
import os
import zlib
from typing import Optional

from ..chaos import injector as _chaos
from ..features import env_int


class PackJournal:
    __slots__ = ("dirty", "dirty_all", "soft", "rows", "admitted",
                 "tainted", "snap_dirty", "snap_all")

    def __init__(self):
        self.dirty: set[str] = set()
        self.soft: dict[str, set[str]] = {}
        # Row-grade dirt: workload key -> owning CQ, last-writer-wins.
        # Multiple touches of the same key inside one cycle collapse to
        # a single row patch (dict assignment is the dedupe).  Consumers
        # that don't understand row grade escalate each entry to its CQ
        # in drain_into.
        self.rows: dict[str, str] = {}
        # Admitted-side row events: CQ -> {workload key: came (True) or
        # went (False)}, the last event of a key standing for it.  The
        # queue is dirty as under ``touch``, and the consumer that asks
        # for this channel also learns which rows of its admitted table
        # to drop and which to derive; one that does not gets the
        # queue-grade mark in drain_into.
        self.admitted: dict[str, dict[str, bool]] = {}
        self.dirty_all = True
        # chaos: a simulated lost update (journal.drop_touch) taints the
        # journal; the next drain reports dirty-all so the pack falls
        # back to a full walk instead of trusting incomplete dirt
        self.tainted = False
        # Second consumer channel: the incremental snapshot builder
        # (cache.Cache.snapshot).  The burst pack's drain_into is
        # destructive, so the snapshot keeps its own dirt accumulator,
        # fed by the same mutators and drained independently.  A lost
        # update (drop_touch) poisons this channel immediately — unlike
        # ``tainted`` it cannot wait for the next burst drain, because
        # the two consumers drain at different times.
        self.snap_dirty: set[str] = set()
        self.snap_all = True

    def touch(self, cq_name: str) -> None:
        if _chaos.ACTIVE is not None:
            if _chaos.ACTIVE.hit("journal.drop_touch") is not None:
                self.tainted = True
                self.snap_all = True
                return
            if _chaos.ACTIVE.hit("journal.spurious_dirty_all") is not None:
                self.dirty_all = True
                self.snap_all = True
        self.dirty.add(cq_name)
        self.snap_dirty.add(cq_name)

    def touch_all(self) -> None:
        self.dirty_all = True
        self.snap_all = True

    def drain_snapshot(self) -> tuple[set, bool]:
        """Drain the snapshot consumer's channel: returns
        ``(dirty_cq_names, was_all)`` and resets only this channel —
        the burst pack's ``dirty``/``soft``/``dirty_all`` state is
        untouched, and vice versa for :meth:`drain_into`."""
        was_all = self.snap_all
        out = self.snap_dirty
        self.snap_dirty = set()
        self.snap_all = False
        return out, was_all

    def note_roundtrip(self, cq_name: str, key: str) -> None:
        s = self.soft.get(cq_name)
        if s is None:
            s = self.soft[cq_name] = set()
        s.add(key)

    def touch_row(self, cq_name: str, key: str) -> None:
        """Row-grade dirt: exactly one workload's row facts changed and
        the CQ's aggregates/membership did not.  Cheaper than
        :meth:`touch` for the streaming patcher (one row re-walked
        instead of the whole CQ); duplicate touches of the same key
        coalesce last-writer-wins."""
        if _chaos.ACTIVE is not None:
            if _chaos.ACTIVE.hit("journal.drop_touch") is not None:
                self.tainted = True
                self.snap_all = True
                return
            if _chaos.ACTIVE.hit("journal.spurious_dirty_all") is not None:
                self.dirty_all = True
                self.snap_all = True
        self.rows[key] = cq_name
        self.snap_dirty.add(cq_name)

    def touch_admitted(self, cq_name: str, key: str, came: bool) -> None:
        """One workload joined (``came``) or left the CQ's admitted
        table: a hard touch of the queue that also names the row, so
        the streaming pack keeps the queue's other admitted rows as
        they are."""
        if _chaos.ACTIVE is not None:
            if _chaos.ACTIVE.hit("journal.drop_touch") is not None:
                self.tainted = True
                self.snap_all = True
                return
            if _chaos.ACTIVE.hit("journal.spurious_dirty_all") is not None:
                self.dirty_all = True
                self.snap_all = True
        events = self.admitted.get(cq_name)
        if events is None:
            events = self.admitted[cq_name] = {}
        events[key] = came
        self.snap_dirty.add(cq_name)

    def drain_into(self, dirty: set, soft: dict, row_of: dict = None,
                   ranges_out: list = None, rows_out: dict = None,
                   admitted_out: dict = None) -> bool:
        """Merge this journal's content into the caller's accumulators
        and reset it; returns the dirty-all flag that was set.  Soft
        roundtrip keys for CQs in the hard dirty set are dropped — those
        CQs are re-walked anyway, so their keys would only bloat the
        O(1) verify set.

        ``row_of`` maps CQ name → packed row index; when given together
        with ``ranges_out``, the drained hard-dirty rows are coalesced
        into ``[lo, hi)`` ranges (see :meth:`coalesce`) and appended, so
        the scatter that pushes the dirty rows back to the device can
        issue one transfer per contiguous run instead of one per row.

        ``rows_out`` receives the deduped row-grade channel
        (``{workload key: cq name}``, last-writer-wins) minus keys whose
        CQ is hard-dirty (the re-walk covers them).  Callers that don't
        pass it get the legacy escalation: each row touch dirties its
        CQ, so consumers unaware of row grade stay correct.

        ``admitted_out`` receives the admitted-side row events
        (``{cq name: {workload key: came}}``) of the CQs that are not
        hard-dirty by a touch without a key, here or in ``dirty``; such
        a CQ is dirty for its pending side and for the rows named, and
        is not added to ``dirty``.  A row-grade touch of a CQ that is
        hard-dirty joins them as an event that says neither (None): the
        hard touch may be of the pending side alone.  Callers that don't
        pass it get the queue-grade mark: each event's CQ joins
        ``dirty``."""
        was_all = self.dirty_all or self.tainted
        if self.admitted:
            if admitted_out is None:
                self.dirty.update(self.admitted)
            else:
                for cq, events in self.admitted.items():
                    if cq in self.dirty or cq in dirty:
                        continue
                    acc = admitted_out.get(cq)
                    if acc is None:
                        admitted_out[cq] = events
                    else:
                        acc.update(events)
        if self.rows:
            if rows_out is None:
                # legacy consumer: escalate row dirt to CQ dirt
                self.dirty.update(self.rows.values())
            else:
                for key, cq in self.rows.items():
                    if cq not in self.dirty and cq not in dirty:
                        rows_out[key] = cq
                    elif admitted_out is not None:
                        # the hard touch may be of the pending side
                        # alone: the row is looked at again
                        admitted_out.setdefault(cq, {}).setdefault(key, None)
        if row_of is not None and ranges_out is not None and (
                self.dirty or self.admitted):
            rows = sorted(row_of[n] for n in self.dirty | set(self.admitted)
                          if n in row_of)
            ranges_out.extend(self.coalesce(rows))
        dirty |= self.dirty
        for name, keys in self.soft.items():
            if name in dirty:
                continue
            acc = soft.get(name)
            if acc is None:
                soft[name] = set(keys)
            else:
                acc |= keys
        for name in dirty:
            soft.pop(name, None)
        if rows_out is not None:
            for key in [k for k, cq in rows_out.items() if cq in dirty]:
                cq = rows_out.pop(key)
                if admitted_out is not None:
                    admitted_out.setdefault(cq, {}).setdefault(key, None)
        self.dirty.clear()
        self.soft.clear()
        self.rows.clear()
        self.admitted = {}
        self.dirty_all = False
        self.tainted = False
        return was_all

    @staticmethod
    def coalesce(rows) -> list:
        """Coalesce sorted row indices into ``[lo, hi)`` ranges.

        Adjacent dirty rows are the common case (cohort members pack
        consecutively), and the device update for a contiguous run is a
        single slice transfer — N singleton scatters would each pay a
        dispatch.  Duplicate indices collapse into their range."""
        out: list[tuple[int, int]] = []
        lo = hi = None
        for r in rows:
            r = int(r)
            if hi is not None and r <= hi:
                hi = max(hi, r + 1)
                continue
            if lo is not None:
                out.append((lo, hi))
            lo, hi = r, r + 1
        if lo is not None:
            out.append((lo, hi))
        return out


# ---------------------------------------------------------------------------
# Write-ahead cycle journal
# ---------------------------------------------------------------------------

class CycleWAL:
    """Write-ahead journal of admission-cycle decision batches.

    ``log(op)`` opens a batch implicitly; ``commit()`` closes it.  The
    driver logs each op just before applying it to the store, and
    commits at cycle boundaries, so the uncommitted ``tail`` is exactly
    the set of decisions a crash may have half-applied.

    Group commit: ``commit_every=N`` flushes the OS file buffer (and
    fsyncs when ``fsync=True``) only every Nth commit, amortising the
    syscall over N cycles.  N=1 (the default, overridable via
    ``KUEUE_TPU_WAL_COMMIT_EVERY``) keeps the seed's flush-per-line
    durability.  Chaos runs always flush per line — the crash-parity
    harness reasons about single-op boundaries.

    ``compact_every=B`` (0 = never) auto-compacts after every B
    committed batches; see :meth:`compact`."""

    def __init__(self, path: Optional[str] = None,
                 commit_every: Optional[int] = None,
                 fsync: bool = False,
                 compact_every: int = 0):
        self.path = path
        self._fh = open(path, "a", encoding="utf-8") if path else None
        self.batches: list[list[dict]] = []   # committed batches
        self._open: Optional[list[dict]] = None
        if commit_every is None:
            commit_every = env_int("KUEUE_TPU_WAL_COMMIT_EVERY")
        self.commit_every = max(1, commit_every)
        self.fsync = fsync
        self.compact_every = max(0, compact_every)
        self._commits_since_flush = 0
        # batches folded away by compaction (keeps batch ids monotonic
        # across a compact; surfaced in the checkpoint record)
        self.folded_batches = 0
        self.folded_ops = 0
        self.stats = {"wal_appends": 0, "wal_commits": 0,
                      "wal_flushes": 0, "wal_fsyncs": 0,
                      "wal_compactions": 0}

    # -- writing --

    def register_appender(self, name) -> None:
        """No-op; duck-compat with ShardedCycleWAL's appender census."""

    def unregister_appender(self, name) -> None:
        """No-op; duck-compat with ShardedCycleWAL's appender census."""

    def log(self, op: dict) -> None:
        from ..obs.trace import span as _span
        # counted leaf: per-op appends are ~2µs, a retained record
        # would cost more than the op — histogram-only timing
        with _span("wal.append", counted=True):
            if self._open is None:
                self._open = []
            self._open.append(op)
            self._emit(dict(op, wal="op"))

    def commit(self) -> None:
        if self._open is None:
            return
        from ..obs.trace import span as _span
        with _span("wal.commit"):
            self._emit({"wal": "commit",
                        "batch": self.folded_batches + len(self.batches),
                        "n": len(self._open)})
            self.batches.append(self._open)
            self._open = None
            self.stats["wal_commits"] += 1
            self._commits_since_flush += 1
            if self._commits_since_flush >= self.commit_every:
                self._flush()
            if self.compact_every and len(self.batches) >= self.compact_every:
                self.compact()

    def _emit(self, rec: dict) -> None:
        if self._fh is None:
            return
        self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
        self.stats["wal_appends"] += 1
        # chaos crash tests cut the process between arbitrary ops: every
        # line must be on disk the instant it is journaled, so group
        # commit is disabled while an injector is installed
        if self.commit_every == 1 or _chaos.ACTIVE is not None:
            self._fh.flush()

    def _flush(self) -> None:
        if self._fh is None:
            return
        self._fh.flush()
        self.stats["wal_flushes"] += 1
        if self.fsync:
            os.fsync(self._fh.fileno())
            self.stats["wal_fsyncs"] += 1
        self._commits_since_flush = 0

    def compact(self) -> int:
        """Fold all committed batches into a checkpoint record and
        atomically rewrite the file as checkpoint + uncommitted tail.

        Recovery only ever replays the tail (committed batches are, by
        definition, fully applied to the store), so dropping their ops
        from the file changes nothing about replay — it just stops a
        long-lived journal growing without bound and makes ``load`` of
        a 1M-decision history O(tail).  Returns the number of batches
        folded by this call."""
        if self._fh is None or self.path is None:
            # in-memory WAL: just fold the batch list
            n = len(self.batches)
            self.folded_batches += n
            self.folded_ops += sum(len(b) for b in self.batches)
            self.batches = []
            return n
        from ..obs.trace import span as _span
        with _span("wal.compact"):
            n = len(self.batches)
            self.folded_batches += n
            self.folded_ops += sum(len(b) for b in self.batches)
            self.batches = []
            tmp = self.path + ".compact"
            with open(tmp, "w", encoding="utf-8") as out:
                out.write(json.dumps(
                    {"wal": "checkpoint",
                     "folded_batches": self.folded_batches,
                     "folded_ops": self.folded_ops}, sort_keys=True) + "\n")
                for op in (self._open or ()):
                    out.write(json.dumps(dict(op, wal="op"),
                                         sort_keys=True) + "\n")
                out.flush()
                os.fsync(out.fileno())
            self._fh.flush()
            self._fh.close()
            self._fh = None   # a crash below must leave close() safe
            if _chaos.ACTIVE is not None:
                # crash here leaves the old journal intact plus a stray
                # .compact temp file: recovery reads the uncompacted log
                _chaos.ACTIVE.crashpoint("wal.compact")
            os.replace(tmp, self.path)
            self._fh = open(self.path, "a", encoding="utf-8")
            self._commits_since_flush = 0
            self.stats["wal_compactions"] += 1
            return n

    def close(self) -> None:
        if self._fh is not None:
            self._fh.flush()
            self._fh.close()
            self._fh = None

    # -- reading --

    @property
    def tail(self) -> list[dict]:
        """Ops journaled since the last commit (possibly half-applied)."""
        return list(self._open or ())

    @classmethod
    def resume(cls, path: str) -> "CycleWAL":
        """Crash recovery for a process that keeps running: rebuild
        batches and tail from disk *and* reopen the file for appending.

        The loaded ``_open`` tail is carried over, so after the caller
        replays it (``replay_tail``) a plain ``commit()`` writes only
        the commit marker — the tail's ops are already on disk — and
        the journal continues exactly where the killed process left it.
        ``commit_every`` falls back to the registry default, as in
        ``__init__``."""
        wal = cls.load(path)
        wal._fh = open(path, "a", encoding="utf-8")
        return wal

    @classmethod
    def load(cls, path: str) -> "CycleWAL":
        """Rebuild a WAL from its JSON-lines file (the recovery read
        path).  The returned WAL is read-only-ish: it has no file handle
        so replay tooling can't accidentally extend the original log."""
        wal = cls()
        wal.path = path
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                kind = rec.get("wal")
                if kind == "commit":
                    wal.batches.append(wal._open or [])
                    wal._open = None
                elif kind == "checkpoint":
                    # a compaction boundary: the folded batches are
                    # fully applied history, only their count survives
                    wal.folded_batches = rec.get("folded_batches", 0)
                    wal.folded_ops = rec.get("folded_ops", 0)
                else:
                    rec.pop("wal", None)
                    if wal._open is None:
                        wal._open = []
                    wal._open.append(rec)
        return wal

    # -- replay --

    def replay_tail(self, store: dict) -> int:
        """Roll the uncommitted tail forward over ``store`` (a
        ``{key: Workload}`` dict).  Idempotent: ops whose effect is
        already visible are skipped, so replay after a crash anywhere
        between journal write and store write converges to the same
        state as the uncrashed apply.  Returns the op count replayed."""
        n = 0
        for op in self.tail:
            if replay_op(store, op):
                n += 1
        return n

    def replay_history(self, store: dict) -> int:
        """Roll *committed* batches forward over ``store``, in order.

        The normal recovery path never needs this — committed batches
        are by definition fully applied to the durable store.  The
        distributed children invert that: their durable store is the
        ingest/manifest journal of *initial* payloads, and the WAL is
        the only record of every decision since, so recovery is
        initial-state + full history + tail.  Refuses a compacted
        journal (the folded ops are gone); dist children therefore run
        with compaction off."""
        if self.folded_batches:
            raise RuntimeError(
                f"replay_history on a compacted WAL ({self.folded_batches} "
                f"batches folded away): full history is gone")
        n = 0
        for batch in self.batches:
            for op in batch:
                if replay_op(store, op):
                    n += 1
        return n


class ShardedCycleWAL:
    """CycleWAL striped across K journal segments.

    At high admission rates the single-file group commit serializes:
    every cycle's ops funnel through one ``write``+``flush`` stream and
    one fsync cadence.  This variant routes each op to one of K
    ``CycleWAL`` segments by a *stable* hash of its workload key (CQ
    shard affinity: one workload's ops always land in one segment), so
    appends and group-commit flushes stripe across K files while a
    process-global monotone ``seq`` stamped into every op preserves the
    total order.  ``tail``/``replay_tail`` merge the per-segment tails
    back into seq order, so recovery converges to the same state as the
    unsharded journal byte for byte (crash-parity test-enforced at
    every ``wal.*`` chaos site).

    Duck-compatible with ``CycleWAL`` (``log``/``commit``/``tail``/
    ``replay_tail``/``compact``/``close``/``stats``/``path``) —
    ``Driver.attach_wal`` and ``recover_from`` take either.  Segment
    files live at ``{path}.s00 .. .s{K-1:02d}``; ``load_cycle_wal``
    autodetects them.  ``wal.shard_merge`` is the chaos crashpoint
    between per-segment compactions: a crash there leaves segments at
    mixed compaction generations, which the merged replay must absorb.

    Striping only pays when appenders are actually concurrent; with a
    single writer it spreads one stream across K buffered files and
    *loses* (0.84x commit wall in SCALE_r18.json).  Appenders therefore
    announce themselves via ``register_appender``/``unregister_appender``
    (the host worker pool does this), and with <=1 registered the router
    collapses every op to segment 0 — single-stream locality — while the
    seq stamp keeps the merged replay identical either way.
    """

    def __init__(self, path: Optional[str] = None, shards: int = 2,
                 commit_every: Optional[int] = None,
                 fsync: bool = False, compact_every: int = 0):
        self.path = path
        self.shards = max(2, int(shards))
        self._shards = [
            CycleWAL(self.shard_path(path, i) if path else None,
                     commit_every=commit_every, fsync=fsync,
                     compact_every=compact_every)
            for i in range(self.shards)]
        self._seq = 0
        self._appenders: set = set()

    @staticmethod
    def shard_path(path: str, i: int) -> str:
        return f"{path}.s{i:02d}"

    def register_appender(self, name) -> None:
        """Announce a concurrent appender; striping engages at >=2."""
        self._appenders.add(name)

    def unregister_appender(self, name) -> None:
        self._appenders.discard(name)

    def _route(self, op: dict) -> int:
        if len(self._appenders) <= 1:
            return 0   # single writer: keep one hot stream (no stripe tax)
        key = op.get("key") or (op.get("keys") or ("",))[0]
        return zlib.crc32(key.encode("utf-8", "replace")) % self.shards

    # -- writing --

    def log(self, op: dict) -> None:
        # stamp seq in place: CycleWAL.log stores the caller's dict by
        # reference anyway (ownership passes to the journal), and the
        # per-op copy was most of the single-appender stripe tax the
        # r19 collapse is meant to remove; the route branch is inlined
        # because a single hot stream takes it 100% of the time
        op["seq"] = self._seq
        self._seq += 1
        if len(self._appenders) <= 1:
            self._shards[0].log(op)   # single writer: one hot stream
        else:
            self._shards[self._route(op)].log(op)

    def commit(self) -> None:
        for sh in self._shards:
            sh.commit()   # no-op for segments with no open batch

    def compact(self) -> int:
        n = 0
        for i, sh in enumerate(self._shards):
            n += sh.compact()
            if i == 0 and _chaos.ACTIVE is not None:
                # crash between segment compactions: segments now sit
                # at mixed generations; the seq-merged replay converges
                _chaos.ACTIVE.crashpoint("wal.shard_merge")
        return n

    def close(self) -> None:
        for sh in self._shards:
            sh.close()

    # -- reading --

    @property
    def tail(self) -> list[dict]:
        """Union of segment tails, merged back into total (seq) order."""
        ops = [op for sh in self._shards for op in sh.tail]
        ops.sort(key=lambda op: op.get("seq", 0))
        return ops

    @property
    def stats(self) -> dict:
        out = {"wal_appends": 0, "wal_commits": 0, "wal_flushes": 0,
               "wal_fsyncs": 0, "wal_compactions": 0}
        appends = []
        for sh in self._shards:
            appends.append(sh.stats["wal_appends"])
            for k in out:
                out[k] += sh.stats[k]
        out["wal_shards"] = self.shards
        out["wal_shard_skew"] = max(appends) - min(appends)
        out["wal_appenders"] = len(self._appenders)
        return out

    @classmethod
    def load(cls, path: str) -> "ShardedCycleWAL":
        """Rebuild from segment files (the recovery read path); like
        ``CycleWAL.load`` the result carries no file handles."""
        wal = cls.__new__(cls)
        wal.path = path
        wal._shards = []
        wal._appenders = set()
        i = 0
        while os.path.exists(cls.shard_path(path, i)):
            wal._shards.append(CycleWAL.load(cls.shard_path(path, i)))
            i += 1
        wal.shards = len(wal._shards)
        wal._seq = 1 + max(
            (op.get("seq", -1) for sh in wal._shards
             for b in (sh.batches + [sh.tail]) for op in b),
            default=-1)
        return wal

    # -- replay --

    def replay_tail(self, store: dict) -> int:
        n = 0
        for op in self.tail:
            if replay_op(store, op):
                n += 1
        return n


def make_cycle_wal(path: Optional[str] = None,
                   commit_every: Optional[int] = None,
                   fsync: bool = False, compact_every: int = 0,
                   shards: Optional[int] = None):
    """WAL factory honoring ``KUEUE_TPU_WAL_SHARDS`` (1 = the classic
    single-file CycleWAL; >1 = the striped variant)."""
    if shards is None:
        shards = env_int("KUEUE_TPU_WAL_SHARDS")
    if shards <= 1:
        return CycleWAL(path, commit_every=commit_every, fsync=fsync,
                        compact_every=compact_every)
    return ShardedCycleWAL(path, shards=shards,
                           commit_every=commit_every, fsync=fsync,
                           compact_every=compact_every)


def load_cycle_wal(path: str):
    """Recovery read path for either WAL layout: segment files beside
    ``path`` mean it was sharded."""
    if os.path.exists(ShardedCycleWAL.shard_path(path, 0)):
        return ShardedCycleWAL.load(path)
    return CycleWAL.load(path)


# -- op encode/decode -------------------------------------------------------

def _encode_condition(c) -> dict:
    return {"type": c.type, "status": c.status.value, "reason": c.reason,
            "message": c.message, "ltt": c.last_transition_time,
            "gen": c.observed_generation}


def _encode_admission(adm) -> dict:
    return {"cluster_queue": adm.cluster_queue,
            "psa": [{"name": a.name, "flavors": dict(a.flavors),
                     "usage": dict(a.resource_usage), "count": a.count}
                    for a in adm.pod_set_assignments]}


def admit_op(wl) -> dict:
    """The SSA-shaped admit record: the workload's full post-decision
    status (admission, conditions, check states, requeue state).  Pure
    data — replay replaces the stored status wholesale, which makes the
    op trivially idempotent."""
    return {
        "op": "admit",
        "key": wl.key,
        "admission": _encode_admission(wl.admission),
        "conditions": [_encode_condition(c)
                       for c in wl.conditions.values()],
        "checks": [{"name": s.name, "state": s.state.value,
                    "message": s.message, "ltt": s.last_transition_time}
                   for s in wl.admission_check_states.values()],
        "requeue": (None if wl.requeue_state is None else
                    {"count": wl.requeue_state.count,
                     "at": wl.requeue_state.requeue_at}),
    }


def evict_op(key: str, reason: str, message: str,
             preempted_reason: Optional[str], now: float) -> dict:
    return {"op": "evict", "key": key, "reason": reason,
            "message": message, "pre": preempted_reason, "now": now}


def requeue_op(key: str, count: int, requeue_at: Optional[float]) -> dict:
    return {"op": "requeue", "key": key, "count": count, "at": requeue_at}


def finish_op(keys: list[str], message: str, now: float) -> dict:
    return {"op": "finish", "keys": list(keys), "message": message,
            "now": now}


def deactivate_op(key: str) -> dict:
    return {"op": "deactivate", "key": key}


def replay_op(store: dict, op: dict) -> bool:
    """Apply one journaled op to the plain workload store.  Pure status
    mutation — no cache or queue side effects; ``restore_workload``
    rebuilds those from the rolled-forward store afterwards.  Returns
    False when the op was already applied (or its workload is gone)."""
    from ..api.types import (Admission, AdmissionCheckState,
                             AdmissionCheckStatus, Condition,
                             ConditionStatus, PodSetAssignment,
                             RequeueState, WL_EVICTED)
    from ..workload import (set_evicted_condition, set_finished_condition,
                            set_pods_ready_condition,
                            set_preempted_condition, set_requeued_condition,
                            unset_quota_reservation)
    kind = op.get("op")
    if kind == "finish":
        any_done = False
        for key in op["keys"]:
            wl = store.get(key)
            if wl is None or wl.is_finished:
                continue
            set_finished_condition(wl, "JobFinished", op["message"],
                                   op["now"])
            any_done = True
        return any_done
    wl = store.get(op.get("key", ""))
    if wl is None:
        return False
    if kind == "admit":
        if wl.is_finished:
            return False
        enc = op["admission"]
        wl.admission = Admission(
            cluster_queue=enc["cluster_queue"],
            pod_set_assignments=[
                PodSetAssignment(name=a["name"], flavors=dict(a["flavors"]),
                                 resource_usage=dict(a["usage"]),
                                 count=a["count"])
                for a in enc["psa"]])
        wl.conditions = {
            c["type"]: Condition(type=c["type"],
                                 status=ConditionStatus(c["status"]),
                                 reason=c["reason"], message=c["message"],
                                 last_transition_time=c["ltt"],
                                 observed_generation=c["gen"])
            for c in op["conditions"]}
        wl.admission_check_states = {
            s["name"]: AdmissionCheckStatus(
                name=s["name"], state=AdmissionCheckState(s["state"]),
                message=s["message"], last_transition_time=s["ltt"])
            for s in op["checks"]}
        rq = op.get("requeue")
        wl.requeue_state = (None if rq is None else
                            RequeueState(count=rq["count"],
                                         requeue_at=rq["at"]))
        return True
    if kind == "evict":
        ev = wl.conditions.get(WL_EVICTED)
        if (ev is not None and ev.status == ConditionStatus.TRUE
                and ev.reason == op["reason"]
                and ev.last_transition_time == op["now"]):
            return False   # the mutation landed before the crash
        now = op["now"]
        set_evicted_condition(wl, op["reason"], op["message"], now)
        from ..api.types import WL_PODS_READY
        if WL_PODS_READY in wl.conditions:
            set_pods_ready_condition(wl, False, now)
        if op.get("pre") is not None:
            set_preempted_condition(wl, op["pre"], op["message"], now)
        for st in wl.admission_check_states.values():
            st.state = AdmissionCheckState.PENDING
        if wl.admission is not None:
            unset_quota_reservation(wl, op["reason"], op["message"], now)
        set_requeued_condition(wl, op["reason"], op["message"], True, now)
        return True
    if kind == "requeue":
        rs = wl.requeue_state
        if rs is not None and rs.count >= op["count"]:
            return False
        if rs is None:
            wl.requeue_state = RequeueState()
        wl.requeue_state.count = op["count"]
        wl.requeue_state.requeue_at = op["at"]
        return True
    if kind == "deactivate":
        if not wl.active:
            return False
        wl.active = False
        return True
    return False


# -- ingest journal ---------------------------------------------------------

class IngestJournal:
    """Durable journal of accepted service submissions.

    The CycleWAL's sibling on the ingest side of the admission service
    (serving/service.py): a submission's accept record is written and
    flushed *before* the submitter's ack and before the entry joins the
    in-memory ingest queue, so a SIGKILL at any point loses zero
    accepted submissions.  Three record kinds, one JSON object per
    line::

        {"ing": "accept", "seq": 7, "token": "t7", "wl": {...}}
        {"ing": "shed",   "seq": 3, "token": "t3"}
        {"ing": "apply",  "upto": 7, "cycle": 12}

    ``accept`` carries the full submission payload — including its
    creation time and runtime — so recovery rebuilds the workload
    bit-identically.  ``shed`` marks an accepted entry later dropped by
    the backpressure policy: a recorded, reported outcome, never a
    silent loss.  ``apply`` marks every seq up to ``upto`` as drained
    into the driver at a cycle boundary.  Recovery replays only the
    un-applied, un-shed suffix in seq order, skipping keys already
    present in the recovered store (the crash may have landed between
    the store apply and the ``apply`` marker) — zero lost, zero
    duplicated.

    Unlike the group-committing CycleWAL, every record flushes
    immediately: ingest records are rare relative to WAL ops (one per
    submission, not one per decision) and each one backs an ack the
    service has already returned.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._fh = open(path, "a", encoding="utf-8") if path else None
        self.seq = 0                       # last assigned accept seq
        self.applied_upto = 0
        self.accepted: list[dict] = []     # accept records, seq order
        self.shed_seqs: set[int] = set()
        self.stats = {"ing_accepts": 0, "ing_sheds": 0, "ing_applies": 0}

    # -- append --

    def accept(self, token: str, payload: dict) -> int:
        self.seq += 1
        rec = {"ing": "accept", "seq": self.seq, "token": token,
               "wl": payload}
        self.accepted.append(rec)
        self._emit(rec)
        self.stats["ing_accepts"] += 1
        return self.seq

    def shed(self, seq: int, token: str) -> None:
        self.shed_seqs.add(seq)
        self._emit({"ing": "shed", "seq": seq, "token": token})
        self.stats["ing_sheds"] += 1

    def mark_applied(self, upto: int, cycle: int) -> None:
        if upto <= self.applied_upto:
            return
        self.applied_upto = upto
        self._emit({"ing": "apply", "upto": upto, "cycle": cycle})
        self.stats["ing_applies"] += 1

    def _emit(self, rec: dict) -> None:
        if self._fh is None:
            return
        self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
        self._fh.flush()

    def close(self) -> None:
        if self._fh is not None:
            self._fh.flush()
            self._fh.close()
            self._fh = None

    # -- read side --

    def unapplied(self) -> list[dict]:
        """Accept records not yet marked applied and not shed, in seq
        order — exactly what recovery must re-enqueue (minus any whose
        key already landed in the recovered store)."""
        return [r for r in self.accepted
                if r["seq"] > self.applied_upto
                and r["seq"] not in self.shed_seqs]

    @classmethod
    def load(cls, path: str) -> "IngestJournal":
        """Rebuild journal state from disk without an append handle
        (read-only inspection)."""
        j = cls(path=None)
        j.path = path
        if not os.path.exists(path):
            return j
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                kind = rec.get("ing")
                if kind == "accept":
                    j.accepted.append(rec)
                    j.seq = max(j.seq, rec["seq"])
                    j.stats["ing_accepts"] += 1
                elif kind == "shed":
                    j.shed_seqs.add(rec["seq"])
                    j.stats["ing_sheds"] += 1
                elif kind == "apply":
                    j.applied_upto = max(j.applied_upto, rec["upto"])
                    j.stats["ing_applies"] += 1
        return j

    @classmethod
    def resume(cls, path: str) -> "IngestJournal":
        """Crash recovery: rebuild state from disk *and* reopen the
        file for appending, continuing the seq numbering."""
        j = cls.load(path)
        j._fh = open(path, "a", encoding="utf-8")
        return j


# -- manifest journal -------------------------------------------------------

class ManifestJournal:
    """Durable store of workload *manifests* — the IngestJournal's
    federation-worker sibling.

    A federation worker process receives workloads through the remote
    CRUD API, not a serving front-end, so there is no accept record to
    recover the initial payload from.  This journal records each
    created workload's manifest (the same dict ``api.manifests``
    round-trips) before the create is acked, and a tombstone on delete;
    together with the worker's CycleWAL (full-history replay, see
    :meth:`CycleWAL.replay_history`) a SIGKILLed worker rebuilds its
    exact pre-kill state.  Two record kinds, one JSON object per line::

        {"mf": "put", "key": "ns/name", "doc": {...}}
        {"mf": "del", "key": "ns/name"}

    Every record flushes immediately — like ingest records, each one
    backs an ack already returned to the manager."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._fh = open(path, "a", encoding="utf-8") if path else None
        self.stats = {"mf_puts": 0, "mf_dels": 0}

    def put(self, key: str, doc: dict) -> None:
        self._emit({"mf": "put", "key": key, "doc": doc})
        self.stats["mf_puts"] += 1

    def delete(self, key: str) -> None:
        self._emit({"mf": "del", "key": key})
        self.stats["mf_dels"] += 1

    def _emit(self, rec: dict) -> None:
        if self._fh is None:
            return
        self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
        self._fh.flush()

    def close(self) -> None:
        if self._fh is not None:
            self._fh.flush()
            self._fh.close()
            self._fh = None

    @classmethod
    def load(cls, path: str) -> dict:
        """Fold the journal into ``{key: manifest}`` with tombstones
        applied — the worker's surviving initial-state store."""
        docs: dict[str, dict] = {}
        if not os.path.exists(path):
            return docs
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                if rec.get("mf") == "put":
                    docs[rec["key"]] = rec["doc"]
                elif rec.get("mf") == "del":
                    docs.pop(rec["key"], None)
        return docs
