"""The round's books: the spans that split a per-cycle cycle's
snapshot and admit, the window's grid patch and copy, ``schedule_burst``'s
own code and the boundary, the collector's span, and the two counters
that sit beside them.

The toy is the benchmark's round at hand size: a boundary through
``finish_workloads``, then one ``schedule_burst`` whose windows are
dropped at their first preempting cycle (``KC_CAP`` lowered, as the
benchmark's rehearsal does), so the per-cycle engine decides and the
second window of the structure is a delta pack."""

from __future__ import annotations

import gc
import threading

import pytest

from kueue_tpu.api.types import (
    PreemptionPolicy,
    ReclaimWithinCohort,
    WithinClusterQueue,
)
from kueue_tpu.obs import trace as trace_mod
from kueue_tpu.obs.flight import decision_digest
from kueue_tpu.obs.trace import HOT_PATH_PHASES, SELF_SUFFIX, Tracer, span

from test_burst import (
    add_workloads,
    build,
    mk,
    run_burst,
    run_host,
    simple_cluster,
)
from test_chaos_recovery import full_state

COHORTS, CQS = 2, 3

# the phases this file is about, each under its parent
BOOK_PHASES = (
    "queue.heads", "cycle.nominate.validate",
    "cycle.admit.prepare", "cycle.admit.fetch", "cycle.admit.apply",
    "cycle.admit.requeue",
    "burst.pack.grid.patch", "burst.pack.grid.snapshot",
    "burst", "burst.callbacks", "boundary", "host.collect", "host.gc",
)


@pytest.fixture(autouse=True)
def _tracer_off():
    trace_mod.clear()
    yield
    trace_mod.clear()


def toy_rounds(traced: bool, rounds: int = 3):
    """``rounds`` rounds of boundary and burst on a full cluster with a
    backlog that preempts.  Returns (driver, tracer or None, the
    applied cycles' stats, the burst solver's stats after each round)."""
    from kueue_tpu.ops import burst
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
    lqs = [f"lq-{c}-{q}" for c in range(COHORTS) for q in range(CQS)]
    d, clock = build(add_workloads(
        simple_cluster(n_cohorts=COHORTS, cqs=CQS, nominal=4000,
                       preemption=pre),
        [mk(f"low-{lq}-{i}", lq, 1000, t=float(4 * n + i + 1))
         for n, lq in enumerate(lqs) for i in range(4)]))
    run_host(d, clock, 4, 0)
    for n, lq in enumerate(lqs):
        for i in range(3):
            d.create_workload(mk(f"high-{lq}-{i}", lq, 2000, prio=100,
                                 t=100.0 + 3 * n + i))
            d.create_workload(mk(f"wait-{lq}-{i}", lq, 1000,
                                 t=200.0 + 3 * n + i))
    tracer = d.obs.enable_tracing() if traced else None
    out, per_round = [], []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(burst, "KC_CAP", 32)
        for r in range(rounds):
            running = sorted(k for k in d.admitted_keys())
            d.finish_workloads(running[r::5])
            out += run_burst(d, clock, 4, 0)
            per_round.append(dict(d._burst_solver.stats))
    d.obs.disable_tracing()
    return d, tracer, out, per_round


@pytest.fixture(scope="module")
def traced_toy():
    got = toy_rounds(True)
    trace_mod.clear()
    return got


def test_the_toy_takes_the_cells_path(traced_toy):
    d, _, out, per_round = traced_toy
    stats = per_round[-1]
    assert stats["burst_dispatches"] >= 3
    assert stats["burst_delta_packs"] >= 1 and stats["burst_full_packs"] == 1
    assert stats["burst_cycles_discarded"] > 0
    assert any(s.preempted_targets for s in out)
    assert d.scheduler.solver.stats["full_cycles"] > 0


@pytest.mark.parametrize("phase", BOOK_PHASES)
def test_book_phase_is_listed_entered_and_parented(phase, traced_toy):
    _, tracer, _, _ = traced_toy
    assert phase in HOT_PATH_PHASES
    parents = {r.parent for r in tracer.trace_spans if r.name == phase}
    assert parents, f"{phase}: never entered"
    if phase == "host.gc":
        return                      # nested in whatever was open
    if phase in ("burst", "boundary", "host.collect"):
        assert parents == {""}
    elif phase == "queue.heads":
        assert parents == {"burst"}     # schedule_once: top level
    else:
        assert parents == {phase.rsplit(".", 1)[0]}, parents


@pytest.mark.parametrize("parent", ["cycle", "cycle.admit",
                                    "burst.pack.grid", "burst"])
def test_children_sum_to_no_more_than_the_parent(parent, traced_toy):
    """Record by record: the children entered inside one instance of
    the parent, and the roster's totals with the self time between."""
    _, tracer, _, _ = traced_toy
    recs = tracer.trace_spans
    mine = [r for r in recs if r.name == parent]
    assert mine
    for p in mine:
        kids = [r for r in recs if r.parent == parent
                and r.depth == p.depth + 1
                and p.t0 <= r.t0 and r.t0 + r.dur <= p.t0 + p.dur + 1e-9]
        assert sum(k.dur for k in kids) <= p.dur + 1e-9
    roster = tracer.roster()
    # host.gc's series also holds the collections under other parents
    names = {r.name for r in recs if r.parent == parent} - {"host.gc"}
    if parent == "burst":
        assert names >= {"burst.pack", "burst.dispatch", "burst.fetch",
                         "burst.callbacks", "queue.heads", "cycle"}
    kids_total = sum(roster[name]["total_s"] for name in names)
    assert 0 < kids_total <= roster[parent]["total_s"] + 1e-9
    assert roster[parent + SELF_SUFFIX]["total_s"] <= (
        roster[parent]["total_s"] - kids_total + 1e-6)


def test_burst_is_the_parent_of_its_cycles(traced_toy):
    _, tracer, _, _ = traced_toy
    under = {r.parent for r in tracer.trace_spans if r.name == "cycle"}
    # run_host's settling cycles ran before tracing was on
    assert under == {"burst"}
    for name in ("burst.pack", "burst.dispatch", "burst.fetch"):
        assert {r.parent for r in tracer.trace_spans
                if r.name == name} == {"burst"}


def test_the_drivers_collection_sits_beside_burst_and_not_in_it(traced_toy):
    """One ``host.collect`` a ``schedule_burst`` call, opened after the
    call's ``burst`` span closed, so ``burst.self`` holds none of it;
    and while a ``burst`` is open the collector does not run at all."""
    _, tracer, _, _ = traced_toy
    recs = tracer.trace_spans
    bursts = [r for r in recs if r.name == "burst"]
    collects = [r for r in recs if r.name == "host.collect"]
    assert len(bursts) == len(collects) == 3
    for b, c, nxt in zip(bursts, collects, bursts[1:] + [None]):
        assert (c.parent, c.depth) == ("", 0)
        assert b.t0 + b.dur <= c.t0
        assert nxt is None or c.t0 + c.dur <= nxt.t0
    gcs = [r for r in recs if r.name == "host.gc"]
    assert [r for r in gcs if any(b.t0 <= r.t0 < b.t0 + b.dur
                                  for b in bursts)] == []
    assert sum(1 for r in gcs if r.parent == "host.collect") == 3
    roster = tracer.roster()
    assert roster["host.collect"]["count"] == 3
    # the driver's collection had a child, so its own code is a series
    assert roster["host.collect" + SELF_SUFFIX]["total_s"] <= (
        roster["host.collect"]["total_s"])


def test_flight_recorder_finds_the_burst_span_a_cycle_later(traced_toy):
    """``burst`` closes after the call's last cycle was recorded, so
    its record is drained with the first cycle of the next call (or
    waits in the buffer after the last one), never lost."""
    d, tracer, out, _ = traced_toy
    ring = list(d.obs.flight.ring)[-len(out):]    # after the settling
    assert [rec.digest for rec in ring] == [decision_digest(s) for s in out]
    in_ring = sum(1 for rec in ring for s in rec.spans if s.name == "burst")
    waiting = sum(1 for s in tracer.cycle_spans if s.name == "burst")
    calls = tracer.roster()["burst"]["count"]
    assert calls == 3 and in_ring == calls - 1 and waiting == 1
    for rec in ring:
        # its own decision's span: the per-cycle engine's, or the apply
        # of a window's modeled cycle
        assert any(s.name in ("cycle", "burst.apply") for s in rec.spans)
    assert tracer.open_spans() == []


def test_tracing_changes_no_decision_and_no_stats(traced_toy):
    dt, _, traced, rounds_t = traced_toy
    dc, _, control, rounds_c = toy_rounds(False)
    assert len(traced) == len(control)
    for k, (x, y) in enumerate(zip(traced, control)):
        assert (x.cycle, x.admitted, x.skipped, x.inadmissible,
                x.preempting, x.preempted_targets) == (
            y.cycle, y.admitted, y.skipped, y.inadmissible, y.preempting,
            y.preempted_targets), f"cycle {k}"
    assert full_state(dt) == full_state(dc)
    assert dt.scheduler.preemptor.stats == dc.scheduler.preemptor.stats

    def counts(stats):
        # not the hand timers, and not whether a snapshot's buffer could
        # be written over: that hangs on when the collector freed the
        # last plan, which is the allocations' timing and no decision,
        # as is the young generation's count where a section closed
        return {k: v for k, v in stats.items()
                if not k.endswith(("_s", "_ms"))
                and not k.startswith("pack_arena_snapshot")
                and k != "collector_deferred_allocations"}
    assert counts(dt.scheduler.solver.stats) == \
        counts(dc.scheduler.solver.stats)
    assert counts(rounds_t[-1]) == counts(rounds_c[-1])


# ---------------------------------------------------------------------------
# host.gc
# ---------------------------------------------------------------------------

def test_gc_callback_is_there_only_while_tracing_is_on():
    before = list(gc.callbacks)
    d, _ = build(simple_cluster())
    assert gc.callbacks == before
    d.obs.enable_tracing()
    assert gc.callbacks == before + [trace_mod._on_gc]
    d.obs.enable_tracing()              # idempotent: one entry
    d2, _ = build(simple_cluster())
    d2.obs.enable_tracing()             # another driver's tracer: one entry
    assert gc.callbacks.count(trace_mod._on_gc) == 1
    d2.obs.disable_tracing()
    assert gc.callbacks == before


def test_collection_inside_a_span_is_one_closed_child():
    t = trace_mod.install(Tracer())
    with span("cycle"):
        with span("cycle.nominate"):
            gc.collect()
        assert t.open_spans() == ["cycle"]
    assert t.open_spans() == []
    recs = [r for r in t.trace_spans if r.name == "host.gc"]
    assert len(recs) == 1
    assert (recs[0].parent, recs[0].depth) == ("cycle.nominate", 2)
    by = {r.name: r for r in t.trace_spans}
    roster = t.roster()
    assert roster["host.gc"]["count"] == 1
    # the parent's self time is free of the collection
    assert roster["cycle.nominate" + SELF_SUFFIX]["total_s"] == \
        pytest.approx(by["cycle.nominate"].dur - recs[0].dur, abs=1e-9)
    # outside any span it is a top-level record
    gc.collect()
    tops = [r for r in t.trace_spans if r.name == "host.gc"][1:]
    assert [(r.parent, r.depth) for r in tops] == [("", 0)]


def test_collection_while_a_pooled_span_is_handed_out():
    """A collection between ``span()`` and ``__enter__`` (it can start
    between any two bytecodes) must not take the pooled span of that
    depth: the collector's span has a slot of its own."""
    t = trace_mod.install(Tracer())
    s = span("cycle")
    gc.collect()
    with s:
        pass
    assert [r.name for r in t.trace_spans] == ["host.gc", "cycle"]


def test_collection_on_another_thread_records_nothing():
    t = trace_mod.install(Tracer())
    done = threading.Event()

    def collect():
        gc.collect()
        done.set()
    with span("cycle"):
        th = threading.Thread(target=collect)
        th.start()
        th.join(timeout=30)
        assert done.is_set() and not th.is_alive()
        assert t.open_spans() == ["cycle"]
    assert [r.name for r in t.trace_spans] == ["cycle"]
    assert t.roster()["host.gc"]["count"] == 0


# ---------------------------------------------------------------------------
# The two counters
# ---------------------------------------------------------------------------

def test_arena_snapshot_bytes_are_the_bytes_copied(traced_toy):
    """Window by window the counter grows by what ``PlaneArena.snapshot``
    copied for the plan (the row planes and the keys): every plane's
    ``nbytes`` in a full pack's window, and in a delta window that
    chains the plan before it the bytes of the runs under its
    ``row_extent`` (a plane with no cell a grid slot whole)."""
    import numpy as np
    from kueue_tpu.ops import burst, stream_pack
    d, _, _, per_round = traced_toy
    arena = d.cache._pack_arena
    planes = set(stream_pack._ROW_PLANES) | {"keys_grid"}
    assert set(arena._snaps) == set(arena._snap_tokens) == planes
    total = per_round[-1]["pack_arena_snapshot_bytes"]
    assert total == arena.stats["arena_snapshot_bytes"] > 0
    assert per_round[-1]["pack_arena_snapshots_delta"] == (
        arena.stats["arena_snapshots_delta"]) > 0
    assert (per_round[-1]["pack_arena_snapshots_delta"]
            + per_round[-1]["pack_arena_snapshots_whole"]) == len(planes) * (
        per_round[-1]["burst_full_packs"] + per_round[-1]["burst_delta_packs"])

    # the same toy with runs short enough that a queue's rows take a few
    real = stream_pack._materialize
    windows = []

    def noting(st, state, *a, **kw):
        stats = state.arena.stats
        before = dict(stats)
        plan = real(st, state, *a, **kw)
        held = {n: plan.arrays[n] for n in stream_pack._ROW_PLANES}
        held["keys_grid"] = plan.keys._g
        windows.append((
            plan.row_extent, plan.M,
            {n: (x.shape, x.nbytes) for n, x in held.items()},
            {k: stats[k] - before[k] for k in (
                "arena_snapshots_delta", "arena_snapshots_whole",
                "arena_snapshots_fresh", "arena_snapshot_bytes")}))
        return plan

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(burst, "RESIDENT_RUN", 2)
        mp.setattr(stream_pack, "_materialize", noting)
        toy_rounds(False)
    assert windows[0][0] is None            # the structure's full pack
    deltas = 0
    for extent, M, shapes, grew in windows:
        whole = sum(nbytes for _, nbytes in shapes.values())
        if extent is None:
            assert grew == {"arena_snapshots_delta": 0,
                            "arena_snapshots_whole": len(planes),
                            "arena_snapshots_fresh": len(planes),
                            "arena_snapshot_bytes": whole}
            continue
        # the toy's grid does not grow and its plans are let go of; on
        # this backend a plane put on the device whole shares the plan's
        # memory, and the mirror keeps the ``death0`` of the upload that
        # installed it: the window after finds that one buffer held
        held = {"death0"} if grew["arena_snapshots_fresh"] else set()
        assert grew["arena_snapshots_fresh"] == len(held)
        assert grew["arena_snapshots_delta"] == len(planes) - len(held)
        W = min(2, M)
        cells = int((-(-np.asarray(extent) // W)).sum()) * W
        want = sum(nbytes if shape[1] != M or name in held
                   else cells * (nbytes // (shape[0] * M))
                   for name, (shape, nbytes) in shapes.items())
        assert grew["arena_snapshot_bytes"] == want < whole
        deltas += 1
    assert deltas >= 2


@pytest.mark.parametrize("how", ["incremental", "full"])
def test_snapshot_cqs_recloned_counts_the_cycles_clones(how):
    """An incremental snapshot clones the dirty cohort trees' queues, a
    full one every queue; the cycle adds what its snapshot cloned."""
    d, clock = build(add_workloads(
        simple_cluster(n_cohorts=COHORTS, cqs=CQS),
        [mk(f"w{i}", "lq-0-0", 1000, t=float(i + 1)) for i in range(3)]))
    stats = d.scheduler.solver.stats
    assert stats["snapshot_cqs_recloned"] == 0
    run_host(d, clock, 1, 0)            # the first snapshot is a full one
    assert stats["snapshot_cqs_recloned"] == COHORTS * CQS
    if how == "full":
        # a structure change: the next snapshot is built from nothing
        add_workloads(simple_cluster(n_cohorts=COHORTS + 1, cqs=CQS), [])(d)
        want = (COHORTS + 1) * CQS
    else:
        want = CQS                      # the admission dirtied co-0's tree
    d.create_workload(mk("late", "lq-0-0", 1000, t=50.0))
    before = stats["snapshot_cqs_recloned"]
    grown = d.cache.snapshot_stats["snap_cqs_recloned"]
    run_host(d, clock, 1, 0)
    assert stats["snapshot_cqs_recloned"] - before == want
    assert d.cache.snapshot_stats["snap_cqs_recloned"] - grown == want
