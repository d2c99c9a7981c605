"""Streaming delta-pack, dtype tightening, and WAL group-commit/compaction.

The streaming pack (ops/stream_pack.py) patches a persistent packed
arena in place instead of re-fusing records each boundary; these tests
pin its three contracts:

- **Bytes-identical plans.**  Every patched plan must equal a
  from-scratch ``pack_burst`` array by array, dtype included — under
  structural churn, row-grade admission-check flips (the ``touch_row``
  channel), and the escalation/bail fallbacks (over-wide keys poison
  the structure, which is then packed in full every window).
- **Tightened launch planes never change decisions.**  The serial
  launch narrows eligible planes to int16/int8; widths are sticky and
  overflow widens (never truncates), so runs with tightening on and
  off admit identically.
- **WAL group commit and compaction are loss-bounded and crash-safe.**
  ``commit_every=N`` flushes every Nth commit (a crash loses at most
  the unflushed suffix, never tears a batch); ``compact()`` rewrites
  checkpoint + tail atomically, so a chaos crash mid-compact leaves
  the old journal readable and recovery proceeds from it unchanged.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from kueue_tpu.api.types import (
    AdmissionCheck,
    AdmissionCheckState,
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PreemptionPolicy,
    QueueingStrategy,
    ReclaimWithinCohort,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    WithinClusterQueue,
)
from kueue_tpu.chaos import injector as chaos
from kueue_tpu.chaos.injector import ChaosInjector, InjectedCrash
from kueue_tpu.controller.driver import Driver
from kueue_tpu.ops.packing import TightenState, tighten_arrays
from kueue_tpu.utils.journal import CycleWAL

from test_burst import build, run_host, simple_cluster
from test_chaos_recovery import (
    assert_admitted_prefix,
    drain_spec,
    full_state,
    recover,
    resume_host,
)
from test_delta_pack import Clock, build_cluster, check_step, mk


@pytest.fixture(autouse=True)
def _chaos_off():
    chaos.clear()
    yield
    chaos.clear()


# ---------------------------------------------------------------------------
# A plan's planes: the arena's snapshot, written over once released
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("holder", ["released", "plan", "view", "other_shape"])
def test_arena_snapshot_is_written_over_only_when_nobody_holds_it(holder):
    """The buffer of the last snapshot takes the next one when the arena
    has the only reference to it; a holder of the array or of any view
    of it keeps its bytes, and the next snapshot is a fresh array."""
    from kueue_tpu.cache.arena import PlaneArena
    arena = PlaneArena()
    live = arena.ensure("p", (3, 5), np.int32, 0)
    live[...] = np.arange(15, dtype=np.int32).reshape(3, 5)
    first = arena.snapshot("p", live)
    assert first.flags.c_contiguous and first.base is None
    assert np.array_equal(first, live)
    where = first.ctypes.data
    kept = first.copy()
    held = {"released": None, "plan": first, "view": first[1, 2:],
            "other_shape": None}[holder]
    del first
    if holder == "other_shape":
        live = arena.ensure("p", (4, 5), np.int32, 0)
    live[...] = 7
    second = arena.snapshot("p", live)
    assert np.array_equal(second, live)
    if holder == "released":
        assert second.ctypes.data == where
        assert arena.stats["arena_snapshots_reused"] == 1
    else:
        assert arena.stats["arena_snapshots_reused"] == 0
        assert arena.stats["arena_snapshots_fresh"] == 2
    if holder == "plan":
        assert np.array_equal(held, kept)
    if holder == "view":
        assert np.array_equal(held, kept[1, 2:])


def _runs_under(extent, n, W):
    """The arena's ``runs`` for the cells ``[i, 0:extent[i])``."""
    from kueue_tpu.ops.burst import _row_runs
    extent = np.asarray(extent)
    at, _ = _row_runs(extent, W, int((-(-extent // W)).sum()))
    return (n, W, (at[:, 0], at[:, 1] // W))


@pytest.mark.parametrize("case", [
    "chains", "trailing_axis", "objects", "one_column", "stale_token",
    "changed_shape", "held", "no_extent", "first_of_a_name",
    "outside_extent", "holder_wrote"])
def test_arena_snapshot_is_brought_up_to_date_by_the_cells_that_changed(case):
    """A kept buffer nobody holds, of the view's shape, that holds the
    state ``prev_token`` takes only the runs the caller names and equals
    a whole copy; any condition short of that is the whole copy.  The
    statement that every changed cell lies in the runs is the caller's
    (the pack's ``row_extent``): a cell changed outside them is not
    picked up.  What a holder wrote into its copy inside the runs (the
    driver's finishes in ``death0``) is written over."""
    from kueue_tpu.cache.arena import PlaneArena
    arena = PlaneArena()
    C, M, W = 5, 16, 4
    tail = {"trailing_axis": (3,)}.get(case, ())
    dtype = object if case == "objects" else np.int32
    fill = None if case == "objects" else -1
    shape = (C, 1) + tail if case == "one_column" else (C, M) + tail
    live = arena.ensure("p", shape, dtype, fill)
    # the slab is wider than the view: the view's rows are strided
    assert arena._slabs["p"].shape[0] > C

    def values(k):
        a = (np.arange(live.size).reshape(live.shape) * 3 + k)
        return (np.frompyfunc(lambda x: f"k{x}", 1, 1)(a)
                if case == "objects" else a.astype(np.int32))

    live[...] = values(0)
    first = arena.snapshot("p", live, token=10)
    assert arena.stats["arena_snapshots_whole"] == 1
    assert arena.stats["arena_snapshot_bytes"] == first.nbytes
    where = first.ctypes.data
    if case == "holder_wrote":
        first[0, 2] = 77        # a row of that plan: under the extent
        first[3, 0] = 78
    held = first if case == "held" else None
    del first
    # the window's changes: rows came, went and moved under the extent
    extent = np.array([6, 0, 16, 3, 1])
    new = values(1)
    under = np.arange(live.shape[1])[None, :] < extent[:, None]
    if case == "one_column":
        under = np.ones((C, 1), bool)
    live[under] = new[under]
    if case == "outside_extent":
        live[1, 9] = 12345      # no run covers it: extent[1] is 0
    if case == "changed_shape":
        live = arena.ensure("p", (C + 1, M), dtype, fill)
    name = "q" if case == "first_of_a_name" else "p"
    runs = None if case == "no_extent" else _runs_under(extent, M, W)
    prev = 9 if case == "stale_token" else 10
    before = dict(arena.stats)
    second = arena.snapshot(name, live, token=11, prev_token=prev, runs=runs)
    grew = {k: v - before[k] for k, v in arena.stats.items()
            if v != before[k]}
    assert second.flags.c_contiguous and second.base is None
    assert second.dtype == live.dtype and second.shape == live.shape
    n_runs = int((-(-extent // W)).sum())
    if case in ("chains", "trailing_axis", "objects", "holder_wrote"):
        assert np.array_equal(second, live)
        assert second.ctypes.data == where
        assert grew == {"arena_snapshots_delta": 1,
                        "arena_snapshots_reused": 1,
                        "arena_snapshot_bytes":
                            n_runs * W * second[0, :1].nbytes}
    elif case == "one_column":
        # no cell a grid slot: copied whole, under the same conditions
        assert np.array_equal(second, live)
        assert grew == {"arena_snapshots_delta": 1,
                        "arena_snapshots_reused": 1,
                        "arena_snapshot_bytes": second.nbytes}
    elif case == "outside_extent":
        assert second[1, 9] != live[1, 9]
        live[1, 9] = second[1, 9]
        assert np.array_equal(second, live)
    elif case in ("stale_token", "no_extent"):
        assert np.array_equal(second, live)
        assert second.ctypes.data == where
        assert grew == {"arena_snapshots_whole": 1,
                        "arena_snapshots_reused": 1,
                        "arena_snapshot_bytes": second.nbytes}
    else:
        assert np.array_equal(second, live)
        assert grew == {"arena_snapshots_whole": 1,
                        "arena_snapshots_fresh": 1,
                        "arena_snapshot_bytes": second.nbytes}
    if case == "held":
        assert np.array_equal(held, values(0))
    # whichever way it was made, the buffer now holds the state 11 and
    # the next window chains it
    del second
    live[2, 0] = live[0, 0]
    chained = arena.stats["arena_snapshots_delta"]
    third = arena.snapshot(name, live, token=12, prev_token=11,
                           runs=_runs_under(extent, M, W))
    assert arena.stats["arena_snapshots_delta"] == chained + 1
    assert np.array_equal(third, live)


def test_a_plan_still_held_keeps_its_planes_across_the_next_pack():
    """Two plans of one pack state held side by side do not share
    memory; released, the next pack takes the newest's buffers, and its
    plan equals a from-scratch pack."""
    from kueue_tpu.ops.burst import pack_burst, pack_burst_cached
    from test_delta_pack import assert_plans_equal, current_structure
    d, clock = build_cluster()
    for i in range(6):
        d.create_workload(mk(f"w{i}", f"lq-{i % 2}-{i % 2}", 1500, t=float(i)))
    stats = {}

    def pack(state):
        st = current_structure(d)
        plan, state, _ = pack_burst_cached(
            st, d.queues, d.cache, d.scheduler, d.clock,
            state=state, window=0, stats=stats)
        return plan, state

    one, state = pack(None)
    kept = {k: np.array(v, copy=True) for k, v in one.arrays.items()}
    kept_keys = one.keys.tolist()
    clock.t += 1.0
    d.schedule_once()
    d.create_workload(mk("late", "lq-0-0", 1000, t=9.0))
    two, state = pack(state)
    assert stats["pack_arena_snapshots_reused"] == 0
    from kueue_tpu.ops.stream_pack import _ROW_PLANES
    for name, was in kept.items():
        assert np.array_equal(one.arrays[name], was), name
    for name in _ROW_PLANES:
        assert not np.shares_memory(one.arrays[name], two.arrays[name]), name
    assert one.keys.tolist() == kept_keys
    assert any(not np.array_equal(one.arrays[n], two.arrays[n])
               for n in ("adm0", "elig0"))
    fresh = stats["pack_arena_snapshots_fresh"]
    del one, two
    d.create_workload(mk("later", "lq-1-1", 1000, t=10.0))
    three, state = pack(state)
    assert stats["pack_arena_snapshots_fresh"] == fresh
    assert stats["pack_arena_snapshots_reused"] > 0
    st = current_structure(d)
    assert_plans_equal(three, pack_burst(st, d.queues, d.cache, d.scheduler,
                                         d.clock, window=0), "third")


@pytest.mark.parametrize("calls", [2, 3])
def test_schedule_burst_packs_every_window_after_the_first_in_place(calls):
    """Driver.schedule_burst lets go of a window before it packs the
    next, within a call and from one call to the next, so later packs
    write over the planes the first one mapped."""
    d, clock = build_cluster(preempt=True)
    for c in range(2):
        for q in range(2):
            for i in range(8):
                d.create_workload(mk(
                    f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 1500,
                    prio=(i % 3) * 10, t=float(10 * c + 3 * q + i)))
    for _ in range(calls):
        d.schedule_burst(
            6, runtime=0,
            on_cycle_start=lambda k: setattr(clock, "t", clock.t + 1.0))
        for key in sorted(d.admitted_keys())[:2]:
            d.finish_workload(key)
    bs = d._burst_solver.stats
    windows = bs["burst_serial_windows"]
    assert windows >= calls
    # fifteen row planes and the keys' grid a window; the runtime lets
    # go of a launch's host arrays at its next call, so a pack that
    # follows a fetch with no launch between may find some still held
    # and map those afresh
    reused = bs["pack_arena_snapshots_reused"]
    assert reused + bs["pack_arena_snapshots_fresh"] == windows * 16
    assert reused > 0


# ---------------------------------------------------------------------------
# Streaming parity: row-grade admission-check flips
# ---------------------------------------------------------------------------

def build_checked_cluster(n_cqs=4, checks=("chk-a", "chk-b"),
                          preempt=False):
    """Two cohorts of queues behind two admission checks; with
    ``preempt`` every queue may preempt, so no admitted row is folded
    out of the grid (``burst.compressible_cqs``)."""
    clock = Clock()
    d = Driver(clock=clock, use_device_solver=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    for c in checks:
        d.apply_admission_check(AdmissionCheck(name=c))
    for i in range(n_cqs):
        d.apply_cluster_queue(ClusterQueue(
            name=f"cq-{i}", cohort=f"co-{i % 2}",
            admission_checks=list(checks),
            preemption=(PreemptionPolicy(
                reclaim_within_cohort=ReclaimWithinCohort.ANY,
                within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
                if preempt else PreemptionPolicy()),
            queueing_strategy=QueueingStrategy.BEST_EFFORT_FIFO,
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=4000,
                                         borrowing_limit=2000)})])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                       cluster_queue=f"cq-{i}"))
    return d, clock


def _reserved_unadmitted(d):
    """Quota-reserved workloads still gated on admission checks."""
    return sorted(k for k, w in d.workloads.items()
                  if w.admission_check_states and not w.is_finished
                  and w.has_quota_reservation and not w.is_admitted)


def test_streaming_parity_row_grade_check_flips():
    """Admission-check state flips journal row-grade dirt (touch_row):
    one ready check out of two moves exactly one workload's ok bit —
    the streaming pack must patch that single row, not re-walk the CQ,
    and stay bit-identical to a fresh pack at every boundary.

    On a preempting cluster: where no queue of a forest preempts, these
    reserved rows are folded out of the grid and the row patch is
    (correctly) skipped — tests/test_aggregate_compression.py covers
    that side."""
    d, clock = build_checked_cluster(preempt=True)
    for i in range(8):
        d.create_workload(mk(f"w{i}", f"lq-{i % 4}", 2000,
                             prio=(i % 3) * 10, t=float(i)))
    stats = {}
    state = check_step(d, None, stats, 0, "init")
    assert stats.get("stream_full_packs", 0) == 1

    clock.t += 1.0
    d.schedule_once()   # quota reservations; check states appear PENDING
    state = check_step(d, state, stats, 0, "reserve")
    gated = _reserved_unadmitted(d)
    assert len(gated) >= 4, "two-phase gate must hold workloads"

    # one of two checks ready: no admitted sync, pure row dirt — and a
    # PENDING->READY flip moves no packed bit (only Retry/Rejected gate
    # rows), so the patcher verifies the rows unchanged in O(1) each
    for key in gated[:2]:
        d.set_admission_check_state(key, "chk-a",
                                    AdmissionCheckState.READY)
    state = check_step(d, state, stats, 0, "chk-a-ready")
    assert stats.get("pack_rows_verified", 0) >= 2
    assert stats.get("pack_row_patches", 0) == 0
    assert stats.get("stream_packs", 0) >= 1

    # external-controller write pattern: flip a check to Retry directly
    # in the status (no driver follow-on) and journal the row — the ok
    # gate flips, so this time the patch must actually land
    wl1 = d.workloads[gated[1]]
    wl1.admission_check_states["chk-a"].state = AdmissionCheckState.RETRY
    d.queues.pack_journal.touch_row(wl1.admission.cluster_queue,
                                    gated[1])
    state = check_step(d, state, stats, 0, "retry-row-patch")
    assert stats.get("pack_row_patches", 0) >= 1
    # put it back the same way before driver-level mutations resume
    wl1.admission_check_states["chk-a"].state = \
        AdmissionCheckState.PENDING
    d.queues.pack_journal.touch_row(wl1.admission.cluster_queue,
                                    gated[1])
    state = check_step(d, state, stats, 0, "retry-undone")

    # both checks ready -> full admission (structural follow-on
    # supersedes the row entry at drain)
    d.set_admission_check_state(gated[0], "chk-b",
                                AdmissionCheckState.READY)
    state = check_step(d, state, stats, 0, "admitted")
    assert d.workloads[gated[0]].is_admitted

    # retry evicts (structural), rejected also deactivates
    d.set_admission_check_state(gated[1], "chk-a",
                                AdmissionCheckState.RETRY)
    state = check_step(d, state, stats, 0, "retry-evict")
    d.set_admission_check_state(gated[2], "chk-b",
                                AdmissionCheckState.REJECTED)
    state = check_step(d, state, stats, 0, "rejected")

    # interleave row dirt with hard dirt on the SAME CQ: the hard
    # re-walk must swallow the row patch, not double-apply it
    d.create_workload(mk("late", "lq-3", 1000, t=50.0))
    d.set_admission_check_state(gated[3], "chk-a",
                                AdmissionCheckState.READY)
    state = check_step(d, state, stats, 0, "mixed-dirt")

    clock.t += 1.0
    d.schedule_once()
    state = check_step(d, state, stats, 0, "cycle")
    assert stats.get("stream_packs", 0) >= 3
    assert stats.get("pack_rank_patches", 0) >= 1


def test_streaming_parity_row_flip_churn_randomized():
    """Randomized interleaving of arrivals / cycles / finishes with
    row-grade check flips; parity after every boundary."""
    import random
    for seed in range(6):
        rng = random.Random(7100 + seed)
        d, clock = build_checked_cluster()
        for i in range(6):
            d.create_workload(mk(f"init{i}", f"lq-{i % 4}", 1500,
                                 prio=(i % 2) * 10, t=float(i)))
        stats = {}
        state = check_step(d, None, stats, 0, f"s{seed}:init")
        n = 0
        for step in range(10):
            roll = rng.random()
            if roll < 0.3:
                n += 1
                d.create_workload(mk(f"w{n}", f"lq-{rng.randrange(4)}",
                                     rng.choice([1000, 2000, 3500]),
                                     prio=rng.choice([0, 10]),
                                     t=clock.t + n * 1e-3))
            elif roll < 0.55:
                clock.t += 1.0
                d.schedule_once()
            elif roll < 0.9:
                gated = _reserved_unadmitted(d)
                if gated:
                    d.set_admission_check_state(
                        rng.choice(gated), rng.choice(["chk-a", "chk-b"]),
                        rng.choice([AdmissionCheckState.READY,
                                    AdmissionCheckState.PENDING]))
            else:
                admitted = sorted(d.admitted_keys())
                if admitted:
                    d.finish_workload(rng.choice(admitted))
            state = check_step(d, state, stats, 0,
                               f"s{seed}:step{step}")
        assert stats.get("stream_packs", 0) >= 1


@pytest.mark.parametrize("windows_after", [1, 2, 3])
def test_stream_bail_wide_key_packs_in_full_every_window(windows_after):
    """A key wider than the fixed-width sort encoding bails the
    streaming path — counted once, poisoned for the structure's
    lifetime — and every window from then on is a full pack that
    carries no state, still bit-identical."""
    d, clock = build_cluster()
    for i in range(4):
        d.create_workload(mk(f"w{i}", "lq-0-0", 1000, t=float(i)))
    # 80-char name -> "default/<name>" far exceeds the 48-byte skey slot
    d.create_workload(mk("x" * 80, "lq-0-1", 1000, t=9.0))
    stats = {}
    state = check_step(d, None, stats, 0, "bail")
    assert state is None
    assert stats.get("stream_pack_bails", 0) == 1
    assert stats.get("burst_full_packs", 0) == 1
    for w in range(windows_after):
        d.create_workload(mk(f"tail{w}", "lq-0-0", 1000, t=10.0 + w))
        state = check_step(d, state, stats, 0, f"post-bail {w}")
        assert state is None
        assert stats["burst_full_packs"] == 2 + w
    assert stats["stream_pack_bails"] == 1
    assert stats.get("stream_packs", 0) == 0
    assert stats.get("burst_delta_packs", 0) == 0


# ---------------------------------------------------------------------------
# Dtype tightening
# ---------------------------------------------------------------------------

def test_tighten_narrows_then_widens_sticky():
    st = TightenState()
    stats = {}
    small = {"nominal_cq": np.arange(8, dtype=np.int32).reshape(2, 4)}
    out = tighten_arrays(small, st, stats)
    assert out["nominal_cq"].dtype == np.int8
    assert np.array_equal(out["nominal_cq"].astype(np.int32),
                          small["nominal_cq"])
    assert small["nominal_cq"].dtype == np.int32, "input must not mutate"
    assert st.width["nominal_cq"] == 1

    mid = {"nominal_cq": np.array([[300, -4000]], dtype=np.int32)}
    out = tighten_arrays(mid, st, stats)
    assert out["nominal_cq"].dtype == np.int16
    assert stats["pack_tighten_widened"] == 1

    big = {"nominal_cq": np.array([[1 << 19]], dtype=np.int32)}
    out = tighten_arrays(big, st, stats)
    assert out["nominal_cq"].dtype == np.int32
    assert stats["pack_tighten_widened"] == 2

    # sticky: small values after an overflow stay wide (stable jit sig)
    out = tighten_arrays(small, st, stats)
    assert out["nominal_cq"].dtype == np.int32
    assert stats["pack_tighten_widened"] == 2
    assert stats["pack_tighten_bytes_saved"] > 0


def test_tighten_widths_do_not_follow_workload_values():
    """The narrowed widths are part of the fused kernel's jit signature:
    rank/row-id planes take theirs from the grid bound and the
    workload-valued planes are never narrowed, so an arriving wave
    (bigger requests, a higher priority, more rows) cannot widen a
    plane and recompile the kernel mid-run."""
    st = TightenState()
    stats = {}
    C, M = 4, 64                       # grid of 256 cells: int16 ranks
    quiet = {
        "wl_cycle_rank": np.zeros((C, M), np.int32),
        "wl_uidrank": np.zeros((C, M), np.int32),
        "cand_rows": np.full((2, 32), -1, np.int32),
        "wl_prio": np.full((C, M), 50, np.int32),
        "wl_req": np.full((C, M, 1), 500, np.int32),
    }
    out = tighten_arrays(quiet, st, stats)
    busy = dict(quiet)
    busy["wl_cycle_rank"] = np.arange(C * M, dtype=np.int32).reshape(C, M)
    busy["wl_uidrank"] = busy["wl_cycle_rank"][::-1].copy()
    busy["cand_rows"] = np.full((2, 32), C * M - 1, np.int32)
    busy["wl_prio"] = np.full((C, M), 200, np.int32)
    busy["wl_req"] = np.full((C, M, 1), 1 << 20, np.int32)
    out2 = tighten_arrays(busy, st, stats)
    for name in quiet:
        assert out[name].dtype == out2[name].dtype, name
    assert out["wl_cycle_rank"].dtype == np.int16
    assert out["wl_prio"].dtype == out["wl_req"].dtype == np.int32
    assert stats.get("pack_tighten_widened", 0) == 0


def test_tighten_skips_sentinel_and_foreign_planes():
    st = TightenState()
    arrays = {
        "wl_rank": np.full((2, 4), np.iinfo(np.int32).max, np.int32),
        "death0": np.full((2, 4), np.iinfo(np.int32).max, np.int32),
        "ts0": np.zeros((2, 4), np.float64),
        "members": np.zeros((2, 4), np.int32),
    }
    out = tighten_arrays(arrays, st)
    assert out["wl_rank"].dtype == np.int32   # sentinel plane untouched
    assert out["death0"].dtype == np.int32
    assert out["ts0"].dtype == np.float64
    assert out["members"].dtype == np.int8


def test_schedule_burst_decisions_identical_tighten_on_off(monkeypatch):
    runs = {}
    for mode in ("1", "0"):
        monkeypatch.setenv("KUEUE_TPU_PACK_TIGHTEN", mode)
        d, clock = build_cluster(preempt=True)
        for c in range(2):
            for q in range(2):
                for i in range(5):
                    d.create_workload(mk(
                        f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 1500,
                        prio=(i % 3) * 10, t=float(10 * c + 3 * q + i)))
        stats = d.schedule_burst(
            10, runtime=2,
            on_cycle_start=lambda k: setattr(clock, "t", clock.t + 1.0))
        runs[mode] = (
            [(sorted(s.admitted), sorted(s.skipped),
              sorted(s.preempted_targets)) for s in stats],
            d.admitted_keys(),
            dict(d._burst_solver.stats))
    assert runs["1"][0] == runs["0"][0]
    assert runs["1"][1] == runs["0"][1]
    assert runs["1"][2].get("burst_launch_bytes_h2d", 0) > 0
    # tightening must actually shrink the serial-launch transfer
    assert (runs["1"][2]["burst_launch_bytes_h2d"]
            < runs["0"][2]["burst_launch_bytes_h2d"])


@pytest.mark.parametrize("batch_bytes,batches_a_launch", [
    (1 << 40, 1),     # every plane in one batch
    (256, None),      # the toy's planes are 4 B to 128 B: a few a batch
    (1, 16),          # a plane over the limit goes up alone: seven row
                      # planes and the nine of the scan state
])
def test_a_launch_stages_its_host_planes_in_bounded_batches(
        monkeypatch, batch_bytes, batches_a_launch):
    """The serial launch that sends its planes whole (every launch with
    ``KUEUE_TPU_RESIDENT=0``; with it on, the one that installs the
    device's copy: tests/test_burst_resident.py) sends the large ones
    ahead of the call in batches under ``H2D_BATCH_BYTES``; the
    decisions are those of a launch that hands the call its host arrays
    (planes under ``H2D_STAGE_MIN_BYTES``, as every test's are)."""
    from kueue_tpu.ops import burst as _b
    monkeypatch.setenv("KUEUE_TPU_RESIDENT", "0")
    runs = {}
    for staged in (False, True):
        if staged:
            monkeypatch.setattr(_b, "H2D_STAGE_MIN_BYTES", 0)
            monkeypatch.setattr(_b, "H2D_BATCH_BYTES", batch_bytes)
        d, clock = build_cluster(preempt=True)
        for c in range(2):
            for q in range(2):
                for i in range(5):
                    d.create_workload(mk(
                        f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 1500,
                        prio=(i % 3) * 10, t=float(10 * c + 3 * q + i)))
        stats = d.schedule_burst(
            10, runtime=2,
            on_cycle_start=lambda k: setattr(clock, "t", clock.t + 1.0))
        runs[staged] = (
            [(sorted(s.admitted), sorted(s.skipped),
              sorted(s.preempted_targets)) for s in stats],
            d.admitted_keys(), dict(d._burst_solver.stats))
    assert runs[True][0] == runs[False][0]
    assert runs[True][1] == runs[False][1]
    assert runs[False][2]["burst_h2d_batches"] == 0
    bs = runs[True][2]
    assert bs["burst_serial_windows"] >= 1
    a_launch = bs["burst_h2d_batches"] / bs["burst_serial_windows"]
    if batches_a_launch is None:
        assert 1 < a_launch < 16
    else:
        assert a_launch == batches_a_launch
    assert bs["burst_launch_bytes_h2d"] == \
        runs[False][2]["burst_launch_bytes_h2d"]


# ---------------------------------------------------------------------------
# WAL group commit
# ---------------------------------------------------------------------------

def _fill(wal, n, start=0):
    for i in range(start, start + n):
        wal.log({"op": "deactivate", "key": f"default/k{i}"})
        wal.commit()


def test_wal_group_commit_flushes_every_nth(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    wal = CycleWAL(path, commit_every=4)
    _fill(wal, 3)
    # nothing flushed yet: a reader (or a crash) sees an empty prefix,
    # never a torn batch
    assert CycleWAL.load(path).batches == []
    assert wal.stats["wal_flushes"] == 0
    _fill(wal, 1, start=3)
    assert wal.stats["wal_flushes"] == 1
    assert len(CycleWAL.load(path).batches) == 4
    _fill(wal, 8, start=4)
    assert wal.stats["wal_flushes"] == 3
    wal.close()
    assert len(CycleWAL.load(path).batches) == 12


def test_wal_commit_every_env_default(tmp_path, monkeypatch):
    monkeypatch.setenv("KUEUE_TPU_WAL_COMMIT_EVERY", "3")
    wal = CycleWAL(str(tmp_path / "w.jsonl"))
    assert wal.commit_every == 3
    monkeypatch.setenv("KUEUE_TPU_WAL_COMMIT_EVERY", "junk")
    assert CycleWAL(str(tmp_path / "w2.jsonl")).commit_every == 1
    # explicit argument beats the env
    assert CycleWAL(str(tmp_path / "w3.jsonl"),
                    commit_every=7).commit_every == 7


def test_wal_chaos_forces_per_line_flush(tmp_path):
    """Crash-parity runs reason about single-op boundaries: an
    installed injector must defeat group commit."""
    path = str(tmp_path / "wal.jsonl")
    wal = CycleWAL(path, commit_every=100)
    chaos.install(ChaosInjector(seed=1))   # installed, nothing armed
    _fill(wal, 2)
    chaos.clear()
    assert len(CycleWAL.load(path).batches) == 2


# ---------------------------------------------------------------------------
# WAL compaction
# ---------------------------------------------------------------------------

def test_wal_compaction_checkpoint_plus_tail(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    wal = CycleWAL(path)
    _fill(wal, 3)
    wal.log({"op": "deactivate", "key": "default/open"})   # open tail
    folded = wal.compact()
    assert folded == 3 and wal.folded_batches == 3
    # the file is now checkpoint + tail only
    with open(path) as fh:
        recs = [json.loads(ln) for ln in fh if ln.strip()]
    assert recs[0]["wal"] == "checkpoint"
    assert recs[0]["folded_batches"] == 3
    assert [r["key"] for r in recs[1:]] == ["default/open"]
    loaded = CycleWAL.load(path)
    assert loaded.batches == [] and loaded.folded_batches == 3
    assert [op["key"] for op in loaded.tail] == ["default/open"]
    # batch numbering survives the fold
    wal.commit()
    assert len(CycleWAL.load(path).batches) == 1
    with open(path) as fh:
        last = json.loads(fh.readlines()[-1])
    assert last == {"wal": "commit", "batch": 3, "n": 1}
    wal.close()


def test_wal_compact_every_auto_compacts(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    wal = CycleWAL(path, compact_every=2)
    _fill(wal, 5)
    assert wal.stats["wal_compactions"] == 2
    assert wal.folded_batches == 4 and len(wal.batches) == 1
    wal.close()
    loaded = CycleWAL.load(path)
    assert loaded.folded_batches == 4 and len(loaded.batches) == 1


def test_wal_compaction_crash_leaves_old_journal_readable(tmp_path):
    """Chaos crash between writing the temp file and the atomic
    os.replace: the original journal survives byte for byte (plus a
    stray .compact temp), so recovery reads the uncompacted history."""
    path = str(tmp_path / "wal.jsonl")
    wal = CycleWAL(path)
    _fill(wal, 3)
    wal.log({"op": "deactivate", "key": "default/open"})
    with open(path) as fh:
        before = fh.read()
    chaos.install(ChaosInjector(seed=7)).arm("wal.compact", at=1)
    with pytest.raises(InjectedCrash):
        wal.compact()
    chaos.clear()
    with open(path) as fh:
        assert fh.read() == before
    assert os.path.exists(path + ".compact")
    loaded = CycleWAL.load(path)
    assert len(loaded.batches) == 3 and loaded.folded_batches == 0
    assert [op["key"] for op in loaded.tail] == ["default/open"]
    # replaying the recovered tail equals replaying the pre-crash tail
    from kueue_tpu.api.types import PodSet, Workload
    store = {"default/open": Workload(
        name="open", queue_name="lq", pod_sets=[
            PodSet(name="main", count=1, requests={"cpu": 100})])}
    assert loaded.replay_tail(store) == 1
    assert store["default/open"].active is False


def test_driver_recovery_after_compaction_crash(tmp_path):
    """End to end: a driver journals cycles, dies mid-compaction, and
    the rebuilt driver recovers from the uncompacted journal and
    finishes the run bit-identical to the fault-free control."""
    spec, cluster = drain_spec(), simple_cluster()
    dc, cc = build(spec)
    control = run_host(dc, cc, 12, 2)

    d1, c1 = build(spec)
    path = str(tmp_path / "wal.jsonl")
    wal = CycleWAL(path)
    d1.attach_wal(wal)
    out = []
    resume_host(d1, c1, 6, 2, out)
    chaos.install(ChaosInjector(seed=5)).arm("wal.compact", at=1)
    with pytest.raises(InjectedCrash):
        wal.compact()
    chaos.clear()

    d2 = recover(cluster, d1, CycleWAL.load(path))
    resume_host(d2, c1, 12, 2, out)
    assert_admitted_prefix(out, control, "compact-crash")
    assert d2.admitted_keys() == dc.admitted_keys()
    assert full_state(d2) == full_state(dc)


# ---------------------------------------------------------------------------
# Bulk apply: one O(N) settle must equal N serial applies
# ---------------------------------------------------------------------------

def _apply_topology(d):
    """6 CQs in 3 cohorts + 1 inactive CQ (dangling admission check) +
    a re-apply that shrinks cq-0's nominal — every path bulk_apply
    defers (add, edge update, update_quotas, activeness)."""
    for i in range(6):
        d.apply_cluster_queue(ClusterQueue(
            name=f"cq-{i}", cohort=f"co-{i // 2}",
            queueing_strategy=QueueingStrategy.BEST_EFFORT_FIFO,
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=4000,
                                         borrowing_limit=2000)})])]))
        d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                       cluster_queue=f"cq-{i}"))
    d.apply_cluster_queue(ClusterQueue(
        name="cq-dangling", admission_checks=["missing-check"],
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=4000)})])]))
    d.apply_local_queue(LocalQueue(name="lq-dangling",
                                   cluster_queue="cq-dangling"))
    d.apply_cluster_queue(ClusterQueue(
        name="cq-0", cohort="co-0",
        queueing_strategy=QueueingStrategy.BEST_EFFORT_FIFO,
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name="default", resources={
                "cpu": ResourceQuota(nominal=2000,
                                     borrowing_limit=2000)})])]))


def test_bulk_apply_parity_with_serial_applies():
    drivers = {}
    for mode in ("serial", "bulk"):
        clock = Clock()
        d = Driver(clock=clock, use_device_solver=True)
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        if mode == "bulk":
            with d.bulk_apply():
                _apply_topology(d)
                # inside the block the rebuild is deferred
                assert d.cache._rebuild_deferred is True
        else:
            _apply_topology(d)
        assert d.cache._rebuild_deferred is False
        for i, w in enumerate((2500,) * 8 + (1500,) * 4):
            q = i % 7
            lq = f"lq-{q}" if q < 6 else "lq-dangling"
            d.create_workload(mk(f"w{i}", lq, w, prio=i % 3,
                                 t=float(i)))
        clock.t += 1.0
        d.schedule_burst(4)
        drivers[mode] = d
    ds, db = drivers["serial"], drivers["bulk"]
    for name in [f"cq-{i}" for i in range(6)] + ["cq-dangling"]:
        assert ds.cache.cluster_queue(name).active \
            == db.cache.cluster_queue(name).active, name
    assert ds.cache.cluster_queue("cq-dangling").active is False
    assert ds.admitted_keys() == db.admitted_keys()
    assert full_state(ds) == full_state(db)


def test_bulk_apply_nested_settles_once_at_outer_exit(monkeypatch):
    clock = Clock()
    d = Driver(clock=clock, use_device_solver=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    calls = {"n": 0}
    real = type(d.cache)._rebuild

    def counting(self):
        if not self._rebuild_deferred:
            calls["n"] += 1
        return real(self)

    monkeypatch.setattr(type(d.cache), "_rebuild", counting)
    with d.bulk_apply():
        with d.bulk_apply():   # inner block must not settle early
            _apply_topology(d)
        assert calls["n"] == 0
    assert calls["n"] == 1
    assert d.cache.cluster_queue("cq-5").active is True
