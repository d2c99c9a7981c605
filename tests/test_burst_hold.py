"""The hold after a dirty truncation.

A window truncated by a dirty cycle leaves the backlog that made it
dirty at the front of the queues, so a window launched again before
quota comes free meets the same head and is dropped at its first cycle.
``schedule_burst`` therefore holds the rest of the call on the per-cycle
engine (``burst_held_cycles``) until a finish releases quota or the call
ends.  The flat toy takes the benchmark cells' path (``KC_CAP`` lowered,
as ``tests/test_obs_books.py`` does): every preempting head makes its
cycle dirty.  Every case also runs the host-only arm, whose decisions
the held call has to equal cycle for cycle."""

from __future__ import annotations

import pytest

from kueue_tpu.api.types import (
    WL_QUOTA_RESERVED,
    PreemptionPolicy,
    ReclaimWithinCohort,
    WithinClusterQueue,
)

from test_burst import add_workloads, build, mk, run_host, simple_cluster

CYCLES = 8


@pytest.fixture(autouse=True)
def _cells_path(monkeypatch):
    from kueue_tpu.ops import burst
    monkeypatch.setattr(burst, "KC_CAP", 32)


def backlog(use_device=True):
    """Two cohorts of three full queues, then a backlog whose
    high-priority heads fit only by evicting: the first cycle of any
    window over it is dirty."""
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
    lqs = [f"lq-{c}-{q}" for c in range(2) for q in range(3)]
    d, clock = build(add_workloads(
        simple_cluster(n_cohorts=2, cqs=3, nominal=4000, preemption=pre),
        [mk(f"low-{lq}-{i}", lq, 1000, t=float(4 * n + i + 1))
         for n, lq in enumerate(lqs) for i in range(4)]),
        use_device=use_device)
    run_host(d, clock, 4, 0)
    for n, lq in enumerate(lqs):
        for i in range(3):
            d.create_workload(mk(f"high-{lq}-{i}", lq, 2000, prio=100,
                                 t=100.0 + 3 * n + i))
            d.create_workload(mk(f"wait-{lq}-{i}", lq, 1000,
                                 t=200.0 + 3 * n + i))
    return d, clock


def burst_call(d, clock, runtime=0, external_finishes=None):
    """One ``schedule_burst`` call; returns its cycles and, as each cycle
    starts, the launches and held cycles counted so far (the window that
    decides the cycle, or the cycle itself if held, included)."""
    books = []

    def on_cycle_start(_k):
        clock.t += 1.0
        b = d._burst_solver.stats
        books.append((b["burst_dispatches"], b["burst_held_cycles"]))
    out = d.schedule_burst(CYCLES, runtime=runtime,
                           external_finishes=external_finishes,
                           on_cycle_start=on_cycle_start)
    return out, books


def host_call(d, clock, runtime=0, external_finishes=None):
    """The host-only arm of ``burst_call``: ``schedule_once`` a cycle and
    the same finishes, an external one only while the workload holds the
    reservation it held when the call began."""
    def reserved(key):
        wl = d.workloads.get(key)
        if wl is None or not wl.has_quota_reservation:
            return None
        return wl.conditions[WL_QUOTA_RESERVED].last_transition_time
    ext = external_finishes or {}
    began = {key: reserved(key) for keys in ext.values() for key in keys}
    out, admitted_at = [], []
    for c in range(CYCLES):
        clock.t += 1.0
        stats = d.schedule_once()
        out.append(stats)
        admitted_at.append({k: reserved(k) for k in stats.admitted})
        due = [k for k in ext.get(c, []) if reserved(k) == began[k]]
        if runtime > 0 and c - runtime >= 0:
            due += [k for k in out[c - runtime].admitted
                    if reserved(k) is not None
                    and reserved(k) == admitted_at[c - runtime][k]]
        if due:
            d.finish_workloads(due)
    return out


def decisions(cycles):
    return [(sorted(s.admitted), sorted(s.skipped), sorted(s.inadmissible),
             sorted(s.preempted_targets)) for s in cycles]


def assert_host_decides_the_same(host, held):
    assert decisions(held) == decisions(host[:len(held)])
    for s in host[len(held):]:
        assert not (s.admitted or s.skipped or s.inadmissible
                    or s.preempting), "the call ended while host was busy"


def test_one_call_launches_one_window_and_holds_the_rest():
    d, clock = backlog()
    h, hclock = backlog(use_device=False)
    out, books = burst_call(d, clock)
    assert_host_decides_the_same(host_call(h, hclock), out)
    b = d._burst_solver.stats
    assert len(out) == CYCLES and books == [(1, k) for k in range(CYCLES)]
    assert b["burst_dispatches"] == 1
    assert b["burst_dirty_cycles"] == b["burst_dirty_preempt"] == 1
    assert b["burst_held_cycles"] == b["burst_suppressed_cycles"] == 7
    assert any(s.preempted_targets for s in out)
    assert d.admitted_keys() == h.admitted_keys()
    assert d.stats["burst"]["burst_held_cycles"] == 7
    assert "kueue_burst_held_cycles 7" in d.metrics.render()


def test_the_hold_ends_with_its_call():
    """The next call starts with no hold: its first cycle is a window."""
    d, clock = backlog()
    h, hclock = backlog(use_device=False)
    first, _ = burst_call(d, clock)
    second, books = burst_call(d, clock)
    host = host_call(h, hclock) + host_call(h, hclock)
    assert_host_decides_the_same(host, first + second)
    assert len(first) == CYCLES and second
    assert books[0] == (2, 7)
    assert d._burst_solver.stats["burst_dispatches"] == 2
    assert d._burst_solver.stats["burst_held_cycles"] == (
        7 + len(second) - 1)


@pytest.mark.parametrize("finish", ["runtime", "external"])
def test_a_finish_inside_the_call_ends_the_hold(finish):
    """Quota released inside the call lifts the hold: the cycle after the
    release is a window again, and the cycles between are held."""
    d, clock = backlog()
    h, hclock = backlog(use_device=False)
    if finish == "runtime":
        kw = {"runtime": 1}
    else:
        # every running workload ends at offset 1; those the window's
        # first cycle evicted are skipped, the rest release quota
        kw = {"external_finishes": {1: sorted(d.admitted_keys())}}
    out, books = burst_call(d, clock, **kw)
    assert_host_decides_the_same(host_call(h, hclock, **kw), out)
    if finish == "runtime":
        # a run ends ``runtime`` cycles after the cycle that admitted it
        released = 1 + next(k for k, s in enumerate(out) if s.admitted)
    else:
        released = 1
    assert books[:released + 2] == (
        [(1, k) for k in range(released + 1)] + [(2, released)])
    b = d._burst_solver.stats
    assert b["burst_held_cycles"] == b["burst_suppressed_cycles"]


@pytest.mark.parametrize("seed", range(2))
def test_a_fair_window_that_applied_a_cycle_holds_the_rest(seed):
    """Fair sharing: the window applies its first cycle, the second holds
    a preempt-capable head (``DIRTY_FAIR_PREEMPT``), and the call's other
    cycles are held."""
    from test_fs_window import random_tree, run
    build_tree = random_tree(seed, 3, preempt=True, n_wl=90)
    host, _ = run("host", build_tree, cycles=CYCLES, runtime=0)
    held, d = run("burst", build_tree, cycles=CYCLES, runtime=0)
    assert held == host
    b, s = d._burst_solver.stats, d.scheduler.solver.stats
    assert b["burst_dispatches"] == 1
    assert s["fs_window_cycles"] >= 1
    assert b["burst_dirty_cycles"] == b["burst_fair_dirty_cycles"] == 1
    assert b["burst_held_cycles"] == CYCLES - s["fs_window_cycles"] - 1
