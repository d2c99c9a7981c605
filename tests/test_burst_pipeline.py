"""Burst boundary pipeline: double-buffered pack + async dispatch.

The two-slot pipeline chains window N+1's kernel dispatch off window
N's device-resident final carry before N's apply loop runs, so pack +
dispatch overlap apply instead of landing serially in one cycle.  These
tests enforce the correctness bar: pipelined decisions are bit-identical
to the serial burst path (and to the per-cycle host path), and any
speculative window whose assumptions were invalidated by apply is
discarded unused — plus regression tests for the satellite fixes that
rode along (clock-monotonicity within a cycle, vanished preempt
targets, seq-headroom gate, required-mode accel check).
"""

from __future__ import annotations

import pytest

from kueue_tpu.api.types import (
    PreemptionPolicy,
    ReclaimWithinCohort,
    WithinClusterQueue,
)
from kueue_tpu.controller.driver import Driver

from test_burst import (
    add_workloads,
    build,
    mk,
    run_host,
    simple_cluster,
)

PRE_ANY = PreemptionPolicy(
    reclaim_within_cohort=ReclaimWithinCohort.ANY,
    within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)


def run_burst_mode(d, clock, cycles, runtime, pipeline, inject=None):
    """One schedule_burst call with the pipeline explicitly on or off;
    ``inject`` maps applied-cycle index -> workload to create at that
    cycle's start (mirrors run_host_inject)."""
    def on_cycle_start(k):
        if inject and k in inject:
            d.create_workload(inject[k])
        clock.t += 1.0
    return d.schedule_burst(cycles, runtime=runtime,
                            on_cycle_start=on_cycle_start,
                            pipeline=pipeline)


def run_host_inject(d, clock, cycles, runtime, inject=None):
    out = []
    for c in range(cycles):
        if inject and c in inject:
            d.create_workload(inject[c])
        clock.t += 1.0
        stats = d.schedule_once()
        out.append(stats)
        if runtime > 0 and c - runtime >= 0:
            for key in out[c - runtime].admitted:
                wl = d.workloads.get(key)
                if wl is not None and wl.has_quota_reservation:
                    d.finish_workload(key)
    return out


def assert_records_equal(a, b, label):
    for k, (x, y) in enumerate(zip(a, b)):
        assert sorted(x.admitted) == sorted(y.admitted), \
            f"{label} cycle {k} admitted: {sorted(x.admitted)} vs " \
            f"{sorted(y.admitted)}"
        assert sorted(x.skipped) == sorted(y.skipped), f"{label} cycle {k}"
        assert sorted(x.inadmissible) == sorted(y.inadmissible), \
            f"{label} cycle {k}"
        assert sorted(x.preempting) == sorted(y.preempting), \
            f"{label} cycle {k}"
        assert sorted(x.preempted_targets) == sorted(y.preempted_targets), \
            f"{label} cycle {k}"


def assert_quiescent_tail(host, burst):
    for s in host[len(burst):]:
        assert not (s.admitted or s.skipped or s.inadmissible
                    or s.preempting), "burst ended while host still active"


def sustained_spec(per_cq=36):
    """Enough pending work to keep >1 full K=32 window busy: 2 CQs with
    2 concurrent slots each, runtime-driven finishes feeding re-admission
    for dozens of cycles."""
    wls = []
    n = 0
    for q in range(2):
        for i in range(per_cq):
            n += 1
            wls.append(mk(f"w-{q}-{i}", f"lq-0-{q}", 2000,
                          prio=(i % 3) * 10, t=float(n)))
    return add_workloads(simple_cluster(n_cohorts=1, cqs=2,
                                        nominal=4000), wls)


def spec_counters(d):
    s = d._burst_solver.stats
    return {k: s[k] for k in ("burst_spec_dispatches",
                              "burst_overlapped_packs",
                              "burst_spec_cancelled",
                              "burst_serial_windows")}


def test_pipeline_parity_and_overlap():
    """The headline correctness bar: pipelined == serial == host on a
    multi-window sustained drain, with at least one window boundary
    actually overlapped (consumed speculative dispatch)."""
    spec = sustained_spec()
    dh, ch = build(spec)
    ds, cs = build(spec)
    dp, cp = build(spec)
    host = run_host(dh, ch, 80, 2)
    serial = run_burst_mode(ds, cs, 80, 2, pipeline=False)
    piped = run_burst_mode(dp, cp, 80, 2, pipeline=True)
    assert len(serial) == len(piped), "pipeline changed cycle count"
    assert_records_equal(serial, piped, "serial-vs-pipelined")
    assert_records_equal(host, piped, "host-vs-pipelined")
    assert_quiescent_tail(host, piped)
    assert dh.admitted_keys() == dp.admitted_keys() == ds.admitted_keys()
    c = spec_counters(dp)
    assert c["burst_overlapped_packs"] >= 1, c
    # every speculative dispatch is either consumed or provably discarded
    assert c["burst_spec_dispatches"] == (
        c["burst_overlapped_packs"] + c["burst_spec_cancelled"]), c
    off = spec_counters(ds)
    assert off["burst_spec_dispatches"] == 0, off
    assert off["burst_overlapped_packs"] == 0, off


@pytest.mark.parametrize("env", [None, "0"])
def test_pipeline_is_the_default_whatever_the_environment(monkeypatch,
                                                          env):
    """schedule_burst pipelines unless its caller says otherwise: the
    argument is the only switch, and no variable stands in for it."""
    if env is not None:
        monkeypatch.setenv("KUEUE_BURST_PIPELINE", env)
    d, clock = build(sustained_spec(per_cq=20))
    d.schedule_burst(60, runtime=2,
                     on_cycle_start=lambda k: setattr(clock, "t",
                                                      clock.t + 1.0))
    assert spec_counters(d)["burst_spec_dispatches"] > 0


def test_midwindow_injection_cancels_speculation():
    """A preemptor created inside a window whose successor was already
    speculatively dispatched: the heads divergence truncates the window
    and the in-flight speculation is cancelled, never applied — and the
    decisions still match the serial path and the host path with the
    same injection."""
    spec = sustained_spec()
    boss = lambda: mk("boss", "lq-0-0", 4000, prio=100, t=500.0)
    inject_at = 36   # inside window 1, after window 2 was speculated
    dh, ch = build(spec)
    ds, cs = build(spec)
    dp, cp = build(spec)
    host = run_host_inject(dh, ch, 80, 2, inject={inject_at: boss()})
    serial = run_burst_mode(ds, cs, 80, 2, pipeline=False,
                            inject={inject_at: boss()})
    piped = run_burst_mode(dp, cp, 80, 2, pipeline=True,
                           inject={inject_at: boss()})
    assert_records_equal(serial, piped, "serial-vs-pipelined")
    assert_records_equal(host, piped, "host-vs-pipelined")
    assert_quiescent_tail(host, piped)
    assert dh.admitted_keys() == dp.admitted_keys()
    assert any("default/boss" in s.admitted for s in piped)
    c = spec_counters(dp)
    assert c["burst_spec_cancelled"] >= 1, c
    assert c["burst_spec_dispatches"] == (
        c["burst_overlapped_packs"] + c["burst_spec_cancelled"]), c


class TickClock:
    """Every read ticks: no two clock samples are ever equal, so two
    admissions in one cycle get distinct reservation timestamps."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        self.t += 1e-4
        return self.t


def test_clock_tick_within_cycle_falls_back_for_preempt():
    """Satellite: >1 distinct admission timestamp inside ONE burst cycle
    flips clock_monotone off, so a later modeled preempt cycle in the
    same window is re-decided on the host path (candidatesOrdering ties
    on real timestamps the kernel's per-cycle seq cannot mirror).

    Scenario: victim is pre-admitted; burst cycle 0 admits ``top`` (which
    fills cq-0-0) and ``filler-1`` (cq-1-0) — two admissions, two ticked
    timestamps.  Cycle 1 models boss preempting victim, but the guard
    forces it onto the host path: no "preempt" kind ever reaches
    apply_burst_cycle.  A static clock (one timestamp per cycle) keeps
    the kernel in charge — the differential pins the trigger on the
    mid-cycle tick."""
    def mkdriver(clock_cls):
        clock = clock_cls()
        d = Driver(clock=clock, use_device_solver=True)
        # two cohorts: cohort 0 has no spare capacity to borrow, so the
        # boss must preempt; cohort 1 exists only to co-admit in cycle 0
        simple_cluster(n_cohorts=2, cqs=1, nominal=8000,
                       preemption=PRE_ANY)(d)
        d.create_workload(mk("victim", "lq-0-0", 4000, prio=0, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("top", "lq-0-0", 4000, prio=200, t=10.0))
        d.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=11.0))
        d.create_workload(mk("filler-1", "lq-1-0", 4000, prio=0, t=12.0))
        return d, clock

    def applied_kinds(d):
        """Record every decision kind the kernel path applies."""
        kinds = []
        real = d.scheduler.apply_burst_cycle

        def spy(heads, modeled):
            kinds.extend(v[0] for v in modeled.values())
            return real(heads, modeled)

        d.scheduler.apply_burst_cycle = spy
        return kinds

    dh, ch = mkdriver(TickClock)
    db, cb = mkdriver(TickClock)
    kinds = applied_kinds(db)
    host = run_host_inject(dh, ch, 6, 0)
    burst = run_burst_mode(db, cb, 6, 0, pipeline=True)
    assert_records_equal(host, burst, "host-vs-burst")
    assert_quiescent_tail(host, burst)
    assert dh.admitted_keys() == db.admitted_keys()
    preempted = {k for s in burst for k in s.preempted_targets}
    assert preempted == {"default/victim"}
    # the guard, not the kernel, decided the preempt cycle
    assert "preempt" not in kinds, kinds

    from test_burst import Clock
    dc, cc = mkdriver(Clock)
    ckinds = applied_kinds(dc)
    cburst = run_burst_mode(dc, cc, 6, 0, pipeline=True)
    assert {k for s in cburst for k in s.preempted_targets} == \
        {"default/victim"}
    assert "preempt" in ckinds, ckinds


def test_vanished_preempt_target_aborts_cycle_unmutated():
    """Satellite: a modeled preempt target with no live admitted Info
    makes apply_burst_cycle return None BEFORE mutating anything — the
    cycle counter does not advance and no decision is applied."""
    d, clock = build(add_workloads(
        simple_cluster(n_cohorts=1, cqs=1, nominal=4000,
                       preemption=PRE_ANY),
        [mk("boss", "lq-0-0", 4000, prio=100, t=1.0)]))
    clock.t += 1.0
    heads = d.queues.heads_nonblocking()
    assert heads
    modeled = {heads[0].key: ("preempt", (0,), (-1,), False,
                              [("default/ghost", "cq-0-0")])}
    cycle_before = d.scheduler.scheduling_cycle
    assert d.scheduler.apply_burst_cycle(heads, modeled) is None
    assert d.scheduler.scheduling_cycle == cycle_before
    assert "default/boss" not in d.admitted_keys()


def test_vanished_target_mid_burst_redecides_on_host(monkeypatch):
    """Driver integration for the same satellite: when the live-info
    lookup transiently fails mid-burst, the window aborts, the counter
    records the divergence, and the host path re-decides identically."""
    def spec(d):
        simple_cluster(n_cohorts=1, cqs=1, nominal=4000,
                       preemption=PRE_ANY)(d)

    def prelude(d, clock):
        d.create_workload(mk("victim", "lq-0-0", 4000, prio=0, t=1.0))
        clock.t += 1.0
        d.schedule_once()
        d.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=50.0))

    dh, ch = build(spec)
    db, cb = build(spec)
    prelude(dh, ch)
    prelude(db, cb)
    host = run_host_inject(dh, ch, 4, 0)     # before the patch lands
    real = type(db.scheduler)._live_admitted_info
    state = {"dropped": False}

    def flaky(self, cq_name, key):
        if not state["dropped"]:
            state["dropped"] = True
            return None
        return real(self, cq_name, key)

    monkeypatch.setattr(type(db.scheduler), "_live_admitted_info", flaky)
    burst = run_burst_mode(db, cb, 4, 0, pipeline=True)
    assert state["dropped"], "modeled preempt never hit the live lookup"
    assert_records_equal(host, burst, "host-vs-burst")
    assert_quiescent_tail(host, burst)
    assert dh.admitted_keys() == db.admitted_keys()
    assert "default/boss" in db.admitted_keys()
    assert db._burst_solver.stats["burst_target_divergences"] >= 1


def test_seq_headroom_gate_scales_with_ladder(monkeypatch):
    """Satellite: the composite-key overflow gate derives its headroom
    from max(K_BURST_LADDER); a ladder that would overflow the 20-bit
    seq field gates every forest out of the preemption envelope."""
    from kueue_tpu.ops import burst as burst_mod
    d, clock = build(simple_cluster(n_cohorts=1, cqs=1, nominal=4000,
                                    preemption=PRE_ANY))
    d.create_workload(mk("low", "lq-0-0", 4000, prio=0, t=1.0))
    clock.t += 1.0
    d.schedule_once()
    d.create_workload(mk("boss", "lq-0-0", 4000, prio=100, t=50.0))
    st = d.scheduler.solver._structure_for(d.cache.snapshot(), [])
    plan = burst_mod.pack_burst(st, d.queues, d.cache, d.scheduler,
                                clock, window=32)
    assert plan is not None and plan.arrays["preempt_ok"].any()
    monkeypatch.setattr(burst_mod, "K_BURST_LADDER", (1 << 20,))
    plan2 = burst_mod.pack_burst(st, d.queues, d.cache, d.scheduler,
                                 clock, window=32)
    assert plan2 is not None
    assert not plan2.arrays["preempt_ok"].any()


def test_dispatch_next_refuses_seq_overflow():
    """The chained-window path re-checks the same headroom before
    advancing seq_base (no pack_burst gate runs for it)."""
    from kueue_tpu.ops.burst import BurstHandle, BurstSolver
    bs = BurstSolver()
    h = BurstHandle(plan=None, K=32, runtime=0,
                    seq_base=(1 << 20) - 16, dev=None,
                    carry=object())
    assert bs.dispatch_next(h, None, None) is None
    h2 = BurstHandle(plan=None, K=32, runtime=0, seq_base=1, dev=None,
                     carry=None)    # never fetched: no carry to chain
    assert bs.dispatch_next(h2, None, None) is None
