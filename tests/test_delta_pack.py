"""Delta-pack parity: the incrementally-maintained burst pack must be
bit-identical to a fresh ``pack_burst`` of the same live state.

``pack_burst_cached`` keeps per-CQ row records alive across windows and
re-walks only journal-dirty CQs; these tests interleave every mutation
class the journal models — arrivals, admissions (host cycles with their
pop/requeue roundtrips), evictions, finishes, backoff park/unpark,
activeness flips, LimitRanges — and after EVERY step compare the
delta-built plan against a from-scratch pack, array by array.  Forced
structure-generation bumps and quota/scale changes must fall back to a
counted full repack.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from kueue_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    QueueingStrategy,
    ReclaimWithinCohort,
    RequeueState,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    WithinClusterQueue,
    Workload,
)
from kueue_tpu.controller.driver import Driver
from kueue_tpu.ops.burst import pack_burst, pack_burst_cached


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def build_cluster(seed=0, preempt=False):
    clock = Clock()
    d = Driver(clock=clock, use_device_solver=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY,
    ) if preempt else PreemptionPolicy()
    for c in range(2):
        for q in range(2):
            name = f"cq-{c}-{q}"
            d.apply_cluster_queue(ClusterQueue(
                name=name, cohort=f"co-{c}", preemption=pre,
                queueing_strategy=QueueingStrategy.BEST_EFFORT_FIFO,
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": ResourceQuota(nominal=4000,
                                             borrowing_limit=2000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{c}-{q}",
                                           cluster_queue=name))
    return d, clock


def mk(name, lq, cpu, prio=0, t=0.0):
    return Workload(name=name, queue_name=lq, priority=prio,
                    creation_time=t,
                    pod_sets=[PodSet(name="main", count=1,
                                     requests={"cpu": cpu})])


def current_structure(d):
    """Mirror driver.schedule_burst's structure refresh."""
    solver = d.scheduler.solver
    st = solver._structure
    if st is None or st.generation != d.cache.structure_generation:
        st = solver._structure_for(d.cache.snapshot(), [])
    return st


def assert_plans_equal(a, b, ctx=""):
    if a is None or b is None:
        assert a is None and b is None, \
            f"{ctx}: one plan is None (delta={a is not None})"
        return
    for attr in ("C", "M", "L", "G", "n_levels", "KC", "seq_base"):
        assert getattr(a, attr) == getattr(b, attr), \
            f"{ctx}: {attr} differs"
    assert a.max_res_ts == b.max_res_ts, f"{ctx}: max_res_ts"
    assert a.keys == b.keys, f"{ctx}: keys grids differ"
    assert a.row_of_key == b.row_of_key, f"{ctx}: row_of_key differs"
    assert set(a.arrays) == set(b.arrays), f"{ctx}: array keys differ"
    for name in a.arrays:
        x, y = np.asarray(a.arrays[name]), np.asarray(b.arrays[name])
        assert x.dtype == y.dtype, f"{ctx}: {name} dtype"
        assert x.shape == y.shape, f"{ctx}: {name} shape"
        assert np.array_equal(x, y), \
            f"{ctx}: array {name} differs at " \
            f"{np.argwhere(x != y)[:5].tolist()}"


def check_step(d, state, stats, window, ctx):
    """One boundary: delta pack vs fresh pack of the same live state."""
    st = current_structure(d)
    plan_d, state, _ = pack_burst_cached(
        st, d.queues, d.cache, d.scheduler, d.clock,
        state=state, window=window, stats=stats)
    plan_f = pack_burst(st, d.queues, d.cache, d.scheduler, d.clock,
                        window=window)
    assert_plans_equal(plan_d, plan_f, ctx)
    return state


def random_mutation(rng, d, clock, names):
    """Apply one randomized driver-level mutation; returns a label."""
    roll = rng.random()
    lqs = [f"lq-{c}-{q}" for c in range(2) for q in range(2)]
    if roll < 0.30:
        n = next(names)
        d.create_workload(mk(f"w{n}", rng.choice(lqs),
                             rng.choice([1000, 2000, 3500, 4500]),
                             prio=rng.choice([0, 0, 10, 50]),
                             t=clock.t + n * 1e-3))
        return "arrival"
    if roll < 0.55:
        clock.t += 1.0
        d.schedule_once()   # admissions + pop/requeue roundtrips
        return "cycle"
    if roll < 0.70:
        admitted = sorted(d.admitted_keys())
        if admitted:
            d.finish_workload(rng.choice(admitted))
            return "finish"
        return "noop"
    if roll < 0.80:
        admitted = sorted(d.admitted_keys())
        if admitted:
            d.deactivate_workload(rng.choice(admitted))
            return "evict"
        return "noop"
    if roll < 0.88:
        # backoff-park an unadmitted workload, as an eviction requeue
        # with a pending backoff timer would
        n = next(names)
        wl = mk(f"b{n}", rng.choice(lqs), 1000, t=clock.t + n * 1e-3)
        wl.requeue_state = RequeueState(count=1,
                                        requeue_at=clock.t + 5.0)
        d.workloads[wl.key] = wl
        d.queues.add_or_update_workload(wl)
        return "backoff-park"
    if roll < 0.94:
        clock.t += 10.0
        d.queues.wake_expired_backoffs()
        return "backoff-wake"
    cq = rng.choice([f"cq-{c}-{q}" for c in range(2) for q in range(2)])
    active = rng.random() < 0.5
    d.queues.set_cluster_queue_active(cq, active)
    if not active:
        # leave it usable for later steps
        d.queues.set_cluster_queue_active(cq, True)
    return "active-flip"


def _counter():
    n = 0
    while True:
        n += 1
        yield n


@pytest.mark.parametrize("window", [0, 4])
def test_delta_pack_randomized_parity(window):
    """>= 200 randomized mutation sequences, parity checked after every
    step; full-repack fallbacks (gen bumps, quota changes) exercised."""
    total_delta = total_full = 0
    n_seqs = 100   # x2 window params = 200 sequences
    for seed in range(n_seqs):
        rng = random.Random(1234 + seed)
        d, clock = build_cluster(seed, preempt=(seed % 3 == 0))
        names = _counter()
        for i in range(6):
            d.create_workload(mk(f"init{i}", f"lq-{i % 2}-{i // 3}",
                                 2000, prio=(i % 3) * 10, t=float(i)))
        stats = {}
        state = check_step(d, None, stats, window, f"seed{seed}:init")
        for step in range(12):
            label = random_mutation(rng, d, clock, names)
            if step == 5 and seed % 4 == 0:
                # forced structure-generation bump -> full repack
                d.apply_resource_flavor(ResourceFlavor(name="default"))
                label += "+genbump"
            if step == 8 and seed % 5 == 0:
                # quota edit: new structure tensors (and possibly a new
                # resource scale) -> key mismatch -> full repack
                d.apply_cluster_queue(ClusterQueue(
                    name="cq-0-0", cohort="co-0",
                    resource_groups=[ResourceGroup(
                        covered_resources=["cpu"],
                        flavors=[FlavorQuotas(
                            name="default",
                            resources={"cpu": ResourceQuota(
                                nominal=4000 + 500 * (step + seed % 3),
                                borrowing_limit=2000)})])]))
                label += "+quota"
            state = check_step(d, state, stats, window,
                               f"seed{seed}:step{step}:{label}")
        total_delta += stats.get("burst_delta_packs", 0)
        total_full += stats.get("burst_full_packs", 0)
    # the delta path must actually run, and the fallbacks must be
    # counted (every sequence starts with at least one full pack)
    assert total_delta > 0, "delta path never taken"
    assert total_full >= n_seqs, "full-repack fallbacks not counted"


def test_delta_pack_rows_reused_counted():
    d, clock = build_cluster()
    for i in range(8):
        d.create_workload(mk(f"w{i}", f"lq-{i % 2}-{i // 4}", 1000,
                             t=float(i)))
    stats = {}
    state = check_step(d, None, stats, 0, "full")
    assert stats["burst_full_packs"] == 1
    # dirty exactly one CQ; the other three reuse their records
    d.create_workload(mk("late", "lq-0-0", 1000, t=99.0))
    state = check_step(d, state, stats, 0, "delta")
    assert stats["burst_delta_packs"] == 1
    assert stats["rows_reused"] > 0
    assert stats["rows_repacked"] > stats["rows_reused"] >= 6
    assert stats["delta_pack_s"] > 0.0


def full_pack_every_window(structure, queues, cache, scheduler, clock,
                           state=None, min_m=0, window=0, stats=None):
    """The control: ``pack_burst`` in ``pack_burst_cached``'s place."""
    return pack_burst(structure, queues, cache, scheduler, clock,
                      min_m=min_m, window=window), None, False


@pytest.mark.parametrize("wide_key", [False, True])
def test_schedule_burst_decisions_identical_delta_on_off(monkeypatch,
                                                         wide_key):
    """End-to-end drift-fair check: schedule_burst decisions with the
    delta pack and with a full pack at every window are identical, and
    the delta run reuses rows.  With a key the streaming encoder cannot
    hold (``wide_key``) the program itself packs in full every window:
    one bail, no delta pack, the same decisions."""
    def spec(d):
        for c in range(2):
            for q in range(2):
                for i in range(6):
                    d.create_workload(mk(
                        f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 1500,
                        prio=(i % 3) * 10, t=float(10 * c + 3 * q + i)))
        if wide_key:
            d.create_workload(mk("x" * 80, "lq-0-1", 1500, t=99.0))

    runs = {}
    for mode in ("1", "0"):
        if mode == "0":
            monkeypatch.setattr("kueue_tpu.ops.burst.pack_burst_cached",
                                full_pack_every_window)
        d, clock = build_cluster()
        spec(d)

        def tick(_k, clock=clock):
            clock.t += 1.0

        stats = d.schedule_burst(6, runtime=2, on_cycle_start=tick)
        d.create_workload(mk("late", "lq-1-1", 1500, t=200.0))
        stats += d.schedule_burst(6, runtime=2, on_cycle_start=tick)
        runs[mode] = (
            [(sorted(s.admitted), sorted(s.skipped),
              sorted(s.inadmissible), sorted(s.preempted_targets))
             for s in stats],
            d.admitted_keys(),
            dict(d._burst_solver.stats))
    assert runs["1"][0] == runs["0"][0]
    assert runs["1"][1] == runs["0"][1]
    assert runs["0"][2]["burst_delta_packs"] == 0
    on = runs["1"][2]
    assert on["burst_full_packs"] >= 1
    if wide_key:
        assert on["stream_pack_bails"] == 1
        assert on["burst_full_packs"] == 2
        assert on["burst_delta_packs"] == 0
        return
    # the pipelined boundary may skip host packs entirely; when more
    # than one host pack ran, at least one must have been a delta pack
    if on["burst_full_packs"] + on["burst_delta_packs"] > 1:
        assert on["burst_delta_packs"] >= 1


def build_wide_cluster(n_cqs=24):
    clock = Clock()
    d = Driver(clock=clock, use_device_solver=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    for i in range(n_cqs):
        d.apply_cluster_queue(ClusterQueue(
            name=f"w-{i}", cohort=f"co-{i % 4}",
            queueing_strategy=QueueingStrategy.BEST_EFFORT_FIFO,
            resource_groups=[ResourceGroup(
                covered_resources=["cpu"],
                flavors=[FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=4000,
                                         borrowing_limit=2000)})])]))
        d.apply_local_queue(LocalQueue(name=f"wlq-{i}",
                                       cluster_queue=f"w-{i}"))
    return d, clock


def test_delta_pack_full_fallback_at_high_dirty_share():
    """Above the dirty-share threshold a delta walk rebuilds nearly
    everything plus bookkeeping, so the boundary takes (and counts) a
    plain full pack; a sparse boundary goes back to the delta path."""
    d, clock = build_wide_cluster(24)
    for i in range(24):
        d.create_workload(mk(f"init-{i}", f"wlq-{i}", 1000, t=float(i)))
    stats = {}
    state = check_step(d, None, stats, 0, "initial")
    assert stats.get("burst_full_packs", 0) == 1
    for i in range(24):   # dirty every CQ: 24 > max(8, 0.5 * 24)
        d.create_workload(mk(f"burst-{i}", f"wlq-{i}", 500,
                             t=100.0 + i))
    state = check_step(d, state, stats, 0, "all-dirty")
    assert stats.get("burst_full_packs", 0) == 2
    assert stats.get("burst_delta_packs", 0) == 0
    d.create_workload(mk("tail-0", "wlq-0", 500, t=200.0))
    d.create_workload(mk("tail-1", "wlq-1", 500, t=201.0))
    state = check_step(d, state, stats, 0, "sparse")
    assert stats.get("burst_delta_packs", 0) == 1
