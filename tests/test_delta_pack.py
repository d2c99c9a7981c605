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


def check_step(d, state, stats, window, ctx, min_m=0, seen=None):
    """One boundary: delta pack vs fresh pack of the same live state;
    ``seen``, a list, gets the fresh plan."""
    st = current_structure(d)
    plan_d, state, _ = pack_burst_cached(
        st, d.queues, d.cache, d.scheduler, d.clock,
        state=state, min_m=min_m, window=window, stats=stats)
    plan_f = pack_burst(st, d.queues, d.cache, d.scheduler, d.clock,
                        min_m=min_m, window=window)
    assert_plans_equal(plan_d, plan_f, ctx)
    if seen is not None:
        seen.append(plan_f)
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


@pytest.mark.parametrize("window", [0, 4])
def test_plans_agree_over_the_envelope(monkeypatch, window):
    """The benchmark cells' windows: a forest's candidate slots ``L * M``
    pass ``KC_CAP`` (lowered here, as tests/test_burst_hold.py does), so
    every forest is out of the in-kernel preemption envelope.  The
    streaming plan still equals the full pack's, poison included, at
    every boundary of a mutation storm on a preempting cluster."""
    from kueue_tpu.ops import burst
    monkeypatch.setattr(burst, "KC_CAP", 32)
    plans, deltas = [], 0
    for seed in range(3):
        rng = random.Random(4600 + seed)
        d, clock = build_cluster(seed, preempt=True)
        names = _counter()
        for i in range(6):
            d.create_workload(mk(f"init{i}", f"lq-{i % 2}-{i // 3}",
                                 2000, prio=(i % 3) * 10, t=float(i)))
        stats = {}
        state = None
        for step in range(10):
            state = check_step(d, state, stats, window,
                               f"seed{seed}:step{step}", min_m=32,
                               seen=plans)
            random_mutation(rng, d, clock, names)
        deltas += stats.get("burst_delta_packs", 0)
    plans = [p for p in plans if p is not None]
    assert deltas > 0 and len(plans) >= 10
    for plan in plans:
        assert plan.KC == 32 < plan.L * plan.M
        assert not np.asarray(plan.arrays["preempt_ok"]).any()


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


def fresh_records(d, window):
    """The full walk's records of the live state, CQ by CQ."""
    from kueue_tpu.ops.burst import _walk_records
    return _walk_records(current_structure(d), d.queues, d.cache,
                         d.scheduler, window)


def assert_records_match(d, state, window, ctx=""):
    """What a record carries beside its rows comes out as a full walk
    gives it: the rows' keys, the pending count, ``bad`` and the
    compressed rows' count and newest reservation."""
    for old, new in zip(state.records, fresh_records(d, window)):
        at = f"{ctx}: cq {new.ci}"
        assert sorted(old.keys.tolist()) == sorted(new.keys.tolist()), at
        assert (old.n_pend, old.n_adm) == (new.n_pend, new.n_adm), at
        assert old.bad == new.bad and old.bad_keys == new.bad_keys, at
        assert old.n_comp == new.n_comp, at
        assert old.comp_max_ts == new.comp_max_ts, at
        assert old.truncated == new.truncated, at


def evict(d, key):
    """An eviction that requeues, as a preemption applies it."""
    d._evict(d.workloads[key], "Preempted", "test eviction")


def admitted_obj(d, key):
    """The object the cache's admitted table holds for ``key``."""
    owner = d.workloads[key].admission.cluster_queue
    return owner, d.cache.cluster_queue(owner).workloads[key].obj


def _case_every_queue_dirty(d, clock, step):
    """Every queue dirty at once: the cells' case, far above the share
    at which the pack used to fall back to the full walk."""
    for c in range(2):
        for q in range(2):
            for i in range(5):
                d.create_workload(mk(f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 1500,
                                     prio=(i % 3) * 10, t=float(i)))
    step("init")
    for rnd in range(3):
        clock.t += 1.0
        d.schedule_once()
        for key in sorted(d.admitted_keys())[:2]:
            d.finish_workload(key)
        for c in range(2):
            for q in range(2):
                d.create_workload(mk(f"late-{rnd}-{c}-{q}", f"lq-{c}-{q}",
                                     500, t=100.0 + rnd))
        step(f"round {rnd}")
    return {"full": 1, "delta": 3}


def _case_same_key_comes_and_goes(d, clock, step):
    """In one interval: a finish, an admission, an eviction and a
    re-admission of one key; then the key evicted and left pending."""
    for i in range(4):
        d.create_workload(mk(f"w{i}", "lq-0-0", 1500, prio=10 * (i % 2),
                             t=float(i)))
    for _ in range(3):
        clock.t += 1.0
        d.schedule_once()
    step("init")
    held = sorted(d.admitted_keys())
    assert len(held) >= 2
    d.finish_workload(held[0])
    evict(d, held[1])
    clock.t += 1.0
    d.schedule_once()                  # held[1] (or a peer) admitted again
    evict(d, held[1]) if held[1] in d.admitted_keys() else None
    clock.t += 1.0
    d.schedule_once()
    step("finish+evict+readmit")
    for key in sorted(d.admitted_keys()):
        evict(d, key)                  # went, and now pending again
    step("all evicted")
    clock.t += 1.0
    d.schedule_once()
    step("readmitted")
    return {"full": 1, "delta": 3}


def _case_truncated_head_admitted(d, clock, step, window=2):
    """A record cut to window + 2 pending rows loses its head to an
    admission: the next row below the cut comes in by itself."""
    for i in range(9):
        d.create_workload(mk(f"w{i}", "lq-0-0", 1000, t=float(i)))
    state = step("init")
    assert state.records[0].truncated
    assert state.records[0].n_pend == window + 2
    for _ in range(3):
        clock.t += 1.0
        d.schedule_once()
        state = step("head admitted")
        assert state.records[0].n_pend == min(
            window + 2, len(d.queues.queue_for("cq-0-0").heap.items())
            + len(d.queues.queue_for("cq-0-0").inadmissible))
    return {"full": 1, "delta": 3}


def _case_admitted_side_empties_and_starts(d, clock, step):
    """One queue loses its last admitted row while another gains its
    first."""
    d.create_workload(mk("only", "lq-0-0", 3000, t=0.0))
    clock.t += 1.0
    d.schedule_once()
    d.create_workload(mk("first", "lq-1-1", 3000, t=1.0))
    d.create_workload(mk("waits", "lq-0-1", 1000, t=2.0))
    state = step("init")
    assert [r.n_adm for r in state.records] == [1, 0, 0, 0]
    d.finish_workload("default/only")
    clock.t += 1.0
    d.schedule_once()
    d.create_workload(mk("more", "lq-0-0", 1000, t=3.0))
    state = step("swap")
    assert state.records[0].n_adm == 0 and state.records[3].n_adm == 1
    return {"full": 1, "delta": 1}


def _case_bad_comes_and_goes(d, clock, step, pending_dirt=False):
    """An admitted workload turns ``bad`` (its Evicted condition set
    with the quota still held) and stops being so; the change reaches
    the journal as a row touch, alone or beside pending-side dirt of
    the same queue."""
    from kueue_tpu.api.types import WL_EVICTED
    from kueue_tpu.workload import set_evicted_condition
    for i in range(3):
        d.create_workload(mk(f"w{i}", "lq-0-0", 1500, t=float(i)))
    d.create_workload(mk("peer", "lq-0-1", 1500, t=5.0))
    clock.t += 1.0
    d.schedule_once()
    state = step("init")
    key = sorted(d.admitted_keys())[0]
    owner, obj = admitted_obj(d, key)
    ci = current_structure(d).cq_index[owner]
    assert not state.records[ci].bad

    def touch(n):
        d.queues.pack_journal.touch_row(owner, key)
        if pending_dirt:
            d.create_workload(mk(f"dirt{n}", "lq-" + owner[3:], 500,
                                 t=10.0 + n))

    set_evicted_condition(obj, "Preempted", "set ahead of the release",
                          clock.t)
    touch(0)
    state = step("turned bad")
    assert state.records[ci].bad_keys == {key}
    assert not state.plan_preempt_ok[ci]
    del obj.conditions[WL_EVICTED]
    touch(1)
    state = step("good again")
    assert not state.records[ci].bad
    assert key in state.records[ci].keys.tolist()
    return {"full": 1, "delta": 2}


def _case_compressed_counts(d, clock, step):
    """The compress arm (no queue of the forest preempts): admitted rows
    are a count and a newest reservation, kept by the events: the newest
    leaves, an older one leaves, new ones come."""
    for i in range(6):
        d.create_workload(mk(f"w{i}", f"lq-0-{i % 2}", 1500, t=float(i)))
    clock.t += 1.0
    d.schedule_once()
    clock.t += 1.0
    d.schedule_once()
    state = step("init")
    assert sum(r.n_comp for r in state.records) == len(d.admitted_keys()) > 2
    by_res = sorted(d.admitted_keys(), key=lambda k: (
        d.workloads[k].conditions["QuotaReserved"].last_transition_time, k))
    d.finish_workload(by_res[-1])      # the newest reservation goes
    state = step("newest gone")
    d.finish_workload(by_res[0])
    clock.t += 1.0
    d.schedule_once()                  # and newer ones come
    state = step("older gone, new came")
    assert sum(r.n_adm for r in state.records) == 0
    return {"full": 1, "delta": 2}


def _case_labelled_rows_that_came(d, clock, step):
    """A labelled queue: the rows that come carry their own masks."""
    from tests.test_flavor_eligibility import (
        TOLERATES_SPOT, head, heads_of_one_cohort_search_different_columns,
        pod_set)
    heads_of_one_cohort_search_different_columns(d)
    state = step("init")
    head(d, "late", "a", pod_set(selector={"instance-type": "on-demand"}),
         priority=0, created=1000.0)
    clock.t += 1.0
    d.schedule_once()
    for n in range(2):
        head(d, f"more-{n}", "b", pod_set(tolerations=[TOLERATES_SPOT]),
             created=2000.0 + n)
        clock.t += 1.0
        d.schedule_once()
        state = step(f"came {n}")
    skip = state.last_plan.arrays["wl_flavor_skip"]
    at = {k.split("/")[1]: int(skip[c, m, 0])
          for k, (c, m) in state.last_plan.row_of_key.items()}
    assert at["late"] == 0b1101 and at["more-1"] == 0b0000
    assert at["own-a-reserved"] == 0b1100
    return {"full": 1, "delta": 2}


def _case_m_grows_a_bucket(d, clock, step):
    """The widest queue outgrows the grid's M inside a delta window."""
    for i in range(4):
        d.create_workload(mk(f"w{i}", "lq-0-0", 1000, t=float(i)))
    clock.t += 1.0
    d.schedule_once()
    state = step("init")
    assert state.M == 4
    for i in range(4, 9):
        d.create_workload(mk(f"w{i}", "lq-0-0", 1000, t=float(i)))
    d.finish_workload(sorted(d.admitted_keys())[0])
    state = step("grown")
    assert state.M == 8
    for key in sorted(d.admitted_keys()):
        d.finish_workload(key)
    state = step("drained")            # M is sticky only through min_m
    return {"full": 1, "delta": 2}


def _case_dropped_touch_forces_the_full_pack(d, clock, step):
    """A lost journal update (chaos ``journal.drop_touch``) taints the
    journal: the next window packs in full, the one after is a delta."""
    from kueue_tpu.chaos import injector as chaos
    from kueue_tpu.chaos.injector import ChaosInjector
    for i in range(4):
        d.create_workload(mk(f"w{i}", f"lq-0-{i % 2}", 1500, t=float(i)))
    clock.t += 1.0
    d.schedule_once()
    step("init")
    try:
        chaos.install(ChaosInjector(seed=3)).arm("journal.drop_touch", at=1)
        d.finish_workload(sorted(d.admitted_keys())[0])   # its event is lost
    finally:
        chaos.clear()
    assert d.cache.pack_journal.tainted
    step("tainted")
    d.finish_workload(sorted(d.admitted_keys())[0])
    step("after")
    return {"full": 2, "delta": 1}


ROW_GRADE_CASES = {
    "every_queue_dirty": (_case_every_queue_dirty, {"preempt": True}, 0),
    "same_key_comes_and_goes": (
        _case_same_key_comes_and_goes, {"preempt": True}, 0),
    "truncated_head_admitted": (
        _case_truncated_head_admitted, {"preempt": True}, 2),
    "admitted_side_empties_and_starts": (
        _case_admitted_side_empties_and_starts, {"preempt": True}, 0),
    "bad_comes_and_goes": (_case_bad_comes_and_goes, {"preempt": True}, 0),
    "bad_comes_and_goes_beside_pending_dirt": (
        lambda d, clock, step: _case_bad_comes_and_goes(
            d, clock, step, pending_dirt=True), {"preempt": True}, 0),
    "compressed_counts": (_case_compressed_counts, {"preempt": False}, 0),
    "labelled_rows_that_came": (_case_labelled_rows_that_came, None, 0),
    "m_grows_a_bucket": (_case_m_grows_a_bucket, {"preempt": True}, 0),
    "dropped_touch_forces_the_full_pack": (
        _case_dropped_touch_forces_the_full_pack, {"preempt": True}, 0),
}


class _Packed:
    """What a case's ``step`` hands back: the pack state, with the
    window's plan and its ``preempt_ok`` beside it."""

    def __init__(self, state, plan):
        self._state = state
        self.last_plan = plan
        self.plan_preempt_ok = (None if plan is None
                                else plan.arrays["preempt_ok"])

    def __getattr__(self, name):
        return getattr(self._state, name)


@pytest.mark.parametrize("case", sorted(ROW_GRADE_CASES))
def test_row_grade_delta_parity(case):
    """Every window after the first is a delta pack, whatever share of
    the queues is dirty, and its plan equals the full pack's of the
    same live state plane for plane; what the records carry beside
    their rows equals a full walk's."""
    fn, cluster, window = ROW_GRADE_CASES[case]
    if cluster is None:
        clock = Clock()
        d = Driver(clock=clock, use_device_solver=True)
    else:
        d, clock = build_cluster(**cluster)
    stats = {}
    held = {"state": None}

    def step(ctx):
        st = current_structure(d)
        plan, state, _ = pack_burst_cached(
            st, d.queues, d.cache, d.scheduler, d.clock,
            state=held["state"], window=window, stats=stats)
        assert_plans_equal(
            plan, pack_burst(st, d.queues, d.cache, d.scheduler, d.clock,
                             window=window), f"{case}: {ctx}")
        assert_records_match(d, state, window, f"{case}: {ctx}")
        held["state"] = state
        return _Packed(state, plan)

    want = fn(d, clock, step)
    assert stats.get("burst_full_packs", 0) == want["full"]
    assert stats.get("burst_delta_packs", 0) == want["delta"]
    assert stats.get("stream_pack_desyncs", 0) == 0


def test_a_delta_window_counts_the_rows_it_derived():
    """``rows_repacked`` in a delta window is the rows derived from an
    ``Info``: the admitted rows that came and the pending rows whose
    ``Info`` is new, not every row of every dirty queue."""
    d, clock = build_cluster(preempt=True)
    for i in range(12):
        d.create_workload(mk(f"w{i}", "lq-0-0", 400, t=float(i)))
    for _ in range(3):
        clock.t += 1.0
        d.schedule_once()
    stats = {}
    state = check_step(d, None, stats, 0, "full")
    n_adm = len(d.admitted_keys())
    assert n_adm >= 3 and stats["rows_repacked"] == 12
    clock.t += 1.0
    d.schedule_once()                   # one more row admitted
    assert len(d.admitted_keys()) == n_adm + 1
    state = check_step(d, state, stats, 0, "delta")
    pending = 12 - n_adm - 1
    assert stats["rows_repacked"] == 12 + 1
    assert stats["rows_reused"] == n_adm + pending
    # an update swaps a pending workload's Info: that row is derived
    # again, with the one that arrives
    wl = mk("w11", "lq-0-0", 500, t=11.0)
    d.workloads[wl.key] = wl
    d.queues.add_or_update_workload(wl)
    d.create_workload(mk("w12", "lq-0-0", 400, t=12.0))
    state = check_step(d, state, stats, 0, "update")
    assert stats["rows_repacked"] == 12 + 1 + 2
    assert stats["rows_reused"] == 2 * (n_adm + pending) + 1 - 1


@pytest.mark.parametrize("asks", [False, True])
def test_journal_admitted_events_drain_by_row_or_by_queue(asks):
    """``touch_admitted`` names the row beside the queue: a consumer
    that asks for the channel gets ``{cq: {key: came}}``, the last
    event of a key standing, less the queues hard-dirty without a key;
    one that does not gets each event's queue as hard dirt."""
    from kueue_tpu.utils.journal import PackJournal
    j = PackJournal()
    j.drain_into(set(), {})            # clear the fresh journal's dirty-all
    j.touch_admitted("cq-a", "k1", True)
    j.touch_admitted("cq-a", "k1", False)
    j.touch_admitted("cq-a", "k2", True)
    j.touch_admitted("cq-b", "k3", True)
    j.touch("cq-b")                    # keyless: the whole queue
    j.touch_row("cq-a", "k9")
    dirty, rows, events, ranges = set(), {}, {}, []
    was_all = j.drain_into(
        dirty, {}, row_of={"cq-a": 0, "cq-b": 1}, ranges_out=ranges,
        rows_out=rows, admitted_out=events if asks else None)
    assert was_all is False and not j.admitted
    assert ranges == [(0, 2)]
    if asks:
        assert dirty == {"cq-b"}
        assert events == {"cq-a": {"k1": False, "k2": True}}
        assert rows == {"k9": "cq-a"}
    else:
        assert dirty == {"cq-a", "cq-b"} and rows == {} and events == {}


def test_delta_pack_runs_at_any_dirty_share():
    """Every queue dirty, and then two of twenty-four: both windows are
    delta packs (the pack no longer gives up at a dirty share)."""
    d, clock = build_wide_cluster(24)
    for i in range(24):
        d.create_workload(mk(f"init-{i}", f"wlq-{i}", 1000, t=float(i)))
    stats = {}
    state = check_step(d, None, stats, 0, "initial")
    assert stats.get("burst_full_packs", 0) == 1
    for i in range(24):   # dirty every CQ
        d.create_workload(mk(f"burst-{i}", f"wlq-{i}", 500,
                             t=100.0 + i))
    state = check_step(d, state, stats, 0, "all-dirty")
    assert stats.get("burst_full_packs", 0) == 1
    assert stats.get("burst_delta_packs", 0) == 1
    d.create_workload(mk("tail-0", "wlq-0", 500, t=200.0))
    d.create_workload(mk("tail-1", "wlq-1", 500, t=201.0))
    state = check_step(d, state, stats, 0, "sparse")
    assert stats.get("burst_delta_packs", 0) == 2
    assert not hasattr(__import__("kueue_tpu.ops.stream_pack",
                                  fromlist=["x"]), "_DELTA_MAX_DIRTY_FRAC")


# ---------------------------------------------------------------------------
# A plan's snapshot is brought up to date from the plan before it
# ---------------------------------------------------------------------------

KINDS = ("one_flavor", "four_plain", "four_labelled", "two_groups",
         "two_podsets")


def build_kind(kind):
    """Four queues in two cohorts, full enough to preempt, in the shape
    of one of the five deployment kinds; returns (driver, clock, a
    maker of the kind's workloads)."""
    from kueue_tpu.api.types import (FlavorFungibility,
                                     FlavorFungibilityPolicy, Taint,
                                     Toleration)
    if kind == "one_flavor":
        d, clock = build_cluster(preempt=True)
        return d, clock, lambda name, lq, cpu, prio, t, k: mk(
            name, lq, cpu, prio=prio, t=t)
    clock = Clock()
    d = Driver(clock=clock, use_device_solver=True)
    spot = Taint(key="spot", value="true", effect="NoSchedule")
    GI = 1 << 30
    if kind in ("four_plain", "four_labelled"):
        labelled = kind == "four_labelled"
        flavors = [
            ResourceFlavor(name=n, node_labels=(
                {"instance-type": n.split("-")[0]} if labelled else {}),
                node_taints=([spot] if labelled and "spot" in n else []))
            for n in ("reserved", "on-demand", "spot-a", "spot-b")]
        groups = [ResourceGroup(
            covered_resources=["cpu"],
            flavors=[FlavorQuotas(name=f.name, resources={
                "cpu": ResourceQuota(nominal=1500, borrowing_limit=1000)})
                for f in flavors])]
    else:
        flavors = [
            ResourceFlavor(name="x86", node_labels={"cpu-arch": "x86"}),
            ResourceFlavor(name="arm", node_labels={"cpu-arch": "arm"}),
            ResourceFlavor(name="default-flavor")]
        groups = [
            ResourceGroup(covered_resources=["cpu"], flavors=[
                FlavorQuotas(name=n, resources={"cpu": ResourceQuota(
                    nominal=3000, borrowing_limit=1000)})
                for n in ("x86", "arm")]),
            ResourceGroup(covered_resources=["memory"], flavors=[
                FlavorQuotas(name="default-flavor", resources={
                    "memory": ResourceQuota(nominal=24 * GI,
                                            borrowing_limit=8 * GI)})])]
    for f in flavors:
        d.apply_resource_flavor(f)
    for c in range(2):
        for q in range(2):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-{c}-{q}", cohort=f"co-{c}",
                flavor_fungibility=FlavorFungibility(
                    when_can_preempt=FlavorFungibilityPolicy.TRY_NEXT_FLAVOR),
                preemption=PreemptionPolicy(
                    reclaim_within_cohort=ReclaimWithinCohort.ANY,
                    within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY),
                queueing_strategy=QueueingStrategy.BEST_EFFORT_FIFO,
                resource_groups=groups))
            d.apply_local_queue(LocalQueue(name=f"lq-{c}-{q}",
                                           cluster_queue=f"cq-{c}-{q}"))
    tolerates = Toleration(key="spot", operator="Exists",
                           effect="NoSchedule")

    def make(name, lq, cpu, prio, t, k):
        if kind == "four_plain":
            sets = [PodSet(name="main", count=1, requests={"cpu": cpu})]
        elif kind == "four_labelled":
            # four job classes, three of them constrained
            sets = [PodSet(
                name="main", count=1, requests={"cpu": cpu},
                node_selector=[{}, {"instance-type": "on-demand"},
                               {"instance-type": "spot"}, {}][k % 4],
                tolerations=[tolerates] if k % 4 >= 2 else [])]
        else:
            sel = [{}, {"cpu-arch": "arm"}, {"cpu-arch": "x86"}][k % 3]
            req = {"cpu": cpu, "memory": 2 * GI}
            sets = [PodSet(name="workers", count=1, requests=dict(req),
                           node_selector=sel)]
            if kind == "two_podsets" and k % 3:
                sets.insert(0, PodSet(name="launcher", count=1,
                                      requests=dict(req),
                                      node_selector={"cpu-arch": "x86"}))
        return Workload(name=name, queue_name=lq, priority=prio,
                        creation_time=t, pod_sets=sets)
    return d, clock, make


def assert_plan_is_the_arenas(d, plan, ctx):
    """Every plane the arena snapshots for a plan equals the arena's
    live view of it, and the plan owns it."""
    from kueue_tpu.ops.stream_pack import _ROW_PLANES
    arena = d.cache._pack_arena
    for name in _ROW_PLANES:
        got = plan.arrays[name]
        live = arena.view(name, got.shape)
        assert got.dtype == live.dtype, f"{ctx}: {name} dtype"
        assert not np.shares_memory(got, live), f"{ctx}: {name}"
        assert np.array_equal(got, live), \
            f"{ctx}: {name} differs from the arena at " \
            f"{np.argwhere(got != live)[:5].tolist()}"
    live = arena.view("keys_grid", (plan.C, plan.M))
    assert plan.keys.tolist() == live.tolist(), f"{ctx}: keys"


@pytest.mark.parametrize("kind", KINDS)
def test_a_delta_windows_snapshot_is_the_whole_copys(monkeypatch, kind):
    """Window after window on each deployment kind's shape, with runs
    short enough that a queue's rows take several: after every
    ``_materialize`` the plan's planes and keys equal the arena's live
    views and a from-scratch pack, though a delta window copied only
    the cells under its ``row_extent`` into the buffer the last plan
    left.  Admissions, finishes, evictions, a queue that shrinks, and
    one that grows past M (a new shape: the whole copy, then the chain
    again)."""
    from kueue_tpu.ops import burst as _b
    monkeypatch.setattr(_b, "RESIDENT_RUN", 4)
    d, clock, make = build_kind(kind)
    k = 0
    for c in range(2):
        for q in range(2):
            for i in range(6):
                k += 1
                d.create_workload(make(
                    f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 1500, (i % 3) * 10,
                    float(10 * c + 3 * q + i), k))
    stats = {}
    box = {"state": None, "min_m": 16}

    def window(ctx):
        st = current_structure(d)
        plan, box["state"], _ = pack_burst_cached(
            st, d.queues, d.cache, d.scheduler, d.clock,
            state=box["state"], min_m=box["min_m"], window=0, stats=stats)
        assert plan is not None, ctx
        assert_plan_is_the_arenas(d, plan, f"{kind}:{ctx}")
        assert_plans_equal(plan, pack_burst(
            st, d.queues, d.cache, d.scheduler, d.clock,
            min_m=box["min_m"], window=0), f"{kind}:{ctx}")
        box["min_m"] = max(box["min_m"], plan.M)
        return plan.M, plan.prev_token is not None

    def counted():
        return tuple(stats.get("pack_arena_snapshots_" + n, 0)
                     for n in ("delta", "whole", "fresh"))

    def cycles(n):
        for _ in range(n):
            clock.t += 1.0
            d.schedule_once()

    def admissions():
        cycles(2)
        assert d.admitted_keys()

    def finishes():
        for key in sorted(d.admitted_keys())[:2]:
            d.finish_workload(key)

    def evictions():
        before = d.admitted_keys()
        for i in range(3):
            d.create_workload(make(f"urgent-{i}", "lq-0-0", 1500, 100,
                                   clock.t + i * 1e-3, 3))
        cycles(4)
        assert before - d.admitted_keys(), "nothing was evicted"

    def arrivals():
        for i in range(3):
            d.create_workload(make(f"late-{i}", f"lq-{i % 2}-1", 1000, 5,
                                   clock.t + i * 1e-3, i))

    def a_queue_shrinks():
        for key in [x for x in sorted(d.workloads) if "w-1-0-" in x][1:]:
            if key in d.admitted_keys():
                d.finish_workload(key)
            else:
                d.delete_workload(key)

    def a_queue_grows_past_m():
        for i in range(box["min_m"]):
            d.create_workload(make(f"more-{i}", "lq-1-1", 1000, 5 * (i % 4),
                                   clock.t + i * 1e-3, i))

    m0, _ = window("init")
    assert counted() == (0, 16, 16)
    chains = 0
    steps = [admissions, evictions, finishes, arrivals, a_queue_shrinks,
             lambda: cycles(1), a_queue_grows_past_m, arrivals, finishes]
    for n, step in enumerate(steps):
        step()
        before = counted()
        m, chained = window(f"{n}:{getattr(step, '__name__', 'cycle')}")
        delta, whole, fresh = (b - a for a, b in zip(before, counted()))
        assert delta + whole == 16
        if not chained:
            # the planes were laid out again: a gang's second PodSet
            assert kind == "two_podsets" and delta == 0
        elif step is a_queue_grows_past_m:
            # new shapes, nothing to bring up to date; a mask plane of
            # one column has no M and keeps its buffer
            assert m > m0 and delta <= 1 and fresh == 16 - delta
        else:
            assert (delta, whole) == (16, 0), (n, counted())
            chains += 1
    assert chains >= 6
    assert stats["burst_full_packs"] + stats["burst_delta_packs"] == (
        1 + len(steps))


def test_a_plans_finishes_are_gone_from_the_next_windows_snapshot(
        monkeypatch):
    """``schedule_burst(K, runtime=2)`` has ``_fill_burst_finishes``
    write finish cycles into the ``death0`` its plan owns.  The next
    delta window brings that very buffer up to date by the cells under
    its ``row_extent``; the finishes were rows of the plan before, so
    they lie under it and the arena's constant plane comes back over
    them: every plan handed to the driver equals the arena's views and
    a from-scratch pack before the driver writes into it."""
    from kueue_tpu.ops import burst as _b
    monkeypatch.setattr(_b, "RESIDENT_RUN", 4)
    d, clock = build_cluster(preempt=True)
    for c in range(2):
        for q in range(2):
            for i in range(8):
                d.create_workload(mk(
                    f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 1500,
                    prio=(i % 3) * 10, t=float(10 * c + 3 * q + i)))
    real_pack = _b.pack_burst_cached
    real_fill = Driver._fill_burst_finishes
    wrote, followed = set(), []

    def checked_pack(structure, queues, cache, scheduler, clk, **kw):
        plan, state, was_delta = real_pack(structure, queues, cache,
                                           scheduler, clk, **kw)
        if plan is not None:
            ctx = f"window {plan.pack_token}"
            assert_plan_is_the_arenas(d, plan, ctx)
            assert (plan.arrays["death0"] == _b.I32_MAX).all(), ctx
            assert_plans_equal(plan, pack_burst(
                structure, queues, cache, scheduler, clk,
                min_m=kw.get("min_m", 0), window=kw.get("window", 0)), ctx)
            if plan.prev_token in wrote:
                followed.append(plan.pack_token)
        return plan, state, was_delta

    def noting_fill(self, st, plan, *a):
        ok = real_fill(self, st, plan, *a)
        if plan.finite_deaths:
            assert (plan.arrays["death0"] != _b.I32_MAX).any()
            wrote.add(plan.pack_token)
        return ok

    monkeypatch.setattr("kueue_tpu.ops.burst.pack_burst_cached",
                        checked_pack)
    monkeypatch.setattr(Driver, "_fill_burst_finishes", noting_fill)

    def tick(_k):
        clock.t += 1.0

    for _ in range(2):
        tick(0)
        d.schedule_once()
    for n in range(4):
        # a finish the caller schedules and the ones ``runtime`` models:
        # both are rows of the plan, written into its ``death0``
        d.schedule_burst(6, runtime=2, on_cycle_start=tick, pipeline=False,
                         external_finishes={1: sorted(d.admitted_keys())[:1]})
        d.create_workload(mk(f"late-{n}", f"lq-{n % 2}-1", 1500,
                             prio=10 * n, t=clock.t + 0.5))
    bs = d._burst_solver.stats
    assert wrote and followed, (wrote, followed)
    assert bs["pack_arena_snapshots_delta"] > 0
    assert bs["pack_arena_snapshots_delta"] % 16 == 0
