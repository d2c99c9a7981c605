"""Per-cycle decision parity: the fully device-decided cycle (classify_np
+ admit_scan with capacity reserves) must match the host admit loop
cycle-for-cycle — admissions (and their order), skips, inadmissible sets,
and assigned flavors — across multi-cycle runs with finishes, borrowing
races, and preempt-classified heads."""

import random

import pytest

from kueue_tpu.api.types import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    ReclaimWithinCohort,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    WithinClusterQueue,
    Workload,
)
from kueue_tpu.controller.driver import Driver
from tests.conftest import FakeClock


def build_driver(seed, use_device, n_cohorts=2, cqs_per_cohort=3, n_wl=60,
                 preemption=True):
    rng = random.Random(seed)
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    pre = (PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
        if preemption else PreemptionPolicy())
    for c in range(n_cohorts):
        for q in range(cqs_per_cohort):
            name = f"cq-{c}-{q}"
            d.apply_cluster_queue(ClusterQueue(
                name=name, cohort=f"cohort-{c}", preemption=pre,
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": ResourceQuota(nominal=4000,
                                             borrowing_limit=8000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{c}-{q}",
                                           cluster_queue=name))
    workloads = []
    for i in range(n_wl):
        c = rng.randrange(n_cohorts)
        q = rng.randrange(cqs_per_cohort)
        workloads.append(Workload(
            name=f"wl-{i}", queue_name=f"lq-{c}-{q}",
            priority=rng.choice([10, 10, 50, 100]),
            creation_time=float(i + 1),
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": rng.choice(
                                 [1000, 2000, 4000])})]))
    return d, clock, workloads


def drive_cycles(d, clock, workloads, n_cycles=40, runtime=2):
    """Create all workloads, run cycles with fake execution; record each
    cycle's decisions."""
    for wl in workloads:
        d.create_workload(wl)
    log = []
    running = []
    for cycle in range(n_cycles):
        clock.t += 1.0
        stats = d.schedule_once()
        admissions = []
        for key in stats.admitted:
            wl = d.workload(key)
            flavors = tuple(sorted(
                (a.name, a.count, tuple(sorted(a.flavors.items())))
                for a in wl.admission.pod_set_assignments))
            admissions.append((key, flavors))
            running.append((cycle + runtime, key))
        log.append({
            "admitted": admissions,
            "skipped": sorted(stats.skipped),
            "inadmissible": sorted(stats.inadmissible),
            "preempting": sorted(stats.preempting),
            "targets": sorted(stats.preempted_targets),
        })
        still = []
        for fin, key in running:
            wl = d.workload(key)
            if wl is None or not wl.has_quota_reservation:
                continue
            if fin <= cycle:
                d.finish_workload(key)
            else:
                still.append((fin, key))
        running = still
    return log


@pytest.mark.parametrize("seed,preemption", [
    (11, True), (12, True), (13, True), (14, True),
    (31, False), (32, False)])
def test_per_cycle_parity_host_vs_device(seed, preemption):
    host, hclock, hwl = build_driver(seed, use_device=False,
                                     preemption=preemption)
    dev, dclock, dwl = build_driver(seed, use_device=True,
                                    preemption=preemption)
    hlog = drive_cycles(host, hclock, hwl)
    dlog = drive_cycles(dev, dclock, dwl)
    for cyc, (h, dv) in enumerate(zip(hlog, dlog)):
        assert h == dv, (
            f"seed {seed} cycle {cyc} diverged:\nhost={h}\ndevice={dv}\n"
            f"stats={dev.scheduler.solver.stats}")
    stats = dev.scheduler.solver.stats
    assert stats["full_cycles"] >= 1, stats
    assert stats["host_cycles"] == 0, stats


def build_preemption_heavy(seed, use_device, n_cohorts=3, cqs_per_cohort=3,
                           n_wl=90):
    """Tight quotas + strong priority split + staggered arrival: later
    high-priority workloads must preempt admitted low-priority ones, so
    cycles carry preempt heads WITH candidates (the in-scan preemption
    path), overlapping-target races, and reclaim across borrowing CQs."""
    rng = random.Random(seed)
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=use_device)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
    for c in range(n_cohorts):
        for q in range(cqs_per_cohort):
            name = f"cq-{c}-{q}"
            d.apply_cluster_queue(ClusterQueue(
                name=name, cohort=f"cohort-{c}", preemption=pre,
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": ResourceQuota(nominal=3000,
                                             borrowing_limit=6000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{c}-{q}",
                                           cluster_queue=name))
    low, high = [], []
    for i in range(n_wl):
        c = rng.randrange(n_cohorts)
        q = rng.randrange(cqs_per_cohort)
        is_high = i % 3 == 2
        wl = Workload(
            name=f"wl-{i}", queue_name=f"lq-{c}-{q}",
            priority=100 if is_high else rng.choice([5, 10]),
            creation_time=float(i + 1),
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": rng.choice(
                                 [1000, 2000, 3000])})])
        (high if is_high else low).append(wl)
    return d, clock, low, high


def drive_two_phase(d, clock, low, high, n_cycles=40, runtime=4):
    """Admit the low-priority wave first, then inject the high wave so
    preemption searches run against real admitted candidates."""
    for wl in low:
        d.create_workload(wl)
    log = []
    running = []

    def one_cycle(cycle):
        clock.t += 1.0
        stats = d.schedule_once()
        admissions = []
        for key in stats.admitted:
            wl = d.workload(key)
            flavors = tuple(sorted(
                (a.name, a.count, tuple(sorted(a.flavors.items())))
                for a in wl.admission.pod_set_assignments))
            admissions.append((key, flavors))
            running.append((cycle + runtime, key))
        log.append({
            "admitted": admissions,
            "skipped": sorted(stats.skipped),
            "inadmissible": sorted(stats.inadmissible),
            "preempting": sorted(stats.preempting),
            "targets": sorted(stats.preempted_targets),
        })
        still = []
        for fin, key in running:
            wl = d.workload(key)
            if wl is None or not wl.has_quota_reservation:
                continue
            if fin <= cycle:
                d.finish_workload(key)
            else:
                still.append((fin, key))
        running[:] = still

    for cycle in range(6):
        one_cycle(cycle)
    for wl in high:
        d.create_workload(wl)
    for cycle in range(6, n_cycles):
        one_cycle(cycle)
    return log


@pytest.mark.parametrize("seed", [21, 22, 23, 24, 25])
def test_preemption_cycle_parity_host_vs_device(seed):
    host, hclock, hlow, hhigh = build_preemption_heavy(seed, use_device=False)
    dev, dclock, dlow, dhigh = build_preemption_heavy(seed, use_device=True)
    hlog = drive_two_phase(host, hclock, hlow, hhigh)
    dlog = drive_two_phase(dev, dclock, dlow, dhigh)
    preempted_any = any(cyc["preempting"] for cyc in hlog)
    assert preempted_any, f"seed {seed}: scenario produced no preemptions"
    for cyc, (h, dv) in enumerate(zip(hlog, dlog)):
        assert h == dv, (
            f"seed {seed} cycle {cyc} diverged:\nhost={h}\ndevice={dv}\n"
            f"stats={dev.scheduler.solver.stats}")
    stats = dev.scheduler.solver.stats
    assert stats["host_cycles"] == 0, stats
    # the device path must have decided preemption cycles in-scan, with
    # targets found by the device preemption search
    assert dev.scheduler.preemptor.stats["device_searches"] >= 1, \
        dev.scheduler.preemptor.stats


def test_reserve_path_runs_on_device():
    """Equal-priority contention: the pending head classifies
    preempt-capable with zero candidates → the device cycle reserves
    capacity and stays fully device-decided (no host fallback)."""
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    d.apply_cluster_queue(ClusterQueue(
        name="cq",
        preemption=PreemptionPolicy(
            within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY),
        resource_groups=[ResourceGroup(covered_resources=["cpu"], flavors=[
            FlavorQuotas(name="default",
                         resources={"cpu": ResourceQuota(nominal=2000)})])]))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    d.create_workload(Workload(name="a", queue_name="lq", priority=50,
                               creation_time=1.0,
                               pod_sets=[PodSet(name="main", count=1,
                                                requests={"cpu": 2000})]))
    d.create_workload(Workload(name="b", queue_name="lq", priority=50,
                               creation_time=2.0,
                               pod_sets=[PodSet(name="main", count=1,
                                                requests={"cpu": 2000})]))
    d.schedule_once()   # admits a
    d.schedule_once()   # b: preempt-capable, equal priority → no candidates
    stats = d.scheduler.solver.stats
    assert stats["reserve_entries"] >= 1, stats
    assert stats["full_cycles"] >= 2, stats
    assert d.admitted_keys() == {"default/a"}
    # b parked with the host-identical insufficient-quota message
    b = d.workload("default/b")
    assert b is not None and not b.has_quota_reservation


def test_drain_scenario_device_share_gate():
    """Regression gate for VERDICT weak item 5: on the bench drain
    scenario shape every cycle must stay fully device-decided (no silent
    eligibility shrink).  If a change makes the solver fall back, this
    fails before the bench regresses."""
    clock = FakeClock()
    d = Driver(clock=clock, use_device_solver=True)
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    for c in range(2):
        for q in range(3):
            name = f"cq-{c}-{q}"
            d.apply_cluster_queue(ClusterQueue(
                name=name, cohort=f"cohort-{c}",
                preemption=PreemptionPolicy(
                    reclaim_within_cohort=ReclaimWithinCohort.ANY,
                    within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY),
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": ResourceQuota(nominal=20_000,
                                             borrowing_limit=100_000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{c}-{q}",
                                           cluster_queue=name))
            i = 0
            for cls, count, units, prio in (("small", 10, 1, 50),
                                            ("medium", 4, 5, 100),
                                            ("large", 2, 20, 200)):
                for k in range(count):
                    i += 1
                    d.create_workload(Workload(
                        name=f"{cls}-{c}-{q}-{k}", queue_name=f"lq-{c}-{q}",
                        priority=prio, creation_time=float(i),
                        pod_sets=[PodSet(name="main", count=1,
                                         requests={"cpu": units * 1000})]))
    running = []
    finished = 0
    total = 96
    for cycle in range(400):
        if finished >= total:
            break
        clock.t += 1.0
        stats = d.schedule_once()
        for key in stats.admitted:
            running.append((cycle + 2, key))
        still = []
        for fin, key in running:
            wl = d.workload(key)
            if wl is None or not wl.has_quota_reservation:
                continue
            if fin <= cycle:
                d.finish_workload(key)
                finished += 1
            else:
                still.append((fin, key))
        running = still
    assert finished == total
    s = d.scheduler.solver.stats
    assert s["host_cycles"] == 0, (
        f"drain scenario regressed off the device path: {s}")
    assert s["full_cycles"] >= 1, s


def test_skip_race_matches_host():
    """Two borrowing heads race for the same cohort headroom: the first
    admits, the second must be SKIPPED (scheduler.go:245) — identically on
    both paths."""
    logs = []
    for use_device in (False, True):
        clock = FakeClock()
        d = Driver(clock=clock, use_device_solver=use_device)
        d.apply_resource_flavor(ResourceFlavor(name="default"))
        for i in range(2):
            d.apply_cluster_queue(ClusterQueue(
                name=f"cq-{i}", cohort="team",
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"],
                    flavors=[FlavorQuotas(name="default", resources={
                        "cpu": ResourceQuota(nominal=1000,
                                             borrowing_limit=2000)})])]))
            d.apply_local_queue(LocalQueue(name=f"lq-{i}",
                                           cluster_queue=f"cq-{i}"))
        # each wants 2000: fits only by borrowing the cohort's slack (the
        # other CQ's unused 1000); the first admission consumes it
        for i in range(2):
            d.create_workload(Workload(
                name=f"w{i}", queue_name=f"lq-{i}",
                creation_time=float(i + 1),
                pod_sets=[PodSet(name="main", count=1,
                                 requests={"cpu": 2000})]))
        stats = d.schedule_once()
        logs.append((list(stats.admitted), sorted(stats.skipped),
                     sorted(stats.inadmissible)))
    assert logs[0] == logs[1], logs
    admitted, skipped, _ = logs[1]
    assert len(admitted) == 1 and len(skipped) == 1, logs


def test_classify_np_matches_jitted_classify():
    """The entry point's pack through the host classify (classify_np)
    and through the jitted cycle's classify half: the same fit slot,
    borrow and preempt-capable verdict for every head."""
    import numpy as np
    import __graft_entry__ as ge
    from kueue_tpu.ops.cycle import classify_np, solve_cycle
    from kueue_tpu.parallel import cycle_args

    _, _, _, packed = ge._packed_cycle()
    out = solve_cycle(*cycle_args(packed), depth=packed.depth,
                      run_scan=False)
    dev_preempt, dev_fit, dev_borrow = [np.asarray(o) for o in out[3:6]]
    ref = classify_np(packed)
    np.testing.assert_array_equal(ref["fit_slot0"], dev_fit)
    np.testing.assert_array_equal(ref["borrows0"], dev_borrow)
    np.testing.assert_array_equal(ref["preempt0"], dev_preempt)
    assert (dev_fit >= 0).any()


def test_contended_pack_admit_scan_matches_host_loop():
    """A contended pack (decision pairs, borrowing, in-scan skips)
    through the jitted admit_scan: the admitted heads, in cycle order,
    are the host admit loop's on the same cluster."""
    import jax
    import numpy as np
    import __graft_entry__ as ge
    from kueue_tpu.ops.cycle import (admit_scan, classify_np,
                                     cycle_order_np, decision_pairs,
                                     slot_frs)

    shape = dict(n_cohorts=4, cqs_per_cohort=4, n_workloads=64,
                 contended=True)
    _, _, _, packed = ge._packed_cycle(**shape)
    st = packed.structure
    out = classify_np(packed)
    fit_mask = out["fit0"]
    dec_fr, dec_amt = decision_pairs(
        slot_frs(st.slot_fr, st.res_group, packed.wl_cq, out["slots0"]),
        packed.wl_requests, fit_mask)
    W = packed.wl_cq.shape[0]
    res_fr = np.full_like(dec_fr, -1)
    res_amt = np.zeros_like(dec_amt)
    no_res = np.zeros(W, dtype=bool)
    order = cycle_order_np(out["borrows0"], packed.wl_priority,
                           packed.wl_timestamp)
    admitted = np.asarray(jax.device_get(admit_scan(
        packed.usage0, st.subtree_quota, st.guaranteed, st.borrow_cap,
        st.has_borrow_limit, st.parent, st.nominal_cq,
        st.nominal_plus_blimit_cq, packed.wl_cq, dec_fr, dec_amt,
        fit_mask, res_fr, res_amt, no_res, no_res, order,
        depth=st.depth)))
    n = packed.wl_count
    assert admitted[:n].any() and not admitted[:n].all(), \
        "scenario must have both admits and in-scan losers"
    host_stats = ge._build_scenario(**shape).schedule_once()
    dev_order = [packed.wl_keys[int(wi)] for wi in order
                 if wi < n and admitted[int(wi)]]
    assert dev_order == list(host_stats.admitted)
    assert host_stats.skipped
