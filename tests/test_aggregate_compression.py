"""Cohort-forest compression: decisions as the host makes them.

The pack keeps admitted rows of non-preempting forests out of the
packed planes (the kernel can never select them as candidates there —
candidate eligibility requires the head CQ's ``wcq_lower``/
``rwc_enabled``) and tracks them in per-CQ counts instead, so kernel
work scales with active CQs and heads rather than live workloads
(``burst.compressible_cqs``).  These tests prove the compressed pack
decides as the host scheduler does: per-cycle decisions under churn
(runtime finishes hitting the ext-release fallback for compressed
keys), flavor walks, preempting cohorts (never compressed), plus
streaming-vs-fresh pack parity and the packed-row shrink itself, held
to the cache's own admitted workloads and usage.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from kueue_tpu.api.types import (
    WL_QUOTA_RESERVED,
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
from kueue_tpu.controller.driver import Driver
from kueue_tpu.ops import burst as _b
from kueue_tpu.ops.burst import compressible_cqs

from test_burst import run_host
from test_delta_pack import (
    Clock,
    _counter,
    build_cluster,
    check_step,
    current_structure,
    mk,
    random_mutation,
)


def build_mixed(two_flavors=False):
    """co-0 preempts (never compressible), co-1 does not (compressible)."""
    clock = Clock()
    d = Driver(clock=clock, use_device_solver=True)
    d.apply_resource_flavor(ResourceFlavor(name="f1"))
    if two_flavors:
        d.apply_resource_flavor(ResourceFlavor(name="f2"))
    pre = PreemptionPolicy(
        reclaim_within_cohort=ReclaimWithinCohort.ANY,
        within_cluster_queue=WithinClusterQueue.LOWER_PRIORITY)
    for c in range(2):
        for q in range(2):
            name = f"cq-{c}-{q}"
            flavors = [FlavorQuotas(name="f1", resources={
                "cpu": ResourceQuota(nominal=4000, borrowing_limit=2000)})]
            if two_flavors:
                flavors.append(FlavorQuotas(name="f2", resources={
                    "cpu": ResourceQuota(nominal=4000,
                                         borrowing_limit=2000)}))
            d.apply_cluster_queue(ClusterQueue(
                name=name, cohort=f"co-{c}",
                preemption=(pre if c == 0 else PreemptionPolicy()),
                queueing_strategy=QueueingStrategy.BEST_EFFORT_FIFO,
                resource_groups=[ResourceGroup(
                    covered_resources=["cpu"], flavors=flavors)]))
            d.apply_local_queue(LocalQueue(name=f"lq-{c}-{q}",
                                           cluster_queue=name))
    return d, clock


def burst_and_host(spec, two_flavors, runtime, cycles=16):
    """``spec`` on two mixed clusters, one driven by ``schedule_burst``
    and one by the host scheduler a cycle at a time (``run_host``).
    Returns each run's (per-cycle decisions, admitted keys, flavors
    used, burst stats); the host's cycles past the burst's last are
    held to decide nothing."""
    runs = []
    for arm in ("burst", "host"):
        d, clock = build_mixed(two_flavors=two_flavors)
        spec(d)
        if arm == "burst":
            cycle_stats = d.schedule_burst(
                cycles, runtime=runtime,
                on_cycle_start=lambda k: setattr(clock, "t", clock.t + 1.0))
        else:
            cycle_stats = run_host(d, clock, cycles, runtime)
        flavors_used = set()
        for w in d.workloads.values():
            if w.admission is not None:
                for a in w.admission.pod_set_assignments:
                    flavors_used.update(a.flavors.values())
        runs.append((
            [(sorted(s.admitted), sorted(s.skipped),
              sorted(s.inadmissible), sorted(s.preempted_targets))
             for s in cycle_stats],
            d.admitted_keys(), flavors_used,
            dict(d._burst_solver.stats) if d._burst_solver else {}))
    burst, host = runs
    assert burst[0], "the burst decided no cycle"
    for h in host[0][len(burst[0]):]:
        assert not any(h), "the burst ended while the host still decided"
    return burst, host


def test_compressible_census_follows_forest_preemption():
    d, _ = build_mixed()
    st = current_structure(d)
    s = _b._pack_statics(st, d.cache)
    by_name = dict(zip(st.cq_names, s.comp_cq.tolist()))
    assert by_name == {"cq-0-0": False, "cq-0-1": False,
                       "cq-1-0": True, "cq-1-1": True}
    # an all-preempting cluster compresses nothing
    dp, _ = build_cluster(preempt=True)
    stp = current_structure(dp)
    assert not compressible_cqs(_b._pack_statics(stp, dp.cache)).any()


def test_compression_drops_admitted_rows_keeps_max_res_ts():
    """Admitted rows of compressible CQs leave the packed planes, while
    ``max_res_ts`` (the clock-monotonicity anchor) is the newest
    reservation of the cache's admitted workloads, compressed ones
    included, and the usage the kernel sees is the cache's."""
    d, clock = build_cluster(preempt=False)
    for i in range(16):
        d.create_workload(mk(f"w{i}", f"lq-{i % 2}-{i // 8}", 1000,
                             t=float(i)))
    for _ in range(3):
        clock.t += 1.0
        d.schedule_once()
    assert len(d.admitted_keys()) >= 8
    st = current_structure(d)
    plan = _b.pack_burst(st, d.queues, d.cache, d.scheduler, d.clock)
    assert int(np.asarray(plan.arrays["adm0"]).sum()) == 0, \
        "compressible admitted rows must not be packed"
    live = [d.cache.cluster_queue(name) for name in st.cq_names]
    reserved = [info.obj.conditions[WL_QUOTA_RESERVED].last_transition_time
                for cq in live for info in cq.workloads.values()]
    assert len(reserved) == len(d.admitted_keys())
    assert plan.max_res_ts == max(reserved), \
        "compressed admissions must still anchor the clock window"
    want = np.zeros_like(np.asarray(plan.arrays["u_cq0"]))
    for ci, cq in enumerate(live):
        for info in cq.workloads.values():
            for fr, v in info.usage().items():
                want[ci, st.fr_index[fr]] += (
                    v // int(st.resource_scale[st.r_index[fr.resource]]))
    assert want.sum() > 0
    assert np.array_equal(np.asarray(plan.arrays["u_cq0"]), want)


@pytest.mark.parametrize("two_flavors", [False, True],
                         ids=["one-flavor", "flavor-walk"])
def test_burst_decisions_identical_agg_on_off(two_flavors):
    """schedule_burst on the mixed cluster decides every cycle as the
    host scheduler's cycles do under churn — runtime finishes release
    compressed rows through the ext-release fallback, the preempting
    cohort keeps its rows, and the flavor-walk arm spills admissions
    onto the second flavor."""
    def spec(d):
        for c in range(2):
            for q in range(2):
                for i in range(8):
                    d.create_workload(mk(
                        f"w-{c}-{q}-{i}", f"lq-{c}-{q}",
                        1500 if i % 3 else 2500,
                        prio=(i % 3) * 10, t=float(10 * c + 3 * q + i)))

    burst, host = burst_and_host(spec, two_flavors, runtime=2)
    assert burst[0] == host[0][:len(burst[0])], "per-cycle decisions diverged"
    assert burst[1] == host[1]
    assert burst[2] == host[2]
    if two_flavors:
        assert "f2" in burst[2], "flavor walk never left f1"
    if "agg_rows_compressed" in burst[3]:
        assert burst[3]["agg_cqs_compressible"] == 2


@pytest.mark.parametrize("window", [0, 4])
def test_streaming_parity_under_churn_with_compression(window):
    """Delta/streaming pack vs fresh pack with compressed forests:
    parity must hold after every mutation class — arrivals, cycles,
    finishes, evictions, backoff park/unpark, activeness flips."""
    for seed in range(8):
        rng = random.Random(7700 + seed)
        d, clock = build_cluster(seed, preempt=(seed % 3 == 0))
        names = _counter()
        for i in range(6):
            d.create_workload(mk(f"init{i}", f"lq-{i % 2}-{i // 3}",
                                 2000, prio=(i % 3) * 10, t=float(i)))
        stats = {}
        state = check_step(d, None, stats, window, f"seed{seed}:init")
        for step in range(10):
            label = random_mutation(rng, d, clock, names)
            state = check_step(d, state, stats, window,
                               f"seed{seed}:step{step}:{label}")


def test_agg_stats_surface_in_driver_stats():
    d, clock = build_cluster(preempt=False)
    for i in range(6):
        d.create_workload(mk(f"w{i}", f"lq-{i % 2}-{i // 3}", 1000,
                             t=float(i)))
    d.schedule_burst(
        6, runtime=2,
        on_cycle_start=lambda k: setattr(clock, "t", clock.t + 1.0))
    out = d.stats
    assert "heap_repair" in out
    if "agg" in out:   # the burst may decide host-side on tiny clusters
        assert out["agg"]["agg_cqs_compressible"] == 4
        assert out["agg"]["agg_rows_packed"] >= 0
