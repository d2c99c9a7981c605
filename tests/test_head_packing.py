"""Head-only packing: budget scoping is decision-invisible.

The pack charges the kernel's 2^19 composite-key row budget (the 19-bit
uid rank plus the n/prio poison gates) only to rows of forests that can
preempt; pending rows of never-preempting forests ride along as rank
context outside the budget, so the active-CQ ceiling scales with
preempting-forest rows instead of all live rows.  The soundness
argument is the census that folds admitted rows out of the grid
(``burst.compressible_cqs``): a row of a never-preempting forest is
never gathered as a preemption candidate (eligibility requires the head
CQ's ``wcq_lower``/``rwc_enabled``), so its uidrank cell is never read
and the scoped rank — the subset rank, order-preserving over budget
rows — yields the same candidate ordering.  These tests prove it:
budget accounting, poison-gate scoping (the ceiling-lift mechanism
observable at unit scale), burst decisions equal to the host
scheduler's on a mixed cluster, and 8-seed streaming parity storms with
head flips.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from kueue_tpu.ops import burst as _b

from test_aggregate_compression import build_mixed, burst_and_host
from test_delta_pack import (
    _counter,
    build_cluster,
    check_step,
    current_structure,
    mk,
    random_mutation,
)


def _fill_pending(d, per_q=3):
    i = 0
    for c in range(2):
        for q in range(2):
            for k in range(per_q):
                d.create_workload(mk(f"p-{c}-{q}-{k}", f"lq-{c}-{q}",
                                     100_000, prio=k * 10, t=float(i)))
                i += 1


def _pack(d):
    st = current_structure(d)
    return _b.pack_burst(st, d.queues, d.cache, d.scheduler, d.clock)


def test_budget_rows_count_preempting_forests_only():
    """build_mixed: co-0 preempts (budget rows), co-1 never does
    (exempt): only co-0's rows are charged.  In a cluster where every
    forest preempts, every packed row is."""
    d, _ = build_mixed()
    _fill_pending(d, per_q=3)
    plan = _pack(d)
    assert plan.grid_rows == 12
    assert plan.budget_rows == 6, "only the preempting cohort is charged"

    d0, _ = build_cluster(preempt=True)
    _fill_pending(d0, per_q=3)
    plan0 = _pack(d0)
    assert plan0.grid_rows == 12 and plan0.budget_rows == 12


def test_scoped_uidrank_is_subset_rank():
    """The uid rank over budget rows is the rank of their uids among
    themselves: dense from 0, in uid order; exempt rows keep the pad
    value 0 (never read)."""
    d, _ = build_mixed()
    _fill_pending(d, per_q=3)
    st = current_structure(d)
    budget_cq = ~np.asarray(_b._pack_statics(st, d.cache).comp_cq)
    plan = _b.pack_burst(st, d.queues, d.cache, d.scheduler, d.clock)
    rank = np.asarray(plan.arrays["wl_uidrank"])
    keys = np.asarray(plan.keys, dtype=object)
    has_row = keys != None                                   # noqa: E711
    bmask = has_row & budget_cq[:, None]
    uids = [d.workloads[k].uid for k in keys[bmask]]
    want = np.empty(len(uids), dtype=np.int64)
    want[np.argsort(np.array(uids), kind="stable")] = np.arange(len(uids))
    assert len(uids) == 6
    assert rank[bmask].tolist() == want.tolist()
    assert (rank[has_row & ~budget_cq[:, None]] == 0).all(), \
        "exempt rows keep the pad rank"


@pytest.mark.parametrize("lq, modeled", [("lq-1-0", True),
                                         ("lq-0-0", False)],
                         ids=["exempt", "budget"])
def test_poison_gates_scoped_to_budget_rows(lq, modeled):
    """The ceiling-lift mechanism, observable at unit scale: a
    field-overflowing priority on an *exempt* CQ must not poison the
    in-kernel preemption envelope; the same priority on a budget CQ
    collapses every forest to the host path."""
    d, _ = build_mixed()
    _fill_pending(d, per_q=2)
    # a 2^21 priority overflows the 20-bit composite-key field
    d.create_workload(mk("huge", lq, 1000, prio=(1 << 21), t=99.0))
    preempt_ok = np.asarray(_pack(d).arrays["preempt_ok"])
    assert preempt_ok.any() == modeled


@pytest.mark.parametrize("runtime", [2, 0], ids=["runtime-2", "no-finish"])
@pytest.mark.parametrize("two_flavors", [False, True],
                         ids=["one-flavor", "flavor-walk"])
def test_burst_decisions_identical_head_pack_on_off(runtime, two_flavors):
    """schedule_burst on the mixed cluster, whose exempt rows are out of
    the budget, decides every cycle as the host scheduler's cycles do on
    the same spec, with runs that finish and with runs that do not."""
    def spec(d):
        for c in range(2):
            for q in range(2):
                for i in range(8):
                    d.create_workload(mk(
                        f"w-{c}-{q}-{i}", f"lq-{c}-{q}",
                        1500 if i % 3 else 2500,
                        prio=(i % 3) * 10, t=float(10 * c + 3 * q + i)))

    burst, host = burst_and_host(spec, two_flavors, runtime)
    assert burst[0] == host[0][:len(burst[0])], "per-cycle decisions diverged"
    assert burst[1] == host[1]


def test_head_flip_sequence_parity():
    """Deterministic head churn: admit, finish the head, evict, requeue
    — streaming pack parity (and the scoped uid order's delta
    maintenance) must hold after every flip."""
    d, clock = build_mixed()
    for i in range(8):
        d.create_workload(mk(f"w{i}", f"lq-{i % 2}-{(i // 2) % 2}", 1500,
                             prio=(i % 4) * 5, t=float(i)))
    stats = {}
    state = check_step(d, None, stats, 0, "init")
    clock.t += 1.0
    d.schedule_once()
    state = check_step(d, state, stats, 0, "admit")
    admitted = sorted(d.admitted_keys())
    if admitted:
        d.finish_workloads([admitted[0]], message="done")
        state = check_step(d, state, stats, 0, "finish-head")
    still = sorted(d.admitted_keys())
    if still:
        wl = d.workloads[still[0]]
        d._evict(wl, "Preempted", "head flip")
        state = check_step(d, state, stats, 0, "evict-head")
    clock.t += 1.0
    d.schedule_once()
    check_step(d, state, stats, 0, "readmit")


@pytest.mark.parametrize("window", [0, 4])
def test_streaming_parity_under_churn_head_pack(window):
    """8-seed mutation storms with head-only packing on (the default):
    delta/streaming pack vs fresh pack parity after every mutation
    class — arrivals, cycles, finishes, evictions, park/unpark,
    activeness flips — across preempting and non-preempting mixes."""
    for seed in range(8):
        rng = random.Random(9100 + seed)
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


def test_head_pack_stats_surface():
    d, clock = build_mixed()
    _fill_pending(d, per_q=2)
    d.schedule_burst(
        6, runtime=2,
        on_cycle_start=lambda k: setattr(clock, "t", clock.t + 1.0))
    out = d.stats
    assert "host_pool" in out
    if "head_pack" in out:   # tiny clusters may decide host-side
        hp = out["head_pack"]
        assert hp["head_pack_budget_rows"] >= 0
        assert hp["head_pack_exempt_rows"] >= 0
