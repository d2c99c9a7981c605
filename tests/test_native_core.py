"""Native (C++) cycle-core parity: identical decisions to the JAX kernel
and the scalar host oracle."""

import random

import numpy as np
import pytest

from kueue_tpu import native
from kueue_tpu.ops.cycle import solve_cycle
from kueue_tpu.ops.packing import pack_cycle
from kueue_tpu.parallel import cycle_args

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no g++ / prebuilt core")


def _packed(seed=0, **kw):
    import __graft_entry__ as ge
    _, _, _, packed = ge._packed_cycle(**kw)
    return packed


def test_native_matches_device_kernel():
    packed = _packed()
    out = solve_cycle(*cycle_args(packed), depth=packed.depth,
                      run_scan=False)
    dev_preempt, dev_fit, dev_borrow = [np.asarray(o) for o in out[3:6]]
    nat_fit, nat_borrow, nat_preempt = native.classify_cycle(packed)
    np.testing.assert_array_equal(nat_fit, dev_fit)
    np.testing.assert_array_equal(nat_borrow, dev_borrow)
    np.testing.assert_array_equal(nat_preempt, dev_preempt)
    assert (nat_fit >= 0).any()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_native_end_to_end_parity_vs_host(seed):
    from tests.test_solver_parity import build_driver
    results = []
    for backend in (None, "native"):
        d, workloads = build_driver(seed, backend is not None)
        if backend is not None:
            d.scheduler.solver.backend = backend
        for wl in workloads:
            d.create_workload(wl)
        d.run_until_settled(max_cycles=300)
        admitted = {}
        for k in d.admitted_keys():
            wl = d.workload(k)
            admitted[k] = tuple(sorted(
                (a.name, a.count, tuple(sorted(a.flavors.items())))
                for a in wl.admission.pod_set_assignments))
        results.append((admitted, d))
    (host, _), (nat, d_nat) = results
    assert host == nat
    assert (d_nat.scheduler.solver.stats["full_cycles"] + d_nat.scheduler.solver.stats["classify_cycles"]) >= 1


def test_native_admit_scan_matches_jitted():
    """The C++ admit loop must equal ops/cycle.admit_scan decision-for-
    decision on contended cycles (pairs, borrowing, in-scan skips)."""
    import jax
    from kueue_tpu.ops.cycle import (admit_scan, cycle_order_np,
                                     decision_pairs_from_slots)

    packed = _packed(n_cohorts=4, cqs_per_cohort=4, n_workloads=64,
                     contended=True)
    st = packed.structure
    from kueue_tpu.ops.cycle import classify_np
    out = classify_np(packed)
    dec_fr, dec_amt, fit_mask = decision_pairs_from_slots(
        st.slot_fr, packed.wl_cq, packed.wl_requests, out["fit_slot0"])
    W = packed.wl_cq.shape[0]
    res_fr = np.full_like(dec_fr, -1)
    res_amt = np.zeros_like(dec_amt)
    no_res = np.zeros(W, dtype=bool)
    order = cycle_order_np(out["borrows0"], packed.wl_priority,
                           packed.wl_timestamp)
    jitted = np.asarray(jax.device_get(admit_scan(
        packed.usage0, st.subtree_quota, st.guaranteed, st.borrow_cap,
        st.has_borrow_limit, st.parent, st.nominal_cq,
        st.nominal_plus_blimit_cq, packed.wl_cq, dec_fr, dec_amt,
        fit_mask, res_fr, res_amt, no_res, no_res, order,
        depth=st.depth)))
    nat = native.admit_scan(packed, dec_fr, dec_amt, fit_mask, res_fr,
                            res_amt, no_res, no_res, order)
    np.testing.assert_array_equal(nat, jitted)
    n = packed.wl_count
    assert jitted[:n].any() and not jitted[:n].all(), \
        "scenario must have both admits and in-scan losers"


@pytest.mark.parametrize("seed", [31, 32])
def test_native_backend_full_cycle_parity(seed):
    """Driver with solver_backend='native': the C++ classify AND the C++
    admit loop decide cycles, matching the host decision-for-decision."""
    from tests.test_device_cycle import build_driver, drive_cycles
    host, hclock, hwl = build_driver(seed, use_device=False,
                                     preemption=False)
    nat, nclock, nwl = build_driver(seed, use_device=True,
                                    preemption=False)
    nat.scheduler.solver.backend = "native"
    hlog = drive_cycles(host, hclock, hwl)
    nlog = drive_cycles(nat, nclock, nwl)
    for cyc, (h, nv) in enumerate(zip(hlog, nlog)):
        assert h == nv, f"seed {seed} cycle {cyc}:\nhost={h}\nnative={nv}"
    stats = nat.scheduler.solver.stats
    assert stats["host_cycles"] == 0, stats


def test_auto_routing_prefers_calibrated_native():
    """On a CPU host backend='auto' hands the admit loop to the C++ core
    when warmup measured it faster than the XLA scan for the bucket —
    with unchanged decisions (weak r3 #5: the native core competes in
    the calibration table instead of needing an explicit switch)."""
    from tests.test_device_cycle import build_driver, drive_cycles
    host, hclock, hwl = build_driver(33, use_device=False,
                                     preemption=False)
    auto, aclock, awl = build_driver(33, use_device=True,
                                     preemption=False)
    s = auto.scheduler.solver
    s.backend = "auto"     # build_driver pins xla; the pick under test
    for W in (8, 16, 32, 64, 128, 256, 512, 1024):
        s.calibration[("xla", "flat", W, W)] = 1e-3
        s.calibration[("native", "flat", W, W)] = 1e-5
        for mfw in (4, 8, 16, 32, 64):
            s.calibration[("xla", "forest", W, mfw)] = 1e-3
            s.calibration[("native", "forest", W, mfw)] = 1e-5
    hlog = drive_cycles(host, hclock, hwl)
    alog = drive_cycles(auto, aclock, awl)
    for cyc, (h, a) in enumerate(zip(hlog, alog)):
        assert h == a, f"cycle {cyc}:\nhost={h}\nauto={a}"
    assert s.stats["native_dispatches"] > 0, s.stats
    assert s.stats["cpu_dispatches"] == 0, s.stats
    # flipping the measurement hands the same cycles back to the XLA scan
    auto2, a2clock, a2wl = build_driver(33, use_device=True,
                                        preemption=False)
    s2 = auto2.scheduler.solver
    for key, v in s.calibration.items():
        s2.calibration[key] = 1e-5 if key[0] == "xla" else 1e-3
    drive_cycles(auto2, a2clock, a2wl)
    assert s2.stats["native_dispatches"] == 0, s2.stats


def test_warmup_records_native_calibration():
    """warmup() itself must produce the ('native', ...) calibration
    entries dispatch compares — guarding the admit_scan_raw argument
    wiring (a drift would otherwise silently disable the native core)."""
    from tests.test_device_cycle import build_driver
    d, _, _ = build_driver(34, use_device=True, preemption=False)
    s = d.scheduler.solver
    s.backend = "auto"
    s.warmup(d.cache.snapshot(), 16)
    assert s.stats["native_calibration_failures"] == 0, s.stats
    native_keys = [k for k in s.calibration if k[0] == "native"]
    assert native_keys, sorted(s.calibration)
    # every native entry has an XLA twin for the same bucket, so the
    # comparison in dispatch always has both sides
    for k in native_keys:
        assert ("xla",) + k[1:] in s.calibration, k
