"""Multichip decision parity on the conftest's 8 virtual CPU devices
(--xla_force_host_platform_device_count=8): an 8-shard dispatch of the
fused burst window and of the FS tournament must be bit-identical to
the serial single-device path — the tentpole's correctness bar, CI-
testable without accelerator hardware.
"""

from __future__ import annotations

import jax
import pytest

from kueue_tpu.controller.driver import Driver
from kueue_tpu.ops.burst import BurstSolver
from kueue_tpu.parallel.sharded import make_mesh

from test_burst import add_workloads, build, mk, run_host, simple_cluster
from test_burst_pipeline import (
    PRE_ANY,
    assert_records_equal,
    run_burst_mode,
    run_host_inject,
    sustained_spec,
)
from test_fs_device import build as fs_build
from test_fs_device import fs_cluster
from test_fs_device import mk as fs_mk
from test_fs_device import run_cycles as fs_run_cycles

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices (conftest)")


def run_burst_shards(d, clock, cycles, runtime, shards, inject=None):
    bs = BurstSolver()
    if shards > 1:
        bs.set_shards(shards)
        assert bs.n_shards == shards, bs.n_shards
    d._burst_solver = bs
    return run_burst_mode(d, clock, cycles, runtime, pipeline=True,
                          inject=inject)


@needs_8_devices
def test_burst_8shard_vs_serial_admit_parity():
    """Sustained multi-window drain: 8-shard == serial == host,
    per-cycle, with the sharded kernel actually dispatched."""
    spec = sustained_spec()
    dh, ch = build(spec)
    ds, cs = build(spec)
    dp, cp = build(spec)
    host = run_host(dh, ch, 80, 2)
    serial = run_burst_shards(ds, cs, 80, 2, shards=0)
    shard = run_burst_shards(dp, cp, 80, 2, shards=8)
    assert len(serial) == len(shard)
    assert_records_equal(serial, shard, "serial-vs-8shard")
    assert_records_equal(host[:len(shard)], shard, "host-vs-8shard")
    assert dh.admitted_keys() == ds.admitted_keys() == dp.admitted_keys()
    st = dp._burst_solver.stats
    assert st["burst_sharded_dispatches"] >= 1, st
    assert len(st["burst_shard_pack_s"]) == 8
    assert len(st["burst_shard_fetch_s"]) == 8


@needs_8_devices
def test_burst_8shard_vs_serial_preempt_parity():
    """A mid-burst high-priority arrival forces the preemption boundary
    (dirty window) on both arms; decisions — including preempted
    targets — must stay bit-identical."""
    wls = []
    n = 0
    for c in range(2):
        for q in range(2):
            for i in range(6):
                n += 1
                wls.append(mk(f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 2000,
                              prio=10, t=float(n)))
    spec = add_workloads(
        simple_cluster(n_cohorts=2, cqs=2, nominal=4000,
                       borrowing=4000, preemption=PRE_ANY), wls)
    inject = {6: mk("hi-a", "lq-0-0", 4000, prio=100, t=100.0),
              9: mk("hi-b", "lq-1-1", 4000, prio=100, t=101.0)}
    dh, ch = build(spec)
    ds, cs = build(spec)
    dp, cp = build(spec)
    host = run_host_inject(dh, ch, 40, 3, inject=inject)
    serial = run_burst_shards(ds, cs, 40, 3, shards=0, inject=inject)
    shard = run_burst_shards(dp, cp, 40, 3, shards=8, inject=inject)
    assert len(serial) == len(shard)
    assert_records_equal(serial, shard, "serial-vs-8shard")
    assert_records_equal(host[:len(shard)], shard, "host-vs-8shard")
    assert any(s.preempted_targets for s in shard), \
        "scenario produced no preemption"
    assert dh.admitted_keys() == ds.admitted_keys() == dp.admitted_keys()
    assert dp._burst_solver.stats["burst_sharded_dispatches"] >= 1


@needs_8_devices
def test_fs_tournament_8shard_vs_serial_parity():
    """The FS tournament routed through the 8-device mesh must decide
    identically to the unmeshed device path and to the host."""
    wls = [fs_mk(f"w-{q}-{i}", f"lq-0-{q}", 1500, t=float(q * 10 + i))
           for q in range(3) for i in range(8)]
    spec = fs_cluster(weights=(1.0, 2.0, 0.5), nominal=2000,
                      borrowing=8000)
    dh, ch = fs_build(spec, use_device=False)
    ds, cs = fs_build(spec, use_device=True)
    dm, cm = fs_build(spec, use_device=True)
    dm.scheduler.solver.set_mesh(make_mesh(8))
    for d in (dh, ds, dm):
        for wl in wls:
            d.create_workload(wl)
    host = fs_run_cycles(dh, ch, 12, runtime=3)
    serial = fs_run_cycles(ds, cs, 12, runtime=3)
    mesh = fs_run_cycles(dm, cm, 12, runtime=3)
    for k, (h, s, m) in enumerate(zip(host, serial, mesh)):
        assert h.admitted == s.admitted == m.admitted, \
            f"cycle {k}: host={h.admitted} serial={s.admitted} " \
            f"mesh={m.admitted}"
        assert sorted(h.skipped) == sorted(s.skipped) == \
            sorted(m.skipped), f"cycle {k} skipped"
    assert dh.admitted_keys() == ds.admitted_keys() == dm.admitted_keys()
    assert dm.scheduler.solver.stats["fs_full_cycles"] > 0
    assert dm.scheduler.solver.stats["sharded_fs_dispatches"] >= 1, \
        dm.scheduler.solver.stats


@needs_8_devices
def test_env_var_activates_sharding(monkeypatch):
    """KUEUE_TPU_SHARDS=8 is the production switch: the driver must
    wire both the cycle-solver mesh and the burst shards, and decisions
    must match the serial run."""
    monkeypatch.setenv("KUEUE_TPU_SHARDS", "8")
    spec = sustained_spec(per_cq=18)
    de, ce = build(spec)
    assert de.scheduler.solver.mesh is not None
    env = run_burst_mode(de, ce, 40, 2, pipeline=True)
    monkeypatch.delenv("KUEUE_TPU_SHARDS")
    ds, cs = build(spec)
    serial = run_burst_mode(ds, cs, 40, 2, pipeline=True)
    assert len(env) == len(serial)
    assert_records_equal(serial, env, "serial-vs-env8")
    assert de.admitted_keys() == ds.admitted_keys()
    assert de._burst_solver.stats["burst_sharded_dispatches"] >= 1


@needs_8_devices
def test_burst_8shard_resident_multiwindow_parity(monkeypatch):
    """Shard-resident boundary: mid-run arrivals force fresh (delta)
    packs across a multi-window drain, so the resident device copy is
    actually reused — only dirty rows scattered — and decisions stay
    bit-identical to serial and host.  VERIFY asserts, inside the
    solver, that every scattered plane equals a full host permute."""
    monkeypatch.setenv("KUEUE_TPU_RESIDENT_VERIFY", "1")
    spec = sustained_spec()
    inject = {36: mk("boss", "lq-0-0", 4000, prio=100, t=500.0)}
    dh, ch = build(spec)
    ds, cs = build(spec)
    dp, cp = build(spec)
    host = run_host_inject(dh, ch, 80, 2, inject=dict(inject))
    serial = run_burst_shards(ds, cs, 80, 2, shards=0,
                              inject=dict(inject))
    shard = run_burst_shards(dp, cp, 80, 2, shards=8,
                             inject=dict(inject))
    assert len(serial) == len(shard)
    assert_records_equal(serial, shard, "serial-vs-8shard-resident")
    assert_records_equal(host[:len(shard)], shard,
                         "host-vs-8shard-resident")
    assert dh.admitted_keys() == ds.admitted_keys() == dp.admitted_keys()
    st = dp._burst_solver.stats
    assert st["burst_resident_hits"] >= 1, st
    assert st["burst_resident_scatter_rows"] >= 1, st
    # coalescing: never more ranges than rows, at least one range
    assert 1 <= st["burst_resident_scatter_ranges"] \
        <= st["burst_resident_scatter_rows"], st
    # the residency must strictly reduce boundary host→device traffic
    assert st["burst_boundary_bytes_h2d"] \
        < st["burst_boundary_bytes_equiv"], st


@needs_8_devices
def test_burst_8shard_to_4_degradation_resident_parity(monkeypatch):
    """8→4 mid-run degradation with the resident boundary on: the
    resident copy is laid out for the dead mesh, so the next fresh pack
    must re-gather from host over the 4 survivors — and every decision
    before and after the loss stays bit-identical to serial and host."""
    monkeypatch.setenv("KUEUE_TPU_RESIDENT_VERIFY", "1")
    spec = sustained_spec()
    dh, ch = build(spec)
    ds, cs = build(spec)
    dp, cp = build(spec)
    # both burst arms restart scheduling at cycle 40 (runtime finishes
    # don't cross a schedule_burst call), so the host control splits too
    host = run_host(dh, ch, 40, 2) + run_host(dh, ch, 40, 2)
    serial = (run_burst_shards(ds, cs, 40, 2, shards=0)
              + run_burst_mode(ds, cs, 40, 2, pipeline=True))
    first = run_burst_shards(dp, cp, 40, 2, shards=8)
    bs = dp._burst_solver
    assert bs.lose_devices(4) == 4
    assert bs._resident is None
    second = run_burst_mode(dp, cp, 40, 2, pipeline=True)
    shard = first + second
    assert len(serial) == len(shard)
    assert_records_equal(serial, shard, "serial-vs-degraded")
    assert_records_equal(host[:len(shard)], shard, "host-vs-degraded")
    assert dh.admitted_keys() == ds.admitted_keys() == dp.admitted_keys()
    st = bs.stats
    assert st["burst_shard_degradations"] == 1, st
    assert bs.n_shards == 4
    # the post-loss windows really ran on the 4-shard mesh and the
    # re-gather was a resident miss, not a stale-layout reuse
    assert len(st["burst_shard_fetch_s"]) == 4
    assert st["burst_resident_misses"] >= 1, st


@needs_8_devices
def test_burst_8shard_cost_rebalance_parity(monkeypatch):
    """Cost-balanced forest partitioning: seeding the solver's cycle-
    cost EWMA (as prior windows would) makes the next layout build use
    measured cost for the LPT — and decisions stay bit-identical to the
    count-based layout, because assignment never affects values."""
    import numpy as np
    monkeypatch.setenv("KUEUE_TPU_RESIDENT_VERIFY", "1")
    wls = []
    n = 0
    for c in range(4):
        for q in range(2):
            for i in range(8):
                n += 1
                wls.append(mk(f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 2000,
                              prio=(i % 3) * 10, t=float(n)))
    spec = add_workloads(
        simple_cluster(n_cohorts=4, cqs=2, nominal=4000), wls)
    ds, cs = build(spec)
    dp, cp = build(spec)
    serial = run_burst_shards(ds, cs, 60, 2, shards=0)

    dpp, cpp = dp, cp
    bs = BurstSolver()
    bs.set_shards(8)
    dpp._burst_solver = bs
    # measured-cost seed: as if prior windows decided heads only in
    # forest 0 — a skewed EWMA the LPT must still spread deterministically
    bs._forest_cost = {"generation": dpp.cache.structure_generation,
                       "ewma": np.array([8.0, 1.0, 1.0, 1.0]),
                       "windows": 5}
    shard = run_burst_mode(dpp, cpp, 60, 2, pipeline=True)
    assert len(serial) == len(shard)
    assert_records_equal(serial, shard, "serial-vs-cost-balanced")
    assert ds.admitted_keys() == dpp.admitted_keys()
    st = bs.stats
    assert st["burst_layout_cost_balanced"] >= 1, st
    assert st["burst_shard_cost_ratio"] >= 1.0, st
    assert len(st.get("burst_shard_cost", [])) == 8, st


def test_burst_shards_one_is_serial_and_too_many_raises():
    """set_shards(1) keeps the serial path; asking for more shards than
    there are devices is an error, not a quieter run."""
    bs = BurstSolver()
    bs.set_shards(1)
    assert bs.n_shards == 1
    assert bs._shard_mesh is None
    with pytest.raises(ValueError, match="shards requested"):
        bs.set_shards(10 ** 6)
    with pytest.raises(ValueError, match="shards requested"):
        make_mesh(10 ** 6)
