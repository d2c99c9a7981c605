"""The one-chip launch keeps the fused window's rows on the device.

``BurstSolver._resident_rows`` holds the row inputs and the scan state's
planes as a mirror of the pack's arena; a plan that chains the mirror's
pack token sends the runs of slots that cover each queue's rows and one
donated update puts them in place (a hit), anything else goes up whole
and becomes the mirror (a miss).  Every launch here runs under
``KUEUE_TPU_RESIDENT_VERIFY=1`` (the device's planes equal the plan's
after it) and is compared, decision for decision, with a second solver
under ``KUEUE_TPU_RESIDENT=0``, the full upload; hits, misses, cells and
bytes are counted as the launch says, and nothing is built after the
launch that installs the mirror.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from kueue_tpu.ops import burst as _b
from kueue_tpu.ops.burst import BurstSolver, pack_burst_cached

from test_delta_pack import build_cluster, current_structure, mk

K = 8
M0 = 32      # a sticky M the cases' queues stay under
RUN = 8      # slots a run here: a queue's rows take one to four


@pytest.fixture(autouse=True)
def verify(monkeypatch):
    monkeypatch.setenv("KUEUE_TPU_RESIDENT_VERIFY", "1")
    monkeypatch.delenv("KUEUE_TPU_RESIDENT", raising=False)
    monkeypatch.setattr(_b, "RESIDENT_RUN", RUN)


@pytest.fixture(scope="module")
def builds():
    """Every executable JAX builds or loads from here on."""
    events = []

    def on_duration(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return events


def cluster(per_queue=6):
    """Four queues in two cohorts, each with running and waiting work."""
    d, clock = build_cluster(preempt=True)
    for c in range(2):
        for q in range(2):
            for i in range(per_queue):
                d.create_workload(mk(
                    f"w-{c}-{q}-{i}", f"lq-{c}-{q}", 1500,
                    prio=(i % 3) * 10, t=float(10 * c + 3 * q + i)))
    for _ in range(2):
        clock.t += 1.0
        d.schedule_once()
    assert d.admitted_keys()
    return d, clock


class Chain:
    """One cluster packed window after window into one solver, beside a
    control solver that sends every window whole."""

    def __init__(self, d, clock, monkeypatch, min_m=M0):
        self.d, self.clock, self.mp = d, clock, monkeypatch
        self.solver, self.control = BurstSolver(), BurstSolver()
        self.state, self.min_m = None, min_m

    def pack(self):
        d = self.d
        plan, self.state, _ = pack_burst_cached(
            current_structure(d), d.queues, d.cache, d.scheduler, d.clock,
            state=self.state, min_m=self.min_m, window=K,
            stats=self.solver.stats)
        assert plan is not None
        self.min_m = max(self.min_m, plan.M)
        return plan

    def ext(self, plan):
        return (np.zeros((K, plan.C, plan.structure.n_frs), np.int32),
                np.zeros((K, plan.G), bool))

    def full_upload(self, call):
        with self.mp.context() as mp:
            mp.setenv("KUEUE_TPU_RESIDENT", "0")
            return call(self.control)

    def launch(self, plan):
        """Dispatch ``plan`` on both solvers; the decisions agree."""
        ext = self.ext(plan)
        got = self.solver.run(plan, K, 0, *ext)
        want = self.full_upload(lambda s: s.run(plan, K, 0, *ext))
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
        return got

    def window(self):
        plan = self.pack()
        self.launch(plan)
        return plan

    def counted(self, *names):
        return tuple(self.solver.stats[n] for n in names)


def mirror_equals(solver, plan):
    res = solver._resident
    assert res is not None and res.token == plan.pack_token
    for name, plane in res.planes.items():
        want = plan.arrays[name]
        if name == "death0":
            want = np.full_like(want, _b.I32_MAX)
        assert np.array_equal(np.asarray(plane), want), name


def finishes(d, clock):
    for key in sorted(d.admitted_keys())[:2]:
        d.finish_workload(key)


def admissions(d, clock):
    finishes(d, clock)
    clock.t += 1.0
    d.schedule_once()


def evictions(d, clock):
    d.create_workload(mk("urgent", "lq-0-0", 3500, prio=100, t=clock.t))
    before = d.admitted_keys()
    for _ in range(3):
        clock.t += 1.0
        d.schedule_once()
    assert before - d.admitted_keys(), "nothing was evicted"


def a_queue_shrinks(d, clock):
    gone = [k for k in sorted(d.workloads) if "w-1-0-" in k]
    for key in gone[1:]:
        if key in d.admitted_keys():
            d.finish_workload(key)
        else:
            d.delete_workload(key)


def a_queue_grows(d, clock):
    for i in range(7):
        d.create_workload(mk(f"late-{i}", "lq-1-1", 1500, prio=5 * i,
                             t=clock.t + i * 1e-3))


@pytest.mark.parametrize("boundary", [
    finishes, admissions, evictions, a_queue_shrinks, a_queue_grows])
def test_a_chained_window_sends_its_cells(monkeypatch, boundary):
    """The first window goes up whole and installs the mirror; after a
    boundary of each kind the next one chains it, sends the runs that
    cover the queues' rows as they were or as they are, whichever reach
    further, and leaves the device holding the plan's planes."""
    d, clock = cluster()
    chain = Chain(d, clock, monkeypatch)
    first = chain.window()
    assert first.row_extent is None and first.prev_token is None
    assert chain.counted("burst_resident_hits", "burst_resident_misses",
                         "burst_resident_scatter_rows") == (0, 1, 0)
    mirror_equals(chain.solver, first)
    rows_before = chain.state.n_rows_cq.copy()
    sent_whole = chain.solver.stats["burst_launch_bytes_h2d"]
    batches = chain.solver.stats["burst_h2d_batches"]

    for step in range(2):
        boundary(d, clock) if step == 0 else finishes(d, clock)
        plan = chain.pack()
        assert plan.prev_token == chain.solver._resident.token
        assert plan.M == first.M
        want_extent = np.maximum(rows_before, chain.state.n_rows_cq)
        assert np.array_equal(plan.row_extent, want_extent)
        if boundary is a_queue_shrinks and step == 0:
            assert (chain.state.n_rows_cq < rows_before).any()
        if boundary is a_queue_grows and step == 0:
            assert (chain.state.n_rows_cq > rows_before).any()
        cells, sent = chain.counted("burst_resident_scatter_rows",
                                    "burst_launch_bytes_h2d")
        planes = chain.solver._resident.planes
        chain.launch(plan)
        mirror_equals(chain.solver, plan)
        assert chain.counted("burst_resident_hits",
                             "burst_resident_misses") == (step + 1, 1)
        runs = int((-(-want_extent // RUN)).sum())
        assert chain.solver.stats["burst_resident_scatter_rows"] \
            == cells + runs * RUN
        # what crossed: the runs' places and, in the mirror's dtypes,
        # their values, made up to a rung, where the first window sent
        # the planes
        rung = next(r for r in chain.solver._resident_rungs[
            chain.solver._resident.layout] if r >= runs)
        a_cell = sum(p.dtype.itemsize * int(np.prod(p.shape[2:]))
                     for name, p in planes.items() if name != "death0")
        whole = sum(p.nbytes for p in planes.values())
        hit_bytes = chain.solver.stats["burst_launch_bytes_h2d"] - sent
        assert sent_whole - hit_bytes == whole - rung * (8 + RUN * a_cell)
        assert chain.solver.stats["burst_h2d_batches"] == batches
        rows_before = chain.state.n_rows_cq.copy()


def test_m_growth_is_a_miss(monkeypatch):
    """A queue that outgrows M changes the planes' shapes: that window
    goes up whole and the next one chains it."""
    d, clock = cluster()
    chain = Chain(d, clock, monkeypatch, min_m=0)
    first = chain.window()
    for i in range(first.M):
        d.create_workload(mk(f"more-{i}", "lq-0-1", 1500, t=clock.t + i))
    grown = chain.window()
    assert grown.M > first.M and grown.prev_token == first.pack_token
    assert chain.counted("burst_resident_hits",
                         "burst_resident_misses") == (0, 2)
    mirror_equals(chain.solver, grown)
    finishes(d, clock)
    chain.window()
    assert chain.counted("burst_resident_hits",
                         "burst_resident_misses") == (1, 2)


def test_a_value_the_mirrors_dtype_cannot_hold_is_a_miss(monkeypatch):
    """The mirror's rank planes are as narrow as the grid's bound let
    the first launch make them; a cell past that width sends the window
    up whole, counted as a widening, and is never cut short."""
    d, clock = cluster()
    chain = Chain(d, clock, monkeypatch)
    chain.window()
    assert chain.solver._resident.planes["wl_uidrank"].dtype == np.int8
    finishes(d, clock)
    plan = chain.pack()
    plan.arrays["wl_uidrank"][0, 0] = 1000
    chain.launch(plan)
    assert chain.counted("burst_resident_hits", "burst_resident_misses",
                         "pack_tighten_widened") == (0, 2, 1)
    held = chain.solver._resident.planes["wl_uidrank"]
    assert held.dtype == np.int16 and int(held[0, 0]) == 1000
    mirror_equals(chain.solver, plan)


@pytest.mark.parametrize("told", [True, None])
def test_finishes_ride_with_their_launch_alone(monkeypatch, told):
    """``death0`` is written on the plan's copy after the pack: the
    launch sends that plane for itself, on a miss and on a hit, and the
    mirror keeps the arena's, which holds no finish.  ``told``: whether
    the writer said so (``finite_deaths``) or the launch looks."""
    d, clock = cluster()
    chain = Chain(d, clock, monkeypatch)
    for window in range(3):
        if window:
            finishes(d, clock)
        plan = chain.pack()
        loc = next(loc for key, loc in sorted(plan.row_of_key.items())
                   if plan.arrays["adm0"][loc])
        plan.arrays["death0"][loc] = 1
        plan.finite_deaths = told
        chain.launch(plan)     # verified: the launch's plane is the plan's
        assert (np.asarray(chain.solver._resident.planes["death0"])
                == _b.I32_MAX).all()
        mirror_equals(chain.solver, plan)
    assert chain.counted("burst_resident_hits",
                         "burst_resident_misses") == (2, 1)


def test_a_speculative_window_takes_the_mirrors_rows(monkeypatch):
    """``dispatch_next`` chains a window off the last one's carry: its
    row inputs are the mirror's, nothing is staged, and the mirror still
    holds the arena's state for the next fresh pack."""
    d, clock = cluster()
    chain = Chain(d, clock, monkeypatch)
    chain.window()
    finishes(d, clock)
    plan = chain.pack()
    ext = chain.ext(plan)

    def two_windows(solver):
        handle = solver.dispatch(plan, K, 0, *ext)
        first = solver.fetch(handle)
        sent = dict(solver.stats)
        spec = solver.dispatch_next(handle, *ext)
        return first, solver.fetch(spec), sent

    first, second, sent = two_windows(chain.solver)
    want_first, want_second, _ = chain.full_upload(two_windows)
    for got, want in ((first, want_first), (second, want_second)):
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
    stats = chain.solver.stats
    assert stats["burst_spec_dispatches"] == 1
    assert stats["burst_h2d_batches"] == sent["burst_h2d_batches"]
    assert chain.counted("burst_resident_hits",
                         "burst_resident_misses") == (1, 1)
    # the small planes alone crossed
    small = (stats["burst_launch_bytes_h2d"]
             - sent["burst_launch_bytes_h2d"])
    assert 0 < small < sum(
        p.nbytes for p in chain.solver._resident.planes.values())
    mirror_equals(chain.solver, plan)
    finishes(d, clock)
    chain.window()
    assert stats["burst_resident_hits"] == 2


def test_runs_past_the_top_rung_are_a_miss_not_a_compile(
        monkeypatch, builds):
    """The update is built for a ladder of run counts when the mirror
    first goes up; a window with more runs than its top rung goes up
    whole, and neither it nor any later window builds a program."""
    monkeypatch.setattr(_b, "RESIDENT_RUNGS", (1,))
    monkeypatch.setattr(_b, "RESIDENT_RUN", 2)
    d, clock = cluster(per_queue=3)
    chain = Chain(d, clock, monkeypatch)
    first = chain.window()
    (rung,), = chain.solver._resident_rungs.values()
    assert rung < first.C * (first.M // 2)
    built = len(builds)
    finishes(d, clock)
    chain.window()
    assert chain.counted("burst_resident_hits",
                         "burst_resident_misses") == (1, 1)
    for late in range(2):
        # every queue's rows come to take more runs than the rung has
        for c in range(2):
            for q in range(2):
                for i in range(12):
                    d.create_workload(mk(
                        f"late{late}-{c}-{q}-{i}", f"lq-{c}-{q}", 1500,
                        t=clock.t + i))
        plan = chain.pack()
        assert plan.M == first.M
        assert int((-(-plan.row_extent // 2)).sum()) > rung
        chain.launch(plan)
        assert chain.counted("burst_resident_hits",
                             "burst_resident_misses") == (1, 2 + late)
        mirror_equals(chain.solver, plan)
    assert len(builds) == built


def test_a_short_grid_takes_whole_rows(monkeypatch):
    """Where M is under a run's length a run is a queue's whole row."""
    monkeypatch.setattr(_b, "RESIDENT_RUN", 1024)
    d, clock = cluster()
    chain = Chain(d, clock, monkeypatch)
    first = chain.window()
    assert chain.solver._resident_rungs[
        chain.solver._resident.layout] == (first.C,)
    finishes(d, clock)
    plan = chain.window()
    assert chain.counted("burst_resident_hits", "burst_resident_misses",
                         "burst_resident_scatter_rows") == (
        1, 1, int((plan.row_extent > 0).sum()) * plan.M)


def test_no_program_is_built_after_the_first_miss(monkeypatch, builds):
    """A hit hands the fused kernel what the miss handed it, device
    array for device array and host array for host array: one kernel
    program serves both, and the update's rungs were built by the miss."""
    d, clock = cluster()
    chain = Chain(d, clock, monkeypatch)
    chain.window()
    assert len(chain.solver._resident_rungs) == 1
    built, kernels = len(builds), _b.burst_cycles._cache_size()
    for boundary in (finishes, admissions, a_queue_grows, a_queue_shrinks):
        boundary(d, clock)
        chain.window()
    assert chain.counted("burst_resident_hits",
                         "burst_resident_misses") == (4, 1)
    assert len(builds) == built
    assert _b.burst_cycles._cache_size() == kernels


def test_the_full_upload_keeps_nothing(monkeypatch):
    """``KUEUE_TPU_RESIDENT=0``: every window goes up whole through
    ``_stage``, nothing stays on the device, nothing is counted."""
    monkeypatch.setenv("KUEUE_TPU_RESIDENT", "0")
    d, clock = cluster()
    chain = Chain(d, clock, monkeypatch)
    chain.window()
    sent = chain.solver.stats["burst_launch_bytes_h2d"]
    finishes(d, clock)
    chain.window()
    assert chain.solver._resident is None
    assert chain.counted("burst_resident_hits", "burst_resident_misses",
                         "burst_resident_scatter_rows") == (0, 0, 0)
    assert chain.solver.stats["burst_launch_bytes_h2d"] == 2 * sent


@pytest.mark.parametrize("pipeline,runtime", [
    (False, 0), (True, 0), (False, 2), (True, 2)])
def test_the_drivers_decisions_are_the_full_uploads(monkeypatch, pipeline,
                                                    runtime):
    """Rounds of a boundary and a burst through ``Driver.schedule_burst``,
    serial and pipelined, with modeled finishes (``runtime``: the
    driver's ``_fill_burst_finishes`` writes ``death0``) and external
    ones: cycle for cycle what the full upload decides."""
    def rounds(resident):
        monkeypatch.setenv("KUEUE_TPU_RESIDENT", resident)
        d, clock = cluster(per_queue=8)
        seen = []
        for rnd in range(4):
            running = sorted(d.admitted_keys())
            stats = d.schedule_burst(
                6, runtime=runtime, pipeline=pipeline,
                external_finishes={1: running[:1]},
                on_cycle_start=lambda k: setattr(clock, "t",
                                                 clock.t + 1.0))
            seen.append([(sorted(s.admitted), sorted(s.skipped),
                          sorted(s.preempted_targets)) for s in stats])
            for key in sorted(d.admitted_keys())[:2]:
                d.finish_workload(key)
            d.create_workload(mk(f"r{rnd}", f"lq-{rnd % 2}-0", 1500,
                                 prio=10 * rnd, t=clock.t + 0.5))
        return seen, dict(d._burst_solver.stats)

    got, stats = rounds("1")
    want, control = rounds("0")
    assert got == want
    assert stats["burst_dispatches"] == control["burst_dispatches"]
    assert stats["burst_resident_hits"] >= 2
    assert (stats["burst_resident_hits"] + stats["burst_resident_misses"]
            == stats["burst_serial_windows"])
    assert control["burst_resident_hits"] \
        == control["burst_resident_misses"] == 0
    assert stats["burst_launch_bytes_h2d"] > 0
