"""The driver's scheduling section: from the entry of ``schedule_burst``
or ``schedule_once`` to the outermost call's return no automatic
collection of the cyclic collector starts; at the return the driver
collects the young generations itself, once, inside a ``host.collect``
span, and hands the collector back as it found it.

Every test watches the collector through a ``gc.callbacks`` entry of its
own.  An automatic collection can only start while the collector is
enabled, and the driver's own runs while it is still held, so what the
entry saw of ``gc.isenabled()`` tells the two apart."""

from __future__ import annotations

import gc
import pathlib
import re
import weakref

import pytest

from kueue_tpu.controller import driver as driver_mod
from kueue_tpu.obs import trace as trace_mod

from test_burst import add_workloads, build, mk, run_burst, simple_cluster

ENTRIES = ("burst", "once")
YOUNG = driver_mod._YOUNG_GENERATION


class Watch:
    """Each collection's start, as (generation, gc.isenabled(), the
    tracer's open spans)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self.starts = []
        self.stops = 0

    def __call__(self, phase, info):
        if phase == "start":
            t = trace_mod.ACTIVE
            self.starts.append((info["generation"], gc.isenabled(),
                                tuple(t.open_spans()) if t else ()))
        else:
            self.stops += 1


@pytest.fixture
def watch():
    """A collector that is enabled and has just collected, so that no
    count left over from another test trips a collection here."""
    trace_mod.clear()
    was = gc.isenabled()
    gc.enable()
    gc.collect()
    w = Watch()
    gc.callbacks.append(w)
    yield w
    gc.callbacks.remove(w)
    trace_mod.clear()
    (gc.enable if was else gc.disable)()


def toy(watch, use_device=True):
    """A small cluster with a backlog; the watch starts here, after the
    collections of the build."""
    d, clock = build(add_workloads(
        simple_cluster(n_cohorts=2, cqs=2),
        [mk(f"w{i}", f"lq-{i % 2}-{i % 2}", 1000, t=float(i + 1))
         for i in range(6)]), use_device=use_device)
    watch.reset()
    return d, clock


def churn(n=5000):
    """Enough live containers to trip the young generation's threshold
    several times over, were the collector free to run."""
    return [[] for _ in range(n)]


def call(d, clock, how, inside):
    """One scheduling call through the entry point ``how``, with
    ``inside()`` run in the middle of it: from ``schedule_burst``'s
    ``on_cycle`` hook, or round ``Scheduler.schedule`` under
    ``schedule_once``."""
    if how == "burst":
        def on_cycle_start(_k):
            clock.t += 1.0
        return d.schedule_burst(
            2, on_cycle_start=on_cycle_start,
            on_cycle=lambda _k, _stats: inside())
    real = d.scheduler.schedule

    def schedule(*a, **kw):
        inside()
        return real(*a, **kw)
    d.scheduler.schedule = schedule
    try:
        clock.t += 1.0
        return d.schedule_once()
    finally:
        d.scheduler.schedule = real


@pytest.mark.parametrize("how", ENTRIES)
def test_no_collection_starts_inside_and_the_drivers_own_runs_at_the_exit(
        how, watch):
    d, clock = toy(watch)
    seen_inside = []

    def inside():
        kept = churn()
        seen_inside.append((gc.isenabled(), list(watch.starts), len(kept)))
    call(d, clock, how, inside)
    starts, stops = list(watch.starts), watch.stops
    assert seen_inside
    for enabled, started, _ in seen_inside:
        assert enabled is False and started == []
    # exactly one collection, of the young generations, begun while the
    # driver still held the collector: its own
    assert [(g, en) for g, en, _ in starts] == [(YOUNG, False)]
    assert stops == 1
    assert gc.isenabled()


@pytest.mark.parametrize("how", ENTRIES)
def test_the_drivers_collection_is_a_host_collect_span_with_host_gc_in_it(
        how, watch):
    d, clock = toy(watch)
    tracer = d.obs.enable_tracing()
    call(d, clock, how, churn)
    d.obs.disable_tracing()
    recs = tracer.trace_spans
    collects = [r for r in recs if r.name == "host.collect"]
    gcs = [r for r in recs if r.name == "host.gc"]
    assert len(collects) == 1 and len(gcs) == 1
    outer, inner = collects[0], gcs[0]
    assert (outer.parent, outer.depth) == ("", 0)
    assert (inner.parent, inner.depth) == ("host.collect", 1)
    assert outer.t0 <= inner.t0
    assert inner.t0 + inner.dur <= outer.t0 + outer.dur + 1e-9
    # and the watch saw it start inside host.collect alone: the call's
    # own spans (burst, cycle) had closed
    assert [spans for _, _, spans in watch.starts] == [("host.collect",)]
    whole = [r for r in recs if r.name in ("burst", "cycle")]
    assert whole
    assert all(r.t0 + r.dur <= outer.t0 + 1e-9 for r in whole)
    assert tracer.open_spans() == []


def test_nested_calls_collect_once(watch):
    """Without the device solver every cycle of ``schedule_burst`` is a
    ``schedule_once`` inside it: only the outermost exit acts."""
    d, clock = toy(watch, use_device=False)
    nested = []
    real = d.schedule_once

    def schedule_once():
        nested.append(gc.isenabled())
        kept = churn()
        try:
            return real()
        finally:
            nested.append((gc.isenabled(), list(watch.starts), len(kept)))
    d.schedule_once = schedule_once
    out = run_burst(d, clock, 3, 0)
    starts = list(watch.starts)
    assert len(out) == 3 and len(nested) == 6
    assert nested[0::2] == [False] * 3
    assert [(en, st) for en, st, _ in nested[1::2]] == [(False, [])] * 3
    assert [(g, en) for g, en, _ in starts] == [(YOUNG, False)]
    assert gc.isenabled()


@pytest.mark.parametrize("how", ENTRIES)
def test_a_caller_that_disabled_the_collector_keeps_it(how, watch):
    d, clock = toy(watch)
    tracer = d.obs.enable_tracing()
    stats = d.scheduler.solver.stats
    before = stats["collector_deferred_allocations"]
    gc.disable()
    try:
        call(d, clock, how, churn)
        assert gc.isenabled() is False
        assert watch.starts == [] and watch.stops == 0
    finally:
        gc.enable()
    d.obs.disable_tracing()
    assert stats["collector_deferred_allocations"] == before
    assert not [r for r in tracer.trace_spans
                if r.name in ("host.collect", "host.gc")]


@pytest.mark.parametrize("how", ENTRIES)
def test_an_exception_inside_hands_the_collector_back(how, watch):
    d, clock = toy(watch)

    def inside():
        churn()
        raise RuntimeError("from inside the section")
    with pytest.raises(RuntimeError, match="inside the section"):
        call(d, clock, how, inside)
    starts = list(watch.starts)
    assert gc.isenabled()
    assert [(g, en) for g, en, _ in starts] == [(YOUNG, False)]
    # and the next call opens a section of its own
    call(d, clock, how, churn)
    assert [(g, en) for g, en, _ in watch.starts] == [(YOUNG, False)] * 2
    assert gc.isenabled()


@pytest.mark.parametrize("how", ENTRIES)
def test_a_cycle_made_inside_is_gone_when_the_call_returns(how, watch):
    class Node:
        pass
    refs = []
    alive_inside = []

    def inside():
        a, b = Node(), Node()
        a.other, b.other = b, a
        refs.append(weakref.ref(a))
        del a, b
        churn()
        alive_inside.append(refs[-1]() is not None)
    d, clock = toy(watch)
    call(d, clock, how, inside)
    assert alive_inside and all(alive_inside)   # nothing collected it
    assert all(r() is None for r in refs)


@pytest.mark.parametrize("how", ENTRIES)
def test_the_counter_grows_by_the_young_count_read_at_the_exit(
        how, watch, monkeypatch):
    d, clock = toy(watch)
    stats = d.scheduler.solver.stats
    read = []
    real = gc.get_count

    def get_count():
        read.append(real())
        return read[-1]
    monkeypatch.setattr(gc, "get_count", get_count)
    before = stats["collector_deferred_allocations"]
    kept = []
    call(d, clock, how, lambda: kept.append(churn()))
    monkeypatch.undo()
    assert len(read) == 1                   # once, at the outermost exit
    assert read[0][0] >= 5000               # the containers kept inside
    assert stats["collector_deferred_allocations"] - before == read[0][0]
    # the collection that followed took them out of the young generation
    assert watch.stops == 1


def test_without_a_solver_the_section_still_collects(watch):
    d, clock = toy(watch, use_device=False)
    assert d.scheduler.solver is None
    call(d, clock, "once", churn)
    assert [(g, en) for g, en, _ in watch.starts] == [(YOUNG, False)]
    assert gc.isenabled()


def test_the_callers_hooks_run_inside_the_section(watch):
    d, clock = toy(watch)
    seen = []

    def on_cycle_start(_k):
        clock.t += 1.0
        seen.append(("start", gc.isenabled()))
    d.schedule_burst(2, on_cycle_start=on_cycle_start,
                     on_cycle=lambda _k, _s: seen.append(
                         ("cycle", gc.isenabled())))
    assert {kind for kind, _ in seen} == {"start", "cycle"}
    assert all(enabled is False for _, enabled in seen)


def test_thresholds_and_the_permanent_generation_are_left_alone(watch):
    d, clock = toy(watch)
    threshold, frozen = gc.get_threshold(), gc.get_freeze_count()
    for how in ENTRIES:
        call(d, clock, how, churn)
    assert gc.get_threshold() == threshold
    assert gc.get_freeze_count() == frozen
    assert all(g == YOUNG for g, _, _ in watch.starts)  # no full one


def test_the_collector_is_free_again_between_two_calls(watch):
    d, clock = toy(watch)
    call(d, clock, "once", churn)
    n = len(watch.starts)
    kept = churn()
    assert len(kept) == 5000
    later = watch.starts[n:]
    assert later and all(enabled for _, enabled, _ in later)


def test_the_library_freezes_nothing_and_sets_no_threshold():
    """A library that freezes leaks every ``Driver`` its process later
    drops, and a threshold outlives the call: both are a deployment's to
    decide, as the benchmark's freeze is."""
    package = pathlib.Path(driver_mod.__file__).parent.parent
    found = [f"{path}:{no}" for path in sorted(package.rglob("*.py"))
             for no, line in enumerate(path.read_text().splitlines(), 1)
             if re.search(r"gc\.(freeze|set_threshold)\(", line)]
    assert found == []
