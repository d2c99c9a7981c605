"""One cell end to end on the CPU at toy size, and the faults the
comparison has to catch.

The rehearsal skips the harness's look for a chip and drives the rest
of a run: set-up through ``restore_workload`` and ``ingest_workloads``,
the per-cycle ladder, a warm round, a measured window through
``finish_workloads`` and ``schedule_burst`` on the device solver (XLA:CPU
here), the replay through the plain reference, the result line.  The
fault tests break the timed path underneath and see ``correct`` come
out false, once for each fault this kind of cell can have: a step that
returns its state unchanged, and an answer altered where it is made.
(Half of a batch left out and the exchange between chips left out have
no counterpart: one process decides whole cycles on one chip.)
"""

import copy
import json
import os
import time

import pytest

import harness
import lint_manifest

from conftest import ROOT


def toy_manifest():
    """``BENCHMARK.json`` as it is, with each configuration's file
    replaced by its toy under tests/data (same policies and classes, 20
    queues): the rehearsal runs the real cells' entries and metrics."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    m = copy.deepcopy(m)
    for c in m["configs"]:
        toy = c["file"].replace("/configs/mk8-1kcq-", "/tests/data/toy-")
        assert toy != c["file"] and os.path.exists(os.path.join(ROOT, toy))
        c["file"] = toy
    return m


TOY = toy_manifest()
CELLS = [w["name"] for w in TOY["workloads"]]


def run(cell, seed, trace=False, seconds=0.5):
    return harness.run_cell(TOY, cell, seed, seconds, trace,
                            time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_untraced(cell):
    r = run(cell, 2_147_483_700)
    assert r["correct"] is True, r["compared"]
    assert lint_manifest.lint_line(TOY, cell, 0, json.dumps(r)) == []
    assert r["facts"]["cycles_compared"] >= 6
    assert r["facts"]["cycles_with_evictions"] > 0
    assert r["facts"]["window_programs"]["programs_built"] == 0


def cpu_cannot_read():
    """The per-layer metrics only a chip gives, from the data and not by
    hand: those whose ``source`` is the device's trace (XLA:CPU writes
    no device plane) and those of the ``memory`` reader (the CPU backend
    reports no ``memory_stats``)."""
    out = set()
    for m in TOY["per_layer"]:
        with open(os.path.join(ROOT, TOY["paths"][0], "metrics",
                               m["name"] + ".json")) as f:
            reader = json.load(f)["kind"]
        if m["source"] == "device_trace" or reader == "memory":
            out.add(m["name"])
    return out


@pytest.mark.parametrize("path", ["windows_applied", "windows_dropped"])
def test_rehearsal_traced(monkeypatch, path):
    """The traced run on both of the fused window's paths.  At the
    cell's size a forest's candidate slots (5 queues x 65,536 rows) are
    over the fused kernel's cap, so a preempting head makes the window
    dirty and the per-cycle engine searches; the toy's 5 x 64 are under
    it and its windows are applied.  With the cap lowered the toy takes
    the cell's path."""
    if path == "windows_dropped":
        from kueue_tpu.ops import burst
        monkeypatch.setattr(burst, "KC_CAP", 32)
    r = run(CELLS[0], 9, trace=True)
    assert r["correct"] is True
    # no TPU here: the trace has no device plane, so its readers and the
    # memory reader find nothing and leave their metrics out
    device_only = cpu_cannot_read()
    assert {"burst_kernel_ms", "burst_kernel_roofline", "decide_roofline",
            "device_idle_pct", "device_peak_gib",
            "search_kernel_ms"} <= device_only
    assert len(TOY["per_layer"]) >= 31
    unread = {m["name"] for m in TOY["per_layer"]} - set(r["metrics"])
    # an applied window keeps the per-cycle engine idle: spans of the
    # scheduler that were never entered are nothing to read, and nothing
    # of another layer may be missing
    bypassed = unread - device_only
    assert {m["layer"] for m in TOY["per_layer"]
            if m["name"] in bypassed} <= {"scheduler"}
    if path == "windows_dropped":
        assert not bypassed
    assert unread >= device_only
    assert "busy_s" not in r["device"]
    # the line passes the contract but for what only a chip can give
    faults = lint_manifest.lint_line(TOY, CELLS[0], 1, json.dumps(r))
    assert sorted(faults) == sorted(
        [f"result: metric {n!r} is missing" for n in unread]
        + [f"result: device.{k} must be above 0 in a traced run"
           for k in ("busy_s", "window_s")])
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["compiles_in_window"] == 0
    assert m["launches_per_round"] >= 1 and m["h2d_mb"] > 0
    if path == "windows_applied":
        return
    # as the cell: every window dropped, every cycle by the per-cycle
    # engine, its searches batched
    assert m["offwindow_cycles_pct"] == 100 and m["apply_ms"] == 0
    assert m["discarded_window_cycles"] == 32 * m["launches_per_round"]
    assert m["nominate_ms"] > 0 and m["search_wait_ms"] > 0
    assert m["single_searches_per_round"] == m["search_fallback_ms"] == 0
    assert 0 <= m["search_pad_pct"] < 100
    # children stay inside their parents
    assert m["percycle_ms"] >= m["nominate_ms"] >= (
        m["nominate_self_ms"] + m["classify_ms"] + m["search_plan_ms"]
        + m["search_pack_ms"] + m["search_wait_ms"])
    assert m["pack_ms"] >= (m["pack_walk_ms"] + m["pack_grid_ms"]
                            + m["pack_self_ms"]) > 0
    assert m["dispatch_ms"] >= m["tighten_ms"] >= 0


def test_fault_state_left_unchanged(monkeypatch):
    """A boundary that releases nothing: the queues stay full, the
    program admits nothing, the reference does."""
    from kueue_tpu.controller.driver import Driver
    monkeypatch.setattr(Driver, "finish_workloads",
                        lambda self, keys, message="": None)
    r = run(CELLS[0], 21)
    assert r["correct"] is False
    assert r["compared"]["mismatched_cycles"]["value"] > 0


def test_fault_answer_altered_where_it_is_made(monkeypatch):
    """One decision flipped as it leaves the solvers: the first head
    that the fused window or the per-cycle scan admits is turned into
    a skip."""
    import numpy as np
    from kueue_tpu.ops import burst
    from kueue_tpu.ops.solver import CycleSolver
    real_burst, real_cycle = burst.BurstSolver.fetch, CycleSolver.fetch
    flipped = []

    def burst_fetch(self, handle):
        out = list(real_burst(self, handle))
        kind = np.array(out[1])
        hits = np.argwhere(kind == burst.KIND_ADMIT)
        if len(hits):
            kind[tuple(hits[0])] = burst.KIND_SKIP
            flipped.append("burst")
        out[1] = kind
        return tuple(out)

    def cycle_fetch(self, handle):
        final = real_cycle(self, handle)
        hits = np.nonzero(np.asarray(final.admitted))[0]
        if len(hits):
            final.admitted = np.array(final.admitted)
            final.admitted[hits[0]] = False
            flipped.append("cycle")
        return final
    monkeypatch.setattr(burst.BurstSolver, "fetch", burst_fetch)
    monkeypatch.setattr(CycleSolver, "fetch", cycle_fetch)
    r = run(CELLS[0], 22)
    assert flipped
    assert r["correct"] is False
    assert r["compared"]["mismatched_cycles"]["value"] > 0


def test_fault_quota_ignored(monkeypatch):
    """An admission the ledger refuses: the apply step admits a head
    twice over (its usage is never charged), so later heads overrun
    the quota or the cycles diverge."""
    from kueue_tpu.cache.cache import Cache
    real = Cache.assume_workload
    monkeypatch.setattr(
        Cache, "assume_workload",
        lambda self, info: (real(self, info), self.forget_workload(info),
                            True)[2])
    r = run(CELLS[0], 23)
    assert r["correct"] is False
