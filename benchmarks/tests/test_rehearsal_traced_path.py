"""The traced rehearsal on the cell's own path: windows dropped, the
per-cycle engine searching, all thirty per-layer metrics.

``test_rehearsal.py::test_rehearsal_traced`` predates the sixteen
metrics of PR 28 and names by hand the four a CPU cannot read; a PR that
adds metrics may add files to the benchmark and edit none, so this file
holds its successor (every assertion of the old test, and more) and
``pytest.ini`` beside it deselects the old one until a ``benchmark`` PR
folds the two.
"""

import json

import lint_manifest

from test_rehearsal import CELLS, TOY, run

# what only a chip gives: the device plane's readers and the memory peak
DEVICE_ONLY = {"burst_kernel_ms", "burst_kernel_roofline",
               "device_idle_pct", "device_peak_gib", "search_kernel_ms"}


def test_rehearsal_traced_on_the_cells_path(monkeypatch):
    # at the cell's size a forest's candidate slots (5 queues x 65,536
    # rows) are over the fused kernel's cap, so a preempting head makes
    # the window dirty and the per-cycle engine searches; the toy's 5 x
    # 64 are under it.  Lower the cap and the toy takes the cell's path.
    from kueue_tpu.ops import burst
    monkeypatch.setattr(burst, "KC_CAP", 32)
    r = run(CELLS[0], 9, trace=True)
    assert r["correct"] is True
    assert len(TOY["per_layer"]) == 30
    assert set(r["metrics"]) == {m["name"] for m in TOY["per_layer"]} \
        - DEVICE_ONLY
    assert "busy_s" not in r["device"]
    # the line passes the contract but for what only a chip can give
    faults = lint_manifest.lint_line(TOY, CELLS[0], 1, json.dumps(r))
    assert sorted(faults) == sorted(
        [f"result: metric {n!r} is missing" for n in DEVICE_ONLY]
        + [f"result: device.{k} must be above 0 in a traced run"
           for k in ("busy_s", "window_s")])
    m = {k: v["value"] for k, v in r["metrics"].items()}
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
    assert m["compiles_in_window"] == 0
    assert m["launches_per_round"] >= 1 and m["h2d_mb"] > 0
