"""The round's books: the metrics that split snapshot, admit, the
grid's patch and its copy, ``schedule_burst``'s own code, the boundary
and the collector, on the toy's traced run by both of the fused
window's paths.  Every one of them is printed (a span that was never
entered reads 0, not nothing), and a parent holds at least its
children."""

import json
import os

import pytest

import lint_manifest

from conftest import ROOT
from test_rehearsal import CELLS, run

NEW = ("snapshot_ms", "snapshot_cqs_recloned_per_round", "validate_ms",
       "admit_ms", "admit_prepare_ms", "admit_fetch_ms", "admit_apply_ms",
       "admit_requeue_ms", "cycle_self_ms", "pack_patch_ms",
       "pack_snapshot_ms", "pack_grid_self_ms", "pack_snapshot_mb",
       "burst_self_ms", "finish_ms", "gc_ms", "burst_callbacks_ms",
       "heads_ms")


@pytest.fixture(scope="module", params=["windows_applied",
                                        "windows_dropped"])
def traced(request):
    """(path, the traced line's metrics by name).  With the fused
    kernel's cap lowered the toy takes the cells' path: every window
    dropped, every cycle the per-cycle engine's."""
    with pytest.MonkeyPatch.context() as mp:
        if request.param == "windows_dropped":
            from kueue_tpu.ops import burst
            mp.setattr(burst, "KC_CAP", 32)
        r = run(CELLS[0], 39, trace=True)
    assert r["correct"] is True
    return request.param, {k: v["value"] for k, v in r["metrics"].items()}


def test_the_manifest_lists_them_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert lint_manifest.lint(manifest) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == CELLS, name
        assert by_name[name]["better"] == "lower"


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_is_printed(traced, name):
    _, m = traced
    assert name in m and m[name] >= 0


def test_parents_hold_their_children(traced):
    path, m = traced
    eps = 1e-6
    assert m["pack_grid_ms"] + eps >= (
        m["pack_patch_ms"] + m["pack_snapshot_ms"] + m["pack_grid_self_ms"])
    assert m["pack_snapshot_ms"] > 0 and m["pack_snapshot_mb"] > 0
    assert m["finish_ms"] > 0
    assert m["finish_ms"] <= m["boundary_ms"]
    assert m["burst_self_ms"] > 0 and m["burst_callbacks_ms"] > 0
    assert m["heads_ms"] > 0
    if path == "windows_applied":
        return                    # the per-cycle engine may stay idle
    assert m["admit_ms"] + eps >= (
        m["admit_prepare_ms"] + m["admit_fetch_ms"] + m["admit_apply_ms"]
        + m["admit_requeue_ms"]) > 0
    assert m["percycle_ms"] + eps >= (
        m["nominate_ms"] + m["snapshot_ms"] + m["admit_ms"]
        + m["cycle_self_ms"])
    assert m["snapshot_ms"] > 0 and m["validate_ms"] > 0
    assert m["nominate_ms"] >= m["validate_ms"] + m["nominate_self_ms"]
    assert m["snapshot_cqs_recloned_per_round"] > 0
    # the second window of a structure is a delta: it patches the grid
    assert m["pack_patch_ms"] > 0
