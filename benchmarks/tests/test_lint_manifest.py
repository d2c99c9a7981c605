"""``BENCHMARK.json`` and the data files against the contract."""

import copy
import json
import os

import pytest

import lint_manifest

from conftest import ROOT
from helpers import shadow_root

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_manifest_has_no_fault():
    raw = open(os.path.join(ROOT, "BENCHMARK.json")).read()
    assert lint_manifest.lint(MANIFEST, ROOT, len(raw.encode())) == []
    for c in MANIFEST["configs"]:
        assert 1 <= len(c["source"]) <= 200        # PR 22 was lost here


def _broken(edit):
    m = copy.deepcopy(MANIFEST)
    edit(m)
    return lint_manifest.lint(m, ROOT)


@pytest.mark.parametrize("edit, says", [
    (lambda m: m["configs"][0].update(source="x" * 201), "source"),
    (lambda m: m["configs"][0].update(source="two\nlines"), "source"),
    (lambda m: m["workloads"][0].update(why="y" * 201), "why"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: m["workloads"].pop(), "no cell uses it"),
    (lambda m: m["end_to_end"][0].update(unit="admissions per second"),
     "unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
    (lambda m: m["per_layer"][0].update(moves="ttft_p95_ms"), "moves"),
    (lambda m: m["per_layer"][0].update(why="a reason"), "not in the"),
    (lambda m: m["per_layer"][0].update(name="has space"), "name"),
    (lambda m: m["per_layer"][0].update(name="no_such_reader"), "reader"),
    (lambda m: m["per_layer"].append(dict(m["per_layer"][0])), "twice"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m.update(extra=1), "top-level"),
    (lambda m: m["command"].append("../x"), "leaves the repo"),
    (lambda m: m["command"].append("bench.py"), "outside paths"),
    (lambda m: m["configs"][0].update(reduced=["hidden_size"]), "width"),
    (lambda m: m["workloads"][0].update(traffic="no_such_mix"),
     "no data file"),
])
def test_lint_finds(edit, says):
    faults = _broken(edit)
    assert any(says in x for x in faults), faults


@pytest.mark.parametrize("edit, says", [
    (lambda cfg: cfg.pop("kind"), "names no deployment kind"),
    (lambda cfg: cfg.update(kind="no_such_kind"), "has no package"),
    (lambda cfg: cfg.update(reduced=["cluster_queues"]), "reduced differs"),
])
def test_lint_finds_in_the_configuration_file(tmp_path, edit, says):
    root = shadow_root(tmp_path)
    path = os.path.join(root, MANIFEST["configs"][0]["file"])
    with open(path) as f:
        cfg = json.load(f)
    edit(cfg)
    os.remove(path)                      # the link, not the file behind it
    with open(path, "w") as f:
        json.dump(cfg, f)
    assert lint_manifest.lint(MANIFEST, ROOT) == []
    faults = lint_manifest.lint(MANIFEST, root)
    assert len(faults) == 1 and says in faults[0], faults


GOOD = {"correct": True, "attempted": 10, "failed": 0,
        "metrics": {"admissions_per_s": {"value": 1.5,
                                         "unit": "admissions/s"},
                    "cycle_ms": {"value": 2.0, "unit": "ms/cycle"},
                    "setup_s": {"value": 3.0, "unit": "s"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 5 << 30},
        "compared": {"mismatched_cycles": {"value": 0, "limit": 0}}}


def test_lint_line():
    cell = MANIFEST["workloads"][0]["name"]
    assert lint_manifest.lint_line(MANIFEST, cell, 0,
                                   json.dumps(GOOD)) == []
    bad = copy.deepcopy(GOOD)
    bad["metrics"]["cycle_ms"]["unit"] = "ms"
    bad["metrics"]["setup_s"]["value"] = 0
    del bad["device"]["memory_peak_bytes"]
    faults = lint_manifest.lint_line(MANIFEST, cell, 0, json.dumps(bad))
    assert len(faults) == 3, faults
    assert lint_manifest.lint_line(MANIFEST, cell, 1, json.dumps(GOOD))
