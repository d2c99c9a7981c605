"""The seam between the harness and a deployment kind.

A kind is a package under ``deployment_kinds/``; the configuration's file
names it.  The contract (benchmarks/harness.py) is held here: every kind
exports exactly its names, the harness, the comparison and the control
import no kind by name, and a kind that did not exist when they were
written, with a configuration, a traffic mix and a cell of its own,
runs through ``run_cell`` with no file of the benchmark edited.
"""

import copy
import importlib
import json
import os
import re
import sys
import time

import pytest

import harness

from conftest import BENCH, ROOT
from helpers import shadow_root

KINDS = sorted(
    d for d in os.listdir(os.path.join(BENCH, "deployment_kinds"))
    if os.path.isfile(os.path.join(BENCH, "deployment_kinds", d,
                                   "__init__.py")))


def test_there_is_a_kind_and_every_configuration_names_one():
    assert "flat_one_flavor" in KINDS
    for x in ("cluster.py", "program.py", "reference.py"):
        assert not os.path.exists(os.path.join(BENCH, x))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for c in manifest["configs"]:
        cfg = harness.load_config(os.path.join(ROOT, c["file"]))
        assert cfg["kind"] in KINDS, c["file"]


@pytest.mark.parametrize("kind", KINDS)
def test_kind_exports_exactly_the_contract(kind):
    mod = importlib.import_module(f"deployment_kinds.{kind}")
    assert sorted(mod.__all__) == sorted(harness.KIND_CONTRACT)
    for name in ("plan_cluster", "summary", "problem", "build_driver",
                 "warm_up", "ledger"):
        assert callable(getattr(mod, name)), name
    assert isinstance(mod.Reference, type)
    for method in ("begin_round", "cycle", "has_heads"):
        assert callable(getattr(mod.Reference, method)), method
    for names in (mod.CONTROLS, mod.COMPARED):
        assert names and all(isinstance(n, str) for n in names)
    # the fields the facts beside the verdict are counted from
    assert "evicted" in mod.COMPARED


@pytest.mark.parametrize("name", harness.KIND_CONTRACT)
def test_contract_is_written_where_the_harness_says(name):
    assert f"``{name}" in harness.__doc__


@pytest.mark.parametrize("module", ["harness", "correct", "control",
                                    "run", "measure", "lint_manifest"])
def test_common_code_imports_no_kind_by_name(module):
    with open(os.path.join(BENCH, module + ".py")) as f:
        src = f.read()
    code = re.sub(r'"""(?s:.*?)"""', "", src)
    for kind in KINDS:
        assert kind not in code, (module, kind)
    # nor one of a kind's modules, as they were imported before the seam
    assert not re.search(
        r"^\s*(import|from)\s+(cluster|program|reference|ledger)\b", code,
        re.M), module
    assert not re.search(r"^\s*(import|from)\s+deployment_kinds\b", code,
                         re.M), module


@pytest.mark.parametrize("edit, says", [
    (lambda cfg: cfg.pop("kind"), "names no deployment kind"),
    (lambda cfg: cfg.update(kind=""), "names no deployment kind"),
    (lambda cfg: cfg.update(kind="no_such_kind"), "there is no package"),
])
def test_no_kind_is_a_system_exit_and_never_a_default(edit, says):
    cfg = harness.load_config(
        os.path.join(BENCH, "tests", "data", "toy-zipf.json"))
    edit(cfg)
    with pytest.raises(SystemExit, match=says):
        harness.deployment_kind(cfg, "toy.json")


# ---- a kind that did not exist when the harness was written --------------

KIND_SRC = '''
"""Throwaway kind: ``flat_one_flavor`` under another name, deciding one
thing more a cycle: the flavor each admission took."""
from deployment_kinds import flat_one_flavor as _flat
from deployment_kinds.flat_one_flavor import (
    CONTROLS, build_driver, ledger, plan_cluster, problem, summary, warm_up)

COMPARED = _flat.COMPARED + ("flavor_of",)


class Reference(_flat.Reference):
    def cycle(self, clock):
        out = super().cycle(clock)
        out.flavor_of = [(k, "default") for k in out.admitted]
        return out


__all__ = _flat.__all__
'''

TRAFFIC_SRC = '''
"""Throwaway traffic kind: ``burst_rounds`` whose cycle record also says
which flavor each admission took (the data file's ``flavor``)."""
from dataclasses import dataclass, field

from traffic_kinds import burst_rounds


@dataclass
class CycleRecord(burst_rounds.CycleRecord):
    flavor_of: list = field(default_factory=list)


class Traffic(burst_rounds.Traffic):
    def __init__(self, params, plan, seed):
        super().__init__(params, plan, seed)
        self.flavor = params["flavor"]

    def round(self, *args, **kwargs):
        rec = super().round(*args, **kwargs)
        rec.cycles = [CycleRecord(**vars(c), flavor_of=[
            (k, self.flavor) for k in c.admitted]) for c in rec.cycles]
        return rec
'''


@pytest.fixture
def new_kind_root(tmp_path, monkeypatch):
    """A root that holds the benchmark as it is and, as new files only,
    the kind ``flavored``, the traffic kind ``flavored_rounds`` with two
    mixes, a configuration and two cells."""
    root = shadow_root(tmp_path)
    bench = os.path.join(root, "benchmarks")
    os.mkdir(os.path.join(bench, "deployment_kinds", "flavored"))
    with open(os.path.join(bench, "deployment_kinds", "flavored",
                           "__init__.py"), "w") as f:
        f.write(KIND_SRC)
    with open(os.path.join(bench, "traffic_kinds",
                           "flavored_rounds.py"), "w") as f:
        f.write(TRAFFIC_SRC)
    with open(os.path.join(BENCH, "traffic", "backlog.json")) as f:
        backlog = json.load(f)
    for mix, flavor in (("flavored", "default"), ("misflavored", "spot")):
        with open(os.path.join(bench, "traffic", mix + ".json"), "w") as f:
            json.dump(dict(backlog, kind="flavored_rounds", flavor=flavor), f)
    cfg = harness.load_config(
        os.path.join(BENCH, "tests", "data", "toy-zipf.json"))
    cfg.update(name="toy-flavored", kind="flavored")
    with open(os.path.join(bench, "configs", "toy-flavored.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = copy.deepcopy(manifest["configs"][0])
    entry.update(name="toy-flavored",
                 file="benchmarks/configs/toy-flavored.json")
    manifest["configs"].append(entry)
    for mix in ("flavored", "misflavored"):
        manifest["workloads"].append({
            "name": "toy-flavored." + mix, "config": "toy-flavored",
            "traffic": mix, "chips": 1, "why": "the seam's own cell"})
    for m in manifest["end_to_end"]:
        assert "workloads" not in m        # every cell reports all three
    for m in manifest["per_layer"]:        # ... and is read layer by layer
        m["workloads"] += [w["name"] for w in manifest["workloads"][-2:]]
    # the new packages' directories, found under the packages' names
    import deployment_kinds
    import traffic_kinds
    for pkg in (deployment_kinds, traffic_kinds):
        monkeypatch.setattr(pkg, "__path__", list(pkg.__path__) + [
            os.path.join(bench, pkg.__name__)])
    yield root, manifest
    for name in ("deployment_kinds.flavored",
                 "traffic_kinds.flavored_rounds"):
        sys.modules.pop(name, None)


@pytest.fixture(scope="module")
def writes():
    """Every file under ``benchmarks/`` that is opened for writing while
    ``writes["on"]`` (an audit hook stays for the life of the process,
    so it is installed once and switched)."""
    seen = {"on": False, "paths": []}

    def on_open(event, args):
        if not (seen["on"] and event == "open"):
            return
        path, mode = args[0], args[1]
        if isinstance(path, (str, bytes, os.PathLike)) \
                and isinstance(mode, str) and set(mode) & set("wax+"):
            real = os.path.realpath(os.fsdecode(path))
            if real.startswith(BENCH + os.sep) \
                    and "__pycache__" not in real:
                seen["paths"].append(real)

    sys.addaudithook(on_open)
    return seen


@pytest.mark.parametrize("mix, correct", [("flavored", True),
                                          ("misflavored", False)])
def test_a_new_kind_runs_with_no_file_of_the_benchmark_edited(
        new_kind_root, writes, mix, correct):
    import lint_manifest
    root, manifest = new_kind_root
    writes.update(on=True, paths=[])
    try:
        assert lint_manifest.lint(manifest, root) == []
        cell = "toy-flavored." + mix
        r = harness.run_cell(manifest, cell, 2_147_483_801, 0.5, False,
                             time.perf_counter(), root=root,
                             require_tpu=False)
    finally:
        writes["on"] = False
    assert writes["paths"] == []
    assert lint_manifest.lint_line(manifest, cell, 0, json.dumps(r)) == []
    assert r["facts"]["cycles_compared"] >= 6 and r["facts"]["admissions"]
    # the kind's extra field is compared: the right flavor passes, and a
    # record that names another flavor fails every cycle that admitted
    assert r["correct"] is correct, r["compared"]
    if not correct:
        assert r["compared"]["mismatched_cycles"]["value"] > 0
        assert all(v["value"] == 0 for k, v in r["compared"].items()
                   if k != "mismatched_cycles")
